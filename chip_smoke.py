"""On-chip smoke run: full-width chatglm3-6b served through the paged
continuous-batching path, plus the MX converter kernel, on a TPU.

    python chip_smoke.py              # one chip: four phases (below)
    python chip_smoke.py --chips 4    # four chips: sharded serving against
                                      # the same requests on one device,
                                      # tokens and teacher-forced logits
                                      # (bf16, f32, and a misplaced shard)

One chip, 28 layers at published widths, bf16 weights from a seed:

1. main      int8 KV pages, dense paged attention;
2. kernel    int8 K / packed e2m1 V pages through the Pallas paged-decode
             kernel, which must be in the compiled decode program, and one
             layer's kernel output checked against ``kernels.ref``;
3. resident  e4m3 weight-resident MX weights through the fused
             dequant x matmul kernel, checked against ``kernels.ref``;
4. convert   ``mx_quantize_2d`` on a (4096, 4096) bf16 tensor for six
             formats x two modes, bit for bit against ``core.convert``.

Every phase serves 8 requests (128-512 prompt tokens, 32 new tokens each)
over 8 slots and must finish each with its token count, no quarantine
(the in-jit guard flags non-finite logits) and no degraded kernel.  A
failed check or any exception exits non-zero.  The last line of
standard output is the JSON result; it is printed only when every phase
passed.  The script runs in one process that owns the chip and needs a
TPU: on any other backend it exits non-zero without a result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "chatglm3_6b"
SEED = 0
N_REQ, SLOTS, NEW, PAGE = 8, 8, 32, 16
PROMPT_LO, PROMPT_HI = 128, 512
# prefill pads every prompt to one 512-token bucket: one prefill program
PREFILL_BUCKET = 512
CONVERT_SHAPE = (4096, 4096)
# kernel vs f32 reference, as a share of the reference's largest |value|
KERNEL_TOL = 2e-2
# 4 chips, teacher-forced on the same tokens: rms of (sharded - one
# device) logits over the logits' rms spread.  bf16 compute; a v5e 2x2
# host read 2.08e-2 for the correct layout and 1.22 with w1's shards
# misplaced (both are read on every run)
LOGIT_TOL = 0.1
# the same bf16 weights computed in f32 (read 7.7e-4 on that host)
F32_TOL = 1e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileClock:
    """Backend compile seconds (persistent-cache reads included), programs
    compiled and programs read from the persistent cache, from JAX's
    monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == self.COMPILE:
                self.seconds += secs
                self.programs += 1

        def on_event(name, **_):
            if name == self.HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def lap(self):
        """(seconds, programs compiled, programs read from the cache)."""
        return self.seconds, self.programs - self.hits, self.hits

    @staticmethod
    def since(a, b) -> str:
        return (f"compile {b[0] - a[0]:.3f}s ({b[1] - a[1]} compiled, "
                f"{b[2] - a[2]} from cache)")


def peak_gib(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LO, PROMPT_HI + 1, size=N_REQ)
    return [rng.integers(0, vocab, size=int(n)).astype(np.int32)
            for n in lens]


def config(policy: str, **over):
    from repro.models import load_config
    from repro.models.config import QuantPolicy
    return load_config(ARCH, mx=QuantPolicy.parse(policy), **over)


def engine(model, params, rules=None):
    from repro.serve import ContinuousBatchingEngine, GenerationConfig
    return ContinuousBatchingEngine(
        model, params, max_slots=SLOTS, page_size=PAGE,
        max_len=PROMPT_HI + NEW + 1, rules=rules,
        gen=GenerationConfig(max_new_tokens=NEW), sync_every=8,
        prefill_bucket=PREFILL_BUCKET)


def check_served(eng, out, name: str) -> None:
    """Every request finished with NEW tokens in vocab range, none was
    quarantined, no kernel degraded."""
    from repro.kernels import backend
    check(not eng.scheduler.failed,
          f"{name}: quarantined " + "; ".join(
              f"rid {r.rid}: {r.error}" for r in eng.scheduler.failed))
    check(len(out) == N_REQ, f"{name}: {len(out)} of {N_REQ} finished")
    for rid, toks in out.items():
        check(toks.shape == (NEW,), f"{name}: rid {rid} got {toks.shape}")
        check(bool(np.all((toks >= 0) & (toks < eng.vocab))),
              f"{name}: rid {rid} token out of range")
    check(not backend.degraded_ops(),
          f"{name}: degraded kernels {backend.degraded_ops()}")


def serve(model, params, reqs, name, clock, dev, rules=None, first=None):
    """Serve ``reqs`` through a fresh engine; ``first(eng)`` runs after the
    first step (admission, prefill, one decode window).  Returns the
    engine and {rid: tokens}."""
    from repro.kernels import backend
    c0 = clock.lap()
    eng = engine(model, params, rules)
    t0 = time.perf_counter()
    for p in reqs:
        eng.add_request(p, NEW)
    if first is not None:
        eng.step()
        first(eng)
    done = {r.rid: np.asarray(r.out, np.int32)
            for r in eng.scheduler.finished}
    done.update(eng.run())
    wall = time.perf_counter() - t0
    c1 = clock.lap()
    check_served(eng, done, name)
    ph = eng.phase
    toks = sum(len(v) for v in done.values())
    log(f"{name}: {len(done)} requests, {toks} tokens, "
        f"{eng.n_steps} decode steps in {eng.n_syncs} windows; wall "
        f"{wall:.3f}s (prefill {ph['prefill']:.3f}s, decode "
        f"{ph['decode']:.3f}s, host-sync {ph['sync']:.3f}s) incl. "
        f"{clock.since(c0, c1)}; peak_bytes_in_use "
        f"{peak_gib(dev):.3f} GiB; quarantined {eng.n_quarantined}; "
        f"degraded kernels {len(backend.degraded_ops())}")
    return eng, done


def decode_hlo(eng) -> str:
    """Compiled text of the engine's own jitted decode window (health
    guards included), lowered with the engine's live arguments."""
    import jax.numpy as jnp
    return eng._multi.lower(
        eng.params, jnp.asarray(eng._cur_tok), eng.pool,
        eng._device_tables(), jnp.asarray(eng._lengths),
        jnp.asarray(eng._remaining), eng._slot_keys,
        eng.sync_every).compile().as_text()


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def paged_kernel_check(model, eng):
    """Layer 0 of the live pool after the first window: the Pallas paged
    kernel against ``kernels.ref`` on every running slot."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.mx_decode_attn import mx_paged_decode_attention
    from repro.kernels.ref import mx_paged_decode_attention_ref
    cfg = model.cfg
    running = sorted(eng.scheduler.running.values(), key=lambda r: r.slot)
    check(len(running) == SLOTS, f"{len(running)} slots running")
    bt = jnp.asarray(eng.blocks.tables[[r.slot for r in running]])
    # attend every position written so far (the newest token is not yet)
    last = jnp.asarray([r.prompt_len + len(r.out) - 2 for r in running],
                       jnp.int32)
    pool = {k: v[0] for k, v in eng.pool["layers"].items()}
    q = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (SLOTS, 1, cfg.n_heads, cfg.hd), jnp.bfloat16)
    args = (q, pool["kc_pages"], pool["ks_pages"], pool["vc_pages"],
            pool["vs_pages"], bt, last)
    kw = dict(key_spec=cfg.mx.kv_key, value_spec=cfg.mx.kv_value,
              rep=cfg.n_heads // cfg.n_kv_heads)
    out = mx_paged_decode_attention(*args, **kw)
    with jax.default_matmul_precision("highest"):
        ref = mx_paged_decode_attention_ref(*args, **kw)
    err = rel_err(out, ref)
    log(f"kernel: paged decode kernel vs kernels.ref, layer 0, "
        f"{SLOTS} slots at {int(last.min()) + 1}-{int(last.max()) + 1} "
        f"tokens: max |err| / max |ref| = {err:.3e} (limit {KERNEL_TOL})")
    check(err <= KERNEL_TOL, f"paged kernel error {err:.3e}")


def matmul_kernel_check(params, cfg):
    """Layer 0's MX w1 through the fused kernel against ``kernels.ref``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import mx_matmul_resident
    from repro.kernels.ref import mx_matmul_2d_ref
    w = params["layers"]["mlp"]["w1"]
    w0 = type(w)(w.codes[0], w.scales[0], w.fmt, w.mode, w.block,
                 w.packed, w.k, w.n)
    x = jax.random.normal(jax.random.PRNGKey(SEED + 2), (SLOTS, w0.k),
                          jnp.bfloat16)
    out = jax.jit(mx_matmul_resident)(x, w0)
    with jax.default_matmul_precision("highest"):
        ref = mx_matmul_2d_ref(x, w0.unpacked_codes(), w0.scales, w0.spec)
    err = rel_err(out, ref)
    log(f"resident: fused mx_matmul vs kernels.ref, layer 0 w1 "
        f"({SLOTS}, {w0.k}) x ({w0.k}, {w0.n}) {w0.spec}: max |err| / "
        f"max |ref| = {err:.3e} (limit {KERNEL_TOL})")
    check(err <= KERNEL_TOL, f"fused matmul error {err:.3e}")


def converter_phase(clock, dev):
    import jax
    import jax.numpy as jnp
    from repro.core.convert import mx_quantize
    from repro.core.formats import ALL_FORMATS
    from repro.core.spec import QuantSpec
    from repro.kernels.mx_quant import mx_quantize_2d
    rng = np.random.default_rng(SEED + 3)
    x = rng.normal(scale=4.0, size=CONVERT_SHAPE).astype(np.float32)
    x.flat[::7] = 0.0
    x.flat[1::13] *= 1e-30
    x.flat[2::17] *= 1e30
    x = jnp.asarray(x, jnp.bfloat16)      # casts huge values to +-inf
    ref_fn = jax.jit(lambda v, spec: mx_quantize(
        v.astype(jnp.float32), spec, axis=-1), static_argnums=1)
    c0 = clock.lap()
    for mode in ("paper", "ocp"):
        for f in ALL_FORMATS:
            spec = QuantSpec(f.name, mode)
            t0 = time.perf_counter()
            codes, scales = mx_quantize_2d(x, spec)
            codes.block_until_ready()
            dt = time.perf_counter() - t0
            ref = ref_fn(x, spec)
            same = (np.array_equal(np.asarray(codes), np.asarray(ref.codes))
                    and np.array_equal(np.asarray(scales),
                                       np.asarray(ref.scales)))
            log(f"convert: {spec} {CONVERT_SHAPE} bf16: kernel == "
                f"core.convert bit for bit: {same} (first call "
                f"{dt:.3f}s incl. compile)")
            check(same, f"converter kernel differs from core.convert "
                        f"for {spec}")
    log(f"convert: {clock.since(c0, clock.lap())}; peak_bytes_in_use "
        f"{peak_gib(dev):.3f} GiB")


def one_chip(clock, dev):
    import jax
    from repro.core.mx_weight import params_nbytes
    from repro.kernels.backend import resolve_matmul_impl
    from repro.launch.serve import build_params
    from repro.models import Model

    key = jax.random.PRNGKey(SEED)
    cfg = config("kv=int8@32:ocp")
    log(f"model: {cfg.name} {cfg.n_layers} layers, d={cfg.d_model}, "
        f"{cfg.n_heads}H/{cfg.n_kv_heads}KV, d_ff={cfg.d_ff}, "
        f"vocab={cfg.vocab}, {cfg.dtype} weights from seed {SEED}")
    reqs = prompts(cfg.vocab)
    log(f"workload: {N_REQ} requests, prompts {sorted(len(p) for p in reqs)}"
        f" tokens, {NEW} new tokens each, {SLOTS} slots, page {PAGE}")

    c0 = clock.lap()
    t0 = time.perf_counter()
    model = Model(cfg)
    params = build_params(model, key)
    jax.block_until_ready(params)
    log(f"init: bf16 params {params_nbytes(params) / 2 ** 30:.3f} GiB in "
        f"{time.perf_counter() - t0:.3f}s incl. "
        f"{clock.since(c0, clock.lap())}; peak_bytes_in_use "
        f"{peak_gib(dev):.3f} GiB")
    eng, _ = serve(model, params, reqs, "main", clock, dev)
    del eng

    cfg_k = config("kv_key=int8@32:ocp,kv_value=e2m1@32:ocp",
                   attn_impl="flash")
    model_k = Model(cfg_k)
    eng, _ = serve(model_k, params, reqs, "kernel", clock, dev,
                   first=lambda e: paged_kernel_check(model_k, e))
    hlo = decode_hlo(eng)
    check("tpu_custom_call" in hlo, "no tpu_custom_call in the decode "
                                    "window's compiled HLO")
    log("kernel: tpu_custom_call in the compiled decode window: True")
    del eng, params
    gc.collect()

    cfg_w = config("kv=int8@32:ocp,weights=e4m3@32:ocp")
    check(resolve_matmul_impl() == "fused", "weight-resident matmul path "
                                            "is not the fused kernel")
    c0 = clock.lap()
    t0 = time.perf_counter()
    model_w = Model(cfg_w)
    params_w = build_params(model_w, key, weight_resident=True)
    jax.block_until_ready(params_w)
    log(f"resident: e4m3 MX params {params_nbytes(params_w) / 2 ** 30:.3f} "
        f"GiB in {time.perf_counter() - t0:.3f}s incl. "
        f"{clock.since(c0, clock.lap())}; peak_bytes_in_use "
        f"{peak_gib(dev):.3f} GiB")
    matmul_kernel_check(params_w, cfg_w)
    eng, _ = serve(model_w, params_w, reqs, "resident", clock, dev)
    hlo = decode_hlo(eng)
    check("tpu_custom_call" in hlo, "no tpu_custom_call in the "
                                    "weight-resident decode window")
    log("resident: tpu_custom_call in the compiled decode window: True")
    del eng, params_w
    gc.collect()

    converter_phase(clock, dev)


def forced_logits(model, params, seqs, rules=None):
    """Next-token logits for each request's NEW generated positions,
    teacher-forced on ``seqs`` (prompt + generated tokens, one request per
    call, padded to one length so one program serves all): (N_REQ, NEW,
    vocab) f32 on the host.  Prefill attends the quantized KV view, as
    decode does, so these are the logits the engine sampled from."""
    import jax
    import jax.numpy as jnp
    from repro.dist.sharding import use_rules
    total = PROMPT_HI + NEW

    @jax.jit
    def fn(p, t, start):
        logits = model.prefill(p, {"tokens": t}, max_len=total)[0][0]
        pos = start - 1 + jnp.arange(NEW)
        return jnp.take(logits, pos, axis=0)[:, :model.cfg.vocab] \
            .astype(jnp.float32)

    out = []
    with use_rules(rules) if rules is not None else contextlib.nullcontext():
        for seq, start in seqs:
            t = np.zeros((1, total), np.int32)
            t[0, :len(seq)] = seq
            out.append(np.asarray(fn(params, jnp.asarray(t), start)))
    return np.stack(out)


def logit_rel(a, b) -> float:
    """rms(a - b) over the rms spread of ``b``'s logits about their mean."""
    spread = np.sqrt(np.mean((b - b.mean(-1, keepdims=True)) ** 2))
    return float(np.sqrt(np.mean((a - b) ** 2)) / spread)


def top2_gap(logits) -> float:
    top = np.sort(logits)[-2:]
    return float(top[1] - top[0])


def four_chips(clock, devs):
    import jax
    import jax.numpy as jnp
    from repro.dist.sharding import make_rules
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_params
    from repro.models import Model

    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    key = jax.random.PRNGKey(SEED)
    cfg = config("kv=int8@32:ocp")
    model = Model(cfg)
    # the same bf16 parameters, computed in f32 at full matmul precision
    model32 = Model(config("kv=int8@32:ocp", dtype="float32"))
    highest = lambda: jax.default_matmul_precision("highest")
    reqs = prompts(cfg.vocab)

    mesh = make_test_mesh(len(devs))
    rules = make_rules(mesh.axis_names, fsdp_params=False, quant=cfg.mx)
    log(f"sharded: mesh {dict(mesh.shape)} over {len(devs)} devices")
    params = build_params(model, key, mesh=mesh)
    for name, w in (("wq", params["layers"]["attn"]["wq"]),
                    ("w1", params["layers"]["mlp"]["w1"])):
        shard = w.addressable_shards[0].data.shape
        check(len(w.sharding.device_set) == len(devs)
              and shard[-1] * mesh.shape["model"] == w.shape[-1],
              f"{name} {w.shape} is not split over the mesh (shard {shard})")
        log(f"sharded: {name} {w.shape} -> per-device shard {shard}")
    with jax.set_mesh(mesh):
        eng, sharded = serve(model, params, reqs, "sharded", clock, devs[0],
                             rules=rules)
        del eng
        # every comparison scores the sharded run's sequences
        seqs = [(np.concatenate([reqs[r], sharded[r][:-1]]), len(reqs[r]))
                for r in range(N_REQ)]
        lg_sh = forced_logits(model, params, seqs, rules)
        with highest():
            lg_sh32 = forced_logits(model32, params, seqs, rules)
        # a misplaced shard: each device holds its neighbour's w1 columns
        w1 = params["layers"]["mlp"]["w1"]
        bad = jax.tree_util.tree_map(lambda x: x, params)
        bad["layers"]["mlp"]["w1"] = jax.device_put(
            jnp.roll(w1, w1.shape[-1] // mesh.shape["model"], -1),
            w1.sharding)
        lg_bad = forced_logits(model, bad, seqs, rules)
        del bad, w1
    for d in devs:
        log(f"sharded: device {d.id} peak_bytes_in_use "
            f"{peak_gib(d):.3f} GiB")
    del params
    gc.collect()

    params = build_params(model, key)        # on the default device
    eng, single = serve(model, params, reqs, "one-device", clock, devs[0])
    del eng
    lg_one = forced_logits(model, params, seqs)
    with highest():
        lg_one32 = forced_logits(model32, params, seqs)

    agree = lambda a, b: float(np.mean(a.argmax(-1) == b.argmax(-1)))
    rel, rel32, rel_bad = (logit_rel(lg_sh, lg_one),
                           logit_rel(lg_sh32, lg_one32),
                           logit_rel(lg_bad, lg_one))
    log(f"compare: teacher-forced logits over {N_REQ}x{NEW} positions, "
        f"rms(sharded - one device) / rms spread: bf16 {rel:.4e} (limit "
        f"{LOGIT_TOL}), argmax agrees at {agree(lg_sh, lg_one):.4f}; "
        f"f32 compute {rel32:.4e} (limit {F32_TOL}), argmax agrees at "
        f"{agree(lg_sh32, lg_one32):.4f}; w1 shards misplaced {rel_bad:.4e}"
        f" (must exceed {LOGIT_TOL})")
    check(rel <= LOGIT_TOL, "sharded logits differ beyond bf16 rounding")
    check(rel32 <= F32_TOL, "sharded logits differ beyond f32 rounding")
    check(rel_bad > LOGIT_TOL, "the logit comparison cannot see a "
                               "misplaced shard")

    diverged = [r for r in range(N_REQ)
                if not np.array_equal(single[r], sharded[r])]
    log(f"compare: {N_REQ - len(diverged)} of {N_REQ} requests "
        f"token-identical (sharded vs one device)")
    # printed, not gated: a token flip where both layouts compute the same
    # function (the f32 check above) is a near-tie broken by bf16 rounding
    noise = float(np.sqrt(np.mean((lg_sh - lg_one) ** 2)))
    for r in diverged:
        t = int(np.argmax(single[r] != sharded[r]))
        gap = top2_gap(lg_one[r, t])
        log(f"compare: rid {r} first diverges at new token {t}: "
            f"{int(single[r][t])} (one device) vs {int(sharded[r][t])} "
            f"(sharded); bf16 top-2 logit gap {gap:.4f} = "
            f"{gap / noise:.2f} x rms(sharded - one device) {noise:.4f}; "
            f"f32 top-2 gap {top2_gap(lg_one32[r, t]):.4f}, f32 argmax "
            f"{int(lg_one32[r, t].argmax())} (one device) vs "
            f"{int(lg_sh32[r, t].argmax())} (sharded)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: sharded serving vs one device, nothing else")
    args = ap.parse_args()
    try:
        from repro.launch.cache import use_compilation_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script: {e}")
    cache_dir = Path(use_compilation_cache())
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX's default backend is "
             f"{devs[0].platform!r}; this run does not fall back")
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    n_cache = lambda: sum(1 for p in cache_dir.rglob("*") if p.is_file()) \
        if cache_dir.is_dir() else 0
    log(f"compile cache {cache_dir}: {n_cache()} entries at start")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(clock, devs)
    else:
        one_chip(clock, dev)
    log(f"total: wall {time.perf_counter() - t0:.3f}s, "
        f"{clock.since((0.0, 0, 0), clock.lap())}; compile cache "
        f"{n_cache()} entries at end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
