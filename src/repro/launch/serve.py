"""Serving launcher: batched greedy/sampled generation with optional MX
weights + MX KV cache (the paper's converter on the serving path).

The quantization policy is one ``--quant`` flag of ``role=spec`` pairs
(see ``repro.core.spec``); K and V pages may use different formats:

    PYTHONPATH=src python -m repro.launch.serve --arch yi_34b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 16 --quant kv=int8@32:ocp

Continuous batching over the paged MX KV cache (variable-length prompts
admitted mid-flight; see README §Continuous batching & paged KV), with
mixed-format pages:

    PYTHONPATH=src python -m repro.launch.serve --arch yi_34b --reduced \
        --paged --page-size 16 --batch 8 --requests 24 --mixed \
        --quant kv_key=int8@32:ocp,kv_value=e2m1@32:ocp

``--quant auto:<budget>`` runs calibrate -> search -> serve in one
command: a few synthetic batches are pushed through the instrumented
forward (``--calib-batches``), every candidate format is scored per
layer, and the budget-constrained search (``repro.calib``) emits a
per-layer ``PolicyTable`` — the budget is total KV bytes per token
summed over all layers (codes + scales, bit-packed).  ``--save-policy``
writes the table as JSON; ``--policy-json`` serves a previously saved
table directly:

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3_6b \
        --reduced --paged --quant auto:96 --calib-batches 4 \
        --save-policy /tmp/policy.json

Fault tolerance (README §Fault tolerance): numeric-health guards are on
by default in paged mode; ``--faults`` injects a seeded deterministic
fault plan, and the async front end recovers via ``--retry`` (quarantine
retry budget), ``--watchdog`` + ``--snapshot-every`` (stalled-step
restore from an engine checkpoint):

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3_6b \
        --reduced --paged --batch 4 --requests 8 --arrival poisson:50 \
        --quant kv=int8@32:ocp --faults prefill_nan:rid=2:always \
        --retry 1 --watchdog 30 --snapshot-every 1

``--mx-kv``/``--mx-mode`` are deprecated aliases for uniform KV policies.
"""
from __future__ import annotations

import argparse
import time

from repro.obs.metrics import rate as safe_rate  # noqa: F401 (re-export)

# ``safe_rate`` is now an alias of ``repro.obs.metrics.rate`` — the one
# zero-duration-safe throughput guard the launcher, the async reporter,
# and bench_serve all share (three hand-rolled copies used to drift).


def make_tracer(args, cfg):
    """Build the launcher's Tracer when ``--trace-out``/``--chrome-trace``
    asked for one (None otherwise); the run's identifying knobs ride the
    trace header's ``meta``."""
    if not (args.trace_out or args.chrome_trace):
        return None
    from repro.obs import Tracer
    return Tracer(meta={"arch": args.arch, "quant": str(cfg.mx),
                        "arrival": args.arrival,
                        "preempt": bool(args.preempt),
                        "faults": args.faults or "",
                        "retry": args.retry,
                        "sync_every": args.sync_every})


def write_obs(args, eng, srv=None) -> None:
    """Export the run's observability artifacts: close every open trace
    track (``finalize_trace``), then write the trace/v1 JSONL, the
    Chrome trace, and the unified metrics snapshot as requested."""
    import json
    if eng.tracer is not None:
        eng.finalize_trace()
        if args.trace_out:
            eng.tracer.write_jsonl(args.trace_out)
            print(f"[serve] wrote trace/v1 JSONL -> {args.trace_out} "
                  f"({len(eng.tracer.events)} events)")
        if args.chrome_trace:
            eng.tracer.write_chrome(args.chrome_trace)
            print(f"[serve] wrote Chrome trace -> {args.chrome_trace}")
    if args.metrics_json:
        snap = srv.obs_snapshot() if srv is not None \
            else {"engine": eng.metrics.snapshot()}
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
        print(f"[serve] wrote metrics snapshot -> {args.metrics_json}")


def build_params(model, key, *, weight_resident: bool = False, mesh=None):
    """Random serving parameters, built on the device.  ``weight_resident``
    stores matmul weights in the policy's ``weights`` spec without the fp
    model ever resident beside them (``Model.init_quantized``).  With a
    ``mesh``, every leaf is placed by the decode-posture parameter
    shardings as it is built, so no device holds the whole model."""
    import jax

    init = model.init_quantized if weight_resident else model.init
    shardings = None
    if mesh is not None:
        from repro.launch.cells import shardings_for_params
        shardings = shardings_for_params(jax.eval_shape(init, key), mesh)
    return init(key, shardings)


def parse_arrival(spec: str):
    """Parse an ``--arrival`` spec into ``(kind, params)``:

    ``batch``                      — pre-load every request (PR 2-7 path)
    ``poisson:<rate>``             — Poisson arrivals at <rate> req/s
    ``onoff:<rate>:<on_s>:<off_s>``— bursty on/off modulated Poisson
    ``trace:<path>``               — replay a JSONL trace
    """
    parts = spec.split(":")
    kind = parts[0]
    try:
        if kind == "batch" and len(parts) == 1:
            return "batch", ()
        if kind == "poisson" and len(parts) == 2:
            return "poisson", (float(parts[1]),)
        if kind == "onoff" and len(parts) == 4:
            return "onoff", (float(parts[1]), float(parts[2]),
                             float(parts[3]))
        if kind == "trace" and len(parts) >= 2:
            return "trace", (spec.split(":", 1)[1],)
    except ValueError:
        pass
    raise ValueError(
        f"bad --arrival {spec!r}: expected batch, poisson:<rate>, "
        f"onoff:<rate>:<on_s>:<off_s>, or trace:<path>")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / paged decode slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant", default=None,
                    help="quantization policy, e.g. "
                         "'kv_key=int8@32:ocp,kv_value=e2m1@32:ocp' "
                         "(roles: weights, activations, kv_key, kv_value, "
                         "grads; 'kv=' sets both KV roles), or "
                         "'auto:<bytes>' to calibrate and search a "
                         "per-layer policy under a total KV "
                         "bytes-per-token budget")
    ap.add_argument("--calib-batches", type=int, default=4,
                    help="calibration batches for --quant auto "
                         "(each --batch x --prompt-len synthetic tokens)")
    ap.add_argument("--save-policy", default=None,
                    help="write the auto-selected PolicyTable JSON here")
    ap.add_argument("--policy-json", default=None,
                    help="serve a previously saved PolicyTable JSON "
                         "(skips calibration)")
    ap.add_argument("--mx-kv", choices=["off", "int8", "e4m3", "e5m2",
                                        "e3m2", "e2m3", "e2m1"],
                    default="off",
                    help="deprecated: use --quant kv=<fmt>@32:<mode>")
    ap.add_argument("--mx-mode", choices=["paper", "ocp"], default="ocp",
                    help="deprecated: use --quant")
    ap.add_argument("--weight-resident", action="store_true",
                    help="store decoder/MoE matmul weights in their "
                         "policy's 'weights' spec (uint8 codes, bit-packed "
                         "for sub-byte formats, + E8M0 scales) and serve "
                         "through the fused dequant-in-VMEM matmul kernel "
                         "— fp weights never materialize in HBM; needs a "
                         "weights role, e.g. --quant weights=e4m3@32:ocp")
    ap.add_argument("--shard", action="store_true",
                    help="serve under a (data, model) mesh with the decode "
                         "sharding rules (needs >1 device)")
    ap.add_argument("--paged", action="store_true",
                    help="continuous batching over the paged KV cache")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="paged mode: decode steps fused into one "
                         "device-resident lax.scan window; the host syncs "
                         "(drain tokens / evict / admit) only at window "
                         "boundaries.  1 = the per-step loop "
                         "(token-identical either way)")
    ap.add_argument("--prefill-bucket", type=int, default=0,
                    help="paged mode: pad admission prompts to a multiple "
                         "of this (rounded up to a page multiple; default "
                         "--page-size) and prefill same-bucket admissions "
                         "as one batch")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged mode: share full KV pages across requests "
                         "with a common prompt prefix (refcounted, "
                         "copy-on-write); only the uncached suffix is "
                         "prefilled — outputs stay token-identical")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="paged mode: give every synthetic prompt this "
                         "many common leading tokens (a system prompt) "
                         "so --prefix-cache has something to share")
    ap.add_argument("--requests", type=int, default=0,
                    help="paged mode: total requests to serve "
                         "(default 2x --batch)")
    ap.add_argument("--mixed", action="store_true",
                    help="paged mode: vary prompt lengths around "
                         "--prompt-len instead of equal lengths")
    ap.add_argument("--shard-pool", action="store_true",
                    help="shard the page pool's page dim over the data "
                         "axes (with --shard)")
    ap.add_argument("--arrival", default="batch",
                    help="paged mode request arrivals: 'batch' (pre-load "
                         "everything), 'poisson:<rate>' req/s, "
                         "'onoff:<rate>:<on_s>:<off_s>' bursty, or "
                         "'trace:<path>' JSONL replay — non-batch "
                         "arrivals serve through the asyncio front end")
    ap.add_argument("--slo", type=float, default=0.0,
                    help="async mode: mark ~2/3 of synthetic requests as "
                         "an interactive class (priority 0) with this "
                         "TTFT deadline in seconds; the rest become a "
                         "batch class (priority 1, no deadline).  0 = "
                         "single default class")
    ap.add_argument("--preempt", action="store_true",
                    help="async mode: preempt-and-swap — under pool "
                         "pressure a lower-priority victim's MX KV pages "
                         "swap (still packed) to host memory and restore "
                         "token-identically on re-admission")
    ap.add_argument("--admission", choices=["block", "reject"],
                    default="block",
                    help="async mode: backpressure policy — 'block' "
                         "queues submissions, 'reject' drops requests "
                         "that cannot start immediately")
    ap.add_argument("--speedup", type=float, default=0.0,
                    help="async mode: divide trace arrival times by this "
                         "(0 = submit as fast as the loop allows)")
    ap.add_argument("--faults", default=None,
                    help="paged mode: seeded fault-injection plan, e.g. "
                         "'prefill_nan:rid=1:always,kernel_fail:nth=0,"
                         "stall:nth=2:stall_s=30' (sites: page_corrupt, "
                         "swap_corrupt, prefill_nan, kernel_fail, "
                         "alloc_fail, stall — see repro.serve.faults)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --faults plan's randomness "
                         "(which byte/page/slot each firing corrupts)")
    ap.add_argument("--no-health-checks", action="store_true",
                    help="paged mode: disable the in-jit numeric-health "
                         "guards (finite-logits + MX scale-poison scans); "
                         "poisoned requests stream garbage instead of "
                         "being quarantined")
    ap.add_argument("--retry", type=int, default=0,
                    help="async mode: per-request retry budget for "
                         "quarantined requests (jittered exponential "
                         "backoff; RetriesExhausted after N attempts)")
    ap.add_argument("--retry-backoff", type=float, default=0.05,
                    help="async mode: base retry backoff in seconds")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="async mode: engine snapshot cadence in sync "
                         "windows (0 = only when --watchdog needs one)")
    ap.add_argument("--watchdog", type=float, default=0.0,
                    help="async mode: stalled-step watchdog timeout in "
                         "seconds; a hung step is aborted and the engine "
                         "restored from the last snapshot (0 = off). "
                         "Must comfortably exceed first-trace compile "
                         "time or slow-but-healthy steps trip spurious "
                         "recoveries")
    ap.add_argument("--trace-out", default=None,
                    help="paged mode: write per-request trace spans "
                         "(queued / prefill / decode windows / preempt / "
                         "restore / quarantine / retry) as trace/v1 "
                         "JSONL to this path — zero extra host syncs; "
                         "token-identical on/off")
    ap.add_argument("--chrome-trace", default=None,
                    help="paged mode: additionally export the trace as "
                         "a Chrome trace_event JSON (load in Perfetto / "
                         "chrome://tracing)")
    ap.add_argument("--metrics-json", default=None,
                    help="paged mode: write the unified metrics-registry "
                         "snapshot (engine + scheduler + paging + prefix "
                         "+ swap + mx.* gauges, plus server counters and "
                         "the latency summary in async mode) as JSON")
    ap.add_argument("--obs-interval", type=int, default=0,
                    help="paged mode: sample the MX-health gauges "
                         "(shared-scale saturation/clip + underflow "
                         "rates, poison markers, per KV role) every N "
                         "sync windows (0 = never; each sample is one "
                         "scalar device reduction)")
    args = ap.parse_args()

    import contextlib
    from pathlib import Path

    import numpy as np
    import jax

    from repro.launch.cache import use_compilation_cache
    use_compilation_cache()

    from repro.dist.sharding import make_rules
    from repro.launch.mesh import make_test_mesh
    from repro.models import Model, load_config, load_reduced, \
        make_concrete_batch
    from repro.models.config import (PolicyTable, QuantPolicy, QuantSpec,
                                     apply_policy_table)
    from repro.serve import (ContinuousBatchingEngine, GenerationConfig,
                             ServeEngine)
    from repro.serve.paging import kv_cache_token_nbytes

    over = {}
    auto_budget = None
    if args.policy_json and (args.quant or args.mx_kv != "off"):
        ap.error("--policy-json and --quant/--mx-kv are mutually "
                 "exclusive: the saved table already fixes the policy "
                 "(re-run calibration with --quant auto:<budget> to "
                 "replace it)")
    if args.quant and (args.quant == "auto"
                       or args.quant.startswith("auto:")):
        from repro.calib import parse_auto_budget
        auto_budget = parse_auto_budget(args.quant)
    elif args.quant:
        over["mx"] = QuantPolicy.parse(args.quant)
    elif args.mx_kv != "off":
        print(f"[serve] --mx-kv/--mx-mode are deprecated; use "
              f"--quant kv={args.mx_kv}@32:{args.mx_mode}")
        kv = QuantSpec(args.mx_kv, args.mx_mode)
        over["mx"] = QuantPolicy(kv_key=kv, kv_value=kv)
    cfg = (load_reduced if args.reduced else load_config)(args.arch, **over)
    if args.policy_json:
        cfg = apply_policy_table(
            cfg, PolicyTable.from_json(Path(args.policy_json).read_text()))
        print(f"[serve] policy table from {args.policy_json}: {cfg.mx}"
              + (f" + {len(cfg.mx_table.overrides)} layer overrides"
                 if cfg.mx_table is not None else ""))
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh(jax.device_count()) if args.shard else None
    if args.weight_resident:
        has_weights = cfg.mx.weights is not None or (
            cfg.mx_table is not None
            and any(cfg.layer_cfg(i).mx.weights is not None
                    for i in range(cfg.n_layers)))
        if not has_weights:
            ap.error("--weight-resident needs a 'weights' role in the "
                     "policy, e.g. --quant weights=e4m3@32:ocp")
    # calibration reads fp weights, so an auto policy quantizes after it
    resident_now = args.weight_resident and auto_budget is None
    params = build_params(model, key, weight_resident=resident_now,
                          mesh=mesh)

    if auto_budget is not None:
        # calibrate -> search -> apply (params are policy-independent,
        # so the freshly initialized weights serve the selected table)
        from repro.calib import collect_model_stats, search_kv_policy
        rng = np.random.default_rng(1)
        batches = [rng.integers(0, cfg.vocab,
                                size=(args.batch, args.prompt_len)
                                ).astype(np.int32)
                   for _ in range(max(1, args.calib_batches))]
        t0 = time.perf_counter()
        stats = collect_model_stats(model, params, batches,
                                    roles=("kv_key", "kv_value"))
        res = search_kv_policy(stats, auto_budget, cfg)
        dt = time.perf_counter() - t0
        print(f"[serve] calibrated {len(batches)} batches + searched "
              f"in {dt:.2f}s")
        print("[serve] " + res.describe().replace("\n", "\n[serve] "))
        if args.save_policy:
            Path(args.save_policy).write_text(res.table.to_json())
            print(f"[serve] wrote policy table -> {args.save_policy}")
        cfg = apply_policy_table(cfg, res.table)
        model = Model(cfg)
        print(f"[serve] KV cache: {kv_cache_token_nbytes(cfg)} B/token "
              f"across {cfg.n_layers} layers "
              f"(budget {auto_budget:.4g} B/token)")
    if args.weight_resident:
        from repro.core.mx_weight import params_nbytes
        fp_bytes = params_nbytes(jax.eval_shape(model.init, key))
        if not resident_now:
            params = model.quantize_weights(params)
        mx_bytes = params_nbytes(params)
        print(f"[serve] weight-resident: params {fp_bytes / 1e6:.2f} MB fp "
              f"-> {mx_bytes / 1e6:.2f} MB MX "
              f"({fp_bytes / max(mx_bytes, 1):.2f}x smaller)")
    rules = None
    mesh_ctx = contextlib.nullcontext()
    if mesh is not None:
        # decode posture: weights stay resident (no per-token ZeRO-3 gather)
        rules = make_rules(mesh.axis_names, fsdp_params=False,
                           paged_pool_sharded=args.shard_pool,
                           quant=cfg.mx)
        mesh_ctx = jax.set_mesh(mesh)
        print(f"[serve] sharded over mesh {dict(mesh.shape)}")
    gen = GenerationConfig(max_new_tokens=args.new_tokens,
                           temperature=args.temperature)

    arrival_kind, arrival_params = parse_arrival(args.arrival)
    if arrival_kind != "batch" and not args.paged:
        ap.error("--arrival needs --paged (the async front end drives "
                 "the continuous-batching engine)")
    if not args.paged and (args.faults or args.no_health_checks):
        ap.error("--faults/--no-health-checks need --paged (the guards "
                 "and injection sites live in the paged engine)")
    if arrival_kind == "batch" and (args.retry or args.watchdog > 0
                                    or args.snapshot_every):
        ap.error("--retry/--watchdog/--snapshot-every need a non-batch "
                 "--arrival (they are front-end recovery policies)")
    if not args.paged and (args.trace_out or args.chrome_trace
                           or args.metrics_json or args.obs_interval):
        ap.error("--trace-out/--chrome-trace/--metrics-json/"
                 "--obs-interval need --paged (the observability layer "
                 "instruments the continuous-batching engine)")

    faults = None
    if args.faults:
        from repro.serve import FaultPlan
        faults = FaultPlan.parse(args.faults, seed=args.fault_seed)
        print(f"[serve] fault plan (seed {args.fault_seed}): {faults}")

    if args.paged and arrival_kind != "batch":
        _serve_async(args, cfg, model, params, rules, mesh_ctx, gen,
                     arrival_kind, arrival_params, faults)
        return

    if args.paged:
        rng = np.random.default_rng(0)
        n_req = args.requests or 2 * args.batch
        if args.mixed:
            lens = rng.integers(max(1, args.prompt_len // 4),
                                2 * args.prompt_len, size=n_req)
        else:
            lens = np.full(n_req, args.prompt_len)
        max_len = int(lens.max()) + args.shared_prefix \
            + args.new_tokens + 1
        eng = ContinuousBatchingEngine(
            model, params, max_slots=args.batch,
            page_size=args.page_size, max_len=max_len, rules=rules,
            gen=gen, sync_every=args.sync_every,
            prefill_bucket=args.prefill_bucket or None,
            prefix_cache=args.prefix_cache, preempt=args.preempt,
            health_checks=not args.no_health_checks, faults=faults,
            tracer=make_tracer(args, cfg),
            obs_interval=args.obs_interval)
        shared = rng.integers(0, cfg.vocab, size=args.shared_prefix
                              ).astype(np.int32)
        prompts = []
        for n in lens:
            tail = rng.integers(0, cfg.vocab,
                                size=max(1, int(n))).astype(np.int32)
            prompts.append(np.concatenate([shared, tail])
                           if args.shared_prefix else tail)
        with mesh_ctx:
            t0 = time.perf_counter()
            for p in prompts:
                eng.add_request(p, args.new_tokens)
            out = eng.run()
            dt = time.perf_counter() - t0
        toks = sum(len(v) for v in out.values())
        ph = eng.phase
        print(f"[serve] {cfg.name} paged quant={cfg.mx} "
              f"page={args.page_size} sync_every={args.sync_every}: "
              f"{len(out)} requests "
              f"({'mixed' if args.mixed else 'uniform'} lengths), "
              f"{toks} tokens in {dt:.2f}s (incl. compile) — "
              f"{safe_rate(toks, dt):.1f} tok/s, "
              f"{eng.n_steps} decode steps in "
              f"{eng.n_syncs} fused windows, "
              f"{eng.blocks.free_pages}/{eng.blocks.num_pages} pages free")
        print(f"[serve] HBM pools: weights "
              f"{eng.weight_pool_nbytes / 1024:.1f} KiB"
              f"{' (MX-resident)' if args.weight_resident else ' (fp)'}, "
              f"kv pages {eng.kv_pool_nbytes / 1024:.1f} KiB")
        print(f"[serve] phase wall: prefill {ph['prefill']:.2f}s, "
              f"decode {ph['decode']:.2f}s, host-sync {ph['sync']:.2f}s")
        if args.prefix_cache:
            px = eng.prefix
            print(f"[serve] prefix cache: hit rate "
                  f"{eng.prefix_hit_rate:.2f} ({px.hits}/{px.lookups} "
                  f"admissions), {px.tokens_matched} tokens reused, "
                  f"{eng.prefill_tokens_computed} prefill tokens "
                  f"computed, {eng.n_cow_forks} COW forks, "
                  f"peak shared pages {eng.peak_shared_pages}, "
                  f"effective pool "
                  f"{eng.kv_pool_bytes_effective / 1024:.1f} KiB "
                  f"(allocated {eng.kv_pool_nbytes / 1024:.1f} KiB)")
        if faults is not None or eng.n_quarantined:
            from repro.kernels import backend
            fails = eng.scheduler.failed
            print(f"[serve] fault tolerance: {eng.n_quarantined} "
                  f"quarantined of {len(out) + len(fails)} submitted"
                  + (f", fired sites "
                     f"{sorted({s for s, _, _ in faults.fired})}"
                     if faults is not None and faults.fired else ""))
            for r in fails:
                print(f"[serve]   rid {r.rid} quarantined: {r.error}")
            for op, why in backend.degraded_ops().items():
                print(f"[serve]   kernel {op!r} degraded to dense: {why}")
        if out:
            first = out[min(out)]
            print("[serve] sample output tokens:", first[:12].tolist())
        write_obs(args, eng)
        return

    batch = make_concrete_batch(cfg, args.batch, args.prompt_len)
    batch.pop("labels", None)
    eng = ServeEngine(model, params,
                      max_len=args.prompt_len + args.new_tokens + 8,
                      rules=rules)
    with mesh_ctx:
        t0 = time.perf_counter()
        out = eng.generate(batch, gen)       # includes compile
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = eng.generate(batch, gen)
        t_steady = time.perf_counter() - t0
    toks = out.size
    print(f"[serve] {cfg.name} quant={cfg.mx}: generated {toks} tokens; "
          f"first {t_first:.2f}s (incl. compile), steady {t_steady:.2f}s "
          f"({safe_rate(toks, t_steady):.1f} tok/s)")
    print(f"[serve] weight HBM: {eng.weight_pool_nbytes / 1024:.1f} KiB"
          f"{' (MX-resident)' if args.weight_resident else ' (fp)'}")
    print("[serve] sample output tokens:", out[0][:12].tolist())


def _serve_async(args, cfg, model, params, rules, mesh_ctx, gen,
                 arrival_kind, arrival_params, faults=None) -> None:
    """Drive the continuous-batching engine through the asyncio front end
    under a synthetic (or replayed) arrival process and report tail
    latency + preemption accounting."""
    import asyncio

    import numpy as np

    from repro.serve import (AsyncServer, ContinuousBatchingEngine,
                             TrafficClass, latency_summary, load_trace,
                             on_off_times, poisson_times, replay,
                             synthesize)

    n_req = args.requests or 2 * args.batch
    if arrival_kind == "trace":
        arrivals = load_trace(arrival_params[0])
        max_prompt = max(a.prompt.shape[0] for a in arrivals)
        max_new = max(a.max_new_tokens for a in arrivals)
    else:
        if arrival_kind == "poisson":
            times = poisson_times(arrival_params[0], n_req, seed=0)
        else:
            rate, on_s, off_s = arrival_params
            times = on_off_times(rate, n_req, on_s=on_s, off_s=off_s,
                                 seed=0)
        lo = max(1, args.prompt_len // 4)
        hi = max(lo + 1, 2 * args.prompt_len) if args.mixed \
            else args.prompt_len + 1
        lo = lo if args.mixed else args.prompt_len
        if args.slo > 0:
            classes = [
                TrafficClass("interactive", (lo, hi),
                             (args.new_tokens, args.new_tokens + 1),
                             priority=0, deadline_s=args.slo, weight=2.0),
                TrafficClass("batch", (lo, hi),
                             (args.new_tokens, 2 * args.new_tokens + 1),
                             priority=1, weight=1.0),
            ]
        else:
            classes = [TrafficClass("default", (lo, hi),
                                    (args.new_tokens,
                                     args.new_tokens + 1))]
        arrivals = synthesize(times, classes, cfg.vocab, seed=0)
        max_prompt = max(a.prompt.shape[0] for a in arrivals)
        max_new = max(a.max_new_tokens for a in arrivals)

    eng = ContinuousBatchingEngine(
        model, params, max_slots=args.batch, page_size=args.page_size,
        max_len=max_prompt + max_new + 1, rules=rules, gen=gen,
        sync_every=args.sync_every,
        prefill_bucket=args.prefill_bucket or None,
        prefix_cache=args.prefix_cache, preempt=args.preempt,
        health_checks=not args.no_health_checks, faults=faults,
        tracer=make_tracer(args, cfg),
        obs_interval=args.obs_interval)
    speedup = args.speedup if args.speedup > 0 else float("inf")
    srv_kw = dict(admission=args.admission, retries=args.retry,
                  retry_backoff_s=args.retry_backoff)
    if args.watchdog > 0:
        srv_kw.update(use_executor=True, watchdog_s=args.watchdog,
                      snapshot_every=args.snapshot_every or 1)
    elif args.snapshot_every:
        srv_kw["snapshot_every"] = args.snapshot_every
    servers = []

    async def run():
        async with AsyncServer(eng, **srv_kw) as srv:
            servers.append(srv)
            return await replay(srv, arrivals, speedup=speedup)

    with mesh_ctx:
        t0 = time.perf_counter()
        streams, rejected = asyncio.run(run())
        dt = time.perf_counter() - t0

    fin = eng.finished_in_window
    summ = latency_summary(fin)
    toks = sum(len(r.out) for r in fin)
    print(f"[serve] {cfg.name} async quant={cfg.mx} "
          f"arrival={args.arrival} admission={args.admission} "
          f"preempt={'on' if args.preempt else 'off'}: "
          f"{len(fin)} served / {len(rejected)} rejected of "
          f"{len(arrivals)} arrivals, {toks} tokens in {dt:.2f}s "
          f"(incl. compile) — {safe_rate(toks, dt):.1f} tok/s, "
          f"{safe_rate(len(fin), dt):.2f} admitted req/s")
    if "ttft_p50_ms" in summ:
        print(f"[serve] TTFT p50 {summ['ttft_p50_ms']:.1f} ms / "
              f"p99 {summ['ttft_p99_ms']:.1f} ms"
              + (f", ITL p50 {summ['itl_p50_ms']:.2f} ms / "
                 f"p99 {summ['itl_p99_ms']:.2f} ms"
                 if "itl_p50_ms" in summ else ""))
    if "slo_attainment" in summ:
        print(f"[serve] SLO attainment (TTFT <= {args.slo:.3g}s): "
              f"{summ['slo_attainment']:.1%}")
    ph = eng.phase
    print(f"[serve] phase wall: prefill {ph['prefill']:.2f}s, decode "
          f"{ph['decode']:.2f}s, host-sync {ph['sync']:.2f}s, swap "
          f"{ph['swap']:.2f}s")
    if args.preempt:
        sw = eng.swap_store
        print(f"[serve] preempt-and-swap: {eng.n_preemptions} "
              f"preemptions, {eng.n_restores} restores, swap traffic "
              f"{sw.bytes_out / 1024:.1f} KiB out / "
              f"{sw.bytes_in / 1024:.1f} KiB in (MX-packed), peak "
              f"resident {sw.peak_resident_bytes / 1024:.1f} KiB")
    srv = servers[0] if servers else None
    if faults is not None or args.retry or args.watchdog > 0 \
            or eng.n_quarantined:
        from repro.kernels import backend
        print(f"[serve] fault tolerance: {eng.n_quarantined} quarantine "
              f"events, {srv.n_retried if srv else 0} retries, "
              f"{srv.n_failed if srv else 0} permanent failures, "
              f"{srv.n_recoveries if srv else 0} watchdog recoveries"
              + (f", fired sites "
                 f"{sorted({s for s, _, _ in faults.fired})}"
                 if faults is not None and faults.fired else ""))
        for r in eng.scheduler.failed:
            print(f"[serve]   rid {r.rid} quarantined: {r.error}")
        for op, why in backend.degraded_ops().items():
            print(f"[serve]   kernel {op!r} degraded to dense: {why}")
    write_obs(args, eng, srv)


if __name__ == "__main__":
    main()
