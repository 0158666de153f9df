"""Training launcher.

Single-process usage (CPU container, reduced configs / ~100M models):

    PYTHONPATH=src python -m repro.launch.train --arch chatglm3_6b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ck

Production posture (documented; the mesh/sharding path is what the dry-run
proves out): one process per host, jax.distributed.initialize(), the same
build_train_step jitted with the param/batch shardings from
repro.launch.cells, the fault-tolerant loop from repro.train.loop (atomic
checkpoints + auto-resume + straggler watchdog), and the launcher retried by
the cluster scheduler on failure.  Recommended libtpu env for overlap:
    LIBTPU_INIT_ARGS="--xla_tpu_enable_async_collective_fusion=true
      --xla_tpu_enable_latency_hiding_scheduler=true
      --xla_tpu_overlap_compute_collective_tc=true"
MX levers: --quant takes the unified per-role policy (e.g.
--quant weights=e4m3@32:ocp,grads=e4m3@32:ocp), --mx {off,paper,ocp} is
the deprecated uniform alias, and --compressed-dp switches the gradient
exchange to the MX-compressed collective (ZeRO-1 explicit-DP path; the
exchange format follows the policy's ``grads`` role).

``--quant auto:<bytes-per-param>`` calibrates instead of hand-picking:
weight statistics come straight off the initialized params, gradient
statistics from ``--calib-batches`` LM-loss backward passes, and the
budget-constrained search (``repro.calib``) assigns each layer its own
``weights`` spec under the average bytes-per-parameter budget (element
code bits + amortized E8M0 scale, over 8 — e.g. int8@32 costs 1.031,
e2m1@32 costs 0.531), plus one uniform ``grads`` spec for the compressed
collective.  The result is a per-layer ``PolicyTable`` trained with QAT
fake-quantization.
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--quant", default=None,
                    help="quantization policy, e.g. "
                         "'weights=e4m3@32:ocp,grads=e4m3@32:ocp', or "
                         "'auto:<bytes-per-param>' to calibrate and "
                         "search a per-layer weights policy")
    ap.add_argument("--calib-batches", type=int, default=2,
                    help="gradient-statistics batches for --quant auto")
    ap.add_argument("--save-policy", default=None,
                    help="write the auto-selected PolicyTable JSON here")
    ap.add_argument("--mx", choices=["off", "paper", "ocp"], default="off",
                    help="deprecated: use --quant (applies e4m3 to "
                         "weights+grads in the given mode)")
    ap.add_argument("--compressed-dp", action="store_true",
                    help="explicit-DP shard_map step with MX-compressed "
                         "gradient all-reduce (needs >1 device)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (testing)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.launch.cache import use_compilation_cache
    use_compilation_cache()

    from repro.data import DataConfig, SyntheticLM, make_batch_for
    from repro.models import Model, load_config, load_reduced
    from repro.models.config import QuantPolicy
    from repro.optim import AdamWConfig
    from repro.train import (LoopConfig, build_train_step,
                             build_train_step_compressed_dp,
                             init_train_state, train_loop)

    over = {}
    auto_budget = None
    if args.quant and (args.quant == "auto"
                       or args.quant.startswith("auto:")):
        from repro.calib import parse_auto_budget
        auto_budget = parse_auto_budget(args.quant)
    elif args.quant:
        over["mx"] = QuantPolicy.parse(args.quant)
    elif args.mx != "off":
        print(f"[train] --mx is deprecated; use --quant "
              f"weights=e4m3@32:{args.mx},grads=e4m3@32:{args.mx}")
        over["mx"] = QuantPolicy.parse(
            f"weights=e4m3@32:{args.mx},grads=e4m3@32:{args.mx}")
    cfg = (load_reduced if args.reduced else load_config)(args.arch, **over)
    model = Model(cfg)
    params, opt_state = init_train_state(model, jax.random.PRNGKey(0))

    if auto_budget is not None:
        import numpy as np

        from repro.calib import (collect_model_stats, search_weights_policy,
                                 sweep_role, weight_param_nbytes)
        from repro.models.config import apply_policy_table

        rng = np.random.default_rng(1)
        batches = [rng.integers(0, cfg.vocab, size=(args.batch, args.seq)
                                ).astype(np.int32)
                   for _ in range(max(1, args.calib_batches))]
        stats = collect_model_stats(model, params, batches,
                                    roles=("weights", "grads"))
        res = search_weights_policy(stats, auto_budget, cfg)
        # one uniform grads spec for the compressed collective: the best
        # aggregate-gradient SQNR among candidates inside the same
        # bytes-per-param budget
        gsweep = sweep_role(stats, "grads", weight_param_nbytes)
        agg = {}
        for scored in gsweep.values():
            for s in scored:
                a = agg.setdefault(s.spec, [0.0, 0])
                a[0] += s.sqnr_db
                a[1] += 1
        in_budget = {sp: v[0] / v[1] for sp, v in agg.items()
                     if weight_param_nbytes(sp) <= auto_budget}
        table = res.table
        if in_budget:
            gspec = max(in_budget, key=in_budget.get)
            table = table.replace(
                default=table.default.replace(grads=gspec),
                overrides=tuple((i, p.replace(grads=gspec))
                                for i, p in table.overrides))
            print(f"[train] grads role -> {gspec} "
                  f"({in_budget[gspec]:.1f}dB aggregate SQNR)")
        print("[train] " + res.describe().replace("\n", "\n[train] "))
        if args.save_policy:
            from pathlib import Path
            Path(args.save_policy).write_text(table.to_json())
            print(f"[train] wrote policy table -> {args.save_policy}")
        cfg = apply_policy_table(cfg, table)
        model = Model(cfg)
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"quant={cfg.mx}, devices={jax.device_count()}")
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 10
                                                       + 1),
                          total_steps=args.steps)
    fake_quant = cfg.mx.weights is not None \
        or cfg.mx.activations is not None
    if args.compressed_dp:
        ndev = jax.device_count()
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((ndev,), ("data",))
        step = build_train_step_compressed_dp(
            model, opt_cfg, mesh=mesh, dp_axes=("data",),
            fake_quant=fake_quant)
        step = jax.jit(step)
        ctx = jax.set_mesh(mesh)
    else:
        step = jax.jit(build_train_step(model, opt_cfg,
                                        microbatches=args.microbatches,
                                        fake_quant=fake_quant))
        import contextlib
        ctx = contextlib.nullcontext()
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))

    def batch_fn(i):
        return make_batch_for(cfg, data.batch(i))

    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every)
    with ctx:
        out = train_loop(loop_cfg, step, params, opt_state, batch_fn)
    h = out["history"]
    print(f"[train] done: loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f} "
          f"over {len(h)} steps")


if __name__ == "__main__":
    main()
