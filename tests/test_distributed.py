"""Multi-device tests (8 host CPU devices in a subprocess so the main test
process keeps seeing 1 device): MX-compressed gradient collectives +
sharded train step + elastic checkpoint restore."""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devprog(body: str, ndev: int = 8) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={ndev}")
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    return out.stdout


def test_row_parallel_mx_gather_divisibility():
    """Satellite regression: row-parallel ("model" on K) FSDP gather of an
    MX weight must refuse K//block scale rows that don't divide the model
    axis (codes would shard while scales silently replicate), and still
    serve cleanly when they do divide."""
    run_devprog("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import MXWeight, QuantSpec
        from repro.core.convert import mx_quantize
        from repro.dist.sharding import make_rules, use_rules
        from repro.launch.mesh import make_mesh
        from repro.models.layers import dense

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = make_rules(("data", "model"), fsdp_params=True)
        spec = QuantSpec("e4m3", "ocp", 32, True)
        rng = np.random.default_rng(0)
        fn = jax.jit(lambda x, w: dense(x, w, tp="row"))

        with jax.set_mesh(mesh), use_rules(rules):
            # K=32 -> K//block=1 scale row, model axis 2: codes' K divides,
            # scales' K//block does not -> loud error naming the sizes
            w_bad = rng.normal(size=(32, 16)).astype(np.float32)
            x_bad = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
            for bad in (mx_quantize(jnp.asarray(w_bad), spec, axis=0),
                        MXWeight.quantize(jnp.asarray(w_bad), spec)):
                try:
                    fn(x_bad, bad)
                    raise SystemExit("expected ValueError for K//block=1")
                except ValueError as e:
                    assert "K//block=1" in str(e) and "size 2" in str(e), e
            # K=128 -> K//block=4 divides the model axis: both container
            # types serve, matching the unsharded dequant matmul
            w_ok = rng.normal(size=(128, 16)).astype(np.float32) * 0.05
            x_ok = jnp.asarray(rng.normal(size=(2, 128)), jnp.float32)
            mxa = mx_quantize(jnp.asarray(w_ok), spec, axis=0)
            mxw = MXWeight.quantize(jnp.asarray(w_ok), spec)
            ya = np.asarray(fn(x_ok, mxa))
            yw = np.asarray(fn(x_ok, mxw))
            ref = np.asarray(x_ok) @ np.asarray(mxw.dequantize())
            np.testing.assert_allclose(ya, ref, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(yw, ref, rtol=1e-5, atol=1e-5)
        print("OK rowshard")
    """, ndev=4)


def test_mx_allreduce_matches_exact_mean():
    run_devprog("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.core.grad_compress import mx_allreduce_mean

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        # per-device gradient shards: (8, n) -> each device holds one row
        n = 4096 + 17
        g = rng.normal(size=(8, n)).astype(np.float32)

        def body(gl):
            gl = gl[0]                      # local (n,)
            return mx_allreduce_mean(gl, ("pod", "data"),
                                     fmt="e4m3", mode="ocp")[None]

        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=P(("pod", "data")),
                               out_specs=P(("pod", "data"))))
        out = np.asarray(fn(jnp.asarray(g)))
        exact = g.mean(0)
        # every device must hold the same compressed mean
        for d in range(8):
            np.testing.assert_array_equal(out[d], out[0])
        # error bounded by the E4M3 block ulp relative to block max
        err = np.abs(out[0] - exact)
        blocks = exact[: n // 32 * 32].reshape(-1, 32)
        bmax = np.abs(blocks).max(1)
        tol = np.repeat(bmax, 32) * 2.0 ** -3 * 1.01 + 1e-7
        assert (err[: len(tol)] <= tol).all(), err.max()
        print("OK allreduce")
    """)


def test_compressed_dp_train_step_runs_and_learns():
    run_devprog("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.data import DataConfig, SyntheticLM, make_batch_for
        from repro.models import Model, load_reduced
        from repro.optim import AdamWConfig
        from repro.train import (build_train_step_compressed_dp,
                                 init_train_state)

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = load_reduced("chatglm3_6b", remat=False)
        model = Model(cfg)
        params, opt_state = init_train_state(model, jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig(lr=2e-3, warmup_steps=2, total_steps=20,
                              weight_decay=0.0)
        step = build_train_step_compressed_dp(
            model, opt_cfg, mesh=mesh, dp_axes=("pod", "data"))
        step = jax.jit(step)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=8, seed=1))
        losses = []
        with jax.set_mesh(mesh):
            for i in range(12):
                batch = make_batch_for(cfg, data.batch(i))
                params, opt_state, m = step(params, opt_state, batch,
                                            jnp.asarray(i))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        assert np.isfinite(losses).all()
        print("OK compressed train", losses[0], losses[-1])
    """)


def test_elastic_checkpoint_restore_across_mesh_shapes():
    run_devprog("""
        import os, tempfile
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import save_atomic, restore, latest_step

        d = tempfile.mkdtemp()
        mesh1 = jax.make_mesh((8,), ("data",))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh1, P("data", None)))
        save_atomic(d, 3, {"w": xs})
        # restore onto a DIFFERENT mesh shape (elastic rescale 8 -> 4x2)
        mesh2 = jax.make_mesh((4, 2), ("data", "model"))
        tgt = NamedSharding(mesh2, P("model", "data"))
        like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        out, meta = restore(d, 3, like, {"w": tgt})
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
        assert out["w"].sharding == tgt
        print("OK elastic restore")
    """)


def test_sharded_fused_decode_token_identical():
    """The fused multi-step decode window under a (data, model) mesh — the
    paged Pallas kernel's shard_map wrapper running *inside* the scanned
    step — is token-identical to single-device per-step decode."""
    run_devprog("""
        import numpy as np, jax
        from repro.dist.sharding import make_rules
        from repro.launch.mesh import make_test_mesh
        from repro.models import Model, load_reduced
        from repro.models.config import QuantPolicy
        from repro.serve import ContinuousBatchingEngine, GenerationConfig

        cfg = load_reduced("chatglm3_6b",
                           mx=QuantPolicy.parse("kv=int8@32:ocp"),
                           attn_impl="flash")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
                   for n in (4, 9, 14, 9)]
        gen = GenerationConfig(max_new_tokens=4)

        mesh = make_test_mesh(jax.device_count())
        rules = make_rules(mesh.axis_names, fsdp_params=False,
                           quant=cfg.mx)
        with jax.set_mesh(mesh):
            eng = ContinuousBatchingEngine(
                model, params, max_slots=2, page_size=8, max_len=19,
                rules=rules, gen=gen, sync_every=4)
            for p in prompts:
                eng.add_request(p, 4)
            sharded = eng.run()
        eng1 = ContinuousBatchingEngine(
            model, params, max_slots=2, page_size=8, max_len=19,
            gen=gen, sync_every=1)
        for p in prompts:
            eng1.add_request(p, 4)
        single = eng1.run()
        for r in sharded:
            np.testing.assert_array_equal(sharded[r], single[r])
        assert eng.n_syncs < eng1.n_syncs
        print("OK sharded fused decode")
    """, ndev=2)


def test_exchanged_bytes_accounting():
    from repro.core.grad_compress import exchanged_bytes
    base = exchanged_bytes(1_000_000, 16, compressed=False)
    comp = exchanged_bytes(1_000_000, 16, compressed=True)
    assert 1.5 < base / comp < 1.7   # (8 vs 4+1.03) * (n-1)/n
