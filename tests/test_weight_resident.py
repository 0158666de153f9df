"""Weight-resident serving: MXWeight storage, fused-kernel dispatch,
per-layer policy tables, and engine-level token identity + HBM accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ALL_FORMATS, MXWeight, QuantSpec, mx_dequantize,
                        mx_quantize, mx_weight_nbytes, pack_codes_rows,
                        params_nbytes, unpack_codes_rows)
from repro.kernels.backend import MATMUL_ENV_VAR, resolve_matmul_impl
from repro.kernels.ops import mx_matmul_resident
from repro.models import (Model, PolicyTable, QuantPolicy,
                          apply_policy_table, load_reduced)
from repro.models import decoder, layers as L
from repro.serve import ContinuousBatchingEngine, GenerationConfig

ALL_FMTS = [f.name for f in ALL_FORMATS]
SUB_BYTE = [(f.name, f.code_bits) for f in ALL_FORMATS if f.code_bits < 8]


# ------------------------------------------------------------- row packing
@pytest.mark.parametrize("fmt,bits", SUB_BYTE)
def test_pack_codes_rows_roundtrip(fmt, bits):
    rng = np.random.default_rng(0)
    for lead in [(), (3,)]:
        k, n = 96, 5
        c = jnp.asarray(rng.integers(0, 2 ** bits, size=lead + (k, n)),
                        jnp.uint8)
        p = pack_codes_rows(c, fmt)
        assert p.shape[:-2] == lead and p.shape[-1] == n
        assert p.shape[-2] == (k // 2 if bits == 4 else k // 4 * 3)
        back = unpack_codes_rows(p, fmt, k)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(c))


# --------------------------------------------------------------- container
@pytest.mark.parametrize("fmt", ALL_FMTS)
def test_mxweight_dequantize_matches_mx_quantize(fmt):
    """MXWeight.quantize (bit-packed storage) must round-trip to the exact
    same f32 weight as the plain mx_quantize/mx_dequantize pipeline — for
    2-D weights and for stacked 3-D MoE expert weights (take(i))."""
    rng = np.random.default_rng(1)
    spec = QuantSpec(fmt, "ocp", 32, True)
    w = jnp.asarray(rng.normal(size=(4, 64, 24)).astype(np.float32) * 0.1)
    mw = MXWeight.quantize(w, spec)
    ref = mx_dequantize(mx_quantize(w, QuantSpec(fmt, "ocp", 32, False),
                                    axis=1))
    np.testing.assert_array_equal(np.asarray(mw.dequantize()),
                                  np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(mw.take(2).dequantize()),
                                  np.asarray(ref[2]))
    # storage accounting: per-matrix analytic bytes times the expert dim
    assert mw.nbytes == 4 * mx_weight_nbytes(64, 24, spec)
    assert mw.packed == (spec.format.code_bits < 8)


def test_packed_e2m1_bits_per_weight():
    spec = QuantSpec("e2m1", "ocp", 32, True)
    k, n = 256, 64
    assert mx_weight_nbytes(k, n, spec) * 8 / (k * n) == 4.25
    rng = np.random.default_rng(2)
    mw = MXWeight.quantize(
        jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)), spec)
    assert mw.nbytes == mx_weight_nbytes(k, n, spec)


# ------------------------------------------------------- fused vs fallback
@pytest.mark.parametrize("fmt", ALL_FMTS)
@pytest.mark.parametrize("mode", ["paper", "ocp"])
def test_resident_fused_bitwise_matches_einsum(fmt, mode):
    """At single-k-tile shapes the fused kernel and the dequant-einsum
    fallback contract in the same order: outputs must be bit-identical,
    for both packed and unpacked storage."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=(5, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128, 40)).astype(np.float32) * 0.05)
    for packed in (True, False):
        mw = MXWeight.quantize(w, QuantSpec(fmt, mode, 32, packed))
        o_fused = mx_matmul_resident(a, mw, "fused")
        o_einsum = mx_matmul_resident(a, mw, "einsum")
        np.testing.assert_array_equal(np.asarray(o_fused),
                                      np.asarray(o_einsum))


def test_dense_dispatch_env_var(monkeypatch):
    monkeypatch.delenv(MATMUL_ENV_VAR, raising=False)
    assert resolve_matmul_impl() == "fused"
    monkeypatch.setenv(MATMUL_ENV_VAR, "einsum")
    assert resolve_matmul_impl() == "einsum"
    assert resolve_matmul_impl("fused") == "fused"   # explicit beats env
    with pytest.raises(ValueError, match="einsum"):
        resolve_matmul_impl("nope")

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
    mw = MXWeight.quantize(
        jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32)),
        "e4m3@32:ocp")
    y_einsum = L.dense(x, mw)                         # env says einsum
    monkeypatch.setenv(MATMUL_ENV_VAR, "fused")
    y_fused = L.dense(x, mw)
    np.testing.assert_array_equal(np.asarray(y_fused),
                                  np.asarray(y_einsum))


# ----------------------------------------------------------- policy tables
def test_policy_table_mixed_layer_quantization():
    """A non-uniform table quantizes each layer per its own spec: layer 0
    e4m3, layer 1 e2m1 (bit-packed), and a no-weights override stays fp."""
    table = PolicyTable(
        default=QuantPolicy.parse("weights=e4m3@32:ocp"),
        overrides=((1, QuantPolicy.parse("weights=e2m1@32:ocp")),))
    cfg = apply_policy_table(load_reduced("chatglm3_6b"), table)
    assert cfg.mx_table is not None
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qp = model.quantize_weights(params)
    layers = qp["layers"]
    assert isinstance(layers, list) and len(layers) == cfg.n_layers
    assert layers[0]["attn"]["wq"].fmt == "e4m3"
    assert not layers[0]["attn"]["wq"].packed
    assert layers[1]["attn"]["wq"].fmt == "e2m1"
    assert layers[1]["attn"]["wq"].packed

    # forward runs through the unrolled per-layer walk, and the env-var
    # impl flip is invisible in the outputs (single-k-tile bit identity)
    tok = jnp.asarray(np.arange(8, dtype=np.int32)[None, :] % cfg.vocab)
    logits_f, _ = decoder.forward(qp, tok, cfg)
    assert np.isfinite(np.asarray(logits_f)).all()

    fp_table = PolicyTable(default=table.default,
                           overrides=((1, QuantPolicy()),))
    cfg_fp = apply_policy_table(load_reduced("chatglm3_6b"), fp_table)
    qp2 = Model(cfg_fp).quantize_weights(params)
    assert isinstance(qp2["layers"][0]["attn"]["wq"], MXWeight)
    assert isinstance(qp2["layers"][1]["attn"]["wq"], jax.Array)


@pytest.mark.parametrize("policy", [
    "weights=e4m3@32:ocp", "weights=e2m1@32:ocp", "table"])
def test_init_quantized_matches_quantize_weights(policy):
    """Building MX weights layer by layer (the fp model never resident
    beside them) gives exactly ``quantize_weights(init(key))``: uniform
    stacked layers, packed sub-byte codes, and a per-layer table whose
    second layer stays fp."""
    if policy == "table":
        table = PolicyTable(default=QuantPolicy.parse("weights=e2m1@32:ocp"),
                            overrides=((1, QuantPolicy()),))
        cfg = apply_policy_table(load_reduced("chatglm3_6b"), table)
    else:
        cfg = load_reduced("chatglm3_6b", mx=QuantPolicy.parse(policy))
    model = Model(cfg)
    key = jax.random.PRNGKey(3)
    want = model.quantize_weights(model.init(key))
    got = model.init_quantized(key)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------- engine-level end-to-end
def test_engine_token_identity_and_weight_pool():
    """Weight-resident serving must emit the same tokens as serving the
    materialized (dequantized) weights, with a strictly smaller weight
    pool whose size matches the params_nbytes accounting."""
    cfg = load_reduced("chatglm3_6b",
                       mx=QuantPolicy.parse("weights=e4m3@32:ocp"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = model.quantize_weights(params)
    is_mx = lambda l: isinstance(l, MXWeight)                 # noqa: E731
    refparams = jax.tree_util.tree_map(
        lambda l: l.dequantize() if is_mx(l) else l, qparams, is_leaf=is_mx)

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (4, 7)]
    gen = GenerationConfig(max_new_tokens=4)
    outs = {}
    for name, p in [("resident", qparams), ("materialized", refparams)]:
        eng = ContinuousBatchingEngine(model, p, max_slots=2, page_size=8,
                                       max_len=16, gen=gen)
        for pr in prompts:
            eng.add_request(pr, 4)
        outs[name] = (eng.run(), eng.weight_pool_nbytes)
    toks_q, bytes_q = outs["resident"]
    toks_f, bytes_f = outs["materialized"]
    for r in toks_q:
        np.testing.assert_array_equal(toks_q[r], toks_f[r])
    assert bytes_q == params_nbytes(qparams)
    assert bytes_q < bytes_f
    # every quantized leaf matches the analytic spec.storage_nbytes bytes
    n_mx = 0
    for leaf in jax.tree_util.tree_leaves(qparams, is_leaf=is_mx):
        if is_mx(leaf):
            lead = int(np.prod(leaf.codes.shape[:-2], dtype=np.int64))
            assert leaf.nbytes == lead * mx_weight_nbytes(leaf.k, leaf.n,
                                                          leaf.spec)
            n_mx += 1
    assert n_mx > 0
