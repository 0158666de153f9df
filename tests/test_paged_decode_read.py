"""Differential tests of the dense MX paged decode read.

``attention_paged_decode``'s dense branch reads MX pages through
``layers._paged_mx_read`` (gathered element codes + per-block factors) and
``layers._sdpa_gqa_mx`` (the factors folded into the score and weighted-sum
contractions).  Here it is compared with the read it replaced,
``paged_cache_gather`` (dequantize the gathered context) + ``_sdpa_gqa``,
on the same pools.

Both compute the same products: every element value and every factor is
exact, and a factor is a power of two.  What differs is the order of the
f32 sums (per scale block, then across blocks), and so, rarely, the bf16
rounding of a softmax weight or of the output.  The tolerance is therefore
bf16 output precision: ``rtol = 2**-7`` (two ulps of bf16's 8 significant
bits) and ``atol = 2**-9`` (one ulp at the outputs' typical 0.25-0.5).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.core.formats import ALL_FORMATS
from repro.core.spec import QuantPolicy, QuantSpec
from repro.models import layers
from repro.models.config import ModelConfig

B, HQ, HKV, D, PAGE, NP = 4, 8, 2, 128, 8, 6
RTOL, ATOL = 2.0 ** -7, 2.0 ** -9
# slot 1 is idle: length 0 and a block-table row of trash page 0
LENGTHS = (41, 0, 13, 30)


def _cfg(key_spec: QuantSpec, value_spec: QuantSpec) -> ModelConfig:
    return ModelConfig(name="read", family="decoder", n_layers=1,
                       d_model=HQ * D, n_heads=HQ, n_kv_heads=HKV, d_ff=64,
                       vocab=32, head_dim=D,
                       mx=QuantPolicy(kv_key=key_spec, kv_value=value_spec))


def _pool(cfg: ModelConfig, seed: int = 0):
    """A page pool written through the program's own converter: each live
    slot's tokens land in shuffled private pages; page 0 is the trash
    page; the idle slot's row is all trash page."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * NP
    pool = layers.init_paged_kv_cache(cfg, n_pages, PAGE, HKV, D)
    perm = rng.permutation(np.arange(1, n_pages)).reshape(B, NP)
    bt = np.where(np.asarray(LENGTHS)[:, None] > 0, perm, 0).astype(np.int32)
    pos = np.arange(NP * PAGE)
    pages = jnp.asarray(bt[:, pos // PAGE])
    offsets = jnp.asarray(np.broadcast_to(pos % PAGE, (B, NP * PAGE)))
    k = jnp.asarray(rng.normal(size=(B, NP * PAGE, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, NP * PAGE, HKV, D)), jnp.float32)
    # live slots write their pages; the idle slot writes only the trash page
    pool = layers.paged_cache_write_tokens(pool, k, v, pages, offsets, cfg)
    q = jnp.asarray(rng.normal(size=(B, 1, HQ, D)), jnp.bfloat16)
    return q, pool, jnp.asarray(bt), jnp.asarray(LENGTHS, jnp.int32)


def _old(q, pool, bt, lengths, cfg):
    ka, va = layers.paged_cache_gather(pool, bt, cfg, q.dtype, D)
    mask = jnp.arange(ka.shape[1])[None, None, None, None, :] \
        <= lengths[:, None, None, None, None]
    return np.asarray(layers._sdpa_gqa(q, ka, va, mask), np.float32)


def _new(q, pool, bt, lengths, cfg):
    ke, kf = layers._paged_mx_read(pool, "kc_pages", "ks_pages",
                                   cfg.mx.kv_key, bt, D, jnp.float32)
    ve, vf = layers._paged_mx_read(pool, "vc_pages", "vs_pages",
                                   cfg.mx.kv_value, bt, D, q.dtype)
    return np.asarray(layers._sdpa_gqa_mx(q, ke, kf, ve, vf, lengths),
                      np.float32)


_CASES = [(QuantSpec(f.name, m), QuantSpec(f.name, m))
          for f in ALL_FORMATS for m in ("paper", "ocp")]
_CASES.append((QuantSpec("int8", "ocp"), QuantSpec("e2m1", "ocp")))


@pytest.mark.parametrize("key_spec,value_spec", _CASES, ids=str)
def test_factored_read_matches_dequantized_read(key_spec, value_spec):
    """All six formats x both modes, and int8 K with bit-packed e2m1 V;
    mixed per-slot lengths and an idle slot on the trash page."""
    cfg = _cfg(key_spec, value_spec)
    q, pool, bt, lengths = _pool(cfg, seed=len(str(key_spec)))
    old = _old(q, pool, bt, lengths, cfg)
    new = _new(q, pool, bt, lengths, cfg)
    assert np.isfinite(new).all()
    np.testing.assert_allclose(new, old, rtol=RTOL, atol=ATOL)


def test_each_slot_sees_only_its_own_length():
    """A slot's output depends on its positions <= its length only."""
    cfg = _cfg(QuantSpec("int8", "ocp"), QuantSpec("int8", "ocp"))
    q, pool, bt, lengths = _pool(cfg, seed=5)
    base = _new(q, pool, bt, lengths, cfg)
    # rewrite every position past slot 2's length (13) in its pages
    page, off = divmod(LENGTHS[2] + 1, PAGE)
    scrambled = dict(pool)
    for name in ("kc_pages", "vc_pages"):
        leaf = np.array(pool[name])
        rows = np.asarray(bt[2, page:])
        leaf[rows[0], off:] = 0x55
        leaf[rows[1:]] = 0x33
        scrambled[name] = jnp.asarray(leaf)
    out = _new(q, scrambled, bt, lengths, cfg)
    np.testing.assert_array_equal(out[2], base[2])


@pytest.mark.parametrize("side", ["k", "v"])
@pytest.mark.parametrize("mode,marker", [("ocp", F.SCALE_NAN),
                                         ("paper", F.SCALE_NAN),
                                         ("paper", F.SCALE_INF)])
def test_poison_scale_poisons_that_slot_only(mode, marker, side):
    """A poison scale byte in one live position of slot 2 makes slot 2's
    output non-finite, as the dequantized read does, and leaves every
    other slot as it was."""
    spec = QuantSpec("int8", mode)
    cfg = _cfg(spec, spec)
    q, pool, bt, lengths = _pool(cfg, seed=7)
    clean = _new(q, pool, bt, lengths, cfg)
    name = f"{side}s_pages"
    leaf = np.array(pool[name])
    pos = LENGTHS[2] // 2
    leaf[int(bt[2, pos // PAGE]), pos % PAGE, 1, 2] = marker
    poisoned = dict(pool, **{name: jnp.asarray(leaf)})
    new = _new(q, poisoned, bt, lengths, cfg)
    old = _old(q, poisoned, bt, lengths, cfg)
    assert not np.isfinite(new[2]).all()
    assert not np.isfinite(old[2]).all()
    others = [0, 1, 3]
    np.testing.assert_array_equal(new[others], clean[others])
    np.testing.assert_allclose(new[others], old[others], rtol=RTOL, atol=ATOL)
