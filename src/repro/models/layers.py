"""Shared neural layers for the model zoo (functional JAX, no framework).

Every matmul routes through ``dense()``, which is where the paper's MX
converter plugs in, steered by the per-tensor-role ``QuantPolicy``:
  * training     — fake-quantization of weights (and optionally
                   activations) per the ``weights``/``activations`` roles;
  * serving      — weights stored as MXArray (uint8 codes + E8M0 scales),
                   dequantized on the fly => ~4x less weight HBM traffic;
  * KV caches    — quantized along head_dim per the ``kv_key``/``kv_value``
                   roles, which may carry *different* element formats
                   (e.g. INT8 keys + E2M1 values).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core.convert import (MXArray, decode_elements, mx_dequantize,
                                mx_quantize, pow2_f32, quantize_dequantize)
from repro.core.mx_weight import MXWeight
from repro.core.pack import pack_codes, unpack_codes
from repro.core.spec import QuantPolicy, QuantSpec
from repro.dist.sharding import (bf16_matmul_out_enabled, logical,
                                 model_axis_size, weight_gather_enabled,
                                 weight_gather_mode)
from repro.models import scopes
from repro.models.config import ModelConfig

Params = Dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def layer_scan(step, carry, xs, cfg: ModelConfig):
    """lax.scan over stacked layers; unrolled when cfg.scan_unroll (dry-run
    accounting mode — while-loop bodies are counted once by HLO cost
    analysis, so accounting lowers a small unrolled depth).  The accounting
    scale context makes kernel-cost records inside the body count once per
    layer (scan traces its body once)."""
    from repro.kernels import accounting
    depth = jax.tree_util.tree_leaves(xs)[0].shape[0]
    with accounting.scale(depth):
        return jax.lax.scan(step, carry, xs,
                            unroll=True if cfg.scan_unroll else 1)


# =============================================================================
# init helpers
# =============================================================================
def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# =============================================================================
# primitives
# =============================================================================
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _gather_spec(tp: str, rank: int):
    """FSDP use-point constraint: un-shard the 'data' dim of the weight so
    GSPMD inserts a per-layer weight all-gather (ZeRO-3) instead of
    k-parallel matmuls that all-reduce activations.  In pure-FSDP mode
    (weight_gather_mode() == "full") the whole weight is gathered and no
    dim stays TP-sharded."""
    lead = (None,) * (rank - 2)
    if weight_gather_mode() == "full" or tp == "none":
        return lead + (None, None)
    if tp == "row":
        return lead + ("model", None)
    return lead + (None, "model")          # col (default)


def _check_mx_row_gather(k: int, block: int, tp: str) -> None:
    """Row-parallel ("model" on K) gather of an MX weight shards the codes'
    K axis *and* the scales' K//block axis with the same spec.  ``logical``
    silently replicates any dim the mesh does not divide, so a K that
    shards while K//block does not would leave codes "model"-sharded but
    scales replicated — inconsistent layouts feeding one matmul.  Refuse
    loudly instead."""
    if tp != "row" or weight_gather_mode() == "full":
        return
    ms = model_axis_size()
    if ms <= 1:
        return
    kblk = k // block
    if k % ms == 0 and kblk % ms != 0:
        raise ValueError(
            f"row-parallel FSDP gather cannot shard this MX weight: the "
            f"codes' contraction axis (K={k}) divides the 'model' axis "
            f"size {ms}, but the scales' axis (K//block={kblk}, block="
            f"{block}) does not — pad K to a multiple of {ms * block} or "
            f"store this weight unquantized")


def dense(x: jax.Array, w, mx: Optional[QuantPolicy] = None,
          fake_quant: bool = False, tp: str = "col") -> jax.Array:
    """x @ w steered by the policy's ``weights``/``activations`` roles
    (see module docstring).

    ``tp`` is the tensor-parallel role of the weight: "col" shards the
    output dim over "model", "row" the input dim (Megatron convention).
    """
    gather = weight_gather_enabled()
    if fake_quant and mx is not None and mx.activations is not None:
        x = quantize_dequantize(x.astype(jnp.float32), mx.activations,
                                axis=-1).astype(x.dtype)
    if isinstance(w, MXWeight):
        # weight-resident serving: codes (possibly bit-packed) + scales go
        # straight to the fused kernel, which dequantizes tiles in VMEM —
        # fp weights are never materialized in HBM
        if gather:
            _check_mx_row_gather(w.kp, w.block, tp)
            spec = _gather_spec(tp, w.codes.ndim)
            w = dataclasses.replace(w, codes=logical(w.codes, *spec),
                                    scales=logical(w.scales, *spec))
        from repro.kernels.backend import resolve_matmul_impl
        if resolve_matmul_impl() == "fused":
            from repro.kernels.ops import mx_matmul_resident
            return mx_matmul_resident(x, w).astype(x.dtype)
        wd = w.dequantize().astype(x.dtype)
    elif isinstance(w, MXArray):
        # gather the *codes* (u8): the FSDP all-gather moves ~4x fewer
        # bytes than gathering f32/bf16 weights — the paper's converter as
        # a collective-compression lever
        if gather:
            _check_mx_row_gather(w.codes.shape[-2], w.block, tp)
            spec = _gather_spec(tp, w.codes.ndim)
            w = dataclasses.replace(w, codes=logical(w.codes, *spec),
                                    scales=logical(w.scales, *spec))
        wd = mx_dequantize(w).astype(x.dtype)
    else:
        if gather:
            w = logical(w, *_gather_spec(tp, w.ndim))
        if fake_quant and mx is not None and mx.weights is not None:
            wd = quantize_dequantize(w.astype(jnp.float32), mx.weights,
                                     axis=0).astype(x.dtype)
        else:
            wd = w.astype(x.dtype)
    # bf16 outputs halve TP partial-sum all-reduce payloads and f32
    # intermediate traffic; the MXU accumulates f32 internally either way
    pref = x.dtype if bf16_matmul_out_enabled() else jnp.float32
    y = jnp.einsum("...k,kn->...n", x, wd, preferred_element_type=pref)
    return y.astype(x.dtype)


def rope_tables(positions: jax.Array, dim: int, theta: float):
    """cos/sin tables (…, dim/2) in f32 for the given positions."""
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               rope_frac: float = 1.0) -> jax.Array:
    """Rotate the first ``rope_frac`` of the head dim (chatglm-style 2d RoPE
    rotates half).  x: (B, S, H, D); cos/sin: (B?, S, D_r/2)."""
    d = x.shape[-1]
    dr = int(d * rope_frac)
    dr -= dr % 2
    xr, xp = x[..., :dr], x[..., dr:]
    x1, x2 = xr[..., : dr // 2], xr[..., dr // 2:]
    c = cos[..., : dr // 2][:, :, None, :].astype(jnp.float32)
    s = sin[..., : dr // 2][:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


def softmax_f32(scores: jax.Array, axis: int = -1) -> jax.Array:
    return jax.nn.softmax(scores.astype(jnp.float32), axis=axis)


# =============================================================================
# KV cache (bf16 or MX; per-role key/value specs)
# =============================================================================
def _code_len(dim: int, block: int) -> int:
    return -(-dim // block) * block


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_kv: int, hd: int, layers_dim: Tuple[int, ...] = ()):
    """Allocate one attention layer's cache (optionally layer-stacked).
    K and V are sized per their policy roles (blocks may differ)."""
    kk, kv = cfg.mx.kv_key, cfg.mx.kv_value
    if kk is not None:
        def side(spec):
            cl = _code_len(hd, spec.block)
            codes = jnp.zeros(layers_dim + (batch, max_len, n_kv, cl),
                              jnp.uint8)
            scales = jnp.zeros(
                layers_dim + (batch, max_len, n_kv, cl // spec.block),
                jnp.uint8)
            return codes, scales

        kc, ks = side(kk)
        vc, vs = side(kv)
        return {"k_codes": kc, "k_scales": ks,
                "v_codes": vc, "v_scales": vs}
    shape = layers_dim + (batch, max_len, n_kv, hd)
    z = jnp.zeros(shape, dtype_of(cfg))
    return {"k": z, "v": z}


def _kv_quant(x: jax.Array, spec: QuantSpec) -> Tuple[jax.Array, jax.Array]:
    mx = mx_quantize(x.astype(jnp.float32), spec, axis=-1)
    return mx.codes, mx.scales


def _kv_dequant(codes: jax.Array, scales: jax.Array, spec: QuantSpec,
                dtype, orig_len: Optional[int] = None) -> jax.Array:
    mx = MXArray.from_spec(codes, scales, spec, orig_len=orig_len,
                           axis=codes.ndim - 1)
    return mx_dequantize(mx).astype(dtype)


@scopes.scoped("kv_write")
def cache_write(cache, k: jax.Array, v: jax.Array, pos, cfg: ModelConfig):
    """Write k/v (B, s, n_kv, hd) into the cache at position ``pos``.

    k/v arrive head-sharded over "model" (col-parallel projections); the
    cache is stored batch-sharded/model-replicated so decode reads never
    all-gather the full cache — only the one-token update is gathered."""
    k = logical(k, "kv_batch", None, None, None)
    v = logical(v, "kv_batch", None, None, None)
    if cfg.mx.kv_key is not None:
        kc, ks = _kv_quant(k, cfg.mx.kv_key)
        vc, vs = _kv_quant(v, cfg.mx.kv_value)
        upd = dict(k_codes=kc, k_scales=ks, v_codes=vc, v_scales=vs)
        out = {}
        for name, val in upd.items():
            tgt = cache[name]
            idx = (0, pos) + (0,) * (tgt.ndim - 2)
            out[name] = jax.lax.dynamic_update_slice(tgt, val, idx)
        return out
    idx = (0, pos, 0, 0)
    return {"k": jax.lax.dynamic_update_slice(cache["k"], k.astype(
                cache["k"].dtype), idx),
            "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(
                cache["v"].dtype), idx)}


@scopes.scoped("kv_read")
def cache_read(cache, cfg: ModelConfig, dtype, hd: Optional[int] = None):
    if cfg.mx.kv_key is not None:
        k = _kv_dequant(cache["k_codes"], cache["k_scales"], cfg.mx.kv_key,
                        dtype, hd)
        v = _kv_dequant(cache["v_codes"], cache["v_scales"],
                        cfg.mx.kv_value, dtype, hd)
        return k, v
    return cache["k"].astype(dtype), cache["v"].astype(dtype)


# =============================================================================
# Paged KV cache (continuous batching)
# =============================================================================
def init_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                        n_kv: int, hd: int,
                        layers_dim: Tuple[int, ...] = ()):
    """Allocate one attention layer's page pool (optionally layer-stacked).

    MX layout packs sub-byte element codes via repro.core.pack (when the
    role's spec says ``packed``), so an FP4 pool really is ~4x smaller
    than bf16 in HBM — and K/V pools are sized per their own roles, so
    INT8 keys can share an engine with half-size E2M1 value pages.
    Page 0 is reserved by the serving engine as the trash page (inactive
    slots write there)."""
    kk, kv = cfg.mx.kv_key, cfg.mx.kv_value
    if kk is not None:
        def side(spec):
            cl = _code_len(hd, spec.block)
            cb = spec.storage_nbytes(cl)
            codes = jnp.zeros(
                layers_dim + (num_pages, page_size, n_kv, cb), jnp.uint8)
            scales = jnp.zeros(
                layers_dim + (num_pages, page_size, n_kv, cl // spec.block),
                jnp.uint8)
            return codes, scales

        kc, ks = side(kk)
        vc, vs = side(kv)
        return {"kc_pages": kc, "ks_pages": ks,
                "vc_pages": vc, "vs_pages": vs}
    # distinct buffers per key: the serving engine donates the pool into
    # its jitted step, and aliased leaves would be donated twice
    shape = layers_dim + (num_pages, page_size, n_kv, hd)
    return {"k_pages": jnp.zeros(shape, dtype_of(cfg)),
            "v_pages": jnp.zeros(shape, dtype_of(cfg))}


def paged_page_size(pool) -> int:
    leaf = pool.get("kc_pages", pool.get("k_pages"))
    return leaf.shape[-3]


# pool key -> (contiguous prefill-cache key, element-code policy role)
PAGED_POOL_KEYS = {
    "kc_pages": ("k_codes", "kv_key"), "ks_pages": ("k_scales", None),
    "vc_pages": ("v_codes", "kv_value"), "vs_pages": ("v_scales", None),
    "k_pages": ("k", None), "v_pages": ("v", None),
}


@scopes.scoped("kv_write")
def paged_cache_scatter(pool, cache, page_ids, cfg: ModelConfig):
    """Scatter a *batched* contiguous prefill cache into the page pool.

    ``pool``/``cache`` are one layer group's dicts (optionally
    layer-stacked on a leading axis); cache leaves are (…, G, Lp, n_kv, X)
    for G prefilled requests padded to the same Lp (a page multiple).
    ``page_ids`` (G, npr) names the physical page of each (request, logical
    page); rows are padded with the trash page where a request's padded
    prompt exceeds its allocation, so bucket padding never touches live
    pages.  Sub-byte codes are bit-packed per role on the way — once, on
    device — and all G requests' pages land in a single scatter per leaf.
    """
    policy = cfg.mx
    g, npr = page_ids.shape
    flat = page_ids.reshape(-1)
    page = paged_page_size(pool)
    out = {}
    for pk, leaf in pool.items():
        ck, role = PAGED_POOL_KEYS[pk]
        val = cache[ck]
        stacked = val.ndim == 5          # (n_scan, G, Lp, n_kv, X)
        spec = policy.role(role) if role is not None else None
        if spec is not None and spec.packed:
            val = pack_codes(val, spec.fmt)
        lead = val.shape[:-4] if stacked else ()
        val = val.reshape(lead + (g * npr, page) + val.shape[-2:])
        out[pk] = leaf.at[:, flat].set(val) if stacked \
            else leaf.at[flat].set(val)
    return out


@scopes.scoped("kv_write")
def paged_cache_write(pool, k: jax.Array, v: jax.Array, pages: jax.Array,
                      offsets: jax.Array, cfg: ModelConfig):
    """Scatter one token per slot into the page pool.

    k/v (B, 1, n_kv, hd); pages/offsets (B,) i32 — slot b's token lands at
    pool[pages[b], offsets[b]].  Distinct active slots own distinct pages,
    so the scatter indices never collide except on the trash page."""
    kk, kv = cfg.mx.kv_key, cfg.mx.kv_value
    if kk is not None:
        kc, ks = _kv_quant(k, kk)
        vc, vs = _kv_quant(v, kv)
        if kk.packed:
            kc = pack_codes(kc, kk.fmt)
        if kv.packed:
            vc = pack_codes(vc, kv.fmt)
        upd = dict(kc_pages=kc, ks_pages=ks, vc_pages=vc, vs_pages=vs)
        return {name: logical(pool[name].at[pages, offsets].set(val[:, 0]),
                              "kv_pages", None, None, None)
                for name, val in upd.items()}
    dt = pool["k_pages"].dtype
    return {"k_pages": logical(pool["k_pages"].at[pages, offsets].set(
                k[:, 0].astype(dt)), "kv_pages", None, None, None),
            "v_pages": logical(pool["v_pages"].at[pages, offsets].set(
                v[:, 0].astype(dt)), "kv_pages", None, None, None)}


@scopes.scoped("kv_write")
def paged_cache_write_tokens(pool, k: jax.Array, v: jax.Array,
                             pages: jax.Array, offsets: jax.Array,
                             cfg: ModelConfig):
    """Scatter a multi-token span per slot into the page pool (suffix
    prefill under prefix caching).

    k/v (G, S, n_kv, hd); pages/offsets (G, S) i32 — request g's token i
    lands at pool[pages[g, i], offsets[g, i]].  Padded positions must be
    routed to the trash page by the caller; live (page, offset) pairs
    never collide across requests because every written page is private
    to its slot (shared pages were copy-on-write forked first)."""
    kk, kv = cfg.mx.kv_key, cfg.mx.kv_value
    if kk is not None:
        kc, ks = _kv_quant(k, kk)
        vc, vs = _kv_quant(v, kv)
        if kk.packed:
            kc = pack_codes(kc, kk.fmt)
        if kv.packed:
            vc = pack_codes(vc, kv.fmt)
        upd = dict(kc_pages=kc, ks_pages=ks, vc_pages=vc, vs_pages=vs)
        return {name: logical(pool[name].at[pages, offsets].set(val),
                              "kv_pages", None, None, None)
                for name, val in upd.items()}
    dt = pool["k_pages"].dtype
    return {"k_pages": logical(pool["k_pages"].at[pages, offsets].set(
                k.astype(dt)), "kv_pages", None, None, None),
            "v_pages": logical(pool["v_pages"].at[pages, offsets].set(
                v.astype(dt)), "kv_pages", None, None, None)}


@scopes.scoped("kv_read")
def paged_cache_gather(pool, block_tables: jax.Array, cfg: ModelConfig,
                       dtype, hd: int) -> Tuple[jax.Array, jax.Array]:
    """Gather a slot-major contiguous (B, max_pages*page, n_kv, hd) K/V view
    through the block table, dequantized to ``dtype``.

    Serves the paged suffix prefill (``attention_paged_prefill``) and bf16
    pools.  The prefill keeps it because it attends exactly the values the
    contiguous prefill attends (``cache_read`` + ``_sdpa_gqa``), which is
    what makes a shared-prefix suffix prefill bit-identical to the full
    one.  The MX decode read is ``_paged_mx_read`` + ``_sdpa_gqa_mx``."""
    b, np_max = block_tables.shape
    if cfg.mx.kv_key is not None:
        def one(codes_key, scales_key, spec):
            cl = _code_len(hd, spec.block)
            c = pool[codes_key][block_tables]   # (B, np, page, n_kv, CB)
            c = c.reshape((b, -1) + c.shape[3:])
            if spec.packed:
                c = unpack_codes(c, spec.fmt, cl)
            s = pool[scales_key][block_tables]
            s = s.reshape((b, -1) + s.shape[3:])
            return _kv_dequant(c, s, spec, dtype, hd)

        return (one("kc_pages", "ks_pages", cfg.mx.kv_key),
                one("vc_pages", "vs_pages", cfg.mx.kv_value))
    k = pool["k_pages"][block_tables]
    v = pool["v_pages"][block_tables]
    k = k.reshape((b, -1) + k.shape[3:])
    v = v.reshape((b, -1) + v.shape[3:])
    return k.astype(dtype), v.astype(dtype)


def _gather_pages_head_major(leaf: jax.Array,
                             block_tables: jax.Array) -> jax.Array:
    """Gather one pool leaf (P, page, n_kv, X) through the block table into
    (B, n_kv, max_pages*page, X).  Pages and heads are indexed together, so
    each head's context comes out as one run: the layout the per-head
    contractions read, with no relayout of the gathered view."""
    b = block_tables.shape[0]
    nkv = leaf.shape[2]
    out = jnp.swapaxes(leaf, 1, 2)[block_tables[:, None, :],
                                   jnp.arange(nkv)[None, :, None]]
    return out.reshape(b, nkv, -1, leaf.shape[-1])


@scopes.scoped("kv_read")
def _paged_mx_read(pool, codes_key: str, scales_key: str, spec: QuantSpec,
                   block_tables: jax.Array, hd: int, factor_dtype
                   ) -> Tuple[jax.Array, jax.Array]:
    """One side (K or V) of the dense MX paged decode read, kept factored.

    Returns the gathered element values ``elem`` (B, n_kv, S, CL) in bf16
    and the per-block factors ``factor`` (B, n_kv, CL // block, S), with
    ``elem * factor`` (each factor over its block's lanes) equal to
    ``mx_dequantize``'s values.  Every MX element value is exact in bf16
    (INT8 as its integer, its 2^-6 moved into the factor), and a factor is
    a power of two, so the product is what ``mx_dequantize(...).astype(
    bf16)`` gives.  A scale byte at the mode's poison threshold
    (``SCALE_NAN``; paper ``SCALE_INF`` too) makes its factor NaN, which
    poisons the slot's output as the dequantized NaN/Inf block does.  The
    factors are gathered as one lane-dense row per page and laid out with
    positions along the lanes, as the score and weight contractions use
    them; no dequantized context is ever written."""
    fmt = spec.format
    codes = pool[codes_key]
    if fmt.is_int and spec.mode == "ocp":
        # two's complement: reinterpret before the gather, so what is left
        # of the decode is the s8 -> bf16 convert the contraction fuses
        codes = jax.lax.bitcast_convert_type(codes, jnp.int8)
    c = _gather_pages_head_major(codes, block_tables)
    if spec.packed:
        c = unpack_codes(c, spec.fmt, _code_len(hd, spec.block))
    if fmt.is_int:
        if spec.mode == "paper":                 # sign-magnitude 1.6
            ci = c.astype(jnp.int32)
            c = jnp.where((ci >> 7) == 1, -(ci & 0x7F), ci & 0x7F)
        elem, shift = c.astype(jnp.bfloat16), -fmt.int_frac_bits
    else:
        elem, shift = decode_elements(c, fmt, spec.mode).astype(
            jnp.bfloat16), 0
    s = pool[scales_key]                         # (P, page, n_kv, nb)
    n_pool, page, nkv, nb = s.shape
    b, np_max = block_tables.shape
    rows = s.reshape(n_pool, page * nkv * nb)[block_tables]
    factor = jnp.where(rows >= F.poison_threshold(spec.mode), jnp.nan,
                       pow2_f32(rows.astype(jnp.int32)
                                - F.SCALE_BIAS + shift))
    factor = factor.astype(factor_dtype).reshape(b, np_max, page, nkv, nb)
    factor = jnp.transpose(factor, (0, 3, 4, 1, 2)).reshape(
        b, nkv, nb, np_max * page)
    return (logical(elem, "kv_batch", None, None, None),
            logical(factor, "kv_batch", None, None, None))


def _block_lanes(nb: int, width: int) -> jax.Array:
    """(nb, width) bool: lane d belongs to scale block j."""
    return (jnp.arange(width)[None, :] // (width // nb)
            == jnp.arange(nb)[:, None])


@scopes.scoped("attn_core")
def _sdpa_gqa_mx(q, k_elem, k_factor, v_elem, v_factor,
                 lengths) -> jax.Array:
    """One-token GQA attention over factored MX K/V (``_paged_mx_read``).

    q (B, 1, Hq, D); elements (B, Hkv, S, CL); factors (B, Hkv, nb, S).
    The block factors are folded into the contractions instead of into
    the context: the score contraction runs once per K scale block (the
    query masked to that block's lanes), and each block's partial score is
    scaled by its key's factor and summed; the bf16 softmax weights are
    scaled by each value block's factor before the weighted sum, and each
    output lane keeps its own block's sum.  Factors are powers of two, so
    bf16(p) * f == bf16(p * f) and both sums carry the products of
    ``_sdpa_gqa`` over dequantized K/V, summed in another order.  Slot b
    attends positions <= lengths[b]; scores and softmax are f32."""
    b, _, hq, hd = q.shape
    hkv, sk, cl = k_elem.shape[1:]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, hd)
    if cl > hd:                                  # codes padded to a block
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, cl - hd)))
    kb = _block_lanes(k_factor.shape[2], cl)
    qb = qg[:, :, :, None, :] * kb.astype(q.dtype)        # (B, G, R, nb, CL)
    part = jnp.einsum("bgrjd,bgkd->bgrjk", qb, k_elem,
                      preferred_element_type=jnp.float32)
    scores = jnp.einsum("bgrjk,bgjk->bgrk", part, k_factor) \
        * (1.0 / np.sqrt(hd))
    mask = jnp.arange(sk)[None, None, None, :] \
        <= lengths[:, None, None, None]
    probs = softmax_f32(jnp.where(mask, scores, -1e30)).astype(q.dtype)
    weights = probs[:, :, :, None, :] * v_factor[:, :, None]
    out = jnp.einsum("bgrjk,bgkd->bgrjd", weights, v_elem,
                     preferred_element_type=jnp.float32)
    vb = _block_lanes(v_factor.shape[2], v_elem.shape[-1])
    out = jnp.sum(jnp.where(vb, out, 0.0), axis=3)[..., :hd]
    return out.astype(q.dtype).reshape(b, 1, hq, hd)


def attention_paged_decode(p: Params, x: jax.Array, cfg: ModelConfig, *,
                           pool, block_tables: jax.Array,
                           lengths: jax.Array, fake_quant: bool = False
                           ) -> Tuple[jax.Array, Any]:
    """GQA decode over the paged KV cache: x (B, 1, d); slot b's new token
    sits at logical position lengths[b] and attends positions <= lengths[b].
    Inactive slots (lengths 0, zeroed block-table row) write to the trash
    page and their outputs are discarded by the engine."""
    b, s, d = x.shape                          # s == 1
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    mx = cfg.mx
    with scopes.scope("attn_qkv"):
        q = dense(x, p["wq"], mx, fake_quant)
        q = logical(q, "batch", None, "model").reshape(b, s, nh, hd)
        k = dense(x, p["wk"], mx, fake_quant).reshape(b, s, nkv, hd)
        v = dense(x, p["wv"], mx, fake_quant).reshape(b, s, nkv, hd)
        positions = lengths[:, None]
        cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rope_frac)
        k = apply_rope(k, cos, sin, cfg.rope_frac)
    page = paged_page_size(pool)
    with scopes.scope("kv_write"):
        pages = jnp.take_along_axis(
            block_tables, (lengths // page)[:, None], axis=1)[:, 0]
    pool = paged_cache_write(pool, k, v, pages, lengths % page, cfg)
    q = logical(q, "kv_batch", None, None, None)
    out = None
    if cfg.mx.kv_key is not None and cfg.attn_impl == "flash":
        from repro.kernels import backend
        from repro.kernels.ops import mx_paged_decode_attention_ctx
        # supervised dispatch: a degraded kernel (injected fault) returns
        # None and the dense read below serves token-identically
        with scopes.scope("attn_core"):
            out = backend.supervised(
                "paged_attn", mx_paged_decode_attention_ctx, q, pool,
                block_tables, lengths, cfg)
    if out is None and cfg.mx.kv_key is not None:
        # dense MX read: gathered codes and block factors, dequantized
        # inside the contractions (slot-sharded, as the gathered view was)
        ke, kf = _paged_mx_read(pool, "kc_pages", "ks_pages", cfg.mx.kv_key,
                                block_tables, hd, jnp.float32)
        ve, vf = _paged_mx_read(pool, "vc_pages", "vs_pages",
                                cfg.mx.kv_value, block_tables, hd, x.dtype)
        out = _sdpa_gqa_mx(q, ke, kf, ve, vf, lengths)
    if out is None:
        ka, va = paged_cache_gather(pool, block_tables, cfg, x.dtype, hd)
        # keep the gathered view slot-sharded (decode reads stay local);
        # without this GSPMD may replicate the full gathered KV per rank
        ka = logical(ka, "kv_batch", None, None, None)
        va = logical(va, "kv_batch", None, None, None)
        sk = ka.shape[1]
        mask = jnp.arange(sk)[None, None, None, None, :] \
            <= lengths[:, None, None, None, None]
        out = _sdpa_gqa(q, ka, va, mask)
    with scopes.scope("attn_out"):
        out = out.reshape(b, s, nh * hd)
        out = dense(out, p["wo"], mx, fake_quant, tp="row")
    return logical(out, "batch", None, None), pool


def attention_paged_prefill(p: Params, x: jax.Array, cfg: ModelConfig, *,
                            pool, block_tables: jax.Array,
                            starts: jax.Array, prompt_lens: jax.Array,
                            trash_page: int = 0,
                            fake_quant: bool = False
                            ) -> Tuple[jax.Array, Any]:
    """GQA prefill of an uncached prompt *suffix* over the paged KV cache
    (prefix sharing): x (G, S, d) holds request g's prompt tokens from
    position ``starts[g]`` (padded past ``prompt_lens[g] - starts[g]``).

    The suffix k/v are written into the slot's private pages first, then
    every query attends the *gathered dequantized* page view — prefix
    positions come from the shared (read-only) pages, suffix positions
    from the bytes just written.  The contiguous prefill attends the same
    dequantized values under an MX policy (see ``attention``), so a
    shared-prefix suffix prefill is bit-identical to the full one.  This
    path is dense on purpose: the flash prefill kernel's online softmax is
    only allclose-level vs ``_sdpa_gqa``, and prefix caching promises
    token identity, not tolerance.

    Padded positions write to ``trash_page`` and their logits are garbage
    the engine never reads."""
    g, s, d = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    mx = cfg.mx
    with scopes.scope("attn_qkv"):
        q = dense(x, p["wq"], mx, fake_quant)
        q = logical(q, "batch", None, "model").reshape(g, s, nh, hd)
        k = dense(x, p["wk"], mx, fake_quant).reshape(g, s, nkv, hd)
        v = dense(x, p["wv"], mx, fake_quant).reshape(g, s, nkv, hd)
        positions = starts[:, None] + jnp.arange(s)[None, :]    # (G, S)
        cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rope_frac)
        k = apply_rope(k, cos, sin, cfg.rope_frac)
    page = paged_page_size(pool)
    np_max = block_tables.shape[1]
    with scopes.scope("kv_write"):
        valid = positions < prompt_lens[:, None]
        page_idx = jnp.clip(positions // page, 0, np_max - 1)
        pages = jnp.where(
            valid, jnp.take_along_axis(block_tables, page_idx, axis=1),
            trash_page)
    pool = paged_cache_write_tokens(pool, k, v, pages, positions % page,
                                    cfg)
    q = logical(q, "kv_batch", None, None, None)
    ka, va = paged_cache_gather(pool, block_tables, cfg, x.dtype, hd)
    ka = logical(ka, "kv_batch", None, None, None)
    va = logical(va, "kv_batch", None, None, None)
    sk = ka.shape[1]
    mask = jnp.arange(sk)[None, None, None, None, :] \
        <= positions[:, None, None, :, None]
    out = _sdpa_gqa(q, ka, va, mask)
    with scopes.scope("attn_out"):
        out = out.reshape(g, s, nh * hd)
        out = dense(out, p["wo"], mx, fake_quant, tp="row")
    return logical(out, "batch", None, None), pool


# =============================================================================
# GQA attention
# =============================================================================
def attn_init(key, cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    return {
        "wq": dense_init(ks[0], d, nh * hd, dt),
        "wk": dense_init(ks[1], d, nkv * hd, dt),
        "wv": dense_init(ks[2], d, nkv * hd, dt),
        "wo": dense_init(ks[3], nh * hd, d, dt),
    }


@scopes.scoped("attn_core")
def _sdpa_gqa(q, k, v, mask) -> jax.Array:
    """Grouped-query attention without materializing repeated K/V.

    q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D); Hq = Hkv * rep.  mask broadcastable to
    (B, 1, 1, Sq, Sk).  Grouped einsums keep K/V in their stored layout —
    no (B,Sk,Hq,D) expansion ever hits HBM.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    qg = q.reshape(b, sq, hkv, rep, d)
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, -1e30)
    probs = softmax_f32(scores).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, sq, hq, d)


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array, causal: bool = True,
              cache=None, cache_pos=None, fake_quant: bool = False,
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
              tap: Optional[dict] = None) -> Tuple[jax.Array, Any]:
    """GQA attention.  Full mode (cache=None): self-attention over x.
    Decode mode: x is (B,1,d), cache holds S_max past k/v, cache_pos scalar.
    ``kv_override`` serves cross-attention (k/v from the encoder).
    ``tap`` (calibration hook): a dict the post-RoPE, pre-quantization
    k/v land in — exactly the tensors the ``kv_key``/``kv_value`` policy
    roles will quantize (see repro.calib.stats)."""
    b, s, d = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    mx = cfg.mx
    quant_prefill = False
    with scopes.scope("attn_qkv"):
        q = dense(x, p["wq"], mx, fake_quant)
        q = logical(q, "batch", None, "model")
        q = q.reshape(b, s, nh, hd)
        if kv_override is None:
            k = dense(x, p["wk"], mx, fake_quant).reshape(b, s, nkv, hd)
            v = dense(x, p["wv"], mx, fake_quant).reshape(b, s, nkv, hd)
            cos, sin = rope_tables(positions, hd, cfg.rope_theta)
            q = apply_rope(q, cos, sin, cfg.rope_frac)
            k = apply_rope(k, cos, sin, cfg.rope_frac)
            if tap is not None:
                tap["k"], tap["v"] = k, v
        else:
            k, v = kv_override
    new_cache = cache
    if cache is not None and kv_override is None:
        new_cache = cache_write(cache, k, v,
                                0 if cache_pos is None else cache_pos, cfg)
        if s == 1:
            # decode: attend over the (possibly MX-quantized) cache.
            # GQA decode compute is tiny; replicate it over "model" so the
            # cache (batch-sharded) is never all-gathered — otherwise GSPMD
            # kv-subgroup-shards the read and gathers the full cache to
            # honor the cache's replicated output contract.
            q = logical(q, "kv_batch", None, None, None)
            if cfg.mx.kv_key is not None and cfg.attn_impl == "flash":
                # fused path: the u8 cache never leaves HBM un-quantized —
                # dequant happens in VMEM inside the kernel
                from repro.kernels.ops import mx_decode_attention_ctx
                with scopes.scope("attn_core"):
                    ofused = mx_decode_attention_ctx(q, new_cache,
                                                     cache_pos, cfg)
                if ofused is not None:
                    with scopes.scope("attn_out"):
                        out = ofused.reshape(b, s, nh * hd)
                        out = dense(out, p["wo"], mx, fake_quant, tp="row")
                    return logical(out, "batch", None, None), new_cache
            k, v = cache_read(new_cache, cfg, x.dtype, hd)
            k = logical(k, "kv_batch", None, None, None)
            v = logical(v, "kv_batch", None, None, None)
            sk = k.shape[1]
            kpos = jnp.arange(sk)
            mask = (kpos[None, None, None, None, :] <= cache_pos)
        else:
            # prefill: the cache keeps the quantized copy for subsequent
            # decode steps.  Under an MX policy, attend the *dequantized*
            # cache view rather than the fresh k/v: suffix-only prefill
            # over shared prefix pages (repro.serve prefix caching) can
            # only read quantized bytes, so attending them here too keeps
            # full and suffix prefill bit-identical.  An fp cache
            # round-trips exactly — the fresh path stands.
            if cfg.mx.kv_key is not None:
                kq, vq = cache_read(new_cache, cfg, x.dtype, hd)
                k, v = kq[:, :s], vq[:, :s]
                quant_prefill = True
            sk = k.shape[1]
            qpos = jnp.arange(s)
            kpos = jnp.arange(sk)
            mask = kpos[None, None, None, None, :] \
                <= qpos[None, None, None, :, None]
    else:
        sk = k.shape[1]
        if causal:
            qpos = jnp.arange(s)
            kpos = jnp.arange(sk)
            mask = kpos[None, None, None, None, :] \
                <= qpos[None, None, None, :, None]
        else:
            mask = jnp.ones((1, 1, 1, s, sk), bool)
    out = None
    # quantize-aware prefill stays dense: the paged suffix-prefill path it
    # must match bit-for-bit is dense, and the flash kernel's online
    # softmax is only allclose-level against _sdpa_gqa
    if cfg.attn_impl == "flash" and causal and s > 1 \
            and s == k.shape[1] and not quant_prefill:
        from repro.kernels.ops import flash_attention_ctx
        with scopes.scope("attn_core"):
            out = flash_attention_ctx(q, k, v, causal=True)
    if out is None:
        out = _sdpa_gqa(q, k, v, mask)
    with scopes.scope("attn_out"):
        out = out.reshape(b, s, nh * hd)
        out = dense(out, p["wo"], mx, fake_quant, tp="row")
    return logical(out, "batch", None, None), new_cache


# =============================================================================
# MLA attention (deepseek-v2): compressed KV cache + absorbed decode
# =============================================================================
def mla_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    nh = cfg.n_heads
    dq = cfg.q_lora or d
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    p = {
        "w_dkv": dense_init(ks[0], d, cfg.kv_lora, dt),
        "w_kr": dense_init(ks[1], d, cfg.qk_rope_dim, dt),
        "w_uk": dense_init(ks[2], cfg.kv_lora, nh * cfg.qk_nope_dim, dt),
        "w_uv": dense_init(ks[3], cfg.kv_lora, nh * cfg.v_head_dim, dt),
        "wo": dense_init(ks[4], nh * cfg.v_head_dim, d, dt),
        "kv_norm": jnp.ones((cfg.kv_lora,), dt),
    }
    if cfg.q_lora:
        p["w_dq"] = dense_init(ks[5], d, dq, dt)
        p["q_norm"] = jnp.ones((dq,), dt)
        p["w_uq"] = dense_init(ks[6], dq, nh * qk, dt)
    else:
        p["w_uq"] = dense_init(ks[6], d, nh * qk, dt)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   layers_dim: Tuple[int, ...] = ()):
    """MLA caches the compressed c_kv (kv_lora) + shared k_rope — 576 values
    per token instead of 2*H*hd = 32768; optionally MX-quantized.  The
    compressed cache has no separate K/V tensors, so it follows the
    ``kv_key`` role's spec."""
    dt = dtype_of(cfg)
    ckv = layers_dim + (batch, max_len, cfg.kv_lora)
    krs = layers_dim + (batch, max_len, cfg.qk_rope_dim)
    spec = cfg.mx.kv_key
    if spec is not None:
        cl = _code_len(cfg.kv_lora, spec.block)
        clr = _code_len(cfg.qk_rope_dim, spec.block)
        return {"ckv_codes": jnp.zeros(
                    layers_dim + (batch, max_len, cl), jnp.uint8),
                "ckv_scales": jnp.zeros(
                    layers_dim + (batch, max_len, cl // spec.block),
                    jnp.uint8),
                "kr_codes": jnp.zeros(
                    layers_dim + (batch, max_len, clr), jnp.uint8),
                "kr_scales": jnp.zeros(
                    layers_dim + (batch, max_len, clr // spec.block),
                    jnp.uint8)}
    return {"ckv": jnp.zeros(ckv, dt), "kr": jnp.zeros(krs, dt)}


def _q_heads(p, x, cfg, fake_quant):
    b, s, _ = x.shape
    nh = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    mx = cfg.mx
    if cfg.q_lora:
        cq = dense(x, p["w_dq"], mx, fake_quant)
        cq = rms_norm(cq, p["q_norm"], cfg.norm_eps)
        q = dense(cq, p["w_uq"], mx, fake_quant)
    else:
        q = dense(x, p["w_uq"], mx, fake_quant)
    q = logical(q, "batch", None, "model")
    return q.reshape(b, s, nh, qk)


def mla_attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
                  positions: jax.Array, cache=None, cache_pos=None,
                  fake_quant: bool = False) -> Tuple[jax.Array, Any]:
    """Full (train/prefill) path: materialize per-head k/v from c_kv."""
    b, s, d = x.shape
    nh, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                      cfg.v_head_dim)
    mx = cfg.mx
    q = _q_heads(p, x, cfg, fake_quant)
    qn, qr = q[..., :dn], q[..., dn:]
    ckv = dense(x, p["w_dkv"], mx, fake_quant)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    kr = dense(x, p["w_kr"], mx, fake_quant).reshape(b, s, 1, dr)
    cos, sin = rope_tables(positions, dr, cfg.rope_theta)
    qr = apply_rope(qr, cos, sin)
    kr = apply_rope(kr, cos, sin)
    kn = dense(ckv, p["w_uk"], mx, fake_quant).reshape(b, s, nh, dn)
    v = dense(ckv, p["w_uv"], mx, fake_quant).reshape(b, s, nh, dv)
    scale = 1.0 / np.sqrt(dn + dr)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", qr, kr[:, :, 0, :],
                           preferred_element_type=jnp.float32)) * scale
    qpos = jnp.arange(s)
    kpos = jnp.arange(s)
    mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = softmax_f32(scores).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = dense(out.reshape(b, s, nh * dv), p["wo"], mx, fake_quant,
                tp="row")
    new_cache = cache
    if cache is not None:
        new_cache = _mla_cache_write(cache, ckv, kr[:, :, 0, :], cache_pos
                                     if cache_pos is not None else 0, cfg)
    return logical(out, "batch", None, None), new_cache


def _mla_cache_write(cache, ckv, kr, pos, cfg):
    ckv = logical(ckv, "kv_batch", None, None)
    kr = logical(kr, "kv_batch", None, None)
    if cfg.mx.kv_key is not None:
        cc, cs = _kv_quant(ckv, cfg.mx.kv_key)
        kc, kss = _kv_quant(kr, cfg.mx.kv_key)
        out = {}
        for name, val in dict(ckv_codes=cc, ckv_scales=cs, kr_codes=kc,
                              kr_scales=kss).items():
            tgt = cache[name]
            idx = (0, pos) + (0,) * (tgt.ndim - 2)
            out[name] = jax.lax.dynamic_update_slice(tgt, val, idx)
        return out
    return {"ckv": jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0)),
            "kr": jax.lax.dynamic_update_slice(
                cache["kr"], kr.astype(cache["kr"].dtype), (0, pos, 0))}


def _mla_cache_read(cache, cfg, dtype):
    if cfg.mx.kv_key is not None:
        ckv = _kv_dequant(cache["ckv_codes"], cache["ckv_scales"],
                          cfg.mx.kv_key, dtype, cfg.kv_lora)
        kr = _kv_dequant(cache["kr_codes"], cache["kr_scales"],
                         cfg.mx.kv_key, dtype, cfg.qk_rope_dim)
        return ckv, kr
    return cache["ckv"].astype(dtype), cache["kr"].astype(dtype)


def mla_decode(p: Params, x: jax.Array, cfg: ModelConfig, *,
               cache, cache_pos, fake_quant: bool = False
               ) -> Tuple[jax.Array, Any]:
    """Absorbed MLA decode: scores/outputs computed against the compressed
    cache directly (never materializes per-head K/V for past tokens)."""
    b, s, d = x.shape                      # s == 1
    nh, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                      cfg.v_head_dim)
    mx = cfg.mx
    q = _q_heads(p, x, cfg, fake_quant)
    qn, qr = q[..., :dn], q[..., dn:]
    ckv_new = dense(x, p["w_dkv"], mx, fake_quant)
    ckv_new = rms_norm(ckv_new, p["kv_norm"], cfg.norm_eps)
    kr_new = dense(x, p["w_kr"], mx, fake_quant)
    pos = jnp.full((b, s), cache_pos)
    cos, sin = rope_tables(pos, dr, cfg.rope_theta)
    qr = apply_rope(qr, cos, sin)
    kr_new = apply_rope(kr_new.reshape(b, s, 1, dr), cos, sin)[:, :, 0, :]
    cache = _mla_cache_write(cache, ckv_new, kr_new, cache_pos, cfg)
    ckv, kr = _mla_cache_read(cache, cfg, x.dtype)      # (B,S,L), (B,S,dr)
    # absorb W_uk into q:  q_c[b,h,l] = sum_d qn[b,h,d] * W_uk[l, h, d]
    wuk = p["w_uk"].astype(x.dtype).reshape(cfg.kv_lora, nh, dn)
    qc = jnp.einsum("bqhd,lhd->bqhl", qn, wuk,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    scale = 1.0 / np.sqrt(dn + dr)
    scores = (jnp.einsum("bqhl,bkl->bhqk", qc, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", qr, kr,
                           preferred_element_type=jnp.float32)) * scale
    kpos = jnp.arange(ckv.shape[1])
    mask = kpos[None, None, None, :] <= cache_pos
    scores = jnp.where(mask, scores, -1e30)
    probs = softmax_f32(scores).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkl->bqhl", probs, ckv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    wuv = p["w_uv"].astype(x.dtype).reshape(cfg.kv_lora, nh, dv)
    out = jnp.einsum("bqhl,lhd->bqhd", ctx, wuv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = dense(out.reshape(b, s, nh * dv), p["wo"], cfg.mx,
                fake_quant, tp="row")
    return logical(out, "batch", None, None), cache


# =============================================================================
# MLP / MoE
# =============================================================================
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    if cfg.gated_mlp:
        return {"w1": dense_init(ks[0], d, ff, dt),
                "w3": dense_init(ks[1], d, ff, dt),
                "w2": dense_init(ks[2], ff, d, dt)}
    return {"w1": dense_init(ks[0], d, ff, dt),
            "w2": dense_init(ks[2], ff, d, dt)}


def mlp(p: Params, x: jax.Array, cfg: ModelConfig,
        fake_quant: bool = False) -> jax.Array:
    mx = cfg.mx
    h = dense(x, p["w1"], mx, fake_quant)
    if cfg.gated_mlp:
        g = dense(x, p["w3"], mx, fake_quant)
        h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * g
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = logical(h, "batch", None, "model")
    out = dense(h, p["w2"], mx, fake_quant, tp="row")
    return logical(out, "batch", None, None)


def moe_init(key, cfg: ModelConfig) -> Params:
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    std = 1.0 / np.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "experts": {
            "w1": (jax.random.normal(ks[1], (e, d, ff), jnp.float32) * std
                   ).astype(dt),
            "w3": (jax.random.normal(ks[2], (e, d, ff), jnp.float32) * std
                   ).astype(dt),
            "w2": (jax.random.normal(ks[3], (e, ff, d), jnp.float32)
                   / np.sqrt(ff)).astype(dt),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], dataclasses.replace(
            cfg, gated_mlp=True),
            d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


GROUP_SIZE = 256   # dispatch group size (GShard-style capacity routing)


def moe(p: Params, x: jax.Array, cfg: ModelConfig,
        fake_quant: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Capacity-factor top-k MoE; returns (out, aux_loss).

    Tokens are grouped (G, gs); dispatch/combine are one-hot einsums that
    lower to all-to-alls when experts are sharded over "model"."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_topk
    mx = cfg.mx
    n_tok = b * s
    gs = min(GROUP_SIZE, n_tok)
    g = n_tok // gs
    xt = x.reshape(g, gs, d)
    cap = max(1, int(gs * k / e * cfg.capacity_factor))
    logits = jnp.einsum("gsd,de->gse", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                 # (g, gs, k)
    topw = topw / (jnp.sum(topw, -1, keepdims=True) + 1e-9)
    # position of each (token, choice) in its expert's capacity buffer
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # (g, gs, k, e)
    pos_in_e = (jnp.cumsum(onehot.reshape(g, gs * k, e), axis=1)
                .reshape(g, gs, k, e) - 1.0) * onehot
    keep = (pos_in_e < cap) & (onehot > 0)
    posq = jnp.clip(pos_in_e, 0, cap - 1).astype(jnp.int32)
    # (g, gs, k, e, cap): each (token, choice) hits exactly one (e, slot)
    capoh = jax.nn.one_hot(posq, cap, dtype=x.dtype) \
        * keep.astype(x.dtype)[..., None]
    disp = jnp.sum(capoh, axis=2)                        # (g, gs, e, cap)
    comb = jnp.einsum("gsk,gskec->gsec", topw.astype(x.dtype), capoh)
    xe = jnp.einsum("gsec,gsd->gecd", disp, xt)          # (g, e, cap, d)
    xe = logical(xe, "batch", "model", None, None)
    we = p["experts"]

    def act_q(t):
        # activations-role QAT covers the expert matmul inputs too (the
        # dense()/mlp() paths handle their own inputs)
        if fake_quant and mx.activations is not None:
            return quantize_dequantize(t.astype(jnp.float32),
                                       mx.activations,
                                       axis=-1).astype(t.dtype)
        return t

    xe = act_q(xe)

    def exp_mm(t, w, sub):
        if isinstance(w, MXWeight):
            # weight-resident experts: per-expert fused dequant-in-VMEM
            # matmuls (codes stay packed in HBM); einsum fallback
            # materializes the f32 expert stack
            if weight_gather_enabled():
                w = dataclasses.replace(
                    w, codes=logical(w.codes, "model", None, None),
                    scales=logical(w.scales, "model", None, None))
            from repro.kernels.backend import resolve_matmul_impl
            if resolve_matmul_impl() == "fused":
                from repro.kernels.ops import mx_matmul_resident
                cols = [mx_matmul_resident(t[:, i], w.take(i))
                        for i in range(t.shape[1])]
                return jnp.stack(cols, axis=1).astype(t.dtype)
            wd = w.dequantize().astype(t.dtype)
            return jnp.einsum(sub, t, wd,
                              preferred_element_type=jnp.float32
                              ).astype(t.dtype)
        if weight_gather_enabled():
            w = logical(w, "model", None, None)  # EP on E; gather FSDP dim
        if fake_quant and mx.weights is not None:
            w = quantize_dequantize(w.astype(jnp.float32), mx.weights,
                                    axis=1).astype(t.dtype)
        return jnp.einsum(sub, t, w.astype(t.dtype),
                          preferred_element_type=jnp.float32).astype(t.dtype)

    h = exp_mm(xe, we["w1"], "gecd,edf->gecf")
    gte = exp_mm(xe, we["w3"], "gecd,edf->gecf")
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * gte
    h = act_q(h)
    ye = exp_mm(h, we["w2"], "gecf,efd->gecd")
    out = jnp.einsum("gsec,gecd->gsd", comb, ye).reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], x, cfg, fake_quant)
    # load-balance aux loss (Switch): e * sum_e f_e * P_e
    f_e = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f_e * p_e) / k
    return logical(out, "batch", None, None), aux
