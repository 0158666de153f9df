"""Pallas decode-attention kernel over an MX-quantized KV cache.

The paper's converter fused with its consumer: the KV cache stays uint8
(codes + E8M0 scales) in HBM; dequantization happens block-by-block in VMEM
inside the online-softmax loop, so HLO-level HBM traffic is the *quantized*
cache — the full memory-roofline win of the format (a separate dequantize op
would write the f32 cache back to HBM and give most of it back).

K and V carry independent ``QuantSpec``s (the ``kv_key`` / ``kv_value``
policy roles), so e.g. INT8 keys can pair with E2M1 values in one cache.

``mx_paged_decode_attention`` is the kernel: K/V live in a shared page
pool (pages of ``page_size`` tokens, sub-byte codes bit-packed via
repro.core.pack when the spec says ``packed``) and each slot's logical
sequence is the concatenation of the pages named by its
block-table row.  The block table and per-slot lengths ride in as
scalar-prefetch operands so the BlockSpec index maps can translate
(slot, page-step) -> physical page before the DMA is issued — the gather
happens at the HBM->VMEM boundary and HBM traffic stays at the quantized
cache.  The two pools are sized per-role: a packed E2M1 value pool really
is half the bytes of its INT8 key pool.  ``mx_decode_attention`` serves a
contiguous (B, S, Hkv, ·) cache through the same kernel, as a pool with an
identity block table.

Grid (B, Hq, max_pages); per step:
    q_ref        (1, 1, 1, D)         query for this (slot, head)
    kc/vc_ref    (1, page, Hkv, CB)   u8 codes of one page, every kv head
                                      (the TPU refuses a size-1 block on
                                      the second-minor axis); the kernel
                                      selects head h // rep
    ks/vs_ref    (1, page, Hkv, D/32) u8 E8M0 scales
    scratch      acc (1, D) f32, m/l (1,) f32
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.convert import decode_elements, scale_to_f32
from repro.core.pack import pack_codes
from repro.core.spec import QuantSpec, resolve_kv_specs
from repro.kernels import accounting
from repro.kernels.backend import resolve_interpret
from repro.kernels.lanes import gather_lanes, unpack_codes_lanes

DEFAULT_BLK_K = 512
NEG_INF = -1e30

_KV_DEFAULT = QuantSpec("int8", "ocp")


def _require_block32(key_spec: QuantSpec, value_spec: QuantSpec,
                     caller: str) -> None:
    """The decode kernels' scale layout is hardwired to 32-wide blocks
    (D/32 scale columns); reject other block sizes instead of silently
    dequantizing with the wrong grouping."""
    for role, spec in (("key_spec", key_spec), ("value_spec", value_spec)):
        if spec.block != 32:
            raise ValueError(
                f"{caller}: {role}={spec} has block={spec.block}, but the "
                f"decode-attention kernels support only block=32 scale "
                f"layouts")


def _dequant_block(codes, scales, spec: QuantSpec, d: int):
    """(blk, CB) u8 codes + (blk, D/32) u8 scales -> (blk, D) f32, in VMEM.
    Bit-packed sub-byte codes (CB < D) are unpacked first.  Codes and
    scales are spread over the head dim by lane gathers, not reshapes,
    which Mosaic refuses on the lane axis."""
    if codes.shape[-1] != d:
        codes = unpack_codes_lanes(codes, spec.fmt, d)
    elem = decode_elements(codes, spec.format, spec.mode)
    sfac = scale_to_f32(gather_lanes(scales, d, lambda c: c // 32))
    return elem * sfac


def _kv_head(ref, g):
    """Rows of kv head ``g`` (traced) from a (1, blk, Hkv, X) block.  The
    block spans every kv head because the TPU refuses a size-1 block on
    the second-minor axis; a static select picks the head (Hkv is small)."""
    blk = ref[0]
    out = blk[:, 0, :]
    for i in range(1, blk.shape[1]):
        out = jnp.where(g == i, blk[:, i, :], out)
    return out


def mx_decode_attention(q: jax.Array, k_codes: jax.Array,
                        k_scales: jax.Array, v_codes: jax.Array,
                        v_scales: jax.Array, pos: jax.Array, *,
                        spec=None, key_spec=None, value_spec=None,
                        rep: int = 1, blk_k: int = DEFAULT_BLK_K,
                        interpret: Optional[bool] = None,
                        fmt: Optional[str] = None,
                        mode: Optional[str] = None) -> jax.Array:
    """q (B,1,Hq,D); cache codes (B,S,Hkv,D) u8 + scales (B,S,Hkv,D/32);
    attends over positions <= pos.  Returns (B,1,Hq,D).

    ``key_spec``/``value_spec`` (or the uniform ``spec``) select the
    per-role element formats; the ``fmt=``/``mode=`` kwargs are the
    uniform deprecation shim (warns once).  ``interpret=None`` resolves
    backend-aware (interpret only off-TPU)."""
    key_spec, value_spec = resolve_kv_specs(
        spec, key_spec, value_spec, fmt, mode, default=_KV_DEFAULT,
        caller="mx_decode_attention")
    _require_block32(key_spec, value_spec, "mx_decode_attention")
    return _mx_decode_attention(q, k_codes, k_scales, v_codes, v_scales,
                                pos, key_spec, value_spec, rep, blk_k,
                                resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("key_spec", "value_spec",
                                             "rep", "blk_k", "interpret"))
def _mx_decode_attention(q, k_codes, k_scales, v_codes, v_scales, pos,
                         key_spec: QuantSpec, value_spec: QuantSpec,
                         rep: int, blk_k: int,
                         interpret: bool) -> jax.Array:
    """The contiguous cache is a page pool whose block table is the
    identity: slot b's blk_k-token chunks are pages b*nk .. b*nk+nk-1.
    Sub-byte codes are packed as a pool stores them, so both layouts run
    the one kernel on the same bytes."""
    b, s, _, d = k_codes.shape
    if k_codes.shape[-1] != key_spec.storage_nbytes(d):
        k_codes = pack_codes(k_codes, key_spec.fmt)
    if v_codes.shape[-1] != value_spec.storage_nbytes(d):
        v_codes = pack_codes(v_codes, value_spec.fmt)
    bk = min(blk_k, s)
    assert s % bk == 0, (s, bk)
    nk = s // bk

    def pages(x):
        return x.reshape((b * nk, bk) + x.shape[2:])

    block_tables = jnp.arange(b * nk, dtype=jnp.int32).reshape(b, nk)
    lengths = jnp.full((b,), pos, jnp.int32)
    return _mx_paged_decode_attention(
        q, pages(k_codes), pages(k_scales), pages(v_codes), pages(v_scales),
        block_tables, lengths, key_spec, value_spec, rep, interpret)


# =============================================================================
# Paged variant (continuous batching)
# =============================================================================
def _paged_kernel(bt_ref, len_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                  o_ref, acc, mrow, lrow, *, key_spec: QuantSpec,
                  value_spec: QuantSpec, d: int, page: int, np_max: int,
                  rep: int):
    bb = pl.program_id(0)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        mrow[...] = jnp.full_like(mrow, NEG_INF)
        lrow[...] = jnp.zeros_like(lrow)

    g = pl.program_id(1) // rep
    q = q_ref[0, 0].astype(jnp.float32)                    # (1, D)
    k = _dequant_block(_kv_head(kc_ref, g), _kv_head(ks_ref, g), key_spec, d)
    v = _dequant_block(_kv_head(vc_ref, g), _kv_head(vs_ref, g), value_spec,
                       d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) \
        / np.sqrt(d)                                       # (1, page)
    pos = jk * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos <= len_ref[bb]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = mrow[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    lrow[...] = lrow[...] * alpha + jnp.sum(p, axis=-1)
    acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    mrow[...] = m_new

    @pl.when(jk == np_max - 1)
    def _done():
        denom = jnp.where(lrow[...] == 0.0, 1.0, lrow[...])
        o_ref[0, 0] = (acc[...] / denom[:, None]).astype(o_ref.dtype)


def mx_paged_decode_attention(q: jax.Array, kc_pool: jax.Array,
                              ks_pool: jax.Array, vc_pool: jax.Array,
                              vs_pool: jax.Array, block_tables: jax.Array,
                              lengths: jax.Array, *, spec=None,
                              key_spec=None, value_spec=None, rep: int = 1,
                              interpret: Optional[bool] = None,
                              fmt: Optional[str] = None,
                              mode: Optional[str] = None) -> jax.Array:
    """Decode attention over a paged MX KV cache.

    q             (B, 1, Hq, D)
    kc/vc_pool    (n_pages, page, Hkv, CB) u8 — CB is the per-role storage
                  bytes per token-head (== D for 8-bit or unpacked specs;
                  bit-packed below that); K and V pools may differ
    ks/vs_pool    (n_pages, page, Hkv, D/32) u8 E8M0 scales
    block_tables  (B, max_pages) i32 physical page per (slot, logical page);
                  rows padded with 0 (a reserved trash page) past the slot's
                  allocation — those positions are masked by ``lengths``.
    lengths       (B,) i32 — slot b attends to logical positions <= lengths[b]

    Returns (B, 1, Hq, D).  The block table and lengths are scalar-prefetch
    operands: index maps resolve the physical page before the page's DMA.
    ``key_spec``/``value_spec`` (or uniform ``spec``) pick the per-role
    formats; ``fmt=``/``mode=`` is the uniform deprecation shim.
    """
    key_spec, value_spec = resolve_kv_specs(
        spec, key_spec, value_spec, fmt, mode, default=_KV_DEFAULT,
        caller="mx_paged_decode_attention")
    _require_block32(key_spec, value_spec, "mx_paged_decode_attention")
    return _mx_paged_decode_attention(q, kc_pool, ks_pool, vc_pool,
                                      vs_pool, block_tables, lengths,
                                      key_spec, value_spec, rep,
                                      resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("key_spec", "value_spec",
                                             "rep", "interpret"))
def _mx_paged_decode_attention(q, kc_pool, ks_pool, vc_pool, vs_pool,
                               block_tables, lengths,
                               key_spec: QuantSpec, value_spec: QuantSpec,
                               rep: int, interpret: bool) -> jax.Array:
    b, _, hq, d = q.shape
    n_pages, page, hkv, cb_k = kc_pool.shape
    cb_v = vc_pool.shape[-1]
    np_max = block_tables.shape[1]
    assert cb_k == key_spec.storage_nbytes(d), (cb_k, key_spec, d)
    assert cb_v == value_spec.storage_nbytes(d), (cb_v, value_spec, d)
    nbl = d // 32
    qt = q[:, 0][:, :, None, :]                            # (B, Hq, 1, D)
    kernel = functools.partial(_paged_kernel, key_spec=key_spec,
                               value_spec=value_spec, d=d, page=page,
                               np_max=np_max, rep=rep)

    def page_map(bb, h, j, bt, ln):
        return (bt[bb, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, np_max),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d),
                         lambda bb, h, j, bt, ln: (bb, h, 0, 0)),
            pl.BlockSpec((1, page, hkv, cb_k), page_map),
            pl.BlockSpec((1, page, hkv, nbl), page_map),
            pl.BlockSpec((1, page, hkv, cb_v), page_map),
            pl.BlockSpec((1, page, hkv, nbl), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bb, h, j, bt, ln: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        interpret=interpret,
        name="mx_paged_decode_attn",
    )(block_tables, lengths, qt, kc_pool, ks_pool, vc_pool, vs_pool)
    # analytic cost: the gathered pages (quantized bytes), not the pool
    s = np_max * page
    flops = 4.0 * b * hq * s * d + 10.0 * b * hq * s * d
    io = (b * s * hkv * (cb_k + cb_v + 2 * nbl)
          + q.size * q.dtype.itemsize * 2)
    accounting.record(flops, io)
    return out.transpose(0, 2, 1, 3)                       # (B, 1, Hq, D)
