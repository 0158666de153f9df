"""Pallas flash-attention (forward) kernel — online-softmax over key blocks.

The beyond-paper memory-roofline lever for the 32k prefill cells: the
(Sq x Sk) score matrix lives in VMEM scratch and never touches HBM; HBM
traffic is exactly q + k + v + o.  Layout per grid step (bh, iq, ik):

    VMEM:  q block (blk_q, D), k/v blocks (blk_k, D),
           scratch acc (blk_q, D) f32 + running max/denominator (blk_q,)

Causal masking is applied with global block offsets; diagonal blocks are
partially masked, strictly-upper blocks contribute nothing (their compute is
wasted — acceptable v1; a skip would need a data-dependent grid).

Backward is a custom_vjp that recomputes attention densely (chunk-free) —
the forward-only serving/prefill paths get the full win; training gets the
forward half.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import accounting
from repro.kernels.backend import resolve_interpret

DEFAULT_BLK_Q = 256
DEFAULT_BLK_K = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, mrow, lrow, *,
                  scale: float, causal: bool, blk_q: int, blk_k: int,
                  nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        mrow[...] = jnp.full_like(mrow, NEG_INF)
        lrow[...] = jnp.zeros_like(lrow)

    q = q_ref[0].astype(jnp.float32)                 # (blk_q, D)
    k = k_ref[0].astype(jnp.float32)                 # (blk_k, D)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        iq = pl.program_id(1)
        rows = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32,
                                                     (blk_q, blk_k), 0)
        cols = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (blk_q, blk_k), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)
    m_prev = mrow[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])                  # NEG_INF rows -> ~0
    alpha = jnp.exp(m_prev - m_new)
    lrow[...] = lrow[...] * alpha + jnp.sum(p, axis=-1)
    acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    mrow[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        denom = jnp.where(lrow[...] == 0.0, 1.0, lrow[...])
        o_ref[0] = (acc[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, blk_q: int = DEFAULT_BLK_Q,
                         blk_k: int = DEFAULT_BLK_K,
                         interpret: bool = True) -> jax.Array:
    """q (BH, Sq, D), k/v (BH, Sk, D) -> o (BH, Sq, D).  Sq/Sk are padded
    to block multiples; padded key columns are masked via the causal rule
    (causal=True) or must be absent (non-causal requires Sk % blk_k == 0).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    bq = min(blk_q, sq)
    bk = min(blk_k, sk)
    pq, pk = (-sq) % bq, (-sk) % bk
    if pk and not causal:
        raise ValueError("non-causal flash needs Sk % blk_k == 0")
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq, nk = q.shape[1] // bq, k.shape[1] // bk
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               blk_q=bq, blk_k=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn",
    )(q, k, v)
    return out[:, :sq]


def _flash_bshd_fwd(q, k, v, causal, interpret):
    """(B,S,H,D) wrapper with GQA expansion; returns o (B,Sq,H,D)."""
    interpret = resolve_interpret(interpret)
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if hkv != h:
        rep = h // hkv
        idx = jnp.arange(h) // rep
        k = jnp.take(k, idx, axis=2)
        v = jnp.take(v, idx, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    # analytic kernel cost (interpret-mode while bodies are counted once)
    flops = 4.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)
    io = (qt.size + kt.size + vt.size * 2) * q.dtype.itemsize
    accounting.record(flops, io)
    o = flash_attention_bhsd(qt, kt, vt, causal=causal,
                             interpret=interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret=None):
    """Flash attention (B,S,H,D) with GQA k/v (B,S,Hkv,D).

    ``interpret=None`` resolves backend-aware (interpret only off-TPU)."""
    return _flash_bshd_fwd(q, k, v, causal, interpret)


def _fwd(q, k, v, causal, interpret):
    return _flash_bshd_fwd(q, k, v, causal, interpret), (q, k, v)


def _dense_ref(q, k, v, causal):
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    qg = q.reshape(b, sq, hkv, rep, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kf) / np.sqrt(d)
    if causal:
        sk = k.shape[1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, vf)
    return o.reshape(b, sq, h, d).astype(q.dtype)


def _bwd(causal, interpret, res, do):
    """Backward by dense recomputation (forward-only paths never hit this)."""
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _dense_ref(q_, k_, v_, causal),
                     q, k, v)
    return vjp(do)


flash_attention.defvjp(_fwd, _bwd)
