"""Pallas TPU kernel: matmul with MX-quantized weights (dequant-in-VMEM).

This is the *consumer* that makes the paper's converter a framework feature:
weights live in HBM as MX element codes (uint8) + E8M0 scales (uint8, one per
32 along the contraction axis), cutting weight HBM traffic ~3.9x vs f32
(~1.94x vs bf16).  Each grid step:

  HBM->VMEM:  A tile (BM, BK) f32/bf16, W codes (BK, BN) u8,
              W scales (BK/32, BN) u8
  VMEM:       branchless decode codes -> f32  (VPU)
              multiply by broadcast scales    (VPU)
              A @ W_deq accumulated in f32    (MXU)

Tiling: BM=BN=BK=256 default => A 256 KiB + codes 64 KiB + scales 2 KiB +
acc 256 KiB per step; MXU dims are multiples of 128.  The contraction axis
is the innermost grid dimension; the output tile is revisited and
accumulated across it (standard Pallas reduction pattern).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.convert import decode_elements, scale_to_f32
from repro.core.pack import packed_nbytes, unpack_codes_rows
from repro.core.spec import QuantSpec, resolve_spec
from repro.kernels.backend import resolve_interpret

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def dequant_tile(codes: jax.Array, scales: jax.Array,
                 spec: QuantSpec) -> jax.Array:
    """(BK, BN) u8 codes + (BK//block, BN) u8 scales -> (BK, BN) f32."""
    bk, bn = codes.shape
    block = spec.block
    elem = decode_elements(codes, spec.format, spec.mode)
    sfac = scale_to_f32(scales)                      # (BK//block, BN)
    w = elem.reshape(bk // block, block, bn) * sfac[:, None, :]
    return w.reshape(bk, bn)


def _mx_matmul_kernel(a_ref, c_ref, s_ref, o_ref, *, spec: QuantSpec,
                      bk: int, packed: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32)
    codes = c_ref[...]
    if packed:
        # sub-byte codes arrive bit-packed along K; unpack the tile in VMEM
        codes = unpack_codes_rows(codes, spec.fmt, bk)
    w = dequant_tile(codes, s_ref[...], spec)
    o_ref[...] += jnp.dot(a, w, preferred_element_type=jnp.float32)


def mx_matmul_2d(a: jax.Array, codes: jax.Array, scales: jax.Array,
                 spec=None, mode: Optional[str] = None,
                 block: Optional[int] = None, bm: int = DEFAULT_BM,
                 bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                 interpret: Optional[bool] = None, *,
                 fmt: Optional[str] = None) -> jax.Array:
    """a (M, K) @ dequant(codes (K, N), scales (K//block, N)) -> (M, N) f32.

    K must be a multiple of the spec's block; M/N/K are padded to tile
    multiples.  When ``spec.packed`` and the format is sub-byte, ``codes``
    is the bit-packed byte stream along K — shape (storage_nbytes(K), N) —
    and each grid step unpacks its tile in VMEM, so fp (or even unpacked
    u8) weights never round-trip through HBM.  ``spec`` is a QuantSpec
    (deprecation shim: fmt=/mode=).  ``interpret=None`` resolves
    backend-aware (interpret only off-TPU)."""
    spec = resolve_spec(spec, fmt, mode, block,
                        default=QuantSpec("e4m3", "paper"),
                        caller="mx_matmul_2d")
    return _mx_matmul_2d(a, codes, scales, spec, bm, bn, bk,
                         resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "bn", "bk", "interpret"))
def _mx_matmul_2d(a: jax.Array, codes: jax.Array, scales: jax.Array,
                  spec: QuantSpec, bm: int, bn: int, bk: int,
                  interpret: bool) -> jax.Array:
    block = spec.block
    m, k = a.shape
    kc, n = codes.shape
    # Packed-ness is inferred from the code rows, not spec.packed: legacy
    # callers pass unpacked (K, N) codes under specs whose packed flag
    # defaults to True, while the weight-resident path ships the bit-packed
    # byte stream (storage_nbytes(K), N).  Sub-byte packing always shrinks
    # the row count, so the two layouts are unambiguous.
    if kc == k:
        packed = False
    elif spec.format.code_bits < 8 and kc == packed_nbytes(spec.fmt, k):
        packed = True
    else:
        raise ValueError(
            f"codes have {kc} rows; expected K={k} (unpacked) or "
            f"storage_nbytes(K)={packed_nbytes(spec.fmt, k)} (bit-packed) "
            f"for fmt={spec.fmt}")
    assert k % block == 0, f"K={k} must be a multiple of block={block}"
    if min(bm, bn, bk) < 1:
        raise ValueError(f"tile sizes must be positive, got "
                         f"bm={bm}, bn={bn}, bk={bk}")
    bm_ = min(bm, m)
    bn_ = min(bn, n)
    # The scale BlockSpec covers bk_ // block rows, so a bk_ that is not a
    # block multiple would silently truncate the scale tile (e.g. bk=48,
    # block=32 -> one scale row stretched over 48 code rows).  Round down
    # to a whole number of blocks and refuse tiles smaller than one block.
    bk_ = min(bk, k)
    bk_ -= bk_ % block
    if bk_ == 0:
        raise ValueError(
            f"bk={bk} is smaller than the scale block ({block}); the "
            f"contraction tile must cover at least one full block")
    pm, pn, pk = (-m) % bm_, (-n) % bn_, (-k) % bk_
    # k and bk_ are both block multiples, so pk is too: the zero-padded
    # code/scale rows line up on block boundaries and decode to exact 0.0
    # (decode(0) == 0.0 in every format/mode, and 0.0 * 2^-127 == 0.0).
    ap = jnp.pad(a, ((0, pm), (0, pk)))
    pkc = packed_nbytes(spec.fmt, pk) if packed else pk
    cp = jnp.pad(codes, ((0, pkc), (0, pn)))
    sp = jnp.pad(scales, ((0, pk // block), (0, pn)))
    mp, kp = ap.shape
    np_ = cp.shape[1]
    grid = (mp // bm_, np_ // bn_, kp // bk_)
    # bk_ is a multiple of block >= 32, so packed byte rows stay tile-linear:
    # tile kk starts at byte row kk * storage_nbytes(bk_).
    cbk = packed_nbytes(spec.fmt, bk_) if packed else bk_
    kernel = functools.partial(_mx_matmul_kernel, spec=spec, bk=bk_,
                               packed=packed)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((cbk, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk_ // block, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="mx_matmul",
    )(ap, cp, sp)
    return out[:m, :n]
