"""Pallas TPU kernel for the paper's FP32 -> MX converter.

TPU-native adaptation of the combinational circuit (DESIGN.md §2):

  * the paper's 32-input comparator tree  -> a max over each block's 32
    lanes of the VMEM tile, as a 5-level butterfly of lane rotations (the
    same O(log 32) tree, 8x128 lanes at a time);
  * the 32 parallel LUT quantizers        -> branchless integer ops on the
    bitcast(u32) view of the tile (mask/shift exponent extract, add-shift
    ties-away rounding, selects for FTZ / saturation / markers);
  * the 1288-pin I/O interface            -> double-buffered HBM->VMEM tile
    pipeline driven by ``pl.pallas_call`` BlockSpecs.

Tile geometry: each grid step converts a (BM, BN) tile.  The shared scales
leave as a (BM, BN//32) tile, and the TPU takes a block whose last axis is
a multiple of 128 or the whole axis, so BN is the whole (padded) row or a
multiple of 128*32.  Default (32, 4096) => 512 KiB of f32 in, 128 KiB of
codes and 4 KiB of scales out per step.  The per-block max and the scale
compaction move data across lanes with ``kernels.lanes`` (Mosaic refuses
lane-splitting reshapes).

The kernel body reuses the *same* integer-exact element functions as the
pure-JAX reference (repro/core/convert.py), so tests assert bit-identity.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.convert import (_f32_fields, _quant_float_ocp,
                                _quant_float_paper, _quant_int8,
                                _marker_codes, shared_scale)
from repro.core.formats import MXFormat
from repro.core.spec import QuantSpec, resolve_spec
from repro.kernels.backend import resolve_interpret
from repro.kernels.lanes import gather_lanes, segment_max

DEFAULT_BM = 32
DEFAULT_BN = 4096  # 128 lanes of 32-wide blocks
LANES = 128


def _quant_tile(x_tile: jax.Array, fmt: MXFormat, mode: str,
                block: int) -> Tuple[jax.Array, jax.Array]:
    """Quantize one (BM, BN) f32 tile -> (codes u8 (BM,BN), scales u8
    (BM, BN//block)).  Every per-block quantity is computed on each lane of
    its block, so no op reshapes the lane axis."""
    bm, bn = x_tile.shape
    sign, exp, man = _f32_fields(x_tile)
    finite = exp != 0xFF
    is_nan = (~finite) & (man != 0)
    is_inf = (~finite) & (man == 0)
    # step 1: comparator tree == segment max over the block's lanes.  One
    # max carries the NaN/Inf flags above the 8 exponent bits: a flagged
    # block's scale is its marker whatever the exponent max, and NaN wins
    # over Inf in both modes, so (key >= 256) serves as any_inf.
    key = jnp.where(finite, exp, 0) | (is_inf.astype(jnp.int32) << 8) \
        | (is_nan.astype(jnp.int32) << 9)
    key = segment_max(key, block)
    any_nan = key >= 512
    any_inf = key >= 256
    # step 2: shared scale, on every lane of its block
    xscale = shared_scale(key & 0xFF, fmt, mode, any_nan, any_inf)
    xblk = xscale.astype(jnp.int32)
    # step 3: private elements
    if fmt.is_int:
        codes = _quant_int8(sign, exp, man, xblk, mode)
    elif mode == "paper":
        codes = _quant_float_paper(sign, exp, man, xblk, fmt)
    else:
        codes = _quant_float_ocp(sign, exp, man, xblk, fmt)
    if mode == "paper":
        codes = jnp.where(any_inf, _marker_codes(sign, fmt, "inf"), codes)
        codes = jnp.where(any_nan, _marker_codes(sign, fmt, "nan"), codes)
    scales = gather_lanes(xblk, bn // block, lambda c: c * block)
    return codes, scales.astype(jnp.uint8)


def _mx_quant_kernel(x_ref, codes_ref, scales_ref, *, fmt: MXFormat,
                     mode: str, block: int):
    x = x_ref[...].astype(jnp.float32)
    codes, scales = _quant_tile(x, fmt, mode, block)
    codes_ref[...] = codes
    scales_ref[...] = scales


def mx_quantize_2d(x: jax.Array, spec=None, mode: Optional[str] = None,
                   block: Optional[int] = None, bm: int = DEFAULT_BM,
                   bn: int = DEFAULT_BN,
                   interpret: Optional[bool] = None, *,
                   fmt: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Quantize a 2-D array (M, N) along the trailing axis with the Pallas
    converter kernel.  M, N need not be tile-aligned (zero padding; zeros
    never perturb a block's max exponent).  ``spec`` is a QuantSpec; the
    ``fmt=``/``mode=``/``block=`` kwargs are the deprecation shim.
    ``interpret=None`` resolves backend-aware (interpret only off-TPU)."""
    spec = resolve_spec(spec, fmt, mode, block,
                        default=QuantSpec("e4m3", "paper"),
                        caller="mx_quantize_2d")
    return _mx_quantize_2d(x, spec, bm, bn, resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "bn", "interpret"))
def _mx_quantize_2d(x: jax.Array, spec: QuantSpec, bm: int, bn: int,
                    interpret: bool) -> Tuple[jax.Array, jax.Array]:
    f, mode, block = spec.format, spec.mode, spec.block
    if block & (block - 1):
        raise ValueError(f"the converter kernel needs a power-of-two "
                         f"block, got {spec}")
    m, n = x.shape
    bm_ = min(bm, max(1, m))
    # the whole padded row, or a multiple of LANES blocks (scale tile rule)
    n_pad = -(-n // block) * block
    bn_ = min(n_pad, -(-bn // (LANES * block)) * LANES * block)
    # pad to tile multiples (zeros are neutral for the exponent max)
    pm = (-m) % bm_
    pn = (-n) % bn_
    xp = jnp.pad(x.astype(jnp.float32), ((0, pm), (0, pn)))
    mp, np_ = xp.shape
    grid = (mp // bm_, np_ // bn_)
    kernel = functools.partial(_mx_quant_kernel, fmt=f, mode=mode,
                               block=block)
    codes, scales = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm_, bn_), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm_, bn_), lambda i, j: (i, j)),
            pl.BlockSpec((bm_, bn_ // block), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.uint8),
            jax.ShapeDtypeStruct((mp, np_ // block), jnp.uint8),
        ],
        interpret=interpret,
        name="mx_quant",
    )(xp)
    return codes[:m, :n], scales[:m, : (n + block - 1) // block]
