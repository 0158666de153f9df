"""Lane-axis helpers for Pallas TPU kernel bodies.

Mosaic refuses reshapes that split or interleave the lane (last) axis, such
as ``(R, 128) -> (R, 4, 32)``.  MX blocks and bit-packed codes live along
that axis, so the kernels move data between lanes with the two operations
below instead.  Both are exact and run unchanged in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import get_format


def gather_lanes(x: jax.Array, n_out: int, src) -> jax.Array:
    """``out[:, c] = x[:, src(c)]`` for integer ``x`` in [0, 255].

    ``src`` maps an int32 lane-index array to source lanes.  The gather is
    a one-hot matmul on the MXU; every output is one product of a value
    below 256 with 1.0, so it is exact at any matmul precision."""
    n_in = x.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_in, n_out), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_in, n_out), 1)
    onehot = (rows == src(cols)).astype(jnp.float32)
    y = jnp.dot(x.astype(jnp.int32).astype(jnp.float32), onehot,
                preferred_element_type=jnp.float32)
    return y.astype(jnp.int32)


def segment_max(x: jax.Array, seg: int) -> jax.Array:
    """Max of ``x`` (R, N) int32 over each aligned run of ``seg`` lanes,
    broadcast back to every lane of the run (``seg`` a power of two).

    An XOR butterfly: at distance ``s`` each lane meets lane ``l ^ s``,
    which is ``s`` lanes up or down.  Rolling the lane index with the data
    tells which roll brought the partner, so the result does not depend on
    the rotation direction of ``pltpu.roll``."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    s = 1
    while s < seg:
        up = pltpu.roll(x, s, x.ndim - 1)
        down = pltpu.roll(x, n - s, x.ndim - 1)
        src = pltpu.roll(lane, s, x.ndim - 1)
        x = jnp.maximum(x, jnp.where(src == (lane ^ s), up, down))
        s *= 2
    return x


def unpack_codes_lanes(packed: jax.Array, fmt, n: int) -> jax.Array:
    """``core.pack.unpack_codes`` for a kernel body: (R, nbytes) packed
    bytes -> (R, n) int32 codes, with lane gathers in place of the
    interleaving reshape."""
    f = get_format(fmt)
    p = packed.astype(jnp.int32)
    if f.code_bits == 8:
        return p
    lane = jax.lax.broadcasted_iota(jnp.int32, p.shape[:-1] + (n,),
                                    p.ndim - 1)
    if f.code_bits <= 4:                     # 2 per byte: [lo | hi<<4]
        byte = gather_lanes(p, n, lambda c: c // 2)
        return (byte >> ((lane % 2) * 4)) & 0xF
    # 6-bit: 4 codes in 3 little-endian bytes
    w = (gather_lanes(p, n, lambda c: c // 4 * 3)
         | (gather_lanes(p, n, lambda c: c // 4 * 3 + 1) << 8)
         | (gather_lanes(p, n, lambda c: c // 4 * 3 + 2) << 16))
    return (w >> ((lane % 4) * 6)) & 0x3F
