"""Pallas mx_quant kernel vs pure-jnp oracle: bit-identity across
shapes / dtypes / formats / modes (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ALL_FORMATS
from repro.kernels.mx_quant import mx_quantize_2d
from repro.kernels.ref import mx_quantize_2d_ref

ALL_FMTS = [f.name for f in ALL_FORMATS]

SHAPES = [(1, 32), (4, 64), (8, 512), (3, 96), (130, 1024), (257, 160)]


def _rand(shape, dtype, seed=0, scale=4.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(scale=scale, size=shape).astype(np.float32)
    # sprinkle exact zeros and tiny/huge values; "huge" stays within the
    # target dtype's finite range (casting overflowing f32 to float16
    # emits RuntimeWarning and turns the values into inf)
    x.flat[:: 7] = 0.0
    x.flat[1:: 13] *= 1e-20
    x.flat[2:: 17] *= 1e20
    lim = float(jnp.finfo(dtype).max) * 0.9
    np.clip(x, -lim, lim, out=x)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("fmt", ALL_FMTS)
@pytest.mark.parametrize("mode", ["paper", "ocp"])
def test_kernel_matches_ref_formats(fmt, mode):
    x = _rand((16, 256), jnp.float32, seed=1)
    ck, sk = mx_quantize_2d(x, fmt=fmt, mode=mode)
    cr, sr = mx_quantize_2d_ref(x, fmt=fmt, mode=mode)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_ref_shapes(shape):
    x = _rand(shape, jnp.float32, seed=2)
    ck, sk = mx_quantize_2d(x, fmt="e4m3", mode="paper")
    cr, sr = mx_quantize_2d_ref(x, fmt="e4m3", mode="paper")
    assert ck.shape == x.shape
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_kernel_matches_ref_dtypes(dtype):
    x = _rand((32, 512), dtype, seed=3)
    ck, sk = mx_quantize_2d(x, fmt="e5m2", mode="ocp")
    cr, sr = mx_quantize_2d_ref(x.astype(jnp.float32), fmt="e5m2", mode="ocp")
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


def test_kernel_nonfinite_markers():
    x = np.zeros((2, 64), np.float32)
    x[0, 3] = np.inf
    x[1, 40] = np.nan
    x[1, 41] = 5.0
    ck, sk = mx_quantize_2d(jnp.asarray(x), fmt="e4m3", mode="paper")
    cr, sr = mx_quantize_2d_ref(jnp.asarray(x), fmt="e4m3", mode="paper")
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    assert np.asarray(sk)[0, 0] == 0xFE and np.asarray(sk)[1, 1] == 0xFF


def test_kernel_tile_boundary_independence():
    """Same data, different tile shapes -> identical codes (no cross-tile
    state leaks; blocks are 32-aligned within every legal tile).  Column
    tiles are whole rows or multiples of 128 blocks (4096 lanes)."""
    x = _rand((64, 8192), jnp.float32, seed=4)
    c1, s1 = mx_quantize_2d(x, fmt="e3m2", mode="ocp", bm=16, bn=4096)
    c2, s2 = mx_quantize_2d(x, fmt="e3m2", mode="ocp", bm=64, bn=8192)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
