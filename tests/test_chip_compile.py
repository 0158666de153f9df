"""The main-path Pallas kernels compile for a TPU v5e at chatglm3-6b's real
widths.

Nothing runs: each kernel is lowered and compiled for a chip described by
``jax.experimental.topologies`` (the TPU compiler is installed without a
chip), which refuses what Mosaic refuses on the device — block shapes off
the (8, 128) tiling, lane-splitting reshapes, too much VMEM.  Interpret
mode accepts all of those, so these compiles guard what the CPU identity
tests cannot.  The topology is described inside a fixture, never while a
module is imported: only one process may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spec import QuantSpec
from repro.kernels.flash_attn import flash_attention
from repro.kernels.mx_decode_attn import mx_paged_decode_attention
from repro.kernels.mx_matmul import mx_matmul_2d
from repro.kernels.mx_quant import mx_quantize_2d

# chatglm3-6b serving widths
HQ, HKV, D, PAGE = 32, 2, 128, 16
SLOTS, MAX_PAGES = 8, 34                  # 8 slots of 544 tokens
D_MODEL, D_FF = 4096, 13696


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:            # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one; keep the cache out
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel is not in the program"
    return text


@pytest.mark.parametrize("value_fmt", ["int8", "e2m1"])
def test_paged_decode_kernel_compiles(one_chip, value_fmt):
    """int8 K with int8 or bit-packed e2m1 V pages, 32/2 GQA heads."""
    kspec = QuantSpec("int8", "ocp")
    vspec = QuantSpec(value_fmt, "ocp")
    n_pages = 1 + SLOTS * MAX_PAGES
    s = lambda shape, dt=jnp.uint8: _sds(one_chip, shape, dt)

    def step(q, kc, ks, vc, vs, bt, ln):
        return mx_paged_decode_attention(
            q, kc, ks, vc, vs, bt, ln, key_spec=kspec, value_spec=vspec,
            rep=HQ // HKV, interpret=False)

    _compile(step, s((SLOTS, 1, HQ, D), jnp.bfloat16),
             s((n_pages, PAGE, HKV, kspec.storage_nbytes(D))),
             s((n_pages, PAGE, HKV, D // 32)),
             s((n_pages, PAGE, HKV, vspec.storage_nbytes(D))),
             s((n_pages, PAGE, HKV, D // 32)),
             s((SLOTS, MAX_PAGES), jnp.int32), s((SLOTS,), jnp.int32))


@pytest.mark.parametrize("fmt", ["e4m3", "e2m1"])
def test_mx_matmul_compiles(one_chip, fmt):
    """A decode-sized (8, 4096) x (4096, 13696) MLP up-projection; e2m1
    codes arrive bit-packed along K and unpack in VMEM."""
    spec = QuantSpec(fmt, "ocp")

    def mm(a, codes, scales):
        return mx_matmul_2d(a, codes, scales, spec, interpret=False)

    _compile(mm, _sds(one_chip, (SLOTS, D_MODEL), jnp.bfloat16),
             _sds(one_chip, (spec.storage_nbytes(D_MODEL), D_FF), jnp.uint8),
             _sds(one_chip, (D_MODEL // 32, D_FF), jnp.uint8))


@pytest.mark.parametrize("fmt", ["int8", "e2m1"])
def test_converter_kernel_compiles(one_chip, fmt):
    """The paper's converter on a (4096, 4096) bf16 tensor."""
    spec = QuantSpec(fmt, "paper")
    _compile(lambda x: mx_quantize_2d(x, spec, interpret=False),
             _sds(one_chip, (D_MODEL, D_MODEL), jnp.bfloat16))


def test_flash_attention_compiles(one_chip):
    """A 512-token causal prefill, 32 query heads over 2 KV heads."""
    q = _sds(one_chip, (1, 512, HQ, D), jnp.bfloat16)
    kv = _sds(one_chip, (1, 512, HKV, D), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, True, False), q, kv, kv)
