"""The main-path Pallas kernels compile for a TPU v5e at chatglm3-6b's real
widths, and the dense paged decode read compiles without a dequantized copy
of the context.

Nothing runs: each kernel is lowered and compiled for a chip described by
``jax.experimental.topologies`` (the TPU compiler is installed without a
chip), which refuses what Mosaic refuses on the device — block shapes off
the (8, 128) tiling, lane-splitting reshapes, too much VMEM.  Interpret
mode accepts all of those, so these compiles guard what the CPU identity
tests cannot.  The topology is described inside a fixture, never while a
module is imported: only one process may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spec import QuantPolicy, QuantSpec
from repro.kernels.flash_attn import flash_attention
from repro.kernels.mx_decode_attn import mx_paged_decode_attention
from repro.kernels.mx_matmul import mx_matmul_2d
from repro.kernels.mx_quant import mx_quantize_2d
from repro.models import layers
from repro.models.config import ModelConfig

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


def test_dense_paged_decode_read_keeps_the_context_quantized(one_chip):
    """One layer's ``attention_paged_decode`` at the benchmark cell's
    shapes (32 slots x 256 pages of 16, a 1280-page int8 pool, dense):
    no f32 or bf16 tensor of the context's size anywhere in the compiled
    program, no context-sized relayout copy, and fewer temporaries than
    the dequantize-then-attend read (``paged_cache_gather`` +
    ``_sdpa_gqa``) at the same shapes."""
    slots, max_pages, n_pages = 32, 256, 1280
    spec = QuantSpec("int8", "ocp")
    cfg = ModelConfig(name="chatglm3-6b", family="decoder", n_layers=1,
                      d_model=D_MODEL, n_heads=HQ, n_kv_heads=HKV,
                      d_ff=D_FF, vocab=65024, head_dim=D, rope_frac=0.5,
                      mx=QuantPolicy(kv_key=spec, kv_value=spec),
                      attn_impl="dense")
    s = lambda shape, dt=jnp.uint8: _sds(one_chip, shape, dt)
    pool = {"kc_pages": s((n_pages, PAGE, HKV, D)),
            "ks_pages": s((n_pages, PAGE, HKV, D // 32)),
            "vc_pages": s((n_pages, PAGE, HKV, D)),
            "vs_pages": s((n_pages, PAGE, HKV, D // 32))}
    w = lambda n_in, n_out: s((n_in, n_out), jnp.bfloat16)
    p = {"wq": w(D_MODEL, HQ * D), "wk": w(D_MODEL, HKV * D),
         "wv": w(D_MODEL, HKV * D), "wo": w(HQ * D, D_MODEL)}
    bt, lengths = s((slots, max_pages), jnp.int32), s((slots,), jnp.int32)

    def layer(p, x, pool, bt, lengths):
        return layers.attention_paged_decode(p, x, cfg, pool=pool,
                                             block_tables=bt,
                                             lengths=lengths)

    def dequantized_read(q, pool, bt, lengths):
        ka, va = layers.paged_cache_gather(pool, bt, cfg, jnp.bfloat16, D)
        mask = jnp.arange(ka.shape[1])[None, None, None, None, :] \
            <= lengths[:, None, None, None, None]
        return layers._sdpa_gqa(q, ka, va, mask)

    new = jax.jit(layer).lower(p, s((slots, 1, D_MODEL), jnp.bfloat16),
                               pool, bt, lengths).compile()
    old = jax.jit(dequantized_read).lower(
        s((slots, 1, HQ, D), jnp.bfloat16), pool, bt, lengths).compile()
    context = slots * max_pages * PAGE * HKV * D
    text = new.as_text()
    sized = [m.group(0) for m in re.finditer(
        r"\b(f32|bf16|s8|u8)\[([\d,]+)\]", text)
        if np.prod([int(n) for n in m.group(2).split(",")]) == context]
    assert sized, "the gathered codes should be context-sized"
    assert not [t for t in sized if t.startswith(("f32", "bf16"))], \
        sorted(set(sized))
    copies = [line for line in text.splitlines()
              if re.search(r"= \w+\[[\d,]+\]\S* copy\(", line) and np.prod(
                  [int(n) for n in re.search(r"\[([\d,]+)\]",
                                             line).group(1).split(",")])
              == context]
    assert not copies, copies
    assert new.memory_analysis().temp_size_in_bytes \
        < old.memory_analysis().temp_size_in_bytes
