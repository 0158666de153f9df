"""Architecture registry: name -> (config, model driver, input specs)."""
from __future__ import annotations

import importlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import decoder, encdec, hybrid, rwkv_model
from repro.models.config import ModelConfig, ShapeSpec

ARCH_IDS = (
    "internvl2_76b", "seamless_m4t_medium", "chatglm3_6b", "yi_34b",
    "deepseek_67b", "glm4_9b", "zamba2_1p2b", "deepseek_v2_236b",
    "moonshot_v1_16b_a3b", "rwkv6_7b",
)

_FAMILY = {"decoder": decoder, "encdec": encdec, "hybrid": hybrid,
           "rwkv": rwkv_model}


def load_config(arch: str, **overrides) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{arch}")
    cfg = mod.CONFIG
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def load_reduced(arch: str, **overrides) -> ModelConfig:
    """Reduced config for CPU smoke tests.  Defaults to f32 compute: the CPU
    XLA DotThunk cannot execute some bf16xbf16->f32 contractions (MLA); the
    full configs stay bf16 (TPU target, exercised via lowering-only)."""
    arch = arch.replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{arch}")
    cfg = mod.reduced()
    over = {"dtype": "float32", "param_dtype": "float32"}
    over.update(overrides)
    import dataclasses
    return dataclasses.replace(cfg, **over)


def family_module(cfg: ModelConfig):
    return _FAMILY[cfg.family]


class Model:
    """Thin functional wrapper: one uniform interface over all families."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.mod = family_module(cfg)

    def init(self, key, out_shardings=None):
        """Random parameters, built under jit: each weight's RNG draw and
        dtype cast fuse, so no f32 copy of a stacked weight reaches device
        memory.  ``out_shardings`` places each leaf as it is built."""
        return jax.jit(self.mod.init, static_argnums=1,
                       out_shardings=out_shardings)(key, self.cfg)

    def init_quantized(self, key, out_shardings=None):
        """``quantize_weights(init(key))`` without the fp model ever
        resident beside its MX copy (decoder family; see decoder.init)."""
        self._require_decoder()
        return jax.jit(self.mod.init_quantized, static_argnums=1,
                       out_shardings=out_shardings)(key, self.cfg)

    def forward(self, params, batch: Dict[str, jax.Array], *,
                fake_quant: bool = False):
        cfg = self.cfg
        if cfg.family == "encdec":
            return self.mod.forward(params, batch["frames"], batch["tokens"],
                                    cfg, fake_quant=fake_quant)
        if cfg.family == "decoder":
            return self.mod.forward(params, batch["tokens"], cfg,
                                    prefix_embeds=batch.get("prefix_embeds"),
                                    fake_quant=fake_quant)
        return self.mod.forward(params, batch["tokens"], cfg,
                                fake_quant=fake_quant)

    def prefill(self, params, batch, *, max_len: int,
                fake_quant: bool = False):
        cfg = self.cfg
        if cfg.family == "encdec":
            return self.mod.prefill(params, batch["frames"],
                                    batch["tokens"], cfg, max_len=max_len,
                                    fake_quant=fake_quant)
        if cfg.family == "decoder":
            return self.mod.prefill(params, batch["tokens"], cfg,
                                    max_len=max_len,
                                    prefix_embeds=batch.get("prefix_embeds"),
                                    fake_quant=fake_quant)
        return self.mod.prefill(params, batch["tokens"], cfg,
                                max_len=max_len, fake_quant=fake_quant)

    def decode_step(self, params, token, cache, pos, *,
                    fake_quant: bool = False):
        return self.mod.decode_step(params, token, cache, pos, self.cfg,
                                    fake_quant=fake_quant)

    def quantize_weights(self, params):
        """Convert matmul weights to weight-resident MXWeight storage per
        the policy's ``weights`` role (decoder family; see
        decoder.quantize_weights).  Serve the result as-is — ``dense()``
        routes MXWeight operands through the fused dequant-in-VMEM
        matmul kernel."""
        self._require_decoder()
        return self.mod.quantize_weights(params, self.cfg)

    def _require_decoder(self) -> None:
        if self.cfg.family != "decoder":
            raise NotImplementedError(
                f"{self.cfg.name}: weight-resident storage covers the "
                "decoder family")

    def forward_calib(self, params, batch: Dict[str, jax.Array]):
        """Instrumented forward for repro.calib: (logits, aux, taps) with
        per-layer activation / kv_key / kv_value tensors (GQA decoder
        family only — see decoder.forward_calib)."""
        cfg = self.cfg
        if cfg.family != "decoder":
            raise NotImplementedError(
                f"{cfg.name}: calibration taps cover the decoder family")
        return self.mod.forward_calib(
            params, batch["tokens"], cfg,
            prefix_embeds=batch.get("prefix_embeds"))

    def init_cache(self, batch: int, max_len: int, s_enc: int = 0):
        cfg = self.cfg
        if cfg.family == "encdec":
            return self.mod.init_cache(cfg, batch, max_len, s_enc)
        return self.mod.init_cache(cfg, batch, max_len)

    # ---- paged serving (continuous batching; GQA decoder family) ---------
    def supports_paged(self) -> bool:
        cfg = self.cfg
        return (cfg.family == "decoder" and not cfg.mla
                and cfg.frontend == "none")

    def init_paged_cache(self, num_pages: int, page_size: int):
        if not self.supports_paged():
            raise NotImplementedError(
                f"{self.cfg.name}: paged KV serving needs a GQA decoder")
        return self.mod.init_paged_cache(self.cfg, num_pages, page_size)

    def paged_decode_step(self, params, token, cache, block_tables,
                          lengths, *, fake_quant: bool = False):
        return self.mod.paged_decode_step(params, token, cache,
                                          block_tables, lengths, self.cfg,
                                          fake_quant=fake_quant)

    def paged_decode_multi_step(self, params, token, cache, block_tables,
                                lengths, remaining, keys, *, n_steps: int,
                                temperature: float = 0.0,
                                trash_page: int = 0,
                                fake_quant: bool = False,
                                health: bool = False):
        """``n_steps`` fused decode steps in one lax.scan (device-resident
        sampling; see decoder.paged_decode_multi_step).  ``health=True``
        appends a (B,) non-finite-logits flag to the return tuple."""
        return self.mod.paged_decode_multi_step(
            params, token, cache, block_tables, lengths, remaining, keys,
            self.cfg, n_steps=n_steps, temperature=temperature,
            trash_page=trash_page, fake_quant=fake_quant, health=health)

    def scatter_prefill(self, pool, cache, page_ids):
        """Scatter a batched contiguous prefill cache into the page pool."""
        return self.mod.scatter_prefill(self.cfg, pool, cache, page_ids)

    def paged_prefill_suffix(self, params, tokens, starts, prompt_lens,
                             pool, block_tables, *,
                             fake_quant: bool = False):
        """Prefill only the uncached suffix of G prompts over the paged
        pool (prefix sharing; see decoder.paged_prefill_suffix)."""
        return self.mod.paged_prefill_suffix(
            params, tokens, starts, prompt_lens, pool, block_tables,
            self.cfg, fake_quant=fake_quant)

    def copy_pool_pages(self, pool, src, dst):
        """Copy page contents src[i] -> dst[i] in every pool leaf (COW)."""
        return self.mod.copy_pool_pages(pool, src, dst)


# =============================================================================
# input specs (ShapeDtypeStruct stand-ins — no allocation; dry-run food)
# =============================================================================
def batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Training/prefill batch specs for one (arch x shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.family == "encdec":
        se, sd = s // 2, s // 2
        return {
            "frames": jax.ShapeDtypeStruct((b, se, cfg.d_model),
                                           jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((b, sd), i32),
            "labels": jax.ShapeDtypeStruct((b, sd), i32),
        }
    if cfg.frontend == "patch" and cfg.prefix_len:
        st = s - cfg.prefix_len
        return {
            "prefix_embeds": jax.ShapeDtypeStruct(
                (b, cfg.prefix_len, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((b, st), i32),
            "labels": jax.ShapeDtypeStruct((b, st + cfg.prefix_len), i32),
        }
    return {"tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32)}


def decode_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Decode-step specs: one new token against a seq_len-deep cache."""
    b, s = shape.global_batch, shape.seq_len
    model = Model(cfg)
    s_enc = s // 2 if cfg.family == "encdec" else 0
    max_len = s // 2 if cfg.family == "encdec" else s
    cache = jax.eval_shape(
        lambda: model.init_cache(b, max_len, s_enc))
    return {
        "token": jax.ShapeDtypeStruct((b,), jnp.int32),
        "cache": cache,
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_concrete_batch(cfg: ModelConfig, b: int, s: int, key=None):
    """Small real batch for smoke tests."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.family == "encdec":
        se, sd = max(2, s // 2), max(2, s // 2)
        return {
            "frames": jax.random.normal(k1, (b, se, cfg.d_model),
                                        jnp.float32).astype(jnp.bfloat16),
            "tokens": jax.random.randint(k2, (b, sd), 0, cfg.vocab),
            "labels": jax.random.randint(k3, (b, sd), 0, cfg.vocab),
        }
    if cfg.frontend == "patch" and cfg.prefix_len:
        st = max(2, s - cfg.prefix_len)
        return {
            "prefix_embeds": jax.random.normal(
                k1, (b, cfg.prefix_len, cfg.d_model), jnp.float32
            ).astype(jnp.bfloat16),
            "tokens": jax.random.randint(k2, (b, st), 0, cfg.vocab),
            "labels": jax.random.randint(k3, (b, st + cfg.prefix_len), 0,
                                         cfg.vocab),
        }
    return {"tokens": jax.random.randint(k2, (b, s), 0, cfg.vocab),
            "labels": jax.random.randint(k3, (b, s), 0, cfg.vocab)}
