"""Decoder-only LM driver: dense GQA, MLA, MoE, prefix-embed (VLM) variants.

Layers are stacked and driven by ``lax.scan`` (compile-time discipline: one
layer's HLO regardless of depth).  Caches are layer-stacked pytrees carried
through the same scan.

Per-layer quantization (``cfg.mx_table``, a ``PolicyTable``): the scan
body is traced once, so layer-varying *static* specs — and the per-layer
KV cache shapes they imply — cannot ride through it.  When a non-uniform
table is installed (``apply_policy_table`` collapses uniform ones), every
layer walk in this module unrolls into a Python loop over per-layer
configs (``cfg.layer_cfg(i)``) and caches become per-layer lists, each
sized by its own layer's ``kv_key``/``kv_value`` specs.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical
from repro.models import health as H
from repro.models import layers as L
from repro.models import scopes
from repro.models.config import ModelConfig

VOCAB_PAD = 512


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab // VOCAB_PAD) * VOCAB_PAD


def _layer_init(key, cfg: ModelConfig, moe_layer: bool) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    dt = L.dtype_of(cfg)
    p = {"ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt)}
    if cfg.mla:
        p["attn"] = L.mla_init(k1, cfg)
    else:
        p["attn"] = L.attn_init(k1, cfg)
    if moe_layer:
        p["moe"] = L.moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


def init(key, cfg: ModelConfig, *,
         quantized: bool = False) -> Dict[str, Any]:
    """Random parameters from ``key``.  ``quantized=True`` returns
    ``quantize_weights(init(key, cfg), cfg)`` built one layer at a time:
    under jit each layer's fp weights are drawn and quantized inside a
    ``lax.map`` step, so the fp model is never resident beside its MX
    copy."""
    vp = padded_vocab(cfg)
    d = cfg.d_model
    dt = L.dtype_of(cfg)
    keys = jax.random.split(key, 4)
    n_scan = cfg.n_layers - cfg.n_dense_layers
    lkeys = jax.random.split(keys[0], n_scan)
    moe_layer = cfg.n_experts > 0

    def layer(k, cfg_i, moe):
        lp = _layer_init(k, cfg, moe)
        return _quantize_layer_tree(lp, cfg_i.mx.weights) if quantized \
            else lp

    if not quantized:
        layers = jax.vmap(lambda k: _layer_init(k, cfg, moe_layer))(lkeys)
    elif cfg.mx_table is None:
        layers = jax.lax.map(lambda k: layer(k, cfg, moe_layer), lkeys)
    else:
        layers = [layer(k, cfg_i, moe_layer)
                  for k, cfg_i in zip(lkeys, _scan_cfgs(cfg))]
    params = {
        "embed": L.embed_init(keys[1], vp, d, dt),
        "layers": layers,
        "norm_f": jnp.ones((d,), dt),
    }
    if cfg.n_dense_layers:
        dkeys = jax.random.split(keys[2], cfg.n_dense_layers)
        params["dense_layers"] = [
            layer(k, cfg.layer_cfg(i), False) for i, k in enumerate(dkeys)]
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[3], d, vp, dt)
    return params


def _block(lp, x, cfg: ModelConfig, *, positions, cache=None, cache_pos=None,
           moe_layer: bool, fake_quant: bool,
           paged=None, paged_prefill=None,
           tap=None) -> Tuple[jax.Array, Any, jax.Array]:
    with scopes.scope("attn_qkv"):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    s = x.shape[1]
    if paged_prefill is not None:
        block_tables, starts, prompt_lens = paged_prefill
        a, new_cache = L.attention_paged_prefill(
            lp["attn"], h, cfg, pool=cache, block_tables=block_tables,
            starts=starts, prompt_lens=prompt_lens, fake_quant=fake_quant)
    elif paged is not None:
        block_tables, lengths = paged
        a, new_cache = L.attention_paged_decode(
            lp["attn"], h, cfg, pool=cache, block_tables=block_tables,
            lengths=lengths, fake_quant=fake_quant)
    elif cfg.mla:
        if cache is not None and s == 1:
            a, new_cache = L.mla_decode(lp["attn"], h, cfg, cache=cache,
                                        cache_pos=cache_pos,
                                        fake_quant=fake_quant)
        else:
            a, new_cache = L.mla_attention(lp["attn"], h, cfg,
                                           positions=positions, cache=cache,
                                           cache_pos=cache_pos,
                                           fake_quant=fake_quant)
    else:
        a, new_cache = L.attention(lp["attn"], h, cfg, positions=positions,
                                   cache=cache, cache_pos=cache_pos,
                                   fake_quant=fake_quant, tap=tap)
    with scopes.scope("attn_out"):
        x = x + a
    with scopes.scope("mlp"):
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if tap is not None:
            # the activations role quantizes matmul inputs; the two
            # post-norm hidden states are the layer's representative
            # matmul inputs
            tap["activations"] = jnp.concatenate([h, h2], axis=1)
        aux = jnp.zeros((), jnp.float32)
        if moe_layer:
            m, aux = L.moe(lp["moe"], h2, cfg, fake_quant)
        else:
            m = L.mlp(lp["mlp"], h2, cfg, fake_quant)
        return x + m, new_cache, aux


@scopes.scoped("embed")
def _embed(params, cfg, tokens, prefix_embeds):
    x = jnp.take(params["embed"], tokens, axis=0).astype(L.dtype_of(cfg))
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return logical(x, "batch", None, None)


@scopes.scoped("head")
def _head(params, cfg, x):
    x = L.rms_norm(x, params["norm_f"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logical(logits, "batch", None, "model")


def _scan_layer_params(params, i: int):
    """Layer ``i``'s slice of the scanned-layer params (stacked pytree, or
    a per-layer list when heterogeneous weight specs force one — see
    ``quantize_weights``)."""
    layers = params["layers"]
    if isinstance(layers, (list, tuple)):
        return layers[i]
    return jax.tree_util.tree_map(lambda p: p[i], layers)


def _scan_cfgs(cfg: ModelConfig):
    """Per-layer configs of the scanned stack (absolute layer indices
    continue after the leading dense layers)."""
    n_scan = cfg.n_layers - cfg.n_dense_layers
    return [cfg.layer_cfg(cfg.n_dense_layers + i) for i in range(n_scan)]


# matmul weight leaves quantized by ``quantize_weights`` — all stored
# (..., K, N) with the contraction axis at -2.  Router logits, norms,
# embeddings, and the (tied or separate) LM head stay fp.
_WEIGHT_KEYS = frozenset({"wq", "wk", "wv", "wo", "w1", "w2", "w3"})


def _quantize_layer_tree(lp, spec):
    """MXWeight-quantize every matmul weight leaf of one layer's params
    (leading scan/expert axes ride along); None spec keeps the layer fp."""
    if spec is None:
        return lp

    def walk(d):
        out = {}
        for key, val in d.items():
            if isinstance(val, dict):
                out[key] = walk(val)
            elif key in _WEIGHT_KEYS and getattr(val, "ndim", 0) >= 2:
                out[key] = L.MXWeight.quantize(val, spec)
            else:
                out[key] = val
        return out

    return walk(lp)


def _require_gqa_weights(cfg: ModelConfig) -> None:
    if cfg.mla:
        raise NotImplementedError(
            "weight-resident storage covers the GQA decoder family "
            "(MLA projections are not routed through MXWeight yet)")


def init_quantized(key, cfg: ModelConfig) -> Dict[str, Any]:
    """``quantize_weights(init(key, cfg), cfg)``, one layer at a time."""
    _require_gqa_weights(cfg)
    return init(key, cfg, quantized=True)


def quantize_weights(params, cfg: ModelConfig):
    """Convert matmul weights to weight-resident MXWeight storage.

    Uniform policy (``cfg.mx.weights``): the stacked scanned-layer pytree
    is quantized in place — MXWeight is a registered pytree, so
    ``lax.scan`` still slices one layer per step.  Non-uniform tables
    (``cfg.mx_table``): ``params["layers"]`` becomes a per-layer list,
    each layer quantized per its own ``layer_cfg(i).mx.weights`` (layers
    whose table entry has no weights role stay fp) — the unrolled walks
    already consume lists via ``_scan_layer_params``.
    """
    _require_gqa_weights(cfg)
    out = dict(params)
    if cfg.mx_table is not None:
        out["layers"] = [
            _quantize_layer_tree(_scan_layer_params(params, i),
                                 cfg_i.mx.weights)
            for i, cfg_i in enumerate(_scan_cfgs(cfg))]
    else:
        out["layers"] = _quantize_layer_tree(params["layers"],
                                             cfg.mx.weights)
    if "dense_layers" in params:
        out["dense_layers"] = [
            _quantize_layer_tree(dl, cfg.layer_cfg(i).mx.weights)
            for i, dl in enumerate(params["dense_layers"])]
    return out


def forward(params, tokens, cfg: ModelConfig, *, prefix_embeds=None,
            fake_quant: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Training forward: (B,S)->(B,S,Vp) logits + MoE aux loss."""
    x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    moe_layer = cfg.n_experts > 0
    for i, dl in enumerate(params.get("dense_layers", [])):
        x, _, _ = _block(dl, x, cfg.layer_cfg(i), positions=positions,
                         moe_layer=False, fake_quant=fake_quant)
    if cfg.mx_table is not None:
        # non-uniform per-layer policy: specs are jit-static, so the
        # layer walk unrolls — each layer checkpointed like the scanned
        # stack (auto-policy QAT training runs through this path)
        auxs = []
        for i, cfg_i in enumerate(_scan_cfgs(cfg)):
            def one(lp, x, cfg_i=cfg_i):
                y, _, aux = _block(lp, x, cfg_i, positions=positions,
                                   moe_layer=moe_layer,
                                   fake_quant=fake_quant)
                return y, aux

            fn = jax.checkpoint(one) if cfg.remat else one
            x, aux = fn(_scan_layer_params(params, i), x)
            auxs.append(aux)
        return _head(params, cfg, x), jnp.mean(jnp.stack(auxs))

    def step(carry, lp):
        y, new_cache, aux = _block(lp, carry, cfg, positions=positions,
                                   moe_layer=moe_layer,
                                   fake_quant=fake_quant)
        return y, aux

    step_fn = jax.checkpoint(step) if cfg.remat else step
    x, auxs = L.layer_scan(step_fn, x, params["layers"], cfg)
    return _head(params, cfg, x), jnp.mean(auxs)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    n_scan = cfg.n_layers - cfg.n_dense_layers
    if cfg.mla:
        mk = lambda c, ld: L.init_mla_cache(c, batch, max_len, layers_dim=ld)
    else:
        mk = lambda c, ld: L.init_kv_cache(c, batch, max_len, c.n_kv_heads,
                                           c.hd, layers_dim=ld)
    if cfg.mx_table is not None:
        # per-layer specs => per-layer cache shapes: a list, not a stack
        cache = {"layers": [mk(c, ()) for c in _scan_cfgs(cfg)]}
    else:
        cache = {"layers": mk(cfg, (n_scan,))}
    if cfg.n_dense_layers:
        cache["dense_layers"] = [mk(cfg.layer_cfg(i), ())
                                 for i in range(cfg.n_dense_layers)]
    return cache


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    """Paged page-pool analogue of ``init_cache``: pages are shared across
    requests via per-slot block tables (see repro.serve).  Under a
    per-layer policy table each layer's pool is sized by its own
    ``kv_key``/``kv_value`` specs (e.g. INT8 pages on sensitive layers,
    half-size packed E2M1 pages elsewhere)."""
    if cfg.mla:
        raise NotImplementedError(
            "paged KV serving covers the GQA decoder family; the MLA "
            "compressed cache keeps the contiguous layout")
    n_scan = cfg.n_layers - cfg.n_dense_layers
    mk = lambda c, ld: L.init_paged_kv_cache(c, num_pages, page_size,
                                             c.n_kv_heads, c.hd,
                                             layers_dim=ld)
    if cfg.mx_table is not None:
        cache = {"layers": [mk(c, ()) for c in _scan_cfgs(cfg)]}
    else:
        cache = {"layers": mk(cfg, (n_scan,))}
    if cfg.n_dense_layers:
        cache["dense_layers"] = [mk(cfg.layer_cfg(i), ())
                                 for i in range(cfg.n_dense_layers)]
    return cache


def _run_layers(params, cache, x, cfg, positions, cache_pos, fake_quant):
    moe_layer = cfg.n_experts > 0
    new_dense = []
    for i, dl in enumerate(params.get("dense_layers", [])):
        x, nc, _ = _block(dl, x, cfg.layer_cfg(i), positions=positions,
                          cache=cache["dense_layers"][i],
                          cache_pos=cache_pos, moe_layer=False,
                          fake_quant=fake_quant)
        new_dense.append(nc)
    if cfg.mx_table is not None:
        new_layer_cache = []
        for i, cfg_i in enumerate(_scan_cfgs(cfg)):
            x, nc, _ = _block(_scan_layer_params(params, i), x, cfg_i,
                              positions=positions, cache=cache["layers"][i],
                              cache_pos=cache_pos, moe_layer=moe_layer,
                              fake_quant=fake_quant)
            new_layer_cache.append(nc)
    else:
        def step(carry, xs):
            lp, cache_l = xs
            y, nc, _ = _block(lp, carry, cfg, positions=positions,
                              cache=cache_l, cache_pos=cache_pos,
                              moe_layer=moe_layer, fake_quant=fake_quant)
            return y, nc

        x, new_layer_cache = L.layer_scan(
            step, x, (params["layers"], cache["layers"]), cfg)
    new_cache = {"layers": new_layer_cache}
    if new_dense:
        new_cache["dense_layers"] = new_dense
    return x, new_cache


def prefill(params, tokens, cfg: ModelConfig, *, max_len: int,
            prefix_embeds=None, fake_quant: bool = False):
    """Process the prompt, fill the cache at [0, S); returns (logits, cache,
    next position)."""
    x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    cache = init_cache(cfg, b, max_len)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x, cache = _run_layers(params, cache, x, cfg, positions, 0, fake_quant)
    return _head(params, cfg, x), cache, s


def decode_step(params, token, cache, pos, cfg: ModelConfig, *,
                fake_quant: bool = False):
    """One decode step: token (B,) int32, pos scalar int32 (cache length so
    far).  Returns (logits (B,1,Vp), new cache)."""
    x = _embed(params, cfg, token[:, None], None)
    b = x.shape[0]
    positions = jnp.full((b, 1), pos)
    x, cache = _run_layers(params, cache, x, cfg, positions, pos, fake_quant)
    return _head(params, cfg, x), cache


def forward_calib(params, tokens, cfg: ModelConfig, *, prefix_embeds=None):
    """Instrumented forward for activation-statistics calibration.

    Runs the layer walk *unrolled* (the same per-layer path a non-uniform
    policy table uses) and taps, per absolute layer, the tensors each
    quantizable role would see: ``activations`` (the two post-norm matmul
    inputs, concatenated along the sequence axis), and the post-RoPE,
    pre-quantization ``kv_key``/``kv_value`` projections.  Nothing is
    quantized on this path, so the taps are clean regardless of ``cfg.mx``.

    Returns ``(logits, aux, taps)`` with ``taps[role][layer]`` a float
    tensor.  MLA configs are not supported (calibration targets the GQA
    paged-serving family, the same constraint as ``init_paged_cache``).
    """
    if cfg.mla:
        raise NotImplementedError(
            "forward_calib covers the GQA decoder family; the MLA "
            "compressed cache has no per-head K/V tensors to calibrate")
    x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    moe_layer = cfg.n_experts > 0
    taps = {"activations": [], "kv_key": [], "kv_value": []}

    def run(lp, x, cfg_i, moe):
        tap = {}
        x, _, aux = _block(lp, x, cfg_i, positions=positions, moe_layer=moe,
                           fake_quant=False, tap=tap)
        taps["activations"].append(tap["activations"])
        taps["kv_key"].append(tap["k"])
        taps["kv_value"].append(tap["v"])
        return x, aux

    auxs = []
    for i, dl in enumerate(params.get("dense_layers", [])):
        x, aux = run(dl, x, cfg.layer_cfg(i), False)
        auxs.append(aux)
    for i, cfg_i in enumerate(_scan_cfgs(cfg)):
        x, aux = run(_scan_layer_params(params, i), x, cfg_i, moe_layer)
        auxs.append(aux)
    return _head(params, cfg, x), jnp.mean(jnp.stack(auxs)), taps


@scopes.scoped("sample")
def sample_tokens(logits, keys, temperature: float):
    """Sample one token per slot inside the jitted decode path.

    logits (B, vocab) f32; keys (B, 2) uint32 per-slot PRNG keys.  The keys
    are split every call regardless of temperature, so greedy and sampled
    runs share one key-evolution schedule and the fused multi-step loop is
    token-identical to the per-step loop at any temperature.  Returns
    (new keys, tokens (B,) int32)."""
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    keys, sub = split[:, 0], split[:, 1]
    if temperature <= 0.0:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        nxt = jax.vmap(
            lambda k, row: jax.random.categorical(
                k, row.astype(jnp.float32) / temperature)
        )(sub, logits).astype(jnp.int32)
    return keys, nxt


@scopes.scoped("kv_write")
def scatter_prefill(cfg: ModelConfig, pool, cache, page_ids):
    """Scatter a batched contiguous prefill cache (G requests padded to the
    same page-multiple length) into the paged pools; one donated scatter
    per leaf, sub-byte codes packed on the way (see
    layers.paged_cache_scatter)."""
    if cfg.mx_table is not None:
        new = {"layers": [
            L.paged_cache_scatter(pg, cg, page_ids, cfg_i)
            for pg, cg, cfg_i in zip(pool["layers"], cache["layers"],
                                     _scan_cfgs(cfg))]}
    else:
        new = {"layers": L.paged_cache_scatter(
            pool["layers"], cache["layers"], page_ids, cfg)}
    if "dense_layers" in pool:
        new["dense_layers"] = [
            L.paged_cache_scatter(pg, cg, page_ids, cfg.layer_cfg(i))
            for i, (pg, cg) in enumerate(zip(pool["dense_layers"],
                                             cache["dense_layers"]))]
    return new


def copy_pool_pages(pool, src, dst):
    """Copy page contents src[i] -> dst[i] in every pool leaf (COW fork
    under prefix sharing).  src/dst (M,) i32 physical page ids; leaves are
    (P, page, n_kv, X) or layer-stacked (n_scan, P, page, n_kv, X) — the
    bytes copy verbatim whatever the layer's spec, so one call covers
    uniform policies, per-layer tables, and fp pools alike."""
    def leaf(x):
        return x.at[:, dst].set(x[:, src]) if x.ndim == 5 \
            else x.at[dst].set(x[src])
    return jax.tree_util.tree_map(leaf, pool)


def paged_prefill_suffix(params, tokens, starts, prompt_lens, cache,
                         block_tables, cfg: ModelConfig, *,
                         fake_quant: bool = False):
    """Prefill only the *uncached suffix* of G prompts over the paged KV
    cache (prefix sharing): request g's tokens cover prompt positions
    [starts[g], prompt_lens[g]), padded on the right; earlier positions
    are already resident in the slot's (shared, read-only) prefix pages.

    tokens (G, S) int32; starts/prompt_lens (G,) int32; block_tables
    (G, max_pages) int32 — the slots' full rows, with any copy-on-write
    fork already applied.  Returns (logits (G, S, Vp), new page pools);
    logits row i of request g corresponds to prompt position
    ``starts[g] + i`` (the engine samples at ``prompt_lens - starts - 1``).
    """
    x = _embed(params, cfg, tokens, None)
    paged_prefill = (block_tables, starts, prompt_lens)
    moe_layer = cfg.n_experts > 0
    new_dense = []
    for i, dl in enumerate(params.get("dense_layers", [])):
        x, nc, _ = _block(dl, x, cfg.layer_cfg(i), positions=None,
                          cache=cache["dense_layers"][i], moe_layer=False,
                          fake_quant=fake_quant,
                          paged_prefill=paged_prefill)
        new_dense.append(nc)
    if cfg.mx_table is not None:
        new_layer_cache = []
        for i, cfg_i in enumerate(_scan_cfgs(cfg)):
            x, nc, _ = _block(_scan_layer_params(params, i), x, cfg_i,
                              positions=None, cache=cache["layers"][i],
                              moe_layer=moe_layer, fake_quant=fake_quant,
                              paged_prefill=paged_prefill)
            new_layer_cache.append(nc)
    else:
        def step(carry, xs):
            lp, cache_l = xs
            y, nc, _ = _block(lp, carry, cfg, positions=None, cache=cache_l,
                              moe_layer=moe_layer, fake_quant=fake_quant,
                              paged_prefill=paged_prefill)
            return y, nc

        x, new_layer_cache = L.layer_scan(
            step, x, (params["layers"], cache["layers"]), cfg)
    new_cache = {"layers": new_layer_cache}
    if new_dense:
        new_cache["dense_layers"] = new_dense
    return _head(params, cfg, x), new_cache


def paged_decode_step(params, token, cache, block_tables, lengths,
                      cfg: ModelConfig, *, fake_quant: bool = False):
    """One continuous-batching decode step over the paged KV cache.

    token (B,) int32 — one in-flight token per slot; block_tables
    (B, max_pages) int32; lengths (B,) int32 — slot b's token sits at
    position lengths[b] (0 and a zeroed block-table row for idle slots).
    Returns (logits (B,1,Vp), new page pools)."""
    x = _embed(params, cfg, token[:, None], None)
    paged = (block_tables, lengths)
    moe_layer = cfg.n_experts > 0
    new_dense = []
    for i, dl in enumerate(params.get("dense_layers", [])):
        x, nc, _ = _block(dl, x, cfg.layer_cfg(i), positions=None,
                          cache=cache["dense_layers"][i], moe_layer=False,
                          fake_quant=fake_quant, paged=paged)
        new_dense.append(nc)
    if cfg.mx_table is not None:
        new_layer_cache = []
        for i, cfg_i in enumerate(_scan_cfgs(cfg)):
            x, nc, _ = _block(_scan_layer_params(params, i), x, cfg_i,
                              positions=None, cache=cache["layers"][i],
                              moe_layer=moe_layer, fake_quant=fake_quant,
                              paged=paged)
            new_layer_cache.append(nc)
    else:
        def step(carry, xs):
            lp, cache_l = xs
            y, nc, _ = _block(lp, carry, cfg, positions=None, cache=cache_l,
                              moe_layer=moe_layer, fake_quant=fake_quant,
                              paged=paged)
            return y, nc

        x, new_layer_cache = L.layer_scan(
            step, x, (params["layers"], cache["layers"]), cfg)
    new_cache = {"layers": new_layer_cache}
    if new_dense:
        new_cache["dense_layers"] = new_dense
    return _head(params, cfg, x), new_cache


def paged_decode_multi_step(params, token, cache, block_tables, lengths,
                            remaining, keys, cfg: ModelConfig, *,
                            n_steps: int, temperature: float = 0.0,
                            trash_page: int = 0,
                            fake_quant: bool = False,
                            health: bool = False):
    """``n_steps`` fused continuous-batching decode steps in one
    ``lax.scan`` — the device-resident hot loop.

    Carries tokens, per-slot lengths, remaining generation budgets, and
    PRNG keys on device; each iteration runs ``paged_decode_step`` (KV
    writes land in the paged pool inside the scan) and samples the next
    token with ``sample_tokens``.  Slots whose budget hits zero are masked:
    their block-table row is re-pointed at ``trash_page`` (the serving
    engine passes ``repro.serve.paging.TRASH_PAGE``) and their
    length/token freeze, so over-generated steps can never corrupt live
    pages (idle slots enter with remaining == 0 and stay masked).  The
    caller must have pre-granted every page the window's writes need
    (``Scheduler.plan_window``).

    token/lengths/remaining (B,) int32; keys (B, 2) uint32.  Returns
    (tokens (n_steps, B) int32, new cache, new lengths, new remaining,
    new keys) — plus, with ``health=True``, a (B,) bool flagging slots
    whose sampled logits went non-finite at any *active* step of the
    window (the finite-logits half of the serving numeric-health guard;
    masked/done slots are exempt, since their logits are garbage by
    design).  The flag rides the scan carry, so it costs one (B, vocab)
    ``isfinite`` reduction per step and nothing on the host.
    """
    vocab = cfg.vocab

    def one(carry, _):
        tok, cache, lengths, remaining, keys, bad = carry
        done = remaining <= 0
        bt = jnp.where(done[:, None], trash_page, block_tables)
        ln = jnp.where(done, 0, lengths)
        logits, cache = paged_decode_step(params, tok, cache, bt, ln, cfg,
                                          fake_quant=fake_quant)
        last = logits[:, -1, :vocab]
        keys, nxt = sample_tokens(last, keys, temperature)
        if health:
            bad = bad | (H.nonfinite_rows(last) & ~done)
        nxt = jnp.where(done, tok, nxt)
        lengths = jnp.where(done, lengths, lengths + 1)
        remaining = jnp.where(done, remaining, remaining - 1)
        return (nxt, cache, lengths, remaining, keys, bad), nxt

    bad0 = jnp.zeros(token.shape, bool)
    (token, cache, lengths, remaining, keys, bad), toks = jax.lax.scan(
        one, (token, cache, lengths, remaining, keys, bad0), None,
        length=n_steps)
    if health:
        return toks, cache, lengths, remaining, keys, bad
    return toks, cache, lengths, remaining, keys
