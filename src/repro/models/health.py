"""Numeric-health reductions over the paged MX KV pool.

The paper's converter gives every 32-element block an E8M0 scale byte and
reserves the top encodings for non-finite blocks (SCALE_INF/SCALE_NAN in
paper mode; ocp mode folds both into SCALE_NAN).  That makes poison
detection on a serving pool a pure uint8 compare over the *scale* leaves
— a few bytes per token per layer, no dequantization, no touching the
(much larger) code pages.  :func:`slot_scale_poison` folds that compare
into the engine's jitted decode/prefill closures so a NaN/Inf-poisoned
slot is flagged at the window boundary and quarantined before its
garbage tokens are ever emitted.

Scope: MX pools get marker detection; fp (bf16/f32) pools have no scale
bytes, so they rely on the finite-logits guard the decode scan carries
(``decoder.paged_decode_multi_step(health=True)``) — a NaN page always
surfaces as non-finite logits for the slot that attends it.

Masking matters: a slot's block-table row is trash-padded past its
allocation, and recycled pages may still hold stale marker bytes from a
previously quarantined request at positions the new owner has not yet
written.  Both are excluded by the position mask (``pos < length``):
only bytes the slot actually wrote (prefill scatter, decode writes, or a
swap restore) can flag it.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.formats import poison_threshold
from repro.models import scopes
from repro.models.layers import paged_page_size


@scopes.scoped("health")
def nonfinite_rows(logits):
    """(B,) bool: True where a row of ``logits`` (B, vocab) holds a
    non-finite value — the finite-logits half of the guard."""
    return ~jnp.all(jnp.isfinite(logits), axis=-1)


def _group_poison(group, page_tables, live, kk, kv):
    """Poison flags for one layer group's pool dict.

    ``group`` — one layer's (or the stacked scan's) pool leaves;
    ``page_tables`` (B, n) physical page ids; ``live`` (B, n*page) bool
    position mask.  Returns (B,) bool."""
    b = page_tables.shape[0]
    flags = jnp.zeros((b,), bool)
    for sk, spec in (("ks_pages", kk), ("vs_pages", kv)):
        leaf = group.get(sk)
        if leaf is None or spec is None:    # fp pool: no scale bytes
            continue
        thr = jnp.uint8(poison_threshold(spec.mode))
        g = leaf[:, page_tables] if leaf.ndim == 5 else leaf[page_tables]
        bad = g >= thr
        if leaf.ndim == 5:                  # layer-stacked: any layer
            bad = jnp.any(bad, axis=0)
        bad = jnp.any(bad, axis=(-1, -2))   # over (n_kv, blocks)
        flags = flags | jnp.any(bad.reshape(b, -1) & live, axis=-1)
    return flags


@scopes.scoped("health")
def slot_scale_poison(pool, page_tables, lengths, cfg):
    """Per-slot MX-block poison detection: (B,) bool, True where any
    SCALE_NAN/SCALE_INF marker byte sits inside the slot's *live* cache
    positions (pos < lengths[b]) across every layer's K and V pools.

    ``pool`` is the engine's page-pool pytree ({"layers": leaf-dict or
    per-layer list, "dense_layers": [...]}); ``page_tables`` (B, n) int32
    physical page ids per slot (a block-table slice or a prefill's
    page_ids); ``lengths`` (B,) int32 written positions.  Jit-safe; the
    threshold is mode-aware per layer/role (paper: >= SCALE_INF, ocp:
    == SCALE_NAN — see ``core.formats.poison_threshold``).
    """
    page = paged_page_size(
        pool["layers"][0] if isinstance(pool["layers"], list)
        else pool["layers"])
    b, n = page_tables.shape
    live = jnp.arange(n * page)[None, :] < lengths[:, None]
    flags = jnp.zeros((b,), bool)
    lay = pool["layers"]
    if isinstance(lay, list):               # per-layer PolicyTable pools
        for i, g in enumerate(lay):
            c = cfg.layer_cfg(cfg.n_dense_layers + i)
            flags = flags | _group_poison(g, page_tables, live,
                                          c.mx.kv_key, c.mx.kv_value)
    else:
        flags = flags | _group_poison(lay, page_tables, live,
                                      cfg.mx.kv_key, cfg.mx.kv_value)
    for i, g in enumerate(pool.get("dense_layers", [])):
        c = cfg.layer_cfg(i)
        flags = flags | _group_poison(g, page_tables, live,
                                      c.mx.kv_key, c.mx.kv_value)
    return flags
