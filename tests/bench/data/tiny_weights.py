"""A copy of ``bench/weights.py`` that a configuration names as its
``weights`` module in the harness's tests.

Seeded random weights, made by the benchmark and not by the program.

One generator serves both sides: ``make_params`` builds the served bf16
pytree on the device in one jitted call, and the reference calls
``layer_weights`` again for one layer at a time.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 512
_LEAF = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "w1": 4, "w3": 5, "w2": 6}
_TOP = {"embed": 0, "lm_head": 1}
_TOP_KEY = 1 << 20


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (wider than 32
    bits included), the same for the same seed."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray([a, b], jnp.uint32)


def padded_vocab(arch: Dict) -> int:
    return -(-int(arch["vocab"]) // VOCAB_PAD) * VOCAB_PAD


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_weights(key, i, arch: Dict, dtype=jnp.bfloat16) -> Dict:
    """Layer ``i``'s weights (``i`` may be traced): norms at 1, matmuls
    N(0, 1/fan_in)."""
    d, nh, nkv = arch["d_model"], arch["n_heads"], arch["n_kv_heads"]
    hd, ff = arch["head_dim"], arch["d_ff"]
    k = jax.random.fold_in(key, i)
    ks = {n: jax.random.fold_in(k, j) for n, j in _LEAF.items()}
    shapes = {"wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
              "wo": (nh * hd, d), "w1": (d, ff), "w3": (d, ff),
              "w2": (ff, d)}
    w = {n: _normal(ks[n], s, 1.0 / np.sqrt(s[0]), dtype)
         for n, s in shapes.items()}
    return {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
            "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
            "mlp": {n: w[n] for n in ("w1", "w3", "w2")}}


def top_weights(key, arch: Dict, dtype=jnp.bfloat16) -> Dict:
    """Embedding N(0, 0.02^2), final norm at 1, LM head N(0, 1/d)."""
    d, vp = arch["d_model"], padded_vocab(arch)
    k = jax.random.fold_in(key, _TOP_KEY)
    return {"embed": _normal(jax.random.fold_in(k, _TOP["embed"]), (vp, d),
                             0.02, dtype),
            "norm_f": jnp.ones((d,), dtype),
            "lm_head": _normal(jax.random.fold_in(k, _TOP["lm_head"]),
                               (d, vp), 1.0 / np.sqrt(d), dtype)}


def make_params(seed: int, arch: Dict, shardings=None) -> Dict:
    """The served parameters, built on the device in one jitted call, one
    layer per ``lax.map`` step.  ``shardings`` (a pytree of the
    parameters' shardings) places each leaf as it is built, so that no
    device holds the whole model; without it every leaf lands on the
    default device."""
    n = int(arch["n_layers"])

    def build(key):
        params = top_weights(key, arch)
        params["layers"] = jax.lax.map(lambda i: layer_weights(key, i, arch),
                                       jnp.arange(n))
        return params

    jitted = jax.jit(build) if shardings is None \
        else jax.jit(build, out_shardings=shardings)
    return jitted(seed_key(seed))
