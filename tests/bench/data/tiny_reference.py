"""A copy of ``bench/reference.py`` that a configuration names as its
``reference`` module in the harness's tests.

Plain float32 reference of the served decoder, and its control.

Written from the model's equations, importing nothing of the program and
taking nothing it made: the weights are drawn again from the seed, one
layer at a time (``weights.layer_weights``), and upcast to float32; every
matmul runs at ``highest`` precision.  The equations are those the
configuration file names (pre-norm RMSNorm, grouped-query attention with
RoPE on the first ``rope_frac`` of each head in half-split pairs, SwiGLU
MLP, untied LM head).  The KV cache is not quantized here: the served
int8 / e2m1 KV is part of what is compared.

``control`` recomputes the same function with every matmul weight one
precision step below the configuration's bf16 (``fp8_e4m3``, scaled per
output column): the change a later PR could be tempted to make, which the
comparison has to catch.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.tiny_weights import layer_weights, seed_key, top_weights

F32 = jnp.float32
_MATMUL = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")


def lower_precision(w: jax.Array, kind: str) -> jax.Array:
    """``w`` (K, N) f32 rounded to the control's weight format."""
    if kind != "fp8_e4m3":
        raise ValueError(f"unknown control precision {kind!r}")
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, arch):
    """Rotate the first rope_frac of each head: pairs (j, j + r/2) of the
    rotated part turn by pos * theta^(-j / (head_dim / 2))."""
    hd = arch["head_dim"]
    r = int(hd * arch["rope_frac"])
    r -= r % 2
    h = r // 2
    freq = 1.0 / arch["rope_theta"] ** (jnp.arange(h, dtype=F32) / (hd // 2))
    ang = pos[:, None].astype(F32) * freq[None, :]          # (T, h)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :h], x[..., h:r], x[..., r:]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


def _layer(x, lw, arch):
    """One decoder layer over x (T, d) f32, causal."""
    t = x.shape[0]
    nh, nkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["norm_eps"]
    pos = jnp.arange(t)
    h = _rms(x, lw["ln1"], eps)
    q = (h @ lw["wq"]).reshape(t, nh, hd)
    k = (h @ lw["wk"]).reshape(t, nkv, hd)
    v = (h @ lw["wv"]).reshape(t, nkv, hd)
    q, k = _rope(q, pos, arch), _rope(k, pos, arch)
    rep = nh // nkv
    kh = jnp.repeat(k, rep, axis=1)                        # head h -> h // rep
    vh = jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, kh) / np.sqrt(hd)
    sc = jnp.where(pos[None, None, :] <= pos[None, :, None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vh)
    x = x + o.reshape(t, nh * hd) @ lw["wo"]
    h = _rms(x, lw["ln2"], eps)
    return x + (jax.nn.silu(h @ lw["w1"]) * (h @ lw["w3"])) @ lw["w2"]


@functools.partial(jax.jit, static_argnames=("arch_items", "control"))
def _layer_jit(x, key, i, arch_items, control):
    arch = dict(arch_items)
    lw = jax.tree_util.tree_map(lambda a: a.astype(F32),
                                layer_weights(key, i, arch))
    if control is not None:
        for blk in ("attn", "mlp"):
            lw[blk] = {n: lower_precision(w, control) if n in _MATMUL else w
                       for n, w in lw[blk].items()}
    lw = {**lw["attn"], **lw["mlp"], "ln1": lw["ln1"], "ln2": lw["ln2"]}
    return _layer(x, lw, arch)


@functools.partial(jax.jit, static_argnames=("arch_items",))
def _embed_jit(tokens, key, arch_items):
    return top_weights(key, dict(arch_items))["embed"][tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("arch_items",))
def _head_jit(x, tokens, key, arch_items):
    """Per position: the best logit, the logit of ``tokens`` there and the
    argmax (the logits themselves stay on the device)."""
    arch = dict(arch_items)
    top = jax.tree_util.tree_map(lambda a: a.astype(F32),
                                 top_weights(key, arch))
    lg = (_rms(x, top["norm_f"], arch["norm_eps"])
          @ top["lm_head"])[:, :arch["vocab"]]
    at = jnp.take_along_axis(lg, tokens[:, None], axis=1)[:, 0]
    return lg.max(-1), at, lg.argmax(-1).astype(jnp.int32)


class Served:
    """One served sequence: prompt + generated tokens, padded to a
    multiple of ``pad_to`` positions (few distinct shapes to compile;
    causal attention keeps the padding out of the real rows)."""

    def __init__(self, prompt: np.ndarray, out: np.ndarray, pad_to: int):
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        self.t = len(seq)
        self.tp = -(-self.t // pad_to) * pad_to
        self.tokens = np.zeros(self.tp, np.int32)
        self.tokens[:self.t] = seq
        self.rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        self.served = np.zeros(self.tp, np.int32)
        self.served[self.rows] = out


def hidden(seed: int, arch: Dict, tokens: np.ndarray,
           control: Optional[str] = None) -> jax.Array:
    """Final hidden states (T, d) of the reference over ``tokens``."""
    key = seed_key(seed)
    items = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(jnp.asarray(tokens), key, items)
        for i in range(int(arch["n_layers"])):
            x = _layer_jit(x, key, jnp.int32(i), items, control)
    return x


def head(seed: int, arch: Dict, x: jax.Array, tokens: np.ndarray):
    """(best logit, logit of ``tokens``, argmax) per position, on host."""
    with jax.default_matmul_precision("highest"):
        out = _head_jit(x, jnp.asarray(tokens), seed_key(seed),
                        tuple(sorted(arch.items())))
    return tuple(np.asarray(a) for a in out)


def served_gaps(seed: int, arch: Dict,
                served: Sequence[Tuple[np.ndarray, np.ndarray]],
                control: Optional[str] = None,
                pad_to: int = 1024) -> Dict[str, float]:
    """How far the served tokens of ``served`` (prompt, generated tokens)
    pairs lie below the reference's best logit: the widest gap and the
    mean gap over every served token, and the share of served tokens that
    are not the reference's first choice.

    ``altered_max_logit_gap`` is the widest gap the same run would read
    had one request's last token been moved to its neighbour where it was
    produced (a fault that changes nothing else the reference reads),
    taken for the request where that reads least.  With ``control`` set,
    the same numbers for the tokens the lower precision puts first at the
    same positions (``control_`` before each name)."""
    gaps, ctrl, other, ctrl_other, altered = [], [], 0, 0, []
    for prompt, out in served:
        s = Served(prompt, out, pad_to)
        x = hidden(seed, arch, s.tokens)
        best, at, top = head(seed, arch, x, s.served)
        gaps.append(best[s.rows] - at[s.rows])
        other += int(np.sum(top[s.rows] != s.served[s.rows]))
        last = s.rows[-1]
        moved = s.served.copy()
        moved[last] = (moved[last] + 1) % int(arch["vocab"])
        best_m, at_m, _ = head(seed, arch, x, moved)
        altered.append(float(best_m[last] - at_m[last]))
        if control is not None:
            xc = hidden(seed, arch, s.tokens, control)
            _, _, pick = head(seed, arch, xc, s.served)
            del xc
            best, at, top = head(seed, arch, x, pick)
            ctrl.append(best[s.rows] - at[s.rows])
            ctrl_other += int(np.sum(top[s.rows] != pick[s.rows]))
        del x
    res = _gap_stats(gaps, other)
    res["altered_max_logit_gap"] = max(res["max_logit_gap"],
                                       min(altered, default=0.0))
    if control is not None:
        res.update({f"control_{k}": v
                    for k, v in _gap_stats(ctrl, ctrl_other).items()})
    return res


def _gap_stats(gaps: List[np.ndarray], other: int) -> Dict[str, float]:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    n = max(len(g), 1)
    return {"max_logit_gap": float(np.max(g, initial=0.0)),
            "mean_logit_gap": float(np.sum(g) / n),
            "not_first_share": other / n, "tokens": float(len(g))}


def sample(finished: List, k: int, rng: np.random.Generator) -> List:
    """``k`` requests drawn from ``finished`` with the longest (prompt +
    output) always among them."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest and k > 1 else []
    return [finished[longest]] + [finished[rest[int(i)]] for i in pick]
