"""Stable names for the model's layers inside the compiled programs.

Every serving program (the fused decode window, the bucket prefill) wraps
its layers in ``jax.named_scope``s with the names below.  A scope is op
metadata only: it lands in each HLO instruction's ``op_name``
(``jit(_multi)/while/body/.../kv_read/gather``), which a profiler trace
carries as the op's ``tf_op`` stat, so device time can be put down to
layers without changing a single instruction.

* ``embed``     — token embedding lookup;
* ``attn_qkv``  — pre-attention norm, q/k/v projections and RoPE;
* ``kv_write``  — the MX converter on new K/V (``_kv_quant``, code
  packing) and the page scatter;
* ``kv_read``   — page gather, unpack and dequantization;
* ``attn_core`` — scores, softmax and the weighted sum, or the Pallas
  attention kernel;
* ``attn_out``  — output projection ``wo`` and the residual add;
* ``mlp``       — pre-MLP norm, the MLP (or MoE) and the residual add;
* ``head``      — final norm and logits;
* ``sample``    — next-token sampling;
* ``health``    — the non-finite-logits and MX scale-poison flags.

Call sites take the scope function through this module's ``scope``
name (``scoped`` looks it up at each call), so a test can replace it
with ``contextlib.nullcontext`` and compare the compiled programs with
and without scopes.
"""
from __future__ import annotations

import functools

import jax

SCOPES = ("embed", "attn_qkv", "kv_write", "kv_read", "attn_core",
          "attn_out", "mlp", "head", "sample", "health")


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown model scope {name!r}; have {SCOPES}")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: run the function inside ``scope(name)``."""
    if name not in SCOPES:
        raise ValueError(f"unknown model scope {name!r}; have {SCOPES}")

    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco
