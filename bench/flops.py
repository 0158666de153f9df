"""Operations the served model needs, from its shapes and the live
context lengths.

These count the work the algorithm requires, not what an implementation
happens to do: padded prompt positions, idle decode slots, logits of
positions whose token is never sampled and pages a kernel visits past a
slot's length add nothing.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

_PEAKS = Path(__file__).resolve().parent / "peaks.json"

def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def layer_matmul_params(arch: Dict) -> int:
    d, nh, nkv = arch["d_model"], arch["n_heads"], arch["n_kv_heads"]
    hd, ff = arch["head_dim"], arch["d_ff"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff


def matmul_params(arch: Dict) -> int:
    """Weights every token multiplies through (layers and LM head)."""
    return arch["n_layers"] * layer_matmul_params(arch) \
        + arch["d_model"] * arch["vocab"]


def attn_flops(arch: Dict, ctx: float) -> float:
    """QK^T and PV for one query token over ``ctx`` keys, all layers."""
    return 4.0 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * ctx


def prefill_flops(arch: Dict, n: int) -> float:
    """A prompt of ``n`` tokens: every layer over every token, causal
    attention, and the LM head on the last position only."""
    layers = 2.0 * arch["n_layers"] * layer_matmul_params(arch) * n
    attn = attn_flops(arch, 1.0) * n * (n + 1) / 2.0
    return layers + attn + 2.0 * arch["d_model"] * arch["vocab"]


def decode_flops(arch: Dict, ctx: int) -> float:
    """One generated token whose query attends ``ctx`` positions."""
    return 2.0 * matmul_params(arch) + attn_flops(arch, ctx)


def step_model_flops(arch: Dict, step) -> float:
    """Model FLOPs of one engine step (a ``serving.StepRec``): its
    prompts' prefill and its live decode tokens."""
    f = sum(prefill_flops(arch, n) for g in step.groups for n in g)
    for ctx0, live in step.decode_ctx:
        f += sum(decode_flops(arch, ctx0 + t + 1) for t in range(live))
    return f
