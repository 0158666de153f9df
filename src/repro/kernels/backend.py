"""Backend-aware execution-mode defaults for the Pallas kernels.

Every kernel entry point takes ``interpret=None`` and resolves it through
``resolve_interpret``: an explicit boolean wins, otherwise the kernels run
in interpret mode (kernel bodies executed as plain JAX, the correctness
path) exactly when the default backend is the CPU, and compile to Mosaic
on the TPU.

**Supervised dispatch** (fault drills): callers with a dense fallback
route their kernel through :func:`supervised`.  An injected launch fault
(:func:`inject_failure`, driven by ``repro.serve.faults``) marks the op
degraded, logs once, and makes ``supervised`` return None, so the caller's
``if out is None`` dense path takes over for the rest of the process.
Both paths are token-identical by construction (asserted by the kernel
correctness suites).  A real kernel error is never caught here: it
propagates, so a kernel the chip's compiler refuses fails the run instead
of quietly serving at dense speed.
"""
from __future__ import annotations

import logging
import os
from typing import Dict, Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` kwarg: explicit value, else interpret mode
    exactly when the default backend is the CPU.  The result is a plain
    bool so it can ride through jit static arguments."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


MATMUL_ENV_VAR = "REPRO_MX_MATMUL_IMPL"
MATMUL_IMPLS = ("fused", "einsum")


def resolve_matmul_impl(impl: Optional[str] = None) -> str:
    """Resolve the weight-resident matmul path: explicit value > env > fused.

    ``"fused"`` runs the Pallas dequant-in-VMEM kernel (sub-byte codes
    unpacked inside the tile loop; fp weights never hit HBM); ``"einsum"``
    dequantizes the whole weight and falls back to a plain einsum.  The
    ``REPRO_MX_MATMUL_IMPL`` environment override is read at trace time,
    so flipping it only affects newly traced computations.
    """
    if impl is None:
        impl = os.environ.get(MATMUL_ENV_VAR, "fused")
    if impl not in MATMUL_IMPLS:
        raise ValueError(
            f"unknown mx matmul impl {impl!r}; expected one of {MATMUL_IMPLS}")
    return impl


# =============================================================================
# Supervised kernel dispatch (an injected fault degrades to dense)
# =============================================================================
class KernelFault(RuntimeError):
    """An injected kernel launch failure (see ``repro.serve.faults``)."""


_log = logging.getLogger("repro.kernels")
_degraded: Dict[str, str] = {}      # op -> first failure reason
_injected: set = set()              # ops armed to fail at next trace


def is_degraded(op: str) -> bool:
    return op in _degraded


def degraded_ops() -> Dict[str, str]:
    """Snapshot of degraded ops and the failure that demoted each."""
    return dict(_degraded)


def degrade(op: str, reason: str) -> None:
    """Mark ``op`` degraded; the first demotion is logged (once)."""
    if op not in _degraded:
        _degraded[op] = reason
        _log.warning("kernel %r failed (%s); degrading to the dense "
                     "fallback path for this process", op, reason)


def reset_degradation() -> None:
    """Clear degradations and armed failures (test isolation)."""
    _degraded.clear()
    _injected.clear()


def inject_failure(op: str) -> None:
    """Arm a one-shot failure: the next ``supervised(op, ...)`` degrades
    the op instead of running the kernel.  Consumed at
    trace time — the caller must force a retrace (fresh ``jax.jit``
    wrapper) for an already-compiled computation to hit it."""
    _injected.add(op)


def supervised(op: str, fn, *args, **kwargs):
    """Run kernel ``fn`` unless ``op`` is degraded or an injected fault is
    armed for it; those return None and the caller's dense fallback path
    serves.  Exceptions raised by ``fn`` itself propagate."""
    if op in _degraded:
        return None
    if op in _injected:
        _injected.discard(op)
        degrade(op, repr(KernelFault(f"injected {op} launch failure")))
        return None
    return fn(*args, **kwargs)
