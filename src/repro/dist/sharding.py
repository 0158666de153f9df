"""Logical sharding-rule engine: named-axis rules -> GSPMD constraints.

The model zoo is written against *logical* axis names ("batch", "model",
"seq", "wgather", "kv_batch"); a rule table maps each name to zero or more
physical mesh axes.  The launcher builds one table per cell
(``make_rules``), installs it around trace time (``use_rules``), and every
``logical(x, *names)`` call site inside the models resolves to a
``with_sharding_constraint`` — or to an identity no-op when no rules are
installed, so single-device tests and eager exploration never pay a
sharding tax.

Physical axis convention (see repro.launch.mesh):
  pod    — inter-pod data parallelism (gradient reduction only)
  data   — intra-pod DP / FSDP shard axis
  model  — tensor / expert / sequence parallelism

Rule names:
  batch     — activation batch dim            -> ("pod","data") ∩ mesh
  kv_batch  — KV-cache batch dim (decode reads stay local)
  model     — TP-sharded activation dim       -> ("model",)
  seq       — sequence dim (long-context)     -> ("model",) when seq_sharded
  wgather   — FSDP weight-gather axes; None disables use-point gathering
              (decode posture: weights stay resident)

``param_specs`` derives PartitionSpecs for arbitrary parameter pytrees from
the zoo's naming conventions (embed/lm_head, stacked-scan containers, MoE
expert tables, rank-1 norms); the launcher validates divisibility per mesh
(repro.launch.cells._validated) before using them.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

Rules = Dict[str, Any]

DP_AXES = ("pod", "data")       # data-parallel axes, outermost first
TP_AXIS = "model"

# parameter containers whose leaves carry a leading lax.scan layer dim
STACKED_CONTAINERS = frozenset(
    {"layers", "enc_layers", "dec_layers", "blocks"})
# output projections: input dim is the TP-sharded one (Megatron row-parallel)
ROW_PARALLEL = frozenset({"wo", "w2"})

_state = threading.local()


def _stack():
    stack = getattr(_state, "rules", None)
    if stack is None:
        stack = _state.rules = []
    return stack


# =============================================================================
# rule tables
# =============================================================================
def make_rules(axes: Sequence[str], *, fsdp_params: bool = True,
               seq_sharded: bool = False, bf16_matmul_out: bool = False,
               pure_fsdp: bool = False,
               paged_pool_sharded: bool = False,
               quant: Any = None) -> Rules:
    """Build a logical->physical rule table for a mesh with ``axes``.

    ``fsdp_params``    — enable use-point weight gathering (ZeRO-3); decode
                         cells pass False so weights stay resident.
    ``seq_sharded``    — shard the sequence dim of activations/caches over
                         "model" (long-context cells).
    ``bf16_matmul_out``— matmuls emit bf16 (halves TP all-reduce payloads).
    ``pure_fsdp``      — gather the *whole* weight per layer (no dim left
                         TP-sharded); for narrow TP-unfriendly archs.
    ``paged_pool_sharded`` — shard the paged-KV page pool's page dim over
                         the data axes (spreads pool HBM across DP ranks at
                         the cost of a block-table gather per decode step);
                         default False replicates the pool so any slot can
                         reference any physical page locally.
    ``quant``          — a ``repro.core.spec.QuantPolicy`` to install with
                         the rules; distributed consumers resolve their
                         per-tensor-role specs through ``quant_spec_for``
                         (e.g. the compressed-DP gradient exchange reads
                         the "grads" role).
    """
    axes = tuple(axes)
    batch = tuple(a for a in DP_AXES if a in axes)
    model = tuple(a for a in axes if a == TP_AXIS)
    wgather: Optional[Tuple[str, ...]] = None
    if fsdp_params:
        wgather = ("data",) if "data" in axes else (batch or None)
    return {
        "batch": batch,
        "kv_batch": batch,
        "model": model,
        "seq": model if seq_sharded else None,
        "kv_pages": batch if paged_pool_sharded else None,
        "wgather": wgather,
        "wgather_mode": "full" if pure_fsdp else "col",
        "bf16_matmul_out": bool(bf16_matmul_out),
        "quant": quant,
    }


@contextlib.contextmanager
def use_rules(rules: Rules):
    """Install ``rules`` for the duration of the context (trace time)."""
    _stack().append(rules)
    try:
        yield rules
    finally:
        _stack().pop()


def current_rules() -> Optional[Rules]:
    stack = _stack()
    return stack[-1] if stack else None


# =============================================================================
# toggles consumed by models/layers.py and kernels/ops.py
# =============================================================================
def weight_gather_enabled() -> bool:
    r = current_rules()
    return bool(r and r.get("wgather"))


def weight_gather_mode() -> str:
    r = current_rules()
    return (r or {}).get("wgather_mode", "col")


def bf16_matmul_out_enabled() -> bool:
    r = current_rules()
    return bool(r and r.get("bf16_matmul_out"))


def quant_spec_for(role: str):
    """The installed rules' per-tensor-role quantization spec, or None.

    Distributed consumers key their compression off the policy this way
    (e.g. ``grad_compress.mx_allreduce_mean`` defaults its exchange spec
    to the "grads" role) rather than threading fmt/mode strings."""
    r = current_rules()
    pol = (r or {}).get("quant")
    return pol.role(role) if pol is not None else None


# =============================================================================
# use-point constraints
# =============================================================================
def _axes_size(mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def current_mesh():
    """The mesh installed by ``jax.set_mesh`` (an AbstractMesh: axis names
    and sizes), or None when no mesh is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def model_axis_size() -> int:
    """Mesh extent behind the logical "model" name under the installed
    rules (1 when no rules/mesh are active).  Divisibility checks at MX
    weight use points key off this: a dim that this size does not divide
    is silently replicated by ``logical`` rather than sharded."""
    rules = current_rules()
    if rules is None:
        return 1
    mesh = current_mesh()
    if mesh is None:
        return 1
    axes = tuple(a for a in (rules.get("model") or ())
                 if a in mesh.axis_names)
    return _axes_size(mesh, axes) if axes else 1


def logical(x: jax.Array, *names) -> jax.Array:
    """Constrain ``x`` dim-by-dim via the installed rules.

    Each entry of ``names`` is a logical axis name or None (replicated /
    gathered).  Identity no-op when no rules or no mesh are installed; rule
    axes missing from the mesh or not dividing the dim are dropped.
    """
    rules = current_rules()
    if rules is None:
        return x
    mesh = current_mesh()
    if mesh is None:
        return x
    entries = []
    for i in range(x.ndim):
        name = names[i] if i < len(names) else None
        axes = rules.get(name) if isinstance(name, str) else None
        if axes:
            axes = tuple(a for a in axes if a in mesh.axis_names)
        if axes and x.shape[i] % _axes_size(mesh, axes) == 0:
            entries.append(axes if len(axes) > 1 else axes[0])
        else:
            # None is a *hard* replication constraint — this is what forces
            # the per-layer FSDP all-gather at weight use points
            entries.append(None)
    return jax.lax.with_sharding_constraint(x, P(*entries))


# =============================================================================
# parameter PartitionSpecs
# =============================================================================
def _spec_for(keys: Tuple[str, ...], ndim: int) -> P:
    stacked = any(k in STACKED_CONTAINERS for k in keys)
    lead: Tuple[Any, ...] = (None,) if stacked else ()
    nd = ndim - len(lead)
    name = keys[-1] if keys else ""
    if nd <= 1:
        return P(*(lead + (None,) * nd))         # norms/biases: replicated
    if "experts" in keys:
        # MoE expert tables (E, d_in, d_ff[, ...]): expert-parallel over
        # "model", FSDP over "data" on the next dim, rest replicated
        return P(*(lead + (TP_AXIS, "data") + (None,) * (nd - 2)))
    if name == "embed" and nd == 2:
        # vocab-sharded over "model" so the tied-head logits matmul is
        # col-parallel without a transpose-reshard
        return P(*(lead + (TP_AXIS, "data")))
    if name in ROW_PARALLEL:
        body = (None,) * (nd - 2) + (TP_AXIS, "data")
    else:
        body = (None,) * (nd - 2) + ("data", TP_AXIS)   # col (default)
    return P(*(lead + body))


def param_specs(params) -> Any:
    """PartitionSpec pytree mirroring ``params`` (arrays or
    ShapeDtypeStructs).  Divisibility against a concrete mesh is the
    caller's job (see repro.launch.cells._validated)."""
    def one(path, leaf):
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)
        return _spec_for(keys, len(leaf.shape))

    return jax.tree_util.tree_map_with_path(one, params)
