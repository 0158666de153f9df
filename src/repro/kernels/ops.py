"""Jit'd public wrappers over the Pallas kernels.

Pallas execution mode is backend-aware (``kernels.backend``): interpret
mode (kernel bodies executed as plain JAX for correctness validation) on
the CPU, compiled Mosaic on the TPU.  Every wrapper below passes
``interpret=None`` through to the kernels, which resolve it per call.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.convert import MXArray
from repro.core.spec import QuantSpec, resolve_spec
from repro.kernels import mx_matmul as _mm
from repro.kernels import mx_quant as _mq

_PAPER_DEFAULT = QuantSpec("e4m3", "paper")


def mx_quantize_pallas(x: jax.Array, spec=None, mode: Optional[str] = None,
                       block: Optional[int] = None, *,
                       fmt: Optional[str] = None) -> MXArray:
    """Quantize an ND tensor along its trailing axis with the Pallas
    converter kernel; returns the same MXArray container as the pure-JAX
    path (bit-identical codes/scales).  ``spec`` is a QuantSpec; the
    ``fmt=``/``mode=``/``block=`` kwargs are the deprecation shim."""
    spec = resolve_spec(spec, fmt, mode, block, default=_PAPER_DEFAULT,
                        caller="mx_quantize_pallas")
    shape = x.shape
    n = shape[-1]
    x2 = x.reshape(-1, n)
    codes, scales = _mq.mx_quantize_2d(x2, spec, interpret=None)
    nblk = (n + spec.block - 1) // spec.block
    # re-pad codes to the block multiple to match MXArray's invariant
    pad = nblk * spec.block - n
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
    codes = codes.reshape(shape[:-1] + (nblk * spec.block,))
    scales = scales.reshape(shape[:-1] + (nblk,))
    return MXArray.from_spec(codes, scales, spec, orig_len=n,
                             axis=len(shape) - 1)


def mx_matmul(a: jax.Array, w: MXArray) -> jax.Array:
    """a (..., K) @ w, where w is an MXArray of logical shape (K, N)
    quantized along axis 0 (the contraction axis)."""
    assert w.axis == 0, "weights must be quantized along the contraction dim"
    k, n = w.shape
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    out = _mm.mx_matmul_2d(a2, w.codes, w.scales, w.spec,
                           interpret=None)
    return out.reshape(lead + (n,))


def mx_matmul_resident(a: jax.Array, w, impl: Optional[str] = None
                       ) -> jax.Array:
    """a (..., K) @ dequant(w) for a weight-resident ``MXWeight`` (K, N).

    Dispatches through ``kernels.backend.resolve_matmul_impl``: "fused"
    feeds the (possibly bit-packed) codes straight into the Pallas kernel,
    which unpacks and dequantizes tiles in VMEM; "einsum" materializes the
    f32 weight and contracts with a plain einsum.  Both return f32 and are
    bit-identical when the contraction fits one k-tile (K <= bk).
    """
    from repro.core.mx_weight import MXWeight
    from repro.kernels import backend
    from repro.kernels.backend import resolve_matmul_impl
    assert isinstance(w, MXWeight), type(w)
    assert w.codes.ndim == 2, (
        f"mx_matmul_resident takes a single (K, N) weight, codes shape "
        f"{tuple(w.codes.shape)}; slice batch axes with w.take(i)")
    impl = resolve_matmul_impl(impl)
    lead = a.shape[:-1]

    def einsum_path():
        wd = w.dequantize().astype(a.dtype)
        return jnp.einsum("...k,kn->...n", a, wd,
                          preferred_element_type=jnp.float32)

    if impl == "einsum":
        return einsum_path()

    def fused_path():
        a2 = a.reshape(-1, a.shape[-1])
        if a2.shape[1] != w.kp:      # K was padded to a block multiple
            a2 = jnp.pad(a2, ((0, 0), (0, w.kp - a2.shape[1])))
        if backend.resolve_interpret(None):
            # interpret mode (CPU correctness path): per-grid-step overhead
            # dominates, so cover N in one tile and K in few — 5-10x faster
            # than VMEM-sized tiles at decode shapes, same results
            out = _mm.mx_matmul_2d(a2, w.codes, w.scales, w.spec,
                                   bn=w.n, bk=min(w.kp, 1024))
        else:
            out = _mm.mx_matmul_2d(a2, w.codes, w.scales, w.spec)
        return out.reshape(lead + (w.n,))

    # supervised dispatch: an injected launch fault degrades the op to the
    # einsum path (logged once); a real kernel error propagates
    out = backend.supervised("mx_matmul", fused_path)
    return einsum_path() if out is None else out


def quantize_weight(w: jax.Array, spec=None, mode: Optional[str] = None,
                    block: Optional[int] = None, *,
                    fmt: Optional[str] = None) -> MXArray:
    """Quantize a (K, N) weight along K (contraction) for mx_matmul."""
    from repro.core.convert import mx_quantize
    spec = resolve_spec(spec, fmt, mode, block, default=_PAPER_DEFAULT,
                        caller="quantize_weight")
    return mx_quantize(w, spec, axis=0)


def flash_attention_ctx(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True):
    """Flash attention, sharding-aware.

    With sharding rules installed (launcher/dry-run), wraps the Pallas call
    in shard_map manual over (batch, model): q sharded by heads over
    "model", k/v replicated over "model" (GQA kv-heads rarely divide the TP
    axis); the GQA expansion happens per-shard with global head offsets.
    Returns None if the head count does not divide the model axis (caller
    falls back to dense attention).
    """
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import current_mesh, current_rules
    from repro.kernels.flash_attn import flash_attention

    rules = current_rules()
    if rules is None:
        return flash_attention(q, k, v, causal)
    mesh = current_mesh()
    if mesh is None or not rules.get("model"):
        return flash_attention(q, k, v, causal)
    model_ax = rules["model"][0]
    batch_axes = rules.get("batch")
    h, hkv = q.shape[2], k.shape[2]
    rep = h // hkv
    msize = mesh.shape[model_ax]
    bsz = q.shape[0]
    if h % msize != 0 or (batch_axes and bsz % _prod(
            mesh.shape[a] for a in batch_axes) != 0):
        return None
    qspec = P(batch_axes, None, model_ax, None)
    kvspec = P(batch_axes, None, None, None)

    def body(ql, kl, vl):
        hl = ql.shape[2]
        off = jax.lax.axis_index(model_ax) * hl
        idx = (off + jnp.arange(hl)) // rep
        ke = jnp.take(kl, idx, axis=2)
        ve = jnp.take(vl, idx, axis=2)
        return flash_attention(ql, ke, ve, causal)

    manual = set(a for a in ((batch_axes or ()) + (model_ax,)))
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(qspec, kvspec, kvspec),
                         out_specs=qspec, check_vma=False,
                         axis_names=manual)(q, k, v)


def _prod(it):
    out = 1
    for x in it:
        out *= x
    return out


def mx_decode_attention_ctx(q: jax.Array, cache: dict, pos, cfg):
    """Sharded wrapper for the MX decode-attention kernel: the u8 cache is
    consumed directly (batch-sharded over the data axes); q is sliced to
    the local batch by shard_map.  Returns (B, 1, Hq, D) or None if the
    cache layout is unsupported (caller falls back to dequant + dense)."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import current_mesh, current_rules
    from repro.kernels.mx_decode_attn import mx_decode_attention

    kc, ks = cache["k_codes"], cache["k_scales"]
    vc, vs = cache["v_codes"], cache["v_scales"]
    hq, d = q.shape[2], q.shape[3]
    hkv = kc.shape[2]
    rep = hq // hkv
    kk, kv = cfg.mx.kv_key, cfg.mx.kv_value
    if d % 32 or kc.shape[-1] != d or vc.shape[-1] != d \
            or kk.block != 32 or kv.block != 32:
        return None                      # padded code layout unsupported

    def call(q_, kc_, ks_, vc_, vs_, pos_):
        return mx_decode_attention(q_, kc_, ks_, vc_, vs_, pos_,
                                   key_spec=kk, value_spec=kv, rep=rep)

    rules = current_rules()
    if rules is None:
        return call(q, kc, ks, vc, vs, pos)
    mesh = current_mesh()
    if mesh is None:
        return call(q, kc, ks, vc, vs, pos)
    ba = rules.get("kv_batch") or ("data",)
    ba = tuple(a for a in ba if a in mesh.axis_names)
    if q.shape[0] % _prod(mesh.shape[a] for a in ba):
        return None
    bspec = P(ba, None, None, None)
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(bspec, bspec, bspec, bspec, bspec,
                                   P()),
                         out_specs=bspec, check_vma=False,
                         axis_names=set(ba))(q, kc, ks, vc, vs, pos)


def mx_paged_decode_attention_ctx(q: jax.Array, pool: dict,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, cfg):
    """Sharded wrapper for the paged MX decode-attention kernel.

    Slots (the batch dim of q / block tables / lengths) shard over the
    "kv_batch" axes; the page pool follows the "kv_pages" rule — None
    (default) replicates it inside the shard_map region so any slot can
    reference any physical page without a gather.  Returns (B, 1, Hq, D)
    or None if the layout is unsupported (caller falls back to the
    gather + dense path).

    This wrapper is also the kernel entry of the *scanned* decode step:
    the serving engine's fused multi-step window traces it once inside a
    ``lax.scan`` body whose carry includes the page pool, so everything
    here must be trace-stable — the mesh/rules are resolved from ambient
    context (constant across the window), the scalar-prefetch operands
    (block table, lengths) are scan-carried values, and the shard_map
    region closes over no per-step Python state."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import current_mesh, current_rules
    from repro.kernels.mx_decode_attn import mx_paged_decode_attention

    kc, ks = pool["kc_pages"], pool["ks_pages"]
    vc, vs = pool["vc_pages"], pool["vs_pages"]
    hq, d = q.shape[2], q.shape[3]
    hkv = kc.shape[2]
    rep = hq // hkv
    kk, kv = cfg.mx.kv_key, cfg.mx.kv_value
    if d % 32 or ks.shape[-1] * 32 != d \
            or kk.block != 32 or kv.block != 32 \
            or kc.shape[-1] != kk.storage_nbytes(d) \
            or vc.shape[-1] != kv.storage_nbytes(d):
        return None                      # padded head dim unsupported

    def call(q_, kc_, ks_, vc_, vs_, bt_, ln_):
        return mx_paged_decode_attention(q_, kc_, ks_, vc_, vs_, bt_, ln_,
                                         key_spec=kk, value_spec=kv,
                                         rep=rep)

    rules = current_rules()
    if rules is None:
        return call(q, kc, ks, vc, vs, block_tables, lengths)
    mesh = current_mesh()
    if mesh is None:
        return call(q, kc, ks, vc, vs, block_tables, lengths)
    if rules.get("kv_pages"):
        return None                      # sharded pool: use gather fallback
    ba = rules.get("kv_batch") or ("data",)
    ba = tuple(a for a in ba if a in mesh.axis_names)
    if q.shape[0] % _prod(mesh.shape[a] for a in ba):
        return None
    bspec = P(ba, None, None, None)
    pspec = P()                          # pool replicated per shard
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(bspec, pspec, pspec, pspec, pspec,
                                   P(ba, None), P(ba)),
                         out_specs=bspec, check_vma=False,
                         axis_names=set(ba))(q, kc, ks, vc, vs,
                                             block_tables, lengths)
