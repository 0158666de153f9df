"""Serving engines: jitted prefill + decode with (optionally MX) KV cache.

Two engines share the model zoo's decode path:

``ServeEngine`` — static batch: requests of equal prompt length are batched,
prefilled once, then stepped greedily (or sampled).

``ContinuousBatchingEngine`` — slot-based continuous batching over a paged
MX KV cache with a **device-resident decode hot loop**: variable-length
prompts are admitted into decode slots mid-flight, each slot's K/V lives in
fixed-size pages of packed codes + E8M0 scales referenced through a
per-slot block table, and finished requests are evicted so their pages
recycle immediately.  Admissions are *bucket-batched*: same-padded-length
prompts prefill as one batch whose caches scatter (and bit-pack) into their
pages in a single donated call.  Decode fuses up to ``sync_every`` steps
into one jitted ``lax.scan`` that samples on device (greedy + temperature,
per-slot PRNG keys) and keeps tokens, lengths, budgets, and the paged pool
on device — the host is consulted only at window boundaries, where it
drains the emitted-token buffer, evicts finished slots, admits waiting
requests, and pre-grants the pages the next window needs
(``Scheduler.plan_window``).

Either way the KV quantization policy comes from the model config's
``QuantPolicy`` roles (cfg.mx.kv_key / cfg.mx.kv_value) — this is the
serving-side consumer of the paper's converter: INT8/E4M3 KV cuts decode
HBM traffic ~2x vs bf16 (see the decode_32k roofline cells), K and V may
carry *different* element formats (e.g. INT8 keys + E2M1 values, each
pool sized per-role), and with ``attn_impl="flash"`` the paged Pallas
kernel keeps HBM reads at the quantized bytes end-to-end.  A per-layer
``PolicyTable`` (``models.config.apply_policy_table``; usually emitted by
``repro.calib``'s budget-constrained search) additionally varies the
specs *by layer* — the page pools become per-layer lists, each sized by
its own layer's formats.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.mx_weight import params_nbytes
from repro.dist.sharding import use_rules
from repro.kernels import backend
from repro.models import health as H
from repro.obs.metrics import MetricsRegistry, rate
from repro.obs.mxhealth import sample_mx_health
from repro.obs.trace import Tracer
from repro.models.decoder import sample_tokens
from repro.models.registry import Model
from repro.serve import faults as F
from repro.serve.faults import FaultPlan
from repro.serve.paging import (TRASH_PAGE, BlockManager, PageGrantError,
                                pages_needed)
from repro.serve.prefix import PrefixCache
from repro.serve.scheduler import Request, RequestState, Scheduler
from repro.serve.swap import (HostSwapStore, SwapData, concat_snapshots,
                              gather_pages, scatter_pages)


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 => greedy
    seed: int = 0


class ServeEngine:
    def __init__(self, model: Model, params, max_len: int,
                 rules: Optional[Dict[str, Any]] = None):
        """``rules`` (from repro.dist.sharding.make_rules, decode posture:
        fsdp_params=False) installs the logical sharding constraints inside
        the jitted prefill/decode; None serves single-device."""
        self.model = model
        self.params = params
        self.max_len = max_len
        self.rules = rules      # introspection only; already traced into
        cfg = model.cfg         # the jit closures below

        def _ctx():
            return use_rules(rules) if rules is not None \
                else contextlib.nullcontext()

        def _prefill(params, batch):
            with _ctx():
                return model.prefill(params, batch, max_len=max_len)

        def _decode(params, token, cache, pos):
            with _ctx():
                return model.decode_step(params, token, cache, pos)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

    @property
    def weight_pool_nbytes(self) -> int:
        """Serve-time weight HBM bytes as stored (MXWeight leaves count
        their uint8 codes + scales; fp params their dtype width)."""
        return params_nbytes(self.params)

    def generate(self, batch: Dict[str, jax.Array],
                 gen: GenerationConfig = GenerationConfig()
                 ) -> np.ndarray:
        """batch: arch input dict with equal-length prompts.
        Returns (B, max_new_tokens) int32."""
        logits, cache, pos = self._prefill(self.params, batch)
        vocab = self.model.cfg.vocab
        key = jax.random.PRNGKey(gen.seed)
        tok = self._pick(logits[:, -1, :vocab], gen, key)
        out = [np.asarray(tok)]
        for i in range(gen.max_new_tokens - 1):
            logits, cache = self._decode(self.params, tok, cache,
                                         jnp.asarray(pos + i,
                                                     dtype=jnp.int32))
            key, sub = jax.random.split(key)
            tok = self._pick(logits[:, -1, :vocab], gen, sub)
            out.append(np.asarray(tok))
        return np.stack(out, axis=1)

    @staticmethod
    def _pick(logits: jax.Array, gen: GenerationConfig, key) -> jax.Array:
        if gen.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / gen.temperature, axis=-1
        ).astype(jnp.int32)


# =============================================================================
# Continuous batching over the paged MX KV cache
# =============================================================================
class ContinuousBatchingEngine:
    """Slot-based continuous batching over a paged (optionally MX) KV cache
    with a fused, device-resident decode loop.

    ``max_slots``      — decode batch width (requests in flight).
    ``page_size``      — tokens per KV page.
    ``max_len``        — per-request cap on prompt + generated tokens; sets
                         the block-table width.
    ``num_pages``      — page-pool size; defaults to full occupancy
                         (max_slots * pages(max_len) + the trash page).
    ``rules``          — sharding rules (repro.dist.sharding.make_rules,
                         decode posture); the page pool follows the
                         "kv_pages" rule.
    ``sync_every``     — decode steps fused per jitted ``lax.scan`` window;
                         the host syncs (drains tokens, evicts, admits)
                         only at window boundaries.  1 reproduces the
                         per-step engine exactly — higher values are
                         token-identical (asserted in tests) but amortize
                         dispatch + host transfers over the window.
    ``prefill_bucket`` — admission prompts are padded to a multiple of
                         this (rounded up to a page multiple; default
                         page_size) and same-bucket admissions prefill as
                         one batch.  Larger buckets mean fewer distinct
                         prefill shapes (fewer retraces) at the cost of
                         padded FLOPs.
    ``preempt``        — enable preempt-and-swap: when the waiting head
                         cannot be admitted and a strictly lower-priority
                         request is running, the victim's KV pages are
                         copied (MX codes still packed) to the host swap
                         store, its slot freed, and the request restored
                         page-for-page on re-admission — continuation is
                         token-identical to an unpreempted run (asserted
                         in tests/test_serve_preempt.py).
    ``prefix_cache``   — enable prefix sharing: finished prefills publish
                         their full KV pages into a trie keyed by page
                         token content (``repro.serve.prefix``); later
                         admissions map the longest cached prefix
                         read-only into their block table and prefill
                         only the uncached suffix, copy-on-write forking
                         any shared page they must write.  Outputs are
                         token-identical to ``prefix_cache=False`` (under
                         MX policies and fp-dense; asserted in tests) —
                         only the prefill compute and fresh-page demand
                         shrink.
    ``health_checks``  — numeric-health guards: every prefill and decode
                         window additionally reduces (in the same jit) a
                         per-slot non-finite-logits flag and an MX-block
                         poison flag (SCALE_NAN/SCALE_INF scale bytes in
                         the slot's live KV pages, a uint8 compare — no
                         dequantization).  A flagged slot is
                         *quarantined* at the window boundary: its
                         window tokens are suppressed, its pages freed,
                         and the request parked in ``scheduler.failed``
                         with a diagnostic — healthy slots stream on
                         token-identically (batch rows are independent).
    ``faults``         — optional ``serve.faults.FaultPlan`` consulted at
                         named sites (page_corrupt / swap_corrupt /
                         prefill_nan / kernel_fail / alloc_fail / stall)
                         for deterministic fault-injection tests and
                         recovery drills.  None (the default) adds no
                         per-step work.
    ``metrics``        — a shared :class:`~repro.obs.metrics
                         .MetricsRegistry`; None creates a private one.
                         Every serving counter (engine, scheduler, block
                         manager, prefix cache, swap store) lives in it,
                         and the legacy ``n_*`` attributes are
                         registry-backed views — equal to the registry
                         snapshot by construction.
    ``tracer``         — optional :class:`~repro.obs.trace.Tracer`:
                         per-request spans (queued / prefill / decode
                         windows / preempt / restore / quarantine /
                         retry) plus engine phase spans, recorded from
                         the stamps the engine already takes — zero
                         extra host syncs, token-identical on/off
                         (asserted in tests/test_obs_identity.py).
                         Its spans are written after the fact and never
                         reach the profiler's clock; the live
                         ``engine.*`` annotations ``step`` opens do.
    ``obs_interval``   — sample the MX-health gauges (``mx.*``: scale
                         poison markers, saturation/clip and underflow
                         rates per KV role) every N sync windows; 0
                         (default) never samples.  Each sample is one
                         scalar device reduction + transfer.
    """

    _PHASES = ("prefill", "decode", "sync", "swap")

    def __init__(self, model: Model, params, *, max_slots: int = 8,
                 page_size: int = 16, max_len: int = 256,
                 num_pages: Optional[int] = None,
                 rules: Optional[Dict[str, Any]] = None,
                 gen: GenerationConfig = GenerationConfig(),
                 sync_every: int = 8,
                 prefill_bucket: Optional[int] = None,
                 prefix_cache: bool = False,
                 preempt: bool = False,
                 health_checks: bool = True,
                 faults: Optional[FaultPlan] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 obs_interval: int = 0):
        if not model.supports_paged():
            raise NotImplementedError(
                f"{model.cfg.name}: continuous batching needs a GQA "
                "decoder (no MLA / modality frontend)")
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        self.model = model
        self.params = params
        self.page_size = page_size
        self.sync_every = int(sync_every)
        pb = page_size if prefill_bucket is None else int(prefill_bucket)
        if pb < 1:
            raise ValueError(f"prefill_bucket must be >= 1, got {pb}")
        self.prefill_bucket = -(-pb // page_size) * page_size
        self.max_pages_per_slot = pages_needed(max_len, page_size)
        if num_pages is None:
            num_pages = 1 + max_slots * self.max_pages_per_slot
        # one registry for the whole serving stack: the block manager,
        # scheduler, prefix cache, and swap store all register their
        # series here, so registry.reset() restarts every measurement
        # window at once and snapshot() is the single exported view
        self.metrics = MetricsRegistry() if metrics is None else metrics
        self.tracer = tracer
        self.obs_interval = int(obs_interval)
        self._mx_health_jit = None       # built lazily on first sample
        self.blocks = BlockManager(num_pages, page_size, max_slots,
                                   self.max_pages_per_slot,
                                   metrics=self.metrics)
        self.prefix = PrefixCache(self.blocks, metrics=self.metrics) \
            if prefix_cache else None
        self.scheduler = Scheduler(max_slots, self.blocks,
                                   prefix=self.prefix,
                                   metrics=self.metrics)
        self.preempt = bool(preempt)
        self.health_checks = bool(health_checks)
        self.faults = faults
        self.swap_store = HostSwapStore(metrics=self.metrics)
        if faults is not None:
            # alloc_fail fires through the BlockManager's grant hook (only
            # non-trivial ensure() grants consult it — admission's reserved
            # allocations stay exact), swap_corrupt through the store's put
            self.blocks.fault_hook = (
                lambda n: faults.should_fire("alloc_fail") is not None)
            self.swap_store.faults = faults
        self.pool = model.init_paged_cache(num_pages, page_size)
        self.gen = gen
        self.rules = rules
        self._key = jax.random.PRNGKey(gen.seed)
        self._next_rid = 0
        self._cur_tok = np.zeros(max_slots, np.int32)
        self._lengths = np.zeros(max_slots, np.int32)
        self._remaining = np.zeros(max_slots, np.int32)
        # per-slot PRNG keys, folded from the engine key at admission and
        # evolved on device by sample_tokens
        self._slot_keys = jnp.zeros((max_slots, 2), jnp.uint32)
        # device-resident block table, re-uploaded only when the host
        # tables actually changed (admission / page grant / eviction)
        self._bt_version = -1
        self._bt_dev = None
        # engine counters (registry series; the legacy n_* attributes
        # below are property views over these, so bench rows, snapshot
        # capture/restore, and the registry snapshot can never diverge)
        m = self.metrics
        self._c_steps = m.counter(
            "engine.steps", "device decode steps (incl. masked tail)")
        self._c_syncs = m.counter(
            "engine.syncs", "host sync points (fused windows run)")
        self._c_live_steps = m.counter(
            "engine.live_slot_steps",
            "slot-steps of decode windows spent on live requests")
        self._c_generated = m.counter(
            "engine.generated_tokens", "tokens emitted to requests")
        self._c_prefill_tokens = m.counter(
            "engine.prefill_tokens", "unpadded prompt positions prefilled")
        self._c_cow = m.counter(
            "engine.cow_forks", "copy-on-write page forks")
        self._c_preempt = m.counter(
            "engine.preemptions", "requests swapped out to host")
        self._c_restores = m.counter(
            "engine.restores", "swapped requests restored to a slot")
        self._c_quar = m.counter(
            "engine.quarantined", "requests parked by the health guard")
        self._g_peak_mapped = m.gauge(
            "pages.peak_mapped", "peak distinct pages in slot tables")
        self._g_peak_shared = m.gauge(
            "pages.peak_shared", "peak pages mapped by >= 2 entries")
        # per-phase wall clock (bench_serve schema v2; "swap" is v4) —
        # one labeled float counter, surfaced as the ``phase`` dict
        self._c_phase = m.counter(
            "engine.phase_s", "wall seconds by engine phase")
        for k in self._PHASES:
            self._c_phase.inc(0.0, phase=k)
        self._h_window = m.histogram(
            "engine.window_steps", "decode steps fused per sync window")
        self.quarantined_in_step: List[Request] = []
        self._step_progress = False     # quarantine/swap counts as progress
        self._stall_abort = threading.Event()
        self.stall_aborted = False      # watchdog cut a stalled step short
        # latency-observability window start: requests finished before
        # this index in scheduler.finished predate the last reset_metrics
        # (warmup) and are excluded from finished_in_window summaries
        self._metrics_start = 0
        cfg = model.cfg
        self.vocab = cfg.vocab
        temperature = float(gen.temperature)
        health_on = self.health_checks

        def _ctx():
            return use_rules(rules) if rules is not None \
                else contextlib.nullcontext()

        def _prefill_scatter(params, tokens, lens, keys, pool, page_ids):
            """Batched bucket prefill fused with the page scatter: prefill
            G same-bucket prompts at once, scatter every request's pages
            (packing sub-byte codes on device) into the donated pool, and
            sample each request's first token from its own last prompt
            position — one host round-trip per bucket instead of three per
            request.  With health checks on, a per-request guard flag
            (non-finite last-position logits, or an MX poison marker in
            the just-scattered pages) rides along in the same transfer."""
            with _ctx():
                logits, cache, _ = model.prefill(
                    params, {"tokens": tokens}, max_len=tokens.shape[1])
                pool = model.scatter_prefill(pool, cache, page_ids)
                g = tokens.shape[0]
                last = logits[jnp.arange(g), lens - 1, :self.vocab]
                keys, first = sample_tokens(last, keys, temperature)
                if not health_on:
                    return first, keys, pool, jnp.zeros(g, bool)
                bad = H.nonfinite_rows(last)
                bad = bad | H.slot_scale_poison(pool, page_ids, lens, cfg)
                return first, keys, pool, bad

        def _suffix_prefill(params, tokens, starts, lens, keys, pool, bt):
            """Paged suffix prefill for G prefix-cache hits: compute only
            prompt positions [starts, lens) (the shared prefix pages are
            already resident), write their KV into the slots' private
            pages, and sample each request's first token from its last
            prompt position — the hit-path twin of _prefill_scatter
            (including the health-guard flag)."""
            with _ctx():
                logits, pool = model.paged_prefill_suffix(
                    params, tokens, starts, lens, pool, bt)
                g = tokens.shape[0]
                last = logits[jnp.arange(g), lens - starts - 1,
                              :self.vocab]
                keys, first = sample_tokens(last, keys, temperature)
                if not health_on:
                    return first, keys, pool, jnp.zeros(g, bool)
                bad = H.nonfinite_rows(last)
                bad = bad | H.slot_scale_poison(pool, bt, lens, cfg)
                return first, keys, pool, bad

        def _copy_pages(pool, src, dst):
            """Batched COW: duplicate shared pages src -> dst before a
            writer touches them."""
            return model.copy_pool_pages(pool, src, dst)

        def _swap_in(pool, page_ids, host):
            """Batched restore: scatter a swap-store snapshot back into
            freshly allocated pages (donated pool — no double buffer)."""
            return scatter_pages(pool, page_ids, host)

        def _multi(params, tok, pool, bt, lengths, remaining, keys,
                   n_steps):
            """Fused decode window.  With health checks on, two extra (B,)
            flags ride the window's one host transfer: ``bad_logits``
            (any live step saw non-finite logits) and ``poison`` (an MX
            scale byte at/above the mode's poison threshold inside the
            slot's live positions — checked on the post-window pool)."""
            with _ctx():
                if not health_on:
                    toks, pool2, ln, rem, keys2 = \
                        model.paged_decode_multi_step(
                            params, tok, pool, bt, lengths, remaining,
                            keys, n_steps=n_steps,
                            temperature=temperature,
                            trash_page=TRASH_PAGE)
                    z = jnp.zeros(tok.shape, bool)
                    return toks, pool2, ln, rem, keys2, z, z
                toks, pool2, ln, rem, keys2, bad_logits = \
                    model.paged_decode_multi_step(
                        params, tok, pool, bt, lengths, remaining, keys,
                        n_steps=n_steps, temperature=temperature,
                        trash_page=TRASH_PAGE, health=True)
                poison = H.slot_scale_poison(pool2, bt, ln, cfg)
                return toks, pool2, ln, rem, keys2, bad_logits, poison

        # donate the pool: every decode window / prefill scatter rewrites
        # it wholesale, and without donation XLA double-buffers the
        # dominant serving allocation (the CPU backend ignores donation
        # with a warning; on TPU this halves peak KV memory)
        self._fns = {"prefill_scatter": _prefill_scatter,
                     "suffix_prefill": _suffix_prefill,
                     "copy_pages": _copy_pages, "swap_in": _swap_in,
                     "multi": _multi}
        self._rejit()

    def _rejit(self) -> None:
        """(Re)wrap the raw closures in fresh jax.jit caches.  Called once
        at construction and again after a kernel degradation or an armed
        ``backend.inject_failure`` — supervised dispatch decides the
        kernel-vs-dense path at *trace* time, so the next call must
        re-trace for the degraded path to take effect."""
        f = self._fns
        self._prefill_scatter = jax.jit(f["prefill_scatter"],
                                        donate_argnums=(4,))
        self._suffix_prefill = jax.jit(f["suffix_prefill"],
                                       donate_argnums=(5,))
        self._copy_pages = jax.jit(f["copy_pages"], donate_argnums=(0,))
        self._swap_in = jax.jit(f["swap_in"], donate_argnums=(0,))
        self._multi = jax.jit(f["multi"], static_argnums=(7,),
                              donate_argnums=(2,))

    # ------------------------------------- registry-backed counter views
    # The legacy attribute names stay the API (bench_serve, snapshot
    # capture/restore, and tests read/write them), but the storage is the
    # shared MetricsRegistry — "engine counters equal the registry
    # snapshot" is true by construction.  Setters exist because snapshot
    # restore legitimately rewinds them.
    @property
    def n_steps(self) -> int:
        return int(self._c_steps.value())

    @n_steps.setter
    def n_steps(self, v: int) -> None:
        self._c_steps.set(int(v))

    @property
    def n_syncs(self) -> int:
        return int(self._c_syncs.value())

    @n_syncs.setter
    def n_syncs(self, v: int) -> None:
        self._c_syncs.set(int(v))

    @property
    def n_generated(self) -> int:
        return int(self._c_generated.value())

    @n_generated.setter
    def n_generated(self, v: int) -> None:
        self._c_generated.set(int(v))

    @property
    def prefill_tokens_computed(self) -> int:
        return int(self._c_prefill_tokens.value())

    @prefill_tokens_computed.setter
    def prefill_tokens_computed(self, v: int) -> None:
        self._c_prefill_tokens.set(int(v))

    @property
    def n_cow_forks(self) -> int:
        return int(self._c_cow.value())

    @n_cow_forks.setter
    def n_cow_forks(self, v: int) -> None:
        self._c_cow.set(int(v))

    @property
    def n_preemptions(self) -> int:
        return int(self._c_preempt.value())

    @n_preemptions.setter
    def n_preemptions(self, v: int) -> None:
        self._c_preempt.set(int(v))

    @property
    def n_restores(self) -> int:
        return int(self._c_restores.value())

    @n_restores.setter
    def n_restores(self, v: int) -> None:
        self._c_restores.set(int(v))

    @property
    def n_quarantined(self) -> int:
        return int(self._c_quar.value())

    @n_quarantined.setter
    def n_quarantined(self, v: int) -> None:
        self._c_quar.set(int(v))

    @property
    def peak_mapped_pages(self) -> int:
        return int(self._g_peak_mapped.value())

    @peak_mapped_pages.setter
    def peak_mapped_pages(self, v: int) -> None:
        self._g_peak_mapped.set(int(v))

    @property
    def peak_shared_pages(self) -> int:
        return int(self._g_peak_shared.value())

    @peak_shared_pages.setter
    def peak_shared_pages(self, v: int) -> None:
        self._g_peak_shared.set(int(v))

    @property
    def phase(self) -> Dict[str, float]:
        """Per-phase wall clock as a plain dict (bench_serve reads it;
        the storage is the labeled ``engine.phase_s`` counter)."""
        return {k: float(self._c_phase.value(phase=k))
                for k in self._PHASES}

    @phase.setter
    def phase(self, d: Dict[str, float]) -> None:
        for k in self._PHASES:
            self._c_phase.set(float(d.get(k, 0.0)), phase=k)

    def _phase_add(self, k: str, dt: float) -> None:
        # negative clock skew must not trip the counter's monotone check
        self._c_phase.inc(max(0.0, dt), phase=k)

    # ------------------------------------------------------------ queries
    @property
    def kv_pool_nbytes(self) -> int:
        """Allocated page-pool bytes (summed over layers; under a per-layer
        ``PolicyTable`` each layer's pool is sized by its own specs)."""
        return int(sum(np.prod(leaf.shape) * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(self.pool)))

    @property
    def weight_pool_nbytes(self) -> int:
        """Serve-time weight HBM bytes as stored: after
        ``Model.quantize_weights`` the MXWeight leaves flatten to uint8
        codes (bit-packed for sub-byte formats) + E8M0 scales, so this
        reports the ``spec.storage_nbytes`` accounting; fp params count
        at their dtype width."""
        return params_nbytes(self.params)

    @property
    def kv_pool_bytes_effective(self) -> int:
        """Bytes of *distinct* pages the serving working set peaked at —
        peak pages mapped by any slot's block table, times the summed
        per-page bytes across layer pools.  Shared prefix pages count
        once however many slots map them (trie-only pins don't count:
        retention is a cache policy, not working-set demand)."""
        return self.peak_mapped_pages \
            * (self.kv_pool_nbytes // self.blocks.num_pages)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admissions that matched a non-empty cached prefix
        (0.0 with prefix caching off or before any admission)."""
        if self.prefix is None or self.prefix.lookups == 0:
            return 0.0
        return self.prefix.hits / self.prefix.lookups

    def _note_page_stats(self) -> None:
        self._g_peak_mapped.set_max(self.blocks.mapped_pages)
        self._g_peak_shared.set_max(self.blocks.shared_pages)

    @property
    def finished_in_window(self) -> List[Request]:
        """Requests finished since the last ``reset_metrics`` — the
        population latency summaries and bench rows must draw from, so a
        warmup request's TTFT/ITL samples can't leak into steady state."""
        return self.scheduler.finished[self._metrics_start:]

    def reset_metrics(self) -> None:
        """Zero the serving counters, peaks, latency window, and swap
        traffic for a steady-state measurement window (e.g. after a
        warmup request has populated the prefix trie).  The trie, page
        pool, swap-store *residents*, and jitted closures stay warm; only
        the accounting restarts.  Requests finished before the reset drop
        out of ``finished_in_window``, so stale hit-rate or TTFT samples
        cannot survive warmup excision."""
        # one call restarts every subsystem's series at once (engine,
        # scheduler, block manager, prefix cache, swap store — they all
        # live in the shared registry), then the swap store re-anchors
        # its resident-bytes peak to what is still held
        self.metrics.reset()
        self._metrics_start = len(self.scheduler.finished)
        self.swap_store.reset_counters()

    # ------------------------------------------------------------ requests
    def add_request(self, prompt, max_new_tokens: int, *,
                    priority: int = 0,
                    deadline_s: Optional[float] = None,
                    arrival_t: Optional[float] = None) -> int:
        """Queue a prompt; returns the request id.  Admission happens on a
        subsequent ``step()`` when a slot and enough pages are free, in
        (priority, deadline, arrival) order — ``priority`` 0 is the most
        urgent class, ``deadline_s`` an optional TTFT target used for EDF
        ordering within the class and SLO-attainment reporting.
        ``arrival_t`` (a ``time.perf_counter`` stamp) defaults to now;
        the async front end passes the submission-time stamp explicitly
        so queueing delay counts against TTFT.  Raises ValueError (from
        ``Scheduler.submit``) when the sequence can never fit a slot or
        the pool."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (prefill always "
                             "emits the first generated token)")
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, priority=priority,
                      deadline_s=deadline_s,
                      arrival_t=(time.perf_counter()
                                 if arrival_t is None else arrival_t))
        self.scheduler.submit(req)              # validates capacity
        self._next_rid += 1
        if self.tracer is not None:
            self.tracer.begin("request", cat="request", rid=req.rid,
                              ts=req.arrival_t,
                              prompt_len=int(req.prompt_len),
                              max_new_tokens=int(max_new_tokens),
                              priority=int(priority))
            self.tracer.begin("queued", cat="request", rid=req.rid,
                              ts=req.arrival_t)
        return req.rid

    # ---------------------------------------------------------- the engine
    def step(self) -> List[Tuple[int, int]]:
        """One host sync cycle: admit what fits (bucket-batched prefill),
        run one fused decode window of up to ``sync_every`` device steps;
        returns the (request id, token) pairs emitted this cycle in step
        order (admissions emit their prefill token here too).

        Recovery semantics: a slot whose health guard trips is
        quarantined (tokens suppressed, pages freed, request parked in
        ``scheduler.failed``); a mid-window page-grant failure swaps the
        starved slot out and retries next step; a kernel launch failure
        degrades that op to its dense path (``kernels.backend``) and the
        closures re-trace.  ``quarantined_in_step`` holds this cycle's
        quarantined requests for the front end's retry budget.

        The cycle's host phases are ``jax.profiler.TraceAnnotation``s on
        the profiler's clock (``engine.step`` around ``engine.admit``,
        ``engine.prefill``, ``engine.decode_window`` and
        ``engine.fanout``); they cost nothing measurable while no
        profiler runs."""
        with TraceAnnotation("engine.step"):
            return self._step()

    def _step(self) -> List[Tuple[int, int]]:
        emitted: List[Tuple[int, int]] = []
        self.quarantined_in_step = []
        self._step_progress = False
        if self.faults is not None:
            self._consult_step_faults()
            if self.stall_aborted:
                return emitted          # watchdog cut the stall short
        with TraceAnnotation("engine.admit"):
            if self.preempt:
                # swap out one victim at a time until the waiting head
                # fits (or no strictly lower-priority runner remains); the
                # freed slots/pages are re-granted by the admit() below
                while True:
                    victim = self.scheduler.pick_victim()
                    if victim is None:
                        break
                    self._swap_out(victim)
            t0 = time.perf_counter()
            admitted = self.scheduler.admit()
            t_adm = time.perf_counter()
        self._phase_add("sync", t_adm - t0)
        if self.tracer is not None:
            for r in admitted:
                # re-admissions (restore / retry) re-opened "queued";
                # a track missing it was reconciled by a snapshot
                # restore and needs no end here
                if self.tracer.top(r.rid) == "queued":
                    self.tracer.end("queued", cat="request",
                                    rid=r.rid, ts=t_adm)
                self.tracer.instant(
                    "admitted", cat="request", rid=r.rid, ts=t_adm,
                    slot=int(r.slot),
                    matched_tokens=int(r.matched_tokens),
                    restored=r.rid in self.swap_store)
        if admitted:
            self._batched_prefill(admitted, emitted)
        t0 = time.perf_counter()
        if not self.scheduler.running:
            self._phase_add("sync", time.perf_counter() - t0)
            return emitted
        try:
            window = self.scheduler.plan_window(self._lengths,
                                                self.sync_every)
        except PageGrantError as e:
            # a page grant failed mid-window (alloc_fail injection or a
            # genuinely starved pool): swap the starved slot out instead
            # of crashing — its pages free up and the request re-enters
            # the queue at its original rank
            self._swap_out(self.scheduler.running[e.slot])
            self._phase_add("sync", time.perf_counter() - t0)
            return emitted
        self._note_page_stats()             # post-grant working set
        snapshot = sorted(self.scheduler.running.items())
        rem0 = {slot: req.remaining for slot, req in snapshot}
        # the block-table upload feeds the window, so it opens the span;
        # the span closes once the window's tokens are on the host, so
        # every device op of the window lies inside it
        with TraceAnnotation("engine.decode_window", steps=int(window),
                             live=len(snapshot)):
            bt = self._device_tables()
            t1 = time.perf_counter()
            toks, self.pool, _, _, self._slot_keys, badl, poison = \
                self._multi(
                    self.params, jnp.asarray(self._cur_tok), self.pool, bt,
                    jnp.asarray(self._lengths),
                    jnp.asarray(self._remaining), self._slot_keys, window)
            toks = np.asarray(toks)     # the one host transfer per window
            if self.health_checks:
                badl = np.asarray(badl)
                poison = np.asarray(poison)
                bad = badl | poison
            else:
                bad = np.zeros(toks.shape[1], bool)
            t2 = time.perf_counter()
        with TraceAnnotation("engine.fanout"):
            self._fanout(window, snapshot, rem0, toks, bad, badl, t1, t2,
                         emitted)
        self._phase_add("decode", t2 - t1)
        self._phase_add("sync", (t1 - t0) + (time.perf_counter() - t2))
        return emitted

    def _fanout(self, window: int, snapshot, rem0, toks, bad, badl,
                t1: float, t2: float, emitted) -> None:
        """After a decode window: count it, hand each live slot its
        tokens, then quarantine flagged slots and release finished
        ones."""
        self._c_steps.inc(window)
        self._c_live_steps.inc(sum(min(window, rem0[slot])
                                   for slot, _ in snapshot))
        self._c_syncs.inc()
        self._h_window.observe(window)
        if self.tracer is not None:
            # both spans reuse the window's two existing stamps — the
            # tracer adds no host sync of its own
            self.tracer.span("decode_window", t0=t1, t1=t2,
                             steps=int(window), live=len(snapshot))
            for slot, req in snapshot:
                self.tracer.span(
                    "decode", cat="request", rid=req.rid, t0=t1, t1=t2,
                    steps=int(min(window, rem0[slot])), slot=int(slot))
        if self.obs_interval \
                and self.n_syncs % self.obs_interval == 0:
            self._sample_mx_health()
        for t in range(window):
            for slot, req in snapshot:
                if bad[slot]:
                    continue            # quarantined below; no tokens out
                if t < rem0[slot]:
                    tok = int(toks[t, slot])
                    req.out.append(tok)
                    # tokens become *visible* at the sync boundary: every
                    # token of a fused window shares its drain stamp
                    req.t_tokens.append(t2)
                    emitted.append((req.rid, tok))
                    self._c_generated.inc()
        for slot, req in snapshot:
            if bad[slot]:
                why = ("non-finite logits in decode window"
                       if badl[slot]
                       else "MX scale poison marker in KV pages")
                self._quarantine(req, f"numeric-health guard: {why}")
                continue
            take = min(window, rem0[slot])
            self._lengths[slot] += take
            self._remaining[slot] -= take
            if take:
                self._cur_tok[slot] = toks[take - 1, slot]
            if req.done:
                self._release(req)

    def _consult_step_faults(self) -> None:
        """Step-scoped fault-injection sites (no-op without a plan):
        ``stall`` sleeps the host loop (cooperatively — ``abort_stall``
        cuts it short, as the front end's watchdog does before a
        snapshot restore); ``kernel_fail`` arms a one-shot paged-attention
        launch failure and forces the re-trace that lets supervised
        dispatch degrade it; ``page_corrupt`` overwrites one live KV
        position's scale bytes with SCALE_NAN markers — exactly what a
        faulty converter or DMA would leave behind."""
        plan = self.faults
        self.stall_aborted = False
        f = plan.should_fire("stall")
        if f is not None:
            if self.tracer is not None:
                self.tracer.instant("fault:stall", stall_s=f.stall_s)
            deadline = time.monotonic() + f.stall_s
            while time.monotonic() < deadline:
                if self._stall_abort.is_set():
                    self._stall_abort.clear()
                    self.stall_aborted = True
                    return
                time.sleep(0.002)
        if plan.should_fire("kernel_fail") is not None:
            if self.tracer is not None:
                self.tracer.instant("fault:kernel_fail", op="paged_attn")
            backend.inject_failure("paged_attn")
            self._rejit()
        f = plan.should_fire("page_corrupt")
        if f is not None and self.scheduler.running:
            cands = sorted(self.scheduler.running.items())
            if f.rid is not None:
                cands = [(s, r) for s, r in cands if r.rid == f.rid]
            if cands:
                rng = plan.rng("page_corrupt")
                slot, _ = cands[int(rng.integers(len(cands)))]
                length = int(self._lengths[slot])
                if length > 0:
                    pos = int(rng.integers(length))
                    pid = self.blocks.slot_page_ids(slot)[
                        pos // self.page_size]
                    self.pool = F.poison_pool_pages(
                        self.pool, [pid], offset=pos % self.page_size)
                    if self.tracer is not None:
                        self.tracer.instant("fault:page_corrupt",
                                            page=int(pid), pos=pos)

    def _quarantine(self, req: Request, diag: str) -> None:
        """Park a guard-flagged request: free its slot + pages, record the
        diagnostic, suppress its window tokens (already skipped by the
        caller).  Healthy slots are untouched — batch rows are
        independent, so their token streams are identical to a run
        without the poisoned neighbor (asserted in tests)."""
        slot = req.slot
        ids = self.blocks.slot_page_ids(slot)
        self.scheduler.fail(req, diag)
        # quarantine hygiene: the request's now-dead pages hold the very
        # poison that tripped the guard — scrub them to the fresh-page
        # all-zeros state before the allocator can recycle them into a
        # healthy slot (pages still shared/pinned stay untouched: another
        # owner's scan will judge them)
        dead = [pg for pg in ids if self.blocks.page_refcount(pg) == 0]
        if dead:
            self.pool = F.scrub_pool_pages(self.pool, dead)
        req.t_finished = time.perf_counter()
        self._c_quar.inc()
        if self.tracer is not None:
            # leave only the per-request root open: the front end either
            # retries (re-opening "queued") or closes the track with a
            # terminal status once the retry budget is spent
            self.tracer.unwind(req.rid, ts=req.t_finished, keep=1)
            self.tracer.instant("quarantine", cat="request",
                                rid=req.rid, ts=req.t_finished,
                                error=diag)
        self.quarantined_in_step.append(req)
        self._step_progress = True
        self._cur_tok[slot] = 0
        self._lengths[slot] = 0
        self._remaining[slot] = 0

    def retry_request(self, req: Request) -> None:
        """Re-queue a quarantined (failed) request for another attempt —
        the engine half of the front end's retry budget.  The request
        keeps its rid, so its per-slot PRNG key re-derives identically
        and a healthy replay is token-identical at any temperature."""
        self.scheduler.requeue(req)
        if self.tracer is not None:
            if not self.tracer.open_spans(req.rid):
                # track was closed by a snapshot-restore reconciliation;
                # re-open the per-request root for the fresh attempt
                self.tracer.begin("request", cat="request", rid=req.rid,
                                  prompt_len=int(req.prompt_len),
                                  max_new_tokens=int(req.max_new_tokens),
                                  priority=int(req.priority))
            self.tracer.instant("retry", cat="request", rid=req.rid,
                                attempt=int(req.n_retries))
            self.tracer.begin("queued", cat="request", rid=req.rid)

    def resubmit(self, req: Request) -> None:
        """Re-enter a request the engine no longer tracks (post-snapshot
        arrivals discarded by a restore): reset its generation state and
        queue it as if newly submitted, keeping its rid."""
        req.state = RequestState.WAITING
        req.slot = -1
        req.out = []
        req.t_tokens = []
        req.t_finished = None
        req.error = None
        req.matched_tokens = 0
        req.cow_pending = 0
        req.swap_pages = 0
        self.scheduler.submit(req)
        if self.tracer is not None:
            if not self.tracer.open_spans(req.rid):
                self.tracer.begin("request", cat="request", rid=req.rid,
                                  prompt_len=int(req.prompt_len),
                                  max_new_tokens=int(req.max_new_tokens),
                                  priority=int(req.priority))
            else:
                self.tracer.unwind(req.rid, keep=1)
            self.tracer.instant("resubmit", cat="request", rid=req.rid)
            self.tracer.begin("queued", cat="request", rid=req.rid)

    def abort_stall(self) -> None:
        """Cut a faulted ``stall`` sleep short (watchdog thread-safe)."""
        self._stall_abort.set()

    def run(self) -> Dict[int, np.ndarray]:
        """Drive ``step()`` until every queued request finishes; returns
        {request id: generated tokens} for the requests finished by this
        call (the engine is reusable: jitted closures stay warm across
        batches).  Quarantined requests are *not* in the result — they
        sit in ``scheduler.failed`` with ``req.error`` set."""
        start = len(self.scheduler.finished)
        while self.scheduler.has_work():
            emitted = self.step()
            if not emitted and not self.scheduler.running \
                    and not self._step_progress:
                raise RuntimeError(
                    "no progress: waiting requests cannot be admitted")
        return {r.rid: np.asarray(r.out, np.int32)
                for r in self.scheduler.finished[start:]}

    # ------------------------------------------------------------ internals
    def _device_tables(self) -> jax.Array:
        """Device-side block table, refreshed only when the host tables
        changed (BlockManager.version) — steady-state decode windows skip
        the upload entirely."""
        if self._bt_version != self.blocks.version:
            self._bt_dev = jnp.asarray(self.blocks.tables)
            self._bt_version = self.blocks.version
        return self._bt_dev

    def _batched_prefill(self, admitted: List[Request],
                         emitted: List[Tuple[int, int]]) -> None:
        """Prefill admissions bucket-by-bucket: same-padded-length prompts
        run as one batch, and the whole bucket's pages land in a single
        donated prefill+scatter+sample call.

        Prefix-cache hits take the suffix path instead: any owed COW fork
        runs first (one batched device page copy for all hits), then each
        bucket of same-padded *suffix* lengths prefills only its uncached
        positions through the paged pool.  Cold admissions keep the exact
        contiguous prefill+scatter path of ``prefix_cache=False``."""
        restored_rids = {r.rid for r in admitted
                         if r.rid in self.swap_store}
        if restored_rids:
            self._restore_swapped(
                [r for r in admitted if r.rid in restored_rids])
            admitted = [r for r in admitted
                        if r.rid not in restored_rids]
        if not admitted:
            return
        t0 = time.perf_counter()
        cold = [r for r in admitted if r.matched_tokens == 0]
        hits = [r for r in admitted if r.matched_tokens > 0]
        if self.tracer is not None:
            # an open pair, not a retroactive span: a first-decode page
            # grant can swap a request out *inside* _finish_prefill, and
            # that swap_out span must nest within the batch span for the
            # engine track's clock to stay monotone
            self.tracer.begin("prefill_batch", ts=t0,
                              cold=len(cold), hits=len(hits))
        groups: Dict[int, List[Request]] = {}
        for req in cold:
            lp = -(-req.prompt_len // self.prefill_bucket) \
                * self.prefill_bucket
            groups.setdefault(lp, []).append(req)
        for lp, reqs in sorted(groups.items()):
            g = len(reqs)
            toks = np.zeros((g, lp), np.int32)
            lens = np.zeros(g, np.int32)
            slots = np.array([r.slot for r in reqs])
            for i, r in enumerate(reqs):
                toks[i, :r.prompt_len] = r.prompt
                lens[i] = r.prompt_len
            # one vmapped fold per bucket (not one dispatch per request):
            # each slot's key derives from its request id alone, so key
            # evolution is independent of admission grouping
            fresh = jax.vmap(lambda r: jax.random.fold_in(self._key, r))(
                jnp.asarray([r.rid for r in reqs], jnp.uint32))
            # rows are trash-padded past each request's allocation, so a
            # bucket-padded prompt's excess pages scatter harmlessly
            npr = lp // self.page_size
            page_ids = self.blocks.tables[slots, :npr]
            tb = time.perf_counter()
            with TraceAnnotation("engine.prefill", group=g, bucket=lp):
                first, keys, self.pool, bad = self._prefill_scatter(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    fresh, self.pool, jnp.asarray(page_ids))
                self._finish_prefill(reqs, slots, keys, first, emitted,
                                     bad, t0=tb)
        if hits:
            self._cow_forks(hits)
            self._hit_prefill(hits, emitted)
        self._note_page_stats()
        t1 = time.perf_counter()
        self._phase_add("prefill", t1 - t0)
        if self.tracer is not None:
            self.tracer.end("prefill_batch", ts=t1)

    def _cow_forks(self, hits: List[Request]) -> None:
        """Fork every shared page a hit's suffix prefill will write (only
        a fully-cached prompt has one: its last page is recomputed at
        position L-1 to seed the first token) and batch-copy the page
        contents on device before any write lands."""
        src, dst = [], []
        for r in hits:
            for idx in self.blocks.cow_targets(r.slot, r.prefill_start,
                                               r.prompt_len):
                pair = self.blocks.fork_page(r.slot, idx)
                assert pair is not None, \
                    "admission reserved the copy-on-write page"
                src.append(pair[0])
                dst.append(pair[1])
            r.cow_pending = 0
        if src:
            self._c_cow.inc(len(src))
            self.pool = self._copy_pages(
                self.pool, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32))

    def _hit_prefill(self, hits: List[Request],
                     emitted: List[Tuple[int, int]]) -> None:
        """Suffix-only prefill for prefix-cache hits, bucketed by padded
        suffix length."""
        groups: Dict[int, List[Request]] = {}
        for req in hits:
            ls = -(-(req.prompt_len - req.prefill_start)
                   // self.prefill_bucket) * self.prefill_bucket
            groups.setdefault(ls, []).append(req)
        bt = self._device_tables()      # post-COW tables
        for ls, reqs in sorted(groups.items()):
            g = len(reqs)
            toks = np.zeros((g, ls), np.int32)
            starts = np.zeros(g, np.int32)
            lens = np.zeros(g, np.int32)
            slots = np.array([r.slot for r in reqs])
            for i, r in enumerate(reqs):
                s0 = r.prefill_start
                toks[i, :r.prompt_len - s0] = r.prompt[s0:]
                starts[i] = s0
                lens[i] = r.prompt_len
            fresh = jax.vmap(lambda r: jax.random.fold_in(self._key, r))(
                jnp.asarray([r.rid for r in reqs], jnp.uint32))
            tb = time.perf_counter()
            with TraceAnnotation("engine.prefill", group=g, bucket=ls):
                first, keys, self.pool, bad = self._suffix_prefill(
                    self.params, jnp.asarray(toks), jnp.asarray(starts),
                    jnp.asarray(lens), fresh, self.pool,
                    bt[jnp.asarray(slots)])
                self._finish_prefill(reqs, slots, keys, first, emitted,
                                     bad, t0=tb)

    # ------------------------------------------------- preempt-and-swap
    def _swap_out(self, req: Request) -> None:
        """Copy ``req``'s KV pages (MX codes still packed) to the host
        swap store and free its slot — the device side of
        ``Scheduler.preempt``.  The saved per-slot PRNG key plus the
        request's own token history make the later restore
        token-identical."""
        t0 = time.perf_counter()
        slot = req.slot
        ids = self.blocks.slot_page_ids(slot)
        host, nbytes = gather_pages(self.pool, ids)
        self.swap_store.put(req.rid, SwapData(
            pages=host, n_pages=len(ids),
            length=int(self._lengths[slot]),
            key=np.asarray(self._slot_keys[slot]), nbytes=nbytes))
        req.swap_pages = len(ids)
        self.scheduler.preempt(req)
        self._c_preempt.inc()
        self._step_progress = True
        self._cur_tok[slot] = 0
        self._lengths[slot] = 0
        self._remaining[slot] = 0
        t1 = time.perf_counter()
        self._phase_add("swap", t1 - t0)
        if self.tracer is not None:
            self.tracer.span("swap_out", t0=t0, t1=t1,
                             pages=len(ids), nbytes=nbytes)
            self.tracer.instant("preempt", cat="request", rid=req.rid,
                                ts=t1, pages=len(ids))
            self.tracer.begin("queued", cat="request", rid=req.rid,
                              ts=t1)

    def _restore_swapped(self, reqs: List[Request]) -> None:
        """Re-admission of preempted requests: scatter their swap-store
        snapshots into the freshly allocated private pages (one batched
        device call for all restores this cycle) and rebuild the slot
        state — current token, cache length, budget, and the PRNG key
        exactly as saved, so the continuation is bit-identical.  No
        prefill runs and no token is emitted (the first token was already
        streamed before the preemption)."""
        t0 = time.perf_counter()
        ids_all: List[int] = []
        datas = []
        for r in reqs:
            data = self.swap_store.pop(r.rid)
            slot_ids = self.blocks.slot_page_ids(r.slot)
            assert len(slot_ids) == data.n_pages, \
                "restore admission allocated the swapped page count"
            ids_all.extend(slot_ids)
            datas.append(data)
        self.pool = self._swap_in(
            self.pool, jnp.asarray(ids_all, jnp.int32),
            concat_snapshots([d.pages for d in datas]))
        for r, data in zip(reqs, datas):
            slot = r.slot
            # out[-1] is the last sampled (not yet decoded) token; the
            # cache holds prompt + out[:-1] = data.length positions
            self._cur_tok[slot] = r.out[-1]
            self._lengths[slot] = data.length
            self._remaining[slot] = r.max_new_tokens - len(r.out)
            self._slot_keys = self._slot_keys.at[slot].set(
                jnp.asarray(data.key))
            r.swap_pages = 0
            self._c_restores.inc()
        self._note_page_stats()
        t1 = time.perf_counter()
        self._phase_add("swap", t1 - t0)
        if self.tracer is not None:
            self.tracer.span("swap_restore", t0=t0, t1=t1,
                             requests=len(reqs), pages=len(ids_all))
            for r, data in zip(reqs, datas):
                self.tracer.span("restore", cat="request", rid=r.rid,
                                 t0=t0, t1=t1, pages=data.n_pages,
                                 slot=int(r.slot))

    def _finish_prefill(self, reqs: List[Request], slots, keys, first,
                        emitted: List[Tuple[int, int]],
                        bad=None, t0: Optional[float] = None) -> None:
        """Common admission epilogue: install per-slot keys, emit each
        request's first token, account computed prefill positions, and
        grant the first decode write's page.  A request whose prefill
        health flag (``bad``) is set — or whose ``prefill_nan`` fault
        fires here — is quarantined instead of emitting; a failed
        first-decode page grant (alloc_fail) swaps the request out to
        resume when pages free up.  ``t0`` is the bucket's pre-dispatch
        stamp — with a tracer on, each request gets a complete
        "prefill" span from it to the bucket's sync point."""
        self._slot_keys = self._slot_keys.at[slots].set(keys)
        first = np.asarray(first)
        bad = None if bad is None else np.asarray(bad).copy()
        now = time.perf_counter()
        if self.tracer is not None:
            for r in reqs:
                self.tracer.span(
                    "prefill", cat="request", rid=r.rid, t0=t0, t1=now,
                    tokens=int(r.prompt_len - r.prefill_start),
                    suffix=bool(r.prefill_start))
        for i, r in enumerate(reqs):
            slot = r.slot
            if self.faults is not None and \
                    self.faults.should_fire("prefill_nan",
                                            rid=r.rid) is not None:
                # poison exactly the pages holding the prompt's KV — the
                # padded tail of the page_ids row may alias the trash
                # page, which every slot reads
                n_live = pages_needed(r.prompt_len, self.page_size)
                ids = self.blocks.slot_page_ids(slot)[:n_live]
                self.pool = F.poison_pool_pages(self.pool, ids)
                if bad is not None:
                    bad[i] = True
            if bad is not None and bad[i]:
                self._c_prefill_tokens.inc(
                    r.prompt_len - r.prefill_start)
                self._quarantine(
                    r, "numeric-health guard: non-finite logits or MX "
                       "poison marker at prefill")
                continue
            tok = int(first[i])
            self._cur_tok[slot] = tok
            self._lengths[slot] = r.prompt_len
            self._remaining[slot] = r.max_new_tokens - 1
            self._c_prefill_tokens.inc(r.prompt_len - r.prefill_start)
            if self.prefix is not None:
                # publish the prompt's full pages (an existing trie chain
                # dedupes; new nodes pin this slot's private pages)
                n_full = r.prompt_len // self.page_size
                self.prefix.insert(
                    r.prompt, self.blocks.slot_page_ids(slot)[:n_full])
            r.out.append(tok)
            r.t_tokens.append(now)      # first-token (TTFT) stamp
            self._c_generated.inc()
            emitted.append((r.rid, tok))
            if r.done:
                self._release(r)
            elif not self.blocks.ensure(slot, r.prompt_len + 1):
                # the decode write position may sit in a page past the
                # prompt's allocation; a failed grant (alloc_fail) parks
                # the request in the swap store to resume later
                self._swap_out(r)

    def _release(self, req: Request) -> None:
        slot = req.slot
        if self.prefix is not None:
            # publish the finished sequence's full pages before the decref:
            # positions [0, L + gen - 1) hold KV for prompt + out[:-1]
            # (the last sampled token is never fed back), and those pages
            # are stable now — a later prompt extending this conversation
            # prefix-matches them
            seq = np.concatenate(
                [req.prompt, np.asarray(req.out[:-1], np.int32)])
            n_full = len(seq) // self.page_size
            self.prefix.insert(
                seq, self.blocks.slot_page_ids(slot)[:n_full])
        self.scheduler.evict(req)
        req.t_finished = time.perf_counter()
        if self.tracer is not None:
            self.tracer.unwind(req.rid, ts=req.t_finished, keep=1)
            self.tracer.close_track(req.rid, ts=req.t_finished,
                                    status="finished",
                                    tokens=len(req.out))
        self._cur_tok[slot] = 0
        self._lengths[slot] = 0
        self._remaining[slot] = 0

    # --------------------------------------------------- MX-health gauges
    def _sample_mx_health(self) -> None:
        """One device reduction over the live KV pages -> ``mx.*`` gauges
        per KV role: scale bytes scanned, poison-marker count, and the
        shared-scale saturation (== block clip under a shared scale) and
        underflow rates.  Jitted once; the tables/lengths upload rides
        the existing device copies."""
        if self._mx_health_jit is None:
            cfg = self.model.cfg
            self._mx_health_jit = jax.jit(
                lambda pool, bt, lens: sample_mx_health(
                    pool, bt, lens, cfg))
        stats = self._mx_health_jit(self.pool, self._device_tables(),
                                    jnp.asarray(self._lengths))
        stats = jax.tree_util.tree_map(int, stats)
        m = self.metrics
        for role, st in stats.items():
            nb = st["scale_bytes"]
            m.gauge("mx.scale_bytes",
                    "E8M0 scale bytes in live KV pages"
                    ).set(nb, role=role)
            m.gauge("mx.poison_markers",
                    "scale bytes at/above the mode's poison threshold"
                    ).set(st["poison"], role=role)
            m.gauge("mx.saturation_rate",
                    "fraction of blocks at the max legal shared scale"
                    ).set(rate(st["saturated"], nb), role=role)
            m.gauge("mx.clip_rate",
                    "fraction of blocks clipping elements (== the "
                    "saturation rate: a shared scale at top-of-range "
                    "is exactly the block-clip indicator)"
                    ).set(rate(st["saturated"], nb), role=role)
            m.gauge("mx.underflow_rate",
                    "fraction of blocks with a zero shared scale"
                    ).set(rate(st["underflow"], nb), role=role)

    def finalize_trace(self) -> None:
        """Close every request track still open (queued, swapped-out, or
        failed-without-retry requests at shutdown) so the exported trace
        validates: failed requests close with status "failed", the rest
        "aborted".  Idempotent; the launcher calls it before writing the
        trace files."""
        if self.tracer is None:
            return
        failed = {r.rid for r in self.scheduler.failed}
        for rid in self.tracer.open_tracks():
            if rid is None:
                continue
            self.tracer.close_track(
                rid, status="failed" if rid in failed else "aborted")
        self.tracer.close_track(None)
