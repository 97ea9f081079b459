"""Batched serving engine: slot-based continuous batching with CHUNKED
prefill fused into the decode tick (Sarathi-style), over packed-tile
weights.

Design (vLLM/Sarathi-style, adapted to fixed-shape XLA):

* ``n_slots`` concurrent sequences share the decode caches. A request
  occupies a slot from admission to completion and moves through two
  phases: PREFILL (its prompt is streamed into the caches
  ``chunk_tokens`` columns at a time by a fixed-shape ``model.extend``
  call at per-slot offsets) then DECODE (one token per tick through the
  fixed-shape ``(n_slots, 1)`` decode step). Admission is O(1)
  bookkeeping — no model call — so a long prompt never stalls the tick
  loop the way the old admission-time monolithic prefill did.
* Each engine tick = scheduler + at most two jitted calls:
    1. a token-budget pass hands out ``chunk_tokens`` per tick,
       decode-priority: every decoding slot is charged 1 token first,
       the remainder goes to prefilling slots in admission order (the
       head-of-queue prefill always gets >= 1 so it cannot starve).
    2. ``_extend`` advances the scheduled prefill chunks (m = chunk rows
       per slot -> the matmul kernel path),
    3. ``_decode`` advances the decoding slots (m = n_slots rows -> the
       matvec kernel path); its writes are confined to decoding slots by
       a per-slot cache merge, so concurrent prefill state is untouched.
  Both calls have static shapes — nothing recompiles as requests come
  and go, and only token ids cross back to host.
* Sampling runs inside the jitted calls against per-slot ``(n_slots,)``
  temperature/top-k arrays AND per-slot PRNG keys: token t of request r
  is sampled with ``fold_in(fold_in(PRNGKey(seed), r.rid), t)``, so a
  request's tokens are a pure function of (weights, prompt, params,
  seed, rid) — independent of chunk size, batch neighbors, and
  scheduling order. That invariant is what the chunked-vs-monolithic
  parity tests pin down.
* Prompts are NOT padded into the context: slot positions start at 0 and
  only true prompt tokens enter the caches (padding columns of a chunk
  are dropped before the cache write). The old per-bucket left-padded
  prefill — and its per-admission full-cache splice — is gone; the only
  compiled prefill shape is the ``(n_slots, chunk_tokens)`` extend.
* Attention K/V lives in a PAGED POOL (serve/kvpool.py): fixed
  ``page_tokens`` pages, a free-list allocator with per-page refcounts,
  and one int32 page table ``(n_slots, max_len // page_tokens)`` that
  every full-attention layer reads — the jitted tick gathers a per-slot
  contiguous view and scatters new rows through the table, so shapes
  stay static and the compiled functions are unchanged as pages move.
  With ``prefix_cache`` a radix trie over prompt token ids
  (serve/prefix.py) pins completed page runs plus recurrent-state
  snapshots at page boundaries; admission maps the longest cached
  prefix into the slot in O(1) and chunked prefill starts at the first
  uncached token. Retirement publishes the finished prompt's pages back
  into the trie; LRU leaf eviction reclaims pages when the pool runs
  dry. Tokens are byte-identical with the cache on or off (the prefix
  parity wall in tests/test_prefix_cache.py).
* SCHEDULING UNDER PRESSURE (DESIGN.md §6.4): requests carry a priority
  CLASS (``interactive`` > ``batch``). With ``ServeConfig.priorities``
  the admission queue is no longer FIFO: candidates order by
  ``(class rank, uncached prefill tokens, submission order)`` — the
  middle term consults the radix trie WITHOUT pinning it
  (``PrefixTrie.probe``), so a request whose prompt is largely cached
  jumps the queue proportionally to the prefill it skips. A starvation
  floor bounds the jumping: after ``starvation_limit`` consecutive
  admissions that overtook the oldest waiter, the oldest waiter is
  force-admitted. With ``ServeConfig.preempt`` a waiting request of a
  strictly higher class may PREEMPT a lower-class slot when no slot is
  free: the victim's recurrent state is snapshotted (the same
  ``_snapshot_slot`` machinery the prefix trie uses — valid at ANY
  position, not just page boundaries), its pool pages stay retained off
  to the side, and the parked request later resumes into any free slot
  byte-exactly (tokens are scheduling-invariant by the PRNG design
  above, so preempt-on == preempt-off is byte-identical — the parity
  wall in tests/test_preempt.py). A request preempted
  ``max_preempts`` times becomes immune, so the batch class keeps a
  progress floor under a sustained interactive flood.
* Weights are SERVE-form (packed tiles + alphas, repro.serve.weights);
  passing ``mesh=`` places them with the serving sharding rules and
  traces extend/decode under those rules (DESIGN.md §5).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import hashlib
import itertools
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (
    axis_rules,
    cache_shardings,
    param_shardings,
)
from repro.serve.kvpool import KVPool
from repro.serve.prefix import EncoderCache, PrefixTrie
from repro.serve.sampling import SamplingParams, sample_logits_batch
from repro.serve.servable import ensure_servable
from repro.serve.telemetry import PARKED, EngineTelemetry, RequestSpan

PREFILL = "prefill"
DECODE = "decode"
# Encoder-decoder models only: the phase between admission and PREFILL in
# which the request's source frames run through the encoder (one fixed-
# shape batch=1 call, budget-charged against the tick like a prefill
# chunk) and the projected cross-attention K/V lands in its pool pages.
ENCODE = "encode"

# Priority classes, best first: rank 0 outranks rank 1. The class names
# are the wire-level vocabulary (`"priority"` field of POST /generate);
# submit() rejects anything else so typos fail fast instead of silently
# scheduling at an unintended class.
PRIORITY_RANKS: Dict[str, int] = {"interactive": 0, "batch": 1}

# Trace probe: each jitted tick function bumps its counter when its
# PYTHON body runs — i.e. exactly when jax traces (or retraces) it.
# Executing a cached executable (or an AOT-compiled one) never runs the
# body, so a stable counter across a tick is a machine-checkable "this
# tick compiled nothing" — the property ``BatchedEngine.warmup`` exists
# to establish for the first real request (tests/test_warmup.py).
TRACE_COUNTS: "collections.Counter[str]" = collections.Counter()


class AdmissionQueueFull(RuntimeError):
    """Typed backpressure signal: ``submit`` on an engine whose bounded
    admission queue (``ServeConfig.max_queued``) is at capacity. The
    serving front-end maps this to HTTP 429 instead of letting requests
    pile up unboundedly behind the tick loop."""

    def __init__(self, queued: int, capacity: int):
        super().__init__(
            f"admission queue full: {queued} queued >= max_queued "
            f"{capacity} — retry later or raise max_queued"
        )
        self.queued = queued
        self.capacity = capacity


class _AdmissionQueue:
    """Thread-safe waiting set the scheduler picks from by POLICY, not
    position: ``submit`` appends from any thread, the tick thread takes a
    snapshot, chooses a candidate (FIFO, or the priority/prefix-aware
    key), and removes it. Aborted-while-queued requests are pruned lazily
    on every size/snapshot access so ``qsize`` reflects real pressure.
    Keeps the ``queue.Queue``-shaped ``empty``/``qsize`` surface the
    front-end and tests already poll."""

    def __init__(self):
        self._lock = threading.Lock()
        self._items: List["Request"] = []

    def put(self, req: "Request") -> None:
        with self._lock:
            self._items.append(req)

    def _prune_locked(self) -> None:
        self._items = [r for r in self._items if not r.done]

    def qsize(self) -> int:
        with self._lock:
            self._prune_locked()
            return len(self._items)

    def empty(self) -> bool:
        return self.qsize() == 0

    def snapshot(self) -> List["Request"]:
        with self._lock:
            self._prune_locked()
            return list(self._items)

    def remove(self, req: "Request") -> bool:
        with self._lock:
            try:
                self._items.remove(req)
                return True
            except ValueError:
                return False

    def drain(self) -> List["Request"]:
        with self._lock:
            items, self._items = self._items, []
            return [r for r in items if not r.done]


@dataclasses.dataclass
class PreemptedState:
    """Everything needed to resume a preempted request byte-exactly into
    ANY slot: the retained pool-page run (full-attention K/V never moves
    — resuming just rewrites a page table row), the recurrent-family
    snapshot (SSM/RG-LRU carries + windowed rings, captured at the exact
    preemption position), and the host-side slot registers (phase,
    prefill offset, cache length, last sampled token, PRNG fold
    position, pending trie-boundary snapshots)."""

    req: "Request"
    phase: str                          # PREFILL | DECODE at preemption
    offset: int                         # prompt tokens consumed
    length: int                         # cache length (tokens written)
    pages: List[int]                    # retained pool pages, in order
    snapshot: object                    # recurrent pytree (None: stateless)
    snaps: Dict[int, object]            # captured trie-boundary snapshots
    need_snaps: set                     # boundaries still to capture
    count: int                          # emitted tokens == PRNG fold pos
    last_token: int                     # decode input token at preemption
    xpages: List[int] = dataclasses.field(default_factory=list)
    # retained CROSS-pool pages (encoder-decoder models; read-only after
    # encode, so parking retains them exactly like self-attention pages —
    # no re-snapshot needed, resume rewrites the cross table row)
    enc_len: int = 0                    # valid encoder rows behind xpages


def _tick_fns(model, shardings=None):
    """The jitted serving entry points for ``model``, built once per
    ``shardings`` and cached ON the model object: every engine over the
    same model (replica pools, re-created engines, the test matrix's
    chunk-size sweeps) reuses one trace cache instead of recompiling per
    engine. The functions close over nothing but the model; batch width,
    chunk width, page-table width and input shardings are ordinary
    retrace keys.

    ``shardings`` (mesh engines) is ``(slot, caches)``: the sharding of
    the per-slot state vectors and the per-leaf tree of cache shardings
    the engine places its state with. Every tick output is pinned to
    them: a tick's outputs are the next tick's inputs, so a layout left to
    the compiler would differ from the one the AOT executables were
    compiled for."""
    slot_sh = cache_sh = key = None
    if shardings is not None:
        slot_sh, cache_sh = shardings
        leaves, treedef = jax.tree_util.tree_flatten(cache_sh)
        key = (slot_sh, tuple(leaves), treedef)
    cached = getattr(model, "_serve_tick_fns", {})
    if key in cached:
        return cached[key]
    cross = getattr(model, "has_cross_attn", False)

    def _row_keys(base_keys, counts):
        return jax.vmap(jax.random.fold_in)(base_keys, counts)

    def _extra_kw(extra):
        """Cross models thread (cross page table, encoder lengths) as
        trailing varargs so the decoder-only tick signatures — and their
        traces — stay exactly what the existing parity walls pin."""
        if not extra:
            return {}
        xptab, enc_lens = extra
        return {"cross_page_table": xptab, "enc_lens": enc_lens}

    def _decode_tick(params, tokens, caches, lengths, active,
                     temps, topks, base_keys, counts, ptab, *extra):
        """decode step + per-slot sampling fused under one jit, confined
        to the ``active`` decoding slots: the (n_slots, vocab) logits
        never leave the device and prefilling/free slots keep their
        caches, lengths, and last token bit-identical. Paged pool writes
        are confined in-kernel by ``active``; per-slot families by the
        merge."""
        TRACE_COUNTS["decode_tick"] += 1
        logits, new_caches, new_lengths = model.decode_step(
            params, tokens, caches, lengths,
            page_table=ptab, active=active, **_extra_kw(extra),
        )
        nxt = sample_logits_batch(
            logits, _row_keys(base_keys, counts),
            temperature=temps, top_k=topks,
        )
        caches = model.merge_caches(caches, new_caches, active, paged=True)
        lengths = jnp.where(active, new_lengths, lengths)
        nxt = jnp.where(active, nxt, tokens[:, 0])
        return nxt, caches, lengths

    def _extend_tick(params, block, caches, lengths, n_new,
                     temps, topks, base_keys, counts, ptab, *extra):
        """one chunked-prefill step for every scheduled slot + sampling of
        each slot's candidate first token (the host keeps it only for
        slots whose prompt just completed)."""
        TRACE_COUNTS["extend_tick"] += 1
        logits, caches, lengths = model.extend(
            params, block, caches, lengths, n_new, page_table=ptab,
            **_extra_kw(extra),
        )
        toks = sample_logits_batch(
            logits, _row_keys(base_keys, counts),
            temperature=temps, top_k=topks,
        )
        return toks, caches, lengths

    def _reset_slot(caches, slot):
        """Zero one slot's rows across the per-slot cache families
        (recurrent/SSM state MUST start from zeros); paged pool leaves
        pass through — their pages are shared or about to be remapped."""
        TRACE_COUNTS["reset_slot"] += 1
        return model.reset_slot_caches(caches, slot, paged=True)

    def _snapshot_slot(caches, slot):
        """One slot's recurrent-family state (prefix-trie snapshot)."""
        TRACE_COUNTS["snapshot_slot"] += 1
        return model.snapshot_slot_caches(caches, slot)

    def _restore_slot(caches, slot, snaps):
        """Prefix-hit admission: write a pinned snapshot into a slot."""
        TRACE_COUNTS["restore_slot"] += 1
        return model.restore_slot_caches(caches, slot, snaps)

    tick_sh = None if shardings is None else (slot_sh, cache_sh, slot_sh)
    fns = (jax.jit(_decode_tick, out_shardings=tick_sh),
           jax.jit(_extend_tick, out_shardings=tick_sh),
           jax.jit(_reset_slot, out_shardings=cache_sh),
           jax.jit(_snapshot_slot, out_shardings=slot_sh),
           jax.jit(_restore_slot, out_shardings=cache_sh))
    if cross:
        def _encode_tick(params, frames, valid, caches, xptab):
            """ENCODE phase: one padded batch=1 encoder pass + the cross
            K/V projection scattered through the admitted slot's cross
            page-table row. The ONLY writer of cross pages — decode and
            extend treat the family as read-only ever after."""
            TRACE_COUNTS["encode_tick"] += 1
            memory = model.encode_serve(params, frames, valid)
            positions = jnp.broadcast_to(
                jnp.arange(frames.shape[1]), valid.shape)
            return model.write_cross(
                params, memory, caches, xptab, positions, valid)

        fns = fns + (jax.jit(_encode_tick, out_shardings=cache_sh),)
    model._serve_tick_fns = {**cached, key: fns}
    return fns


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (len,) int32
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None  # "eos" | "length" | "aborted"
    admit_step: Optional[int] = None     # engine tick of admission
    token_steps: List[int] = dataclasses.field(default_factory=list)
    # engine tick at which each output token was emitted: token_steps[0]
    # is the TTFT tick; successive gaps are per-token inter-token ticks
    prefix_hit_tokens: int = 0           # prompt tokens served from the
    # prefix cache at admission (page-aligned; 0 on a cold miss)
    priority: str = "batch"              # resolved class (submit() fills it
    # from params.priority / ServeConfig.default_priority and validates)
    submit_step: int = 0                 # engine tick at submission: the
    # per-class TTFT stats measure from here, queue wait included
    preempt_count: int = 0               # times preempted so far; at
    # ServeConfig.max_preempts the request becomes preemption-immune
    frames: Optional[np.ndarray] = None  # (enc_len, d_model) source frame
    # embeddings — required for encoder-decoder models, rejected otherwise
    enc_digest: Optional[bytes] = None   # blake2b of the frame bytes: the
    # EncoderCache key (two requests over the same source share pages)
    enc_reused: bool = False             # admission skipped ENCODE via a
    # warm EncoderCache hit (the encdec analogue of prefix_hit_tokens)
    span: Optional[RequestSpan] = None   # wall-clock lifecycle span
    # (telemetry on only; observation-only — never read by the scheduler)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    n_slots: int = 4
    max_len: int = 256                  # cache capacity per slot
    chunk_tokens: int = 32              # extend width == per-tick token budget
    temperature: float = 0.0
    top_k: Optional[int] = None
    seed: int = 0
    page_tokens: int = 16               # attention KV pool page size
    pool_pages: Optional[int] = None    # pool capacity; default = the
    # dense-equivalent n_slots * (max_len // page_tokens)
    prefix_cache: bool = False          # radix-trie shared-prefix reuse
    prefix_nodes: int = 512             # trie node cap (snapshots hold
    # real device memory for the recurrent families)
    max_queued: Optional[int] = None    # admission-queue capacity; a full
    # queue makes submit() raise AdmissionQueueFull (typed backpressure —
    # the HTTP front-end's 429) instead of queueing unboundedly. None
    # keeps the historical unbounded queue for batch drivers.
    priorities: bool = False            # class-aware + prefix-aware
    # admission ordering (off = strict FIFO, the historical behavior)
    preempt: bool = False               # preempt-and-resume of strictly
    # lower-priority slots when a higher-class request waits with no free
    # slot; requires priorities (a FIFO admission would hand the freed
    # slot to the wrong request and thrash)
    default_priority: str = "batch"     # class for requests that don't say
    starvation_limit: int = 8           # priority mode's aging floor: max
    # consecutive admissions that may overtake the oldest waiter before
    # it is force-admitted
    max_preempts: int = 3               # per-request preemption cap; at
    # the cap a request becomes immune (the batch-class progress floor)
    enc_tokens: Optional[int] = None    # encoder-decoder models: padded
    # encoder width (the ENCODE tick's one compiled shape) and the cap on
    # a request's frame count. None resolves to max_len in the engine.
    cross_pages: Optional[int] = None   # cross-attention pool capacity;
    # default = (n_slots + 1) runs so one EncoderCache entry can stay
    # warm beside a full house of live slots
    enc_cache_entries: int = 128        # EncoderCache entry cap (LRU)
    compute_path: str = "float"         # dense serve compute: "float"
    # (byte-parity reference) | "int8" | "xnor" — the integer paths
    # quantize decode-tick activations and accumulate on the packed tile
    # words (kernels/tiled_xnor.py). The MODEL must be built with the
    # matching ModelContext.compute_path (launch/serve.py --compute-path
    # sets both); the engine records it here for validation and /stats.
    telemetry: bool = True              # serving telemetry (DESIGN.md §6.6):
    # metric registry + request spans + tick phase timing + the retrace
    # detector. Observation-only — tokens are byte-identical on or off
    # (the parity wall in tests/test_telemetry.py); off removes even the
    # per-tick perf_counter reads for overhead-sensitive benchmarking.
    trace_events: int = 0               # capacity of the structured
    # trace-event ring (submit/admit/preempt/resume/finish/retrace);
    # 0 disables the ring. Drained by the CLI's --trace-log sink.

    def __post_init__(self):
        """Fail fast on an impossible engine shape.

        n_slots/max_len: a zero-slot engine wedges the scheduler silently
        (every submit queues forever) and a zero-length cache can hold no
        token. chunk_tokens is both the extend call's compiled column
        count and the per-tick token budget; non-positive wedges the
        scheduler, past max_len could scatter past the cache.
        page_tokens must divide max_len so the paged gather view is
        EXACTLY the dense (max_len,) layout — that equality is what makes
        paged-vs-dense tokens byte-identical. pool_pages below one slot's
        worth could never complete a full-length sequence."""
        if self.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1: {self.n_slots}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1: {self.max_len}")
        if self.chunk_tokens <= 0:
            raise ValueError(
                f"chunk_tokens must be positive: {self.chunk_tokens}"
            )
        if self.chunk_tokens > self.max_len:
            raise ValueError(
                f"chunk_tokens {self.chunk_tokens} exceeds max_len "
                f"{self.max_len}: a chunk could not fit the decode cache"
            )
        if self.page_tokens <= 0:
            raise ValueError(
                f"page_tokens must be positive: {self.page_tokens}"
            )
        if self.max_len % self.page_tokens:
            raise ValueError(
                f"page_tokens {self.page_tokens} must divide max_len "
                f"{self.max_len}: the per-slot page-table view must be "
                f"exactly the dense cache layout"
            )
        if self.pool_pages is not None:
            if self.pool_pages < self.max_len // self.page_tokens:
                raise ValueError(
                    f"pool_pages {self.pool_pages} is below one slot's "
                    f"worth ({self.max_len // self.page_tokens} pages): "
                    f"no sequence could reach max_len"
                )
        if self.prefix_nodes < 1:
            raise ValueError(
                f"prefix_nodes must be >= 1: {self.prefix_nodes}"
            )
        if self.max_queued is not None and self.max_queued < 1:
            raise ValueError(
                f"max_queued must be >= 1 (or None for unbounded): "
                f"{self.max_queued}"
            )
        if self.preempt and not self.priorities:
            raise ValueError(
                "preempt=True requires priorities=True: preemption frees "
                "a slot FOR a higher-class waiter, but FIFO admission "
                "would hand it to the oldest request instead and thrash"
            )
        if self.default_priority not in PRIORITY_RANKS:
            raise ValueError(
                f"default_priority {self.default_priority!r} is not a "
                f"priority class: {sorted(PRIORITY_RANKS)}"
            )
        if self.starvation_limit < 1:
            raise ValueError(
                f"starvation_limit must be >= 1: {self.starvation_limit} "
                f"(0 would force-admit the oldest waiter every time — "
                f"that is just FIFO; use priorities=False)"
            )
        if self.max_preempts < 0:
            raise ValueError(
                f"max_preempts must be >= 0: {self.max_preempts}"
            )
        if self.enc_tokens is not None and self.enc_tokens < 1:
            raise ValueError(
                f"enc_tokens must be >= 1 (or None for max_len): "
                f"{self.enc_tokens}"
            )
        if self.cross_pages is not None and self.cross_pages < 1:
            raise ValueError(
                f"cross_pages must be >= 1 (or None for the default): "
                f"{self.cross_pages}"
            )
        if self.enc_cache_entries < 1:
            raise ValueError(
                f"enc_cache_entries must be >= 1: {self.enc_cache_entries}"
            )
        if self.trace_events < 0:
            raise ValueError(
                f"trace_events must be >= 0 (0 disables the ring): "
                f"{self.trace_events}"
            )
        if self.trace_events and not self.telemetry:
            raise ValueError(
                "trace_events requires telemetry=True: the trace ring is "
                "emitted from the telemetry call sites"
            )
        from repro.kernels.tiled_xnor import COMPUTE_PATHS

        if self.compute_path not in COMPUTE_PATHS:
            raise ValueError(
                f"unknown compute_path {self.compute_path!r}: expected "
                f"one of {COMPUTE_PATHS}"
            )


class BatchedEngine:
    def __init__(self, model, params, cfg: ServeConfig, *, mesh=None):
        # The model <-> engine contract is the ServableModel protocol
        # (serve/servable.py, DESIGN.md §6.5); fail at construction with
        # the family menu, not mid-tick with an AttributeError.
        ensure_servable(model)
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None:
            # Place the serve weights with the serving rules: packed tile
            # rows ("tile_rows") shard over the model axis, ragged or
            # non-dividing dims drop to replicated (distributed/sharding).
            from repro.nn import module as mod

            logical = mod.logical_axes(model.specs())
            abstract = jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params
            )
            shardings = param_shardings(
                mesh, logical, abstract_tree=abstract
            )
            params = jax.device_put(params, shardings)
        self.params = params
        self._queue = _AdmissionQueue()
        self._live: Dict[int, Request] = {}      # slot -> request
        # preempted requests waiting to resume: pages retained, recurrent
        # state snapshotted, no slot. Competes with the admission queue
        # under the same candidate key (a decode-phase parked request has
        # zero remaining prefill, so it naturally resumes first in class).
        self._parked: List[PreemptedState] = []
        self._overtakes = 0       # consecutive non-oldest admissions
        self._preempted_since_tick = False
        self._class_ttft: Dict[str, List[int]] = {}  # class -> [sum, n]
        self._free = list(range(cfg.n_slots))
        self._rid = itertools.count()
        self._root_key = jax.random.PRNGKey(cfg.seed)

        # per-slot phase machine (host side)
        self._phase = [None] * cfg.n_slots       # None | PREFILL | DECODE
        self._offsets = np.zeros((cfg.n_slots,), np.int64)  # prompt consumed
        self._admit_order: List[int] = []        # prefill scheduling FIFO

        # paged attention KV pool + per-slot page tables (host-managed;
        # the table rides into the jitted calls as a runtime int32 array)
        self.pt = cfg.page_tokens
        self.npp = cfg.max_len // self.pt        # pages per slot
        self._paged = model.has_full_attn
        n_pages = cfg.pool_pages or cfg.n_slots * self.npp
        self.pool = KVPool(n_pages, self.pt) if self._paged else None
        self._ptab = np.zeros((cfg.n_slots, self.npp), np.int32)
        self._n_mapped = np.zeros((cfg.n_slots,), np.int64)  # pages held

        # Cross-attention cache family (encoder-decoder models): a SECOND
        # pool with its own page-table rows. Pages are written once by the
        # ENCODE tick and read-only ever after, masked by per-slot encoder
        # lengths — so sharing them across requests over the same source
        # is pure refcounting, exactly like trie-pinned prefix pages.
        self._cross = getattr(model, "has_cross_attn", False)
        if self._cross:
            self.enc_tokens = cfg.enc_tokens or cfg.max_len
            self.x_npp = -(-self.enc_tokens // self.pt)  # x-pages per slot
            x_pages = cfg.cross_pages or (cfg.n_slots + 1) * self.x_npp
            if x_pages < self.x_npp:
                raise ValueError(
                    f"cross_pages {x_pages} is below one request's worth "
                    f"({self.x_npp} pages for enc_tokens={self.enc_tokens})"
                )
            self.xpool = KVPool(x_pages, self.pt, family="cross_attn")
            self._xptab = np.zeros((cfg.n_slots, self.x_npp), np.int32)
            self._xn_mapped = np.zeros((cfg.n_slots,), np.int64)
            self._enc_lens = np.zeros((cfg.n_slots,), np.int32)
        else:
            self.enc_tokens = None
            self.x_npp = 0
            self.xpool = None

        # shared-prefix radix trie + per-slot boundary snapshots. Cross
        # models DISABLE the token-keyed trie regardless of prefix_cache:
        # decoder self-attention K/V depends on the cross-attended encoder
        # memory, so a prompt prefix computed against one source would be
        # silently WRONG for another. What prefix_cache buys them instead
        # is the digest-keyed EncoderCache — reuse of the encoder output
        # itself, which IS prompt-independent.
        self.trie = (
            PrefixTrie(self.pt, pool=self.pool, max_nodes=cfg.prefix_nodes)
            if cfg.prefix_cache and not self._cross else None
        )
        self.enc_cache = (
            EncoderCache(self.xpool, max_entries=cfg.enc_cache_entries)
            if cfg.prefix_cache and self._cross else None
        )
        self._stateful = model.has_recurrent_state
        self._snaps: List[Dict[int, object]] = [
            {} for _ in range(cfg.n_slots)
        ]
        # page boundaries of the slot's prompt that must be snapshotted
        # (their trie node is missing or snapshotless); computed once at
        # admission so prefill neither pauses at nor captures boundaries
        # the trie already covers
        self._need_snaps: List[set] = [set() for _ in range(cfg.n_slots)]
        self._stats = {
            "admitted": 0, "prefix_hits": 0, "prefix_tokens": 0,
            "prompt_tokens": 0, "tokens_out": 0, "aborted": 0,
            "rejected": 0, "peak_queue_depth": 0,
            "preempt_free_ticks": 0, "work_ticks": 0,
            "preempts": 0, "resumes": 0, "preempted_tokens": 0,
            "encode_ticks": 0, "enc_cache_hits": 0,
        }

        # Streaming hooks: the front-end registers these to learn about
        # tokens the instant the tick emits them (on_token runs in
        # whatever thread drives step(); it must be cheap and non-blocking
        # — the server's implementation just enqueues onto the detokenize
        # backlog). on_finish fires exactly once per request, including
        # aborts.
        self.on_token: Optional[Callable[[Request, int], None]] = None
        self.on_finish: Optional[Callable[[Request], None]] = None

        cache_dtype = getattr(model.ctx, "compute_dtype", jnp.bfloat16)
        self._cache_dtype = cache_dtype
        cache_kw = {}
        if self._cross:
            cache_kw["cross_pages"] = self.xpool.n_pages
        init_caches = functools.partial(
            model.init_caches, cfg.n_slots, cfg.max_len, cache_dtype,
            page_tokens=self.pt if self._paged else None,
            n_pages=n_pages if self._paged else None,
            **cache_kw,
        )
        self.lengths = jnp.zeros((cfg.n_slots,), jnp.int32)
        self.tokens = jnp.zeros((cfg.n_slots, 1), jnp.int32)
        # Per-slot sampling params, populated at admission from the
        # request's resolved SamplingParams (None sentinels -> ServeConfig
        # defaults). temps/topks/keys ride into the jitted calls as runtime
        # arrays; eos ids stay host-side for retirement bookkeeping.
        self.temps = jnp.zeros((cfg.n_slots,), jnp.float32)
        self.topks = jnp.zeros((cfg.n_slots,), jnp.int32)
        self._eos_ids = np.full((cfg.n_slots,), -1, np.int64)
        # per-slot request key + emitted-token count: token t of a request
        # samples with fold_in(request_key, t), independent of scheduling
        self._slot_keys = jnp.zeros((cfg.n_slots, 2), jnp.uint32)
        self._counts = np.zeros((cfg.n_slots,), np.int64)
        shardings = None
        if mesh is None:
            self.caches = init_caches()
        else:
            # Under a mesh the caches come up in place — KV heads over the
            # model axis, 1/TP of the pool per chip (cache_shardings) —
            # and the per-slot vectors, a few bytes a slot, replicated on
            # every chip; left on the default device they would all land
            # on the first chip. _tick_fns keeps every tick output so.
            cache_sh = cache_shardings(mesh, jax.eval_shape(init_caches))
            self.caches = jax.jit(init_caches, out_shardings=cache_sh)()
            slot_sh = NamedSharding(mesh, P())
            (self.lengths, self.tokens, self.temps, self.topks,
             self._slot_keys) = jax.device_put(
                (self.lengths, self.tokens, self.temps, self.topks,
                 self._slot_keys), slot_sh)
            shardings = (slot_sh, cache_sh)

        fns = _tick_fns(model, shardings)
        (self._decode, self._extend, self._reset,
         self._snapshot, self._restore) = fns[:5]
        self._encode = fns[5] if len(fns) > 5 else None
        # AOT-compiled executables keyed by tick-fn name, filled by
        # warmup(): call sites prefer these over the lazily-traced jit
        # wrappers so a warmed engine's first real tick runs zero traces.
        self._aot: Dict[str, object] = {}
        self.steps = 0

        # Serving telemetry (DESIGN.md §6.6). Strictly observation-only:
        # every call site below is a counter bump, a span transition, or
        # a perf_counter read — nothing feeds back into scheduling or
        # sampling, so tokens are byte-identical with tel on or off.
        self.tel: Optional[EngineTelemetry] = (
            EngineTelemetry(trace_events=cfg.trace_events).bind_engine(self)
            if cfg.telemetry else None
        )
        self._tick_phases: Dict[str, float] = {}
        # Retrace detector: armed by warmup(). Compares the global
        # TRACE_COUNTS sum across ONE tick (this thread runs the whole
        # tick, so any delta is attributable to this engine's tick fns),
        # not against a warmup-time snapshot — another engine warming up
        # on this process must not trip a false positive here.
        self._retrace_armed = False
        self._retrace_warned = False

    def _mesh_ctx(self):
        """Sharding-rule context for traces/executions; no-op without mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return axis_rules(self.mesh)

    def _cross_extra(self):
        """Trailing tick-fn args for the cross family: (cross page table,
        per-slot encoder lengths). Empty for decoder-only models, so
        their tick calls — and compiled signatures — are unchanged."""
        if not self._cross:
            return ()
        return (jnp.asarray(self._xptab), jnp.asarray(self._enc_lens))

    # ------------------------------------------------------------------
    def warmup(self) -> Dict[str, float]:
        """Ahead-of-time compile every tick executable for THIS engine's
        shapes (``jax.jit(...).lower(...).compile()`` per entry point), so
        the first real request never pays a trace+compile inside its TTFT.

        The engine has exactly two hot compiled shapes — the
        ``(n_slots, 1)`` decode tick and the ``(n_slots, chunk_tokens)``
        extend tick — plus the per-slot reset that admission runs, and
        (prefix cache on a stateful model) the snapshot/restore pair.
        Warmup lowers each against the live engine state arrays, which
        are byte-for-byte the avals the real ticks will pass, and stores
        the compiled executables in ``self._aot``; the tick call sites
        prefer those over the lazily-traced jit wrappers, so a warmed
        engine's first tick runs ZERO new traces (the ``TRACE_COUNTS``
        probe in tests/test_warmup.py pins this).

        Returns per-entry-point compile seconds. Raises ``RuntimeError``
        naming the entry point and its scheduler-side shapes when a
        lower/compile fails — a warmup that silently half-succeeds would
        just move the first trace stall back into serving."""
        cfg = self.cfg
        active = jnp.asarray(np.zeros((cfg.n_slots,), bool))
        counts = jnp.asarray(self._counts)
        ptab = jnp.asarray(self._ptab)
        block = jnp.asarray(np.zeros((cfg.n_slots, cfg.chunk_tokens),
                                     np.int32))
        n_new = jnp.asarray(np.zeros((cfg.n_slots,), np.int32))
        extra = self._cross_extra()
        plans = [
            ("decode_tick", self._decode,
             (self.params, self.tokens, self.caches, self.lengths, active,
              self.temps, self.topks, self._slot_keys, counts, ptab,
              *extra),
             f"tokens int32[{cfg.n_slots},1], ptab int32[{cfg.n_slots},"
             f"{self.npp}]"),
            ("extend_tick", self._extend,
             (self.params, block, self.caches, self.lengths, n_new,
              self.temps, self.topks, self._slot_keys, counts, ptab,
              *extra),
             f"block int32[{cfg.n_slots},{cfg.chunk_tokens}], ptab "
             f"int32[{cfg.n_slots},{self.npp}]"),
            ("reset_slot", self._reset, (self.caches, 0),
             f"slot int32[], {cfg.n_slots}-slot caches"),
        ]
        if self._cross:
            d = self.model.cfg.d_model
            plans.append((
                "encode_tick", self._encode,
                (self.params,
                 jnp.zeros((1, self.enc_tokens, d), self._cache_dtype),
                 jnp.zeros((1, self.enc_tokens), bool), self.caches,
                 jnp.zeros((1, self.x_npp), jnp.int32)),
                f"frames [{1},{self.enc_tokens},{d}], xptab "
                f"int32[1,{self.x_npp}]"))
        # snapshot/restore executables serve BOTH the prefix trie's
        # boundary snapshots and the preempting scheduler's parking; warm
        # them whenever a stateful model could need either.
        if self._stateful and (self.trie is not None or cfg.preempt):
            plans.append(("snapshot_slot", self._snapshot, (self.caches, 0),
                          f"slot int32[], {cfg.n_slots}-slot caches"))
        timings: Dict[str, float] = {}
        with self._mesh_ctx():
            for name, fn, args, desc in plans:
                t0 = time.perf_counter()
                try:
                    self._aot[name] = fn.lower(*args).compile()
                except Exception as e:
                    raise RuntimeError(
                        f"AOT warmup failed for '{name}' ({desc}): {e}"
                    ) from e
                timings[name] = time.perf_counter() - t0
            if "snapshot_slot" in self._aot:
                # restore's input signature includes the snapshot pytree;
                # one warm snapshot execution (on the zeroed caches, result
                # discarded) yields exactly the avals admission will pass
                snaps = self._aot["snapshot_slot"](self.caches, 0)
                t0 = time.perf_counter()
                try:
                    self._aot["restore_slot"] = self._restore.lower(
                        self.caches, 0, snaps).compile()
                except Exception as e:
                    raise RuntimeError(
                        f"AOT warmup failed for 'restore_slot' (slot "
                        f"int32[], {len(jax.tree_util.tree_leaves(snaps))}"
                        f"-leaf snapshot): {e}"
                    ) from e
                timings["restore_slot"] = time.perf_counter() - t0
        # arm the steady-state retrace detector: after AOT warmup every
        # tick must execute compiled code only, so any TRACE_COUNTS bump
        # inside a subsequent step() is a compile stall worth flagging
        self._retrace_armed = True
        return timings

    @property
    def aot_warm(self) -> bool:
        return bool(self._aot)

    # ------------------------------------------------------------------
    def submit(
        self, prompt, params: Optional[SamplingParams] = None,
        frames=None,
    ) -> Request:
        prompt = np.asarray(prompt, np.int32)
        # Validate HERE, not at admission: a bad prompt then fails fast
        # without consuming a slot or wedging the tick loop mid-admission.
        if prompt.ndim != 1 or len(prompt) == 0:
            raise ValueError("prompt must be a non-empty 1-D token sequence")
        if len(prompt) > self.cfg.max_len:
            raise ValueError(
                f"prompt len {len(prompt)} exceeds max_len {self.cfg.max_len}"
            )
        enc_digest = None
        if self._cross:
            if frames is None:
                raise ValueError(
                    "encoder-decoder serving: submit() needs frames "
                    "(enc_len, d_model) source embeddings alongside the "
                    "decoder prompt")
            frames = np.ascontiguousarray(np.asarray(frames, np.float32))
            if (frames.ndim != 2
                    or frames.shape[1] != self.model.cfg.d_model):
                raise ValueError(
                    f"frames must be (enc_len, d_model="
                    f"{self.model.cfg.d_model}): got {frames.shape}")
            if not 0 < frames.shape[0] <= self.enc_tokens:
                raise ValueError(
                    f"frame count {frames.shape[0]} outside "
                    f"(0, enc_tokens={self.enc_tokens}]")
            # digest over shape + bytes: the EncoderCache key — two
            # requests over the same source share cross pages verbatim
            enc_digest = hashlib.blake2b(
                np.int64(frames.shape[0]).tobytes() + frames.tobytes(),
                digest_size=16,
            ).digest()
        elif frames is not None:
            raise ValueError(
                f"{type(self.model).__name__} has no encoder: frames are "
                f"only accepted for encoder-decoder models")
        params = params or SamplingParams()
        cls = (params.priority if params.priority is not None
               else self.cfg.default_priority)
        if cls not in PRIORITY_RANKS:
            raise ValueError(
                f"unknown priority class {cls!r}: expected one of "
                f"{sorted(PRIORITY_RANKS)}"
            )
        if (self.cfg.max_queued is not None
                and self._queue.qsize() >= self.cfg.max_queued):
            self._stats["rejected"] += 1
            if self.tel is not None:
                self.tel.rejected.inc()
            raise AdmissionQueueFull(self._queue.qsize(),
                                     self.cfg.max_queued)
        req = Request(
            rid=next(self._rid),
            prompt=prompt,
            params=params,
            priority=cls,
            submit_step=self.steps,
            frames=frames,
            enc_digest=enc_digest,
        )
        if self.tel is not None:
            req.span = RequestSpan(req.rid, time.monotonic())
            self.tel.submitted.inc()
            if self.tel.ring is not None:
                self.tel.ring.emit(
                    "submit", rid=req.rid, prompt_tokens=len(prompt),
                    priority=cls)
        self._queue.put(req)
        return req

    @property
    def has_work(self) -> bool:
        """True while anything is queued, live, or parked — the tick
        loop's "keep stepping" predicate. Parked requests count: they
        hold pages and an unfinished stream even when no slot is live."""
        return (bool(self._live) or bool(self._parked)
                or not self._queue.empty())

    def _maybe_retire(self, slot: int, req: Request, tok: int) -> bool:
        """Retire a just-extended request. EOS is checked before the length
        cap so a stop token arriving exactly at max_tokens reports "eos";
        the cache-capacity cap retires a sequence whose NEXT decode step
        would write K/V past max_len — every emitted token attended a
        complete cache, instead of silently dropping the newest rows and
        generating from a truncated context. Retirement publishes the
        finished prompt's complete pages (and boundary snapshots) into
        the prefix trie, then drops the slot's page references — shared
        pages survive through the trie's pin."""
        if tok == int(self._eos_ids[slot]):
            req.finish_reason = "eos"
        elif len(req.output) >= req.params.max_tokens:
            req.finish_reason = "length"
        elif len(req.prompt) + len(req.output) > self.cfg.max_len:
            req.finish_reason = "length"
        else:
            return False
        req.done = True
        if self.trie is not None:
            n_pub = len(req.prompt) // self.pt
            if n_pub:
                pages = (
                    [int(self._ptab[slot, i]) for i in range(n_pub)]
                    if self.pool is not None else None
                )
                self.trie.insert(
                    req.prompt[: n_pub * self.pt], pages,
                    self._snaps[slot], now=self.steps,
                )
        self._release_slot(slot)
        self._finish_telemetry(req)
        if self.on_finish is not None:
            self.on_finish(req)
        return True

    def _finish_telemetry(self, req: Request):
        """Close a request's span and record its end-of-life metrics —
        the shared telemetry tail of retirement and abort (idempotent:
        an abort racing a natural finish observes once)."""
        tel, span = self.tel, req.span
        if tel is None or span is None or span.finish_t is not None:
            return
        span.finish(time.monotonic(), req.finish_reason)
        tel.finished.labels(reason=req.finish_reason).inc()
        tel.e2e.observe(span.wall)
        if tel.ring is not None:
            tel.ring.emit(
                "finish", rid=req.rid, reason=req.finish_reason,
                tokens=len(req.output), wall_s=round(span.wall, 6),
                phases={k: round(v, 6) for k, v in span.phases.items()})

    def _release_slot(self, slot: int):
        """Return a slot (and every page it maps) to the free pools: the
        shared tail of retirement and abort. Shared pages survive through
        the trie's pin — only this slot's references drop."""
        if self.pool is not None:
            for i in range(int(self._n_mapped[slot])):
                self.pool.release(int(self._ptab[slot, i]))
            self._n_mapped[slot] = 0
        if self.xpool is not None:
            for i in range(int(self._xn_mapped[slot])):
                self.xpool.release(int(self._xptab[slot, i]))
            self._xn_mapped[slot] = 0
            self._enc_lens[slot] = 0
        self._snaps[slot] = {}
        self._need_snaps[slot] = set()
        self._live.pop(slot, None)
        self._free.append(slot)
        self._phase[slot] = None
        if slot in self._admit_order:
            self._admit_order.remove(slot)
        # Reset the slot's sampling params: a stale temperature/top-k on a
        # dead slot would keep tripping jnp.any(...) in the batch sampler
        # and defeat its all-greedy / no-top-k fast paths for every later
        # tick until the slot is reused.
        self.temps = self.temps.at[slot].set(0.0)
        self.topks = self.topks.at[slot].set(0)
        self._eos_ids[slot] = -1
        self._counts[slot] = 0

    def abort(self, req: Request) -> bool:
        """Cancel a request, queued, live, or PARKED: its slot and pages
        free immediately, nothing is published to the prefix trie (an
        aborted prompt may have prefilled only partially — publishing a
        half-written page run would poison later prefix hits), and
        ``on_finish`` fires with ``finish_reason == "aborted"``. Aborting
        a parked request releases its retained page run and drops its
        snapshot — no slot is involved.

        NOT thread-safe against a concurrent ``step()`` — the caller
        (the server's shutdown path) must stop the tick loop first.
        Returns False if the request already finished."""
        if req.done:
            return False
        req.done = True
        req.finish_reason = "aborted"
        self._stats["aborted"] += 1
        for slot, r in list(self._live.items()):
            if r is req:
                self._release_slot(slot)
                break
        else:
            for parked in self._parked:
                if parked.req is req:
                    self._release_parked(parked)
                    break
        # a queued (never-admitted) request drops out of the waiting set
        # on the next prune (the done flag set above is the tombstone)
        self._finish_telemetry(req)
        if self.on_finish is not None:
            self.on_finish(req)
        return True

    def abort_all(self) -> int:
        """Abort every queued, parked, and live request (server
        shutdown); returns how many actually transitioned."""
        n = 0
        for r in list(self._live.values()):
            n += bool(self.abort(r))
        for parked in list(self._parked):
            n += bool(self.abort(parked.req))
        for r in self._queue.drain():
            n += bool(self.abort(r))
        return n

    def _release_parked(self, parked: PreemptedState):
        """Drop a parked request's held resources: page references back to
        the pool, snapshot to the GC. The inverse of the retain-in-place
        that ``preempt_slot`` performed."""
        if self.pool is not None:
            for pid in parked.pages:
                self.pool.release(pid)
        if self.xpool is not None:
            for pid in parked.xpages:
                self.xpool.release(pid)
        parked.pages = []
        parked.xpages = []
        parked.snapshot = None
        parked.snaps = {}
        self._parked.remove(parked)

    def _admit(self, slot: int, req: Request):
        """O(1) admission: claim the slot, zero its per-slot state, and —
        with the prefix cache on — map the longest trie-pinned prefix in:
        the matched page run lands in the slot's page table (refcounted,
        no K/V copy) and the deepest boundary snapshot restores the
        recurrent families, so chunked prefill starts at the first
        UNCACHED token."""
        self._live[slot] = req
        self._phase[slot] = PREFILL
        self._admit_order.append(slot)
        req.admit_step = self.steps
        boundary, path = 0, []
        if self.trie is not None:
            path = self.trie.match(
                req.prompt, require_snapshot=self._stateful, now=self.steps
            )
            boundary = len(path) * self.pt
        self._stats["admitted"] += 1
        self._stats["prompt_tokens"] += len(req.prompt)
        if boundary:
            self._stats["prefix_hits"] += 1
            self._stats["prefix_tokens"] += boundary
        req.prefix_hit_tokens = boundary
        if self.pool is not None:
            for i, node in enumerate(path):
                self.pool.retain(node.page)
                self._ptab[slot, i] = node.page
            self._n_mapped[slot] = len(path)
        self._snaps[slot] = {}
        self._need_snaps[slot] = (
            self._boundaries_needing_snapshots(req.prompt)
            if self.trie is not None and self._stateful else set()
        )
        self._offsets[slot] = boundary
        self.lengths = self.lengths.at[slot].set(boundary)
        self.caches = self._aot.get("reset_slot", self._reset)(
            self.caches, slot)
        if boundary and self._stateful:
            self.caches = self._aot.get("restore_slot", self._restore)(
                self.caches, slot, path[-1].snapshot
            )
        # Resolve the request's sampling params against the engine defaults
        # (is-None sentinels: an explicit temperature=0.0 / top_k=0 wins
        # over a stochastic ServeConfig default) and pin them to the slot —
        # every token of this request reads them from the per-slot arrays.
        res = req.params.resolve(self.cfg.temperature, self.cfg.top_k)
        self.temps = self.temps.at[slot].set(res.temperature)
        self.topks = self.topks.at[slot].set(res.top_k)
        self._eos_ids[slot] = res.eos_id
        # An explicit per-request seed roots the key stream at
        # PRNGKey(seed) — rid-independent, so a stochastic request replays
        # identically no matter what admission order a concurrent
        # front-end produced. Without one, the historical rid-derived
        # stream keeps batch drivers reproducible per (engine seed, rid).
        self._slot_keys = self._slot_keys.at[slot].set(
            jax.random.PRNGKey(res.seed) if res.seed is not None
            else jax.random.fold_in(self._root_key, req.rid)
        )
        self._counts[slot] = 0
        if self._cross:
            # encoder-decoder: the request must ENCODE before its prompt
            # can prefill — unless the EncoderCache already holds this
            # source, in which case the whole cross page run maps in O(1)
            # and the phase machine skips straight to PREFILL
            self._enc_lens[slot] = len(req.frames)
            self._xn_mapped[slot] = 0
            self._phase[slot] = ENCODE
            self._try_enc_cache(slot, req)
        if self.tel is not None and req.span is not None:
            now = time.monotonic()
            # phase strings are shared between the engine's phase machine
            # and the span vocabulary, so the slot's resolved phase (an
            # enc-cache hit lands straight in PREFILL) names the interval
            req.span.mark_admit(now, self._phase[slot])
            self.tel.queue_wait.observe(now - req.span.submit_t)
            if self.tel.ring is not None:
                self.tel.ring.emit(
                    "admit", rid=req.rid, slot=slot,
                    prefix_hit_tokens=boundary,
                    phase=self._phase[slot])

    def _try_enc_cache(self, slot: int, req: Request) -> bool:
        """Warm-source admission: map a cached encoder output's page run
        into the slot's cross table and skip the ENCODE phase."""
        if self.enc_cache is None or req.enc_digest is None:
            return False
        entry = self.enc_cache.get(req.enc_digest, now=self.steps)
        if entry is None:
            return False
        for i, pid in enumerate(entry.pages):
            self._xptab[slot, i] = pid
        self._xn_mapped[slot] = len(entry.pages)
        self._enc_lens[slot] = entry.enc_len
        self._phase[slot] = PREFILL
        req.enc_reused = True
        self._stats["enc_cache_hits"] += 1
        # a LATE warm hit (resolved by step(), not at admission) ends the
        # span's encode interval; at admission the span has not marked
        # admit yet and _admit names the resolved phase itself
        if req.span is not None and req.span.admit_t is not None:
            req.span.to_phase(PREFILL, time.monotonic())
        return True

    # ---- scheduling under pressure -----------------------------------
    def preempt_slot(self, slot: int) -> bool:
        """Park the request occupying ``slot`` and free the slot, keeping
        every byte of its progress: pool pages stay retained (the K/V
        never moves — resuming rewrites a page-table row), recurrent
        state is snapshotted at the CURRENT position (snapshot/restore is
        position-exact; the page-boundary rule exists only for trie
        sharing semantics), and the host-side registers (offset, length,
        last token, PRNG fold count, pending boundary snapshots) ride in
        the :class:`PreemptedState`. The scheduler calls this when a
        strictly higher-class request waits with no free slot; tests call
        it directly to force preemption at arbitrary ticks. Like
        ``abort``, not safe against a concurrent ``step()``.
        Returns False if the slot is not live."""
        req = self._live.get(slot)
        if req is None:
            return False
        with self._mesh_ctx():
            snap = None
            if self._stateful:
                snap = self._aot.get("snapshot_slot", self._snapshot)(
                    self.caches, slot)
            pages = (
                [int(self._ptab[slot, i])
                 for i in range(int(self._n_mapped[slot]))]
                if self.pool is not None else []
            )
            xpages = (
                [int(self._xptab[slot, i])
                 for i in range(int(self._xn_mapped[slot]))]
                if self.xpool is not None else []
            )
            parked = PreemptedState(
                req=req,
                phase=self._phase[slot],
                offset=int(self._offsets[slot]),
                length=int(self.lengths[slot]),
                pages=pages,
                snapshot=snap,
                snaps=self._snaps[slot],
                need_snaps=self._need_snaps[slot],
                count=int(self._counts[slot]),
                last_token=int(self.tokens[slot, 0]),
                xpages=xpages,
                enc_len=(int(self._enc_lens[slot])
                         if self.xpool is not None else 0),
            )
            self._parked.append(parked)
            req.preempt_count += 1
            # free the slot WITHOUT releasing its pages (they now belong
            # to the parked record) and without firing on_finish
            self._n_mapped[slot] = 0
            if self.xpool is not None:
                self._xn_mapped[slot] = 0
                self._enc_lens[slot] = 0
            self._snaps[slot] = {}
            self._need_snaps[slot] = set()
            self._live.pop(slot)
            self._free.append(slot)
            self._phase[slot] = None
            if slot in self._admit_order:
                self._admit_order.remove(slot)
            self.temps = self.temps.at[slot].set(0.0)
            self.topks = self.topks.at[slot].set(0)
            self._eos_ids[slot] = -1
            self._counts[slot] = 0
            self._stats["preempts"] += 1
            self._stats["preempted_tokens"] += parked.length
            self._preempted_since_tick = True
            if self.tel is not None:
                self.tel.preempts.inc()
                if req.span is not None:
                    req.span.to_phase(PARKED, time.monotonic())
                if self.tel.ring is not None:
                    self.tel.ring.emit(
                        "preempt", rid=req.rid, slot=slot,
                        phase=parked.phase, tokens_kept=parked.length)
        return True

    def _resume(self, slot: int, parked: PreemptedState):
        """Restore a parked request into ``slot`` byte-exactly: page run
        back into the slot's table row, recurrent snapshot over the
        freshly zeroed per-slot rows, host registers verbatim. The
        request's tokens continue exactly where the uninterrupted run
        would be (the preemption parity wall pins this)."""
        req = parked.req
        self._live[slot] = req
        self._phase[slot] = parked.phase
        if parked.phase in (PREFILL, ENCODE):
            self._admit_order.append(slot)
        if self.pool is not None:
            for i, pid in enumerate(parked.pages):
                self._ptab[slot, i] = pid
            self._n_mapped[slot] = len(parked.pages)
        if self.xpool is not None:
            # cross pages come back by table rewrite alone: they were
            # written once at encode and never re-snapshotted (read-only)
            for i, pid in enumerate(parked.xpages):
                self._xptab[slot, i] = pid
            self._xn_mapped[slot] = len(parked.xpages)
            self._enc_lens[slot] = parked.enc_len
        self._snaps[slot] = parked.snaps
        self._need_snaps[slot] = parked.need_snaps
        self._offsets[slot] = parked.offset
        self.lengths = self.lengths.at[slot].set(parked.length)
        self.caches = self._aot.get("reset_slot", self._reset)(
            self.caches, slot)
        if self._stateful and parked.snapshot is not None:
            self.caches = self._aot.get("restore_slot", self._restore)(
                self.caches, slot, parked.snapshot)
        res = req.params.resolve(self.cfg.temperature, self.cfg.top_k)
        self.temps = self.temps.at[slot].set(res.temperature)
        self.topks = self.topks.at[slot].set(res.top_k)
        self._eos_ids[slot] = res.eos_id
        self._slot_keys = self._slot_keys.at[slot].set(
            jax.random.PRNGKey(res.seed) if res.seed is not None
            else jax.random.fold_in(self._root_key, req.rid)
        )
        self._counts[slot] = parked.count
        self.tokens = self.tokens.at[slot, 0].set(parked.last_token)
        self._stats["resumes"] += 1
        if self.tel is not None:
            self.tel.resumes.inc()
            if req.span is not None:
                req.span.to_phase(parked.phase, time.monotonic())
            if self.tel.ring is not None:
                self.tel.ring.emit("resume", rid=req.rid, slot=slot,
                                   phase=parked.phase)

    def _rank(self, req: Request) -> int:
        return PRIORITY_RANKS[req.priority]

    def _candidate_key(self, cand: Union[Request, PreemptedState]):
        """Priority-mode admission order: (class rank, uncached prefill
        tokens, submission order). The middle term is the work admission
        would actually schedule — a parked decode-phase request costs 0
        (it resumes straight into decode), a parked prefill resumes at
        its offset, and a fresh request's cached prefix is probed from
        the trie WITHOUT pinning recency (the probe is advisory; the
        authoritative match happens at admission)."""
        if isinstance(cand, PreemptedState):
            if cand.phase == ENCODE:
                # parked before its encoder ran: full encode + prefill
                cost = len(cand.req.prompt) + cand.enc_len
            elif cand.phase == PREFILL:
                cost = len(cand.req.prompt) - cand.offset
            else:
                cost = 0
            return (self._rank(cand.req), cost, cand.req.rid)
        cached = 0
        if self.trie is not None:
            cached = self.trie.probe(
                cand.prompt, require_snapshot=self._stateful)
        cost = len(cand.prompt) - cached
        if self._cross and cand.frames is not None:
            # charge the ENCODE pass unless the source is already warm in
            # the EncoderCache (advisory, like the trie probe — the
            # authoritative lookup happens at admission)
            if not (self.enc_cache is not None
                    and cand.enc_digest in self.enc_cache):
                cost += len(cand.frames)
        return (self._rank(cand), cost, cand.rid)

    @staticmethod
    def _cand_rid(cand: Union[Request, PreemptedState]) -> int:
        return (cand.req if isinstance(cand, PreemptedState) else cand).rid

    def _pick_candidate(self, cands: List[Union[Request, PreemptedState]]):
        """Choose the next admission from the waiting set (queued + parked).

        FIFO mode: oldest submission first (parked requests keep their
        original rid, so a forced preemption resumes in order). Priority
        mode: best ``_candidate_key``, with the aging floor — once
        ``starvation_limit`` consecutive admissions have overtaken the
        oldest waiter, the oldest waiter is force-admitted and the
        counter resets. No class can starve: the floor is class-blind."""
        oldest = min(cands, key=self._cand_rid)
        if not self.cfg.priorities:
            return oldest
        if self._overtakes >= self.cfg.starvation_limit:
            choice = oldest
        else:
            choice = min(cands, key=self._candidate_key)
        if choice is oldest:
            self._overtakes = 0
        else:
            self._overtakes += 1
        return choice

    def _admissions(self):
        """Fill free slots from the waiting set: resume parked requests
        and admit fresh ones under one ordering rule."""
        while self._free:
            cands = self._queue.snapshot() + self._parked
            if not cands:
                break
            choice = self._pick_candidate(cands)
            slot = self._free.pop(0)
            if isinstance(choice, PreemptedState):
                self._parked.remove(choice)
                self._resume(slot, choice)
            else:
                self._queue.remove(choice)
                self._admit(slot, choice)

    def _preempt_pass(self) -> int:
        """Free slots for waiting higher-class requests by parking
        strictly lower-class victims. A victim must outrank (numerically:
        higher rank value than) the BEST waiting overflow request and
        still be under its ``max_preempts`` immunity cap; among victims
        the worst class goes first, most recent admission breaking ties
        (deterministic, and the youngest slot has the least decode
        momentum). Each parking frees one slot, so the overflow shrinks
        monotonically and the loop terminates."""
        n = 0
        while True:
            waiting = self._queue.snapshot() + self._parked
            if self.cfg.priorities:
                waiting.sort(key=self._candidate_key)
            else:                        # pragma: no cover - preempt=>prio
                waiting.sort(key=self._cand_rid)
            overflow = waiting[len(self._free):]
            if not overflow:
                break
            best_rank = min(
                self._rank(c.req if isinstance(c, PreemptedState) else c)
                for c in overflow
            )
            victims = [
                s for s, r in self._live.items()
                if self._rank(r) > best_rank
                and r.preempt_count < self.cfg.max_preempts
            ]
            if not victims:
                break
            victim = max(
                victims,
                key=lambda s: (self._rank(self._live[s]),
                               self._live[s].admit_step,
                               self._live[s].rid),
            )
            self.preempt_slot(victim)
            n += 1
        return n

    # ------------------------------------------------------------------
    def _boundaries_needing_snapshots(self, prompt) -> set:
        """Page boundaries of ``prompt`` whose trie node is missing (or
        snapshotless, e.g. republished after eviction) — the only places
        prefill must pause at and capture recurrent state. Once the walk
        falls off the trie every deeper boundary needs one."""
        need, node = set(), self.trie.root
        for i in range(len(prompt) // self.pt):
            if node is not None:
                key = tuple(int(t) for t in
                            prompt[i * self.pt:(i + 1) * self.pt])
                node = node.children.get(key)
            if node is None or node.snapshot is None:
                need.add((i + 1) * self.pt)
        return need

    def _alloc_page(self) -> int:
        """Take a page from the pool, evicting LRU trie leaves on demand.
        A trie eviction drops the trie's reference; the loop keeps going
        because a page shared with a live slot does not free until that
        slot retires."""
        pid = self.pool.alloc()
        while pid is None:
            if self.trie is None or not self.trie.evict_one():
                raise RuntimeError(
                    f"KV page pool exhausted ({self.pool.n_pages} pages, "
                    f"0 free, {len(self.trie) if self.trie else 0} trie "
                    f"nodes): raise pool_pages"
                )
            pid = self.pool.alloc()
        return pid

    def _alloc_xpage(self) -> int:
        """Take a cross-pool page, evicting LRU EncoderCache entries on
        demand (their pages free once no live slot maps them)."""
        pid = self.xpool.alloc()
        while pid is None:
            if self.enc_cache is None or not self.enc_cache.evict_one():
                raise RuntimeError(
                    f"cross-attention page pool exhausted "
                    f"({self.xpool.n_pages} pages, 0 free, "
                    f"{len(self.enc_cache) if self.enc_cache else 0} "
                    f"cached encoder outputs): raise cross_pages"
                )
            pid = self.xpool.alloc()
        return pid

    def _run_encode(self, slot: int) -> int:
        """The slot's ENCODE phase: allocate its cross page run, run the
        padded batch=1 encoder tick (frames -> memory -> per-layer cross
        K/V scattered through the slot's cross table row), publish the
        result to the EncoderCache, and advance the phase machine to
        PREFILL. Returns the token charge against this tick's budget."""
        req = self._live[slot]
        enc_len = int(self._enc_lens[slot])
        need = -(-enc_len // self.pt)
        while self._xn_mapped[slot] < need:
            self._xptab[slot, self._xn_mapped[slot]] = self._alloc_xpage()
            self._xn_mapped[slot] += 1
        d = self.model.cfg.d_model
        frames = np.zeros((1, self.enc_tokens, d), np.float32)
        frames[0, :enc_len] = req.frames
        valid = np.zeros((1, self.enc_tokens), bool)
        valid[0, :enc_len] = True
        self.caches = self._aot.get("encode_tick", self._encode)(
            self.params, jnp.asarray(frames, self._cache_dtype),
            jnp.asarray(valid), self.caches,
            jnp.asarray(self._xptab[slot:slot + 1]),
        )
        self._phase[slot] = PREFILL
        self._stats["encode_ticks"] += 1
        if self.tel is not None:
            self.tel.encode_ticks.inc()
            if req.span is not None:
                req.span.to_phase(PREFILL, time.monotonic())
        if self.enc_cache is not None:
            pages = [int(self._xptab[slot, i]) for i in range(need)]
            self.enc_cache.put(req.enc_digest, pages, enc_len,
                               now=self.steps)
        return min(enc_len, self.cfg.chunk_tokens)

    def _ensure_pages(self, slot: int, last_pos: int):
        """Grow the slot's page table to cover ``last_pos``: fresh private
        pages for everything past the mapped prefix. Positions past the
        table's reach (length overruns) are left to the scatter's drop —
        identical to the dense cache's out-of-bounds behavior."""
        if self.pool is None:
            return
        need = min(last_pos // self.pt, self.npp - 1)
        while self._n_mapped[slot] <= need:
            pid = self._alloc_page()
            self._ptab[slot, self._n_mapped[slot]] = pid
            self._n_mapped[slot] += 1

    def _schedule_prefill(self, n_decoding: int,
                          extra_charge: int = 0) -> Dict[int, int]:
        """Token-budget pass: chunk_tokens per tick, decode-priority.

        Every decoding slot is charged one token up front; what remains
        goes to prefilling slots in admission order, each capped at the
        chunk width. The head of the prefill queue always receives at
        least one token so prefill progresses even when decoding slots
        consume the whole budget.

        With the prefix cache on a STATEFUL model (recurrent carries or
        windowed rings), a chunk additionally never crosses a page
        boundary that still NEEDS a snapshot (``_need_snaps``, computed
        at admission): the recurrent state right after the chunk then
        sits at exactly the boundary the trie pins. Boundaries the trie
        already covers don't pause the chunk, so a warm repeat of a
        shared prompt prefills at full chunk width. Stateless (pure
        full-attention) models never cap — their pages are position-
        addressed, chunk splits don't matter.

        ``extra_charge`` bills work already done this tick outside this
        pass — the ENCODE phase's padded encoder call — against the same
        budget, so an encode-heavy tick hands out fewer prefill columns
        (the head-of-queue floor still guarantees progress)."""
        c = self.cfg.chunk_tokens
        budget = c - n_decoding - extra_charge
        takes: Dict[int, int] = {}
        first = True
        for slot in self._admit_order:
            if self._phase[slot] != PREFILL:
                continue
            off = int(self._offsets[slot])
            rem = len(self._live[slot].prompt) - off
            floor = 1 if first else 0
            take = min(c, rem, max(budget, floor))
            ahead = [b for b in self._need_snaps[slot] if b > off]
            if ahead:
                take = min(take, min(ahead) - off)
            first = False
            if take <= 0:
                continue
            takes[slot] = take
            budget -= take
        return takes

    def _run_extend(self, takes: Dict[int, int]):
        cfg = self.cfg
        tel = self.tel
        block = np.zeros((cfg.n_slots, cfg.chunk_tokens), np.int32)
        n_new = np.zeros((cfg.n_slots,), np.int32)
        for slot, take in takes.items():
            off = int(self._offsets[slot])
            block[slot, :take] = self._live[slot].prompt[off:off + take]
            n_new[slot] = take
            self._ensure_pages(slot, off + take - 1)
        t0 = time.perf_counter() if tel is not None else 0.0
        toks, self.caches, self.lengths = self._aot.get(
            "extend_tick", self._extend)(
            self.params, jnp.asarray(block), self.caches, self.lengths,
            jnp.asarray(n_new), self.temps, self.topks,
            self._slot_keys, jnp.asarray(self._counts),
            jnp.asarray(self._ptab), *self._cross_extra(),
        )
        if tel is not None:
            # bound the device phase: async dispatch means the call above
            # returned before the computation finished; waiting on the
            # sampled tokens (needed on host immediately below anyway)
            # splits device compute from host bookkeeping without
            # changing any value
            jax.block_until_ready(toks)
            t1 = time.perf_counter()
            self._tick_phases["prefill_device"] = t1 - t0
            tel.prefill_tokens.inc(sum(takes.values()))
        toks_host = np.asarray(toks)
        for slot, take in takes.items():
            req = self._live[slot]
            self._offsets[slot] += take
            off_new = int(self._offsets[slot])
            if off_new in self._need_snaps[slot]:
                # prefill just landed on a boundary the trie is missing:
                # pin the recurrent state HERE so the published (or
                # snapshot-backfilled) node can restore it
                self._snaps[slot][off_new] = self._aot.get(
                    "snapshot_slot", self._snapshot)(self.caches, slot)
            if self._offsets[slot] == len(req.prompt):
                # prompt complete: the chunk's last-column logits are the
                # request's first sampled token
                self._phase[slot] = DECODE
                self._admit_order.remove(slot)
                tok = int(toks_host[slot])
                req.output.append(tok)
                req.token_steps.append(self.steps)
                if len(req.output) == 1:
                    # first token: per-class TTFT in ticks, measured from
                    # submission (queue wait + parked time included)
                    acc = self._class_ttft.setdefault(req.priority, [0, 0])
                    acc[0] += self.steps - req.submit_step
                    acc[1] += 1
                self._counts[slot] += 1
                self._stats["tokens_out"] += 1
                if tel is not None:
                    tel.tokens.inc()
                    if req.span is not None:
                        now = time.monotonic()
                        req.span.to_phase(DECODE, now)
                        if req.span.token(now):
                            tel.ttft.observe(now - req.span.submit_t)
                self.tokens = self.tokens.at[slot, 0].set(tok)
                if self.on_token is not None:
                    self.on_token(req, tok)
                self._maybe_retire(slot, req, tok)
        if tel is not None:
            self._tick_phases["prefill_host"] = time.perf_counter() - t1

    def _run_decode(self, decoding: List[int]):
        tel = self.tel
        active = np.zeros((self.cfg.n_slots,), bool)
        active[decoding] = True
        for slot in decoding:
            req = self._live[slot]
            pos = len(req.prompt) + len(req.output) - 1  # row this step writes
            if pos < self.cfg.max_len:
                self._ensure_pages(slot, pos)
        t0 = time.perf_counter() if tel is not None else 0.0
        nxt, self.caches, self.lengths = self._aot.get(
            "decode_tick", self._decode)(
            self.params, self.tokens, self.caches, self.lengths,
            jnp.asarray(active), self.temps, self.topks,
            self._slot_keys, jnp.asarray(self._counts),
            jnp.asarray(self._ptab), *self._cross_extra(),
        )
        if tel is not None:
            jax.block_until_ready(nxt)
            t1 = time.perf_counter()
            self._tick_phases["decode_device"] = t1 - t0
        nxt_host = np.asarray(nxt)
        self.tokens = nxt[:, None]
        for slot in decoding:
            req = self._live[slot]
            tok = int(nxt_host[slot])
            req.output.append(tok)
            req.token_steps.append(self.steps)
            self._counts[slot] += 1
            self._stats["tokens_out"] += 1
            if tel is not None:
                tel.tokens.inc()
                if req.span is not None:
                    now = time.monotonic()
                    prev = req.span.last_token_t
                    if req.span.token(now):
                        tel.ttft.observe(now - req.span.submit_t)
                    else:
                        tel.itl.observe(now - prev)
            if self.on_token is not None:
                self.on_token(req, tok)
            self._maybe_retire(slot, req, tok)
        if tel is not None:
            self._tick_phases["decode_host"] = time.perf_counter() - t1

    def step(self):
        """One engine tick: preemption pass + admissions/resumes +
        scheduled prefill chunks + one batched decode step. Every live
        decoding slot emits exactly one token per tick regardless of
        concurrent prefill (the fairness invariant); a prefilling slot
        emits its first token on the tick its final chunk lands. A slot
        preempted this tick emits nothing — exactly the cost the
        preempt-free tick rate reports."""
        tel = self.tel
        with self._mesh_ctx():
            if tel is not None:
                t_tick = time.perf_counter()
                trace_pre = (sum(TRACE_COUNTS.values())
                             if self._retrace_armed else 0)
            if self.cfg.preempt:
                self._preempt_pass()
            if tel is not None:
                t_adm = time.perf_counter()
                self._tick_phases["preempt"] = t_adm - t_tick
            self._admissions()
            depth = self._queue.qsize()
            if depth > self._stats["peak_queue_depth"]:
                self._stats["peak_queue_depth"] = depth
            if tel is not None:
                self._tick_phases["admission"] = time.perf_counter() - t_adm
            if not self._live:
                # idle tick: no jitted call ran, nothing to observe (an
                # empty-engine poll loop must not drown the tick
                # histograms in zero-work samples)
                self._tick_phases.clear()
                return
            # ENCODE pass (cross models): warm-cache late hits resolve in
            # O(1); at most ONE padded encoder call actually runs per tick
            # and its cost is billed against the prefill budget below.
            enc_charge = 0
            if self._cross:
                t_enc = time.perf_counter() if tel is not None else 0.0
                for s in list(self._admit_order):
                    if self._phase[s] != ENCODE:
                        continue
                    if self._try_enc_cache(s, self._live[s]):
                        continue
                    enc_charge = self._run_encode(s)
                    break
                if tel is not None and enc_charge:
                    self._tick_phases["encode"] = (
                        time.perf_counter() - t_enc)
            decoding = [s for s in range(self.cfg.n_slots)
                        if self._phase[s] == DECODE]
            dec_reqs = [(self._live[s], len(self._live[s].output))
                        for s in decoding]
            takes = self._schedule_prefill(len(decoding),
                                           extra_charge=enc_charge)
            if takes:
                self._run_extend(takes)
            if decoding:
                self._run_decode(decoding)
            # preempt-free accounting: a work tick is clean iff no slot
            # was preempted since the last tick AND every slot that
            # entered it decoding emitted exactly one token. The rate is
            # what the preempting scheduler SPENT to keep the interactive
            # class's TTFT down.
            self._stats["work_ticks"] += 1
            if (not self._preempted_since_tick
                    and all(len(r.output) == n + 1 for r, n in dec_reqs)):
                self._stats["preempt_free_ticks"] += 1
            self._preempted_since_tick = False
            if tel is not None:
                self._observe_tick(t_tick, trace_pre)
        self.steps += 1

    def _observe_tick(self, t_tick: float, trace_pre: int):
        """End-of-tick telemetry: the tick + per-phase histograms, then
        the steady-state retrace check. Runs only with telemetry on."""
        tel = self.tel
        tel.tick.observe(time.perf_counter() - t_tick)
        for phase, dt in self._tick_phases.items():
            tel.tick_phase[phase].observe(dt)
        self._tick_phases.clear()
        if not self._retrace_armed:
            return
        delta = sum(TRACE_COUNTS.values()) - trace_pre
        if delta <= 0:
            return
        # a tick function's Python body ran DURING this tick — after AOT
        # warmup that means jax compiled something mid-serving (shape
        # drift, a cache miss, an un-warmed entry point): exactly the
        # stall class warmup() exists to prevent. Count every retrace,
        # warn once per engine.
        tel.retraces.inc(delta)
        if tel.ring is not None:
            tel.ring.emit("retrace", tick=self.steps, n_traces=delta)
        if not self._retrace_warned:
            self._retrace_warned = True
            warnings.warn(
                f"serve engine re-traced {delta} tick function(s) at tick "
                f"{self.steps} after AOT warmup — a compile stall is "
                f"hiding in the serving path (see serve_retraces_total)",
                RuntimeWarning,
                stacklevel=2,
            )

    def stats(self) -> Dict[str, object]:
        """Engine health counters for the serve CLI / HTTP ``/stats``
        endpoint (and tests): admission hit rate, prefill tokens the
        prefix cache skipped, page-pool utilization, queue pressure
        (current + peak depth, typed rejects), throughput (tokens out,
        work ticks), the preempt-free tick rate, and whether the tick
        executables are AOT-warm."""
        s = dict(self._stats)
        s["hit_rate"] = s["prefix_hits"] / max(s["admitted"], 1)
        s["prefill_tokens_skipped"] = s.pop("prefix_tokens")
        if self.pool is not None:
            s["pool_pages"] = self.pool.n_pages
            s["pages_in_use"] = self.pool.used_pages
            s["page_utilization"] = self.pool.used_pages / self.pool.n_pages
        # per-cache-family pool utilization (ServableModel cache families;
        # the flat pool_* keys above stay for the historical dashboards)
        s["cache_families"] = {
            p.family: {
                "pages": p.n_pages,
                "in_use": p.used_pages,
                "utilization": p.used_pages / p.n_pages,
            }
            for p in (self.pool, self.xpool) if p is not None
        }
        s["enc_cache_entries"] = (
            len(self.enc_cache) if self.enc_cache is not None else 0
        )
        s["trie_nodes"] = len(self.trie) if self.trie is not None else 0
        s["evictions"] = self.trie.evictions if self.trie is not None else 0
        s["queue_depth"] = self._queue.qsize()
        s["compute_path"] = self.cfg.compute_path
        s["live_slots"] = len(self._live)
        s["free_slots"] = len(self._free)
        s["parked"] = len(self._parked)
        s["ticks"] = self.steps
        s["preempt_free_tick_rate"] = (
            s["preempt_free_ticks"] / max(s["work_ticks"], 1)
        )
        # per-class first-token latency in TICKS (submission -> first
        # token, queue wait and parked time included): the number the
        # priority scheduler exists to move, wall-clock-free so tests can
        # assert on it deterministically
        s["class_ttft_ticks"] = {
            cls: round(total / n, 2)
            for cls, (total, n) in sorted(self._class_ttft.items())
        }
        s["class_counts"] = {
            cls: n for cls, (_, n) in sorted(self._class_ttft.items())
        }
        s["aot_warm"] = self.aot_warm
        if self.tel is not None:
            # wall-clock latency quantiles from the telemetry histograms
            # (bucket-interpolated, ms): the /stats mirror of what
            # /metrics exposes raw — absent entirely with telemetry off
            s["latency"] = self.tel.latency_summary()
            s["retraces"] = self.tel.retraces.get()
        return s

    def run_until_drained(self, max_steps: int = 10_000, on_tick=None) -> int:
        """Step until every submitted request completes; returns the tick
        count. ``on_tick(engine)`` runs after each tick — drivers hook it
        for per-tick wall-clock latency accounting without forfeiting the
        bounded-steps wedge diagnostics below."""
        for i in range(max_steps):
            if not self.has_work:
                return i
            self.step()
            if on_tick is not None:
                on_tick(self)
        slots = ", ".join(
            f"slot {s}: rid={r.rid} {self._phase[s]}"
            f"@{int(self._offsets[s])}/{len(r.prompt)}"
            f" ({len(r.output)}/{r.params.max_tokens} tok)"
            for s, r in sorted(self._live.items())
        )
        parked = ", ".join(
            f"rid={p.req.rid} parked {p.phase}@{p.offset}"
            f"/{len(p.req.prompt)} ({len(p.req.output)} tok, "
            f"preempted x{p.req.preempt_count})"
            for p in self._parked
        )
        raise RuntimeError(
            f"engine did not drain after {max_steps} steps: "
            f"{self._queue.qsize()} queued, {len(self._live)} live, "
            f"{len(self._parked)} parked — "
            f"{'; '.join(x for x in (slots, parked) if x) or 'no live slots'}"
        )
