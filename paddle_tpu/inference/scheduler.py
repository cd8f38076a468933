"""Continuous-batching scheduler over the paged-KV serving engine.

LLMEngine.generate() is a static-batch API: equal-length prompts, the
batch frozen for the whole call, a sequence that hits EOS squatting on
its slot and pages until every other sequence finishes. This module adds
the scheduling layer the north star needs (PAPERS.md ragged paged
attention supplies the kernel substrate; MPK attacks the same gap from
the compiler side): request-at-a-time serving over the same pools.

  ContinuousBatchingEngine(model, ...).add_request(ids, ...) -> uid
  .step()          one engine iteration (admit / prefill chunk / decode)
  .drain()         run until idle, return {uid: output}
  .generate_many() submit-and-drain convenience (greedy outputs are
                   byte-identical to one-at-a-time LLMEngine.generate())

Scheduling model:
  - max_batch SLOTS. A request is admitted into the lowest free slot
    once its KV pages fit, prefills its prompt in fixed-size CHUNKS
    (long prompts interleave with in-flight decodes instead of stalling
    them), then joins the decode batch. Each sequence retires at ITS OWN
    EOS/budget and its slot + pages free immediately for the queue.
  - the decode step stays a handful of compiled programs: one per SLOT
    BUCKET (power-of-two widths), each taking a slot-active mask that
    the paged-attention kernel uses to skip retired slots' compute and
    page DMA. Chunked prefill is ONE more compiled program.
  - prefix cache: full prompt pages are content-addressed (a chain hash
    of page-sized token chunks); a new request sharing a cached prefix
    takes refcounted read-only references instead of re-prefilling, and
    a cached page covering the request's divergence point is shared too
    and COPY-ON-WRITten at the first divergent write. Cache-held pages
    evict LRU under pool pressure.

Numerics: chunk-prefill attention gathers the sequence's pages and
masks causally, so a chunk attends exactly the same values a dense
prefill would (on CPU/f32 bitwise so — the greedy-equivalence tests
assert byte identity with generate()).
"""
import collections
import contextlib
import math
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..failsafe import InjectedFault, fault_point
from ..failsafe import armed as _faults_armed
from ..profiler import RecordEvent as _span
from .adapters import AdapterError, UnknownAdapterError
from .sampling import (GREEDY, NEG, SamplingParams, TokenMaskAutomaton,
                       apply_penalties, fold_keys, select_from_topk,
                       stop_hit)
from ..profiler import phase, record_counters
from . import latent, sparse_heads
from ..ops.sparse_attention import SPARSE_COUNTS
from .description import UnsupportedByDescription
from .serving import LLMEngine, EngineFullError, _rms, _mm, _mm_f32
from .speculative import resolve_drafter

from ..ops.pallas.chunk_attention import latent_live_steps
from ..ops.pallas.paged_attention import (expand_kv_heads, mxu_operands,
                                          paged_attention,
                                          ragged_paged_attention,
                                          spec_verify_attention)

# The engine's one span call is profiler.RecordEvent (`_span`), always on:
# every host phase of a step lands on the host plane of any active
# jax.profiler session (TraceAnnotation, the device lines' clock) and in
# profiler.span_totals(), session or not. Names, extents and the metric
# each is for: docs/observability.md "Profiler integration".
_NULL_SPAN = contextlib.nullcontext()

QUEUED, PREFILL, DECODE, DONE, FAILED, CANCELLED = \
    "queued", "prefill", "decode", "done", "failed", "cancelled"
# terminal state of a request whose KV pages were handed off to another
# engine (export_kv_pages -> release_handoff): its continuation — and
# its result — live on the importing engine
MIGRATED = "migrated"
# NON-terminal parked state: the request's device pages were demoted to
# the KV tier (host RAM/disk — inference/tiering.py); a restore sweep
# re-seats it at a block boundary and it continues byte-identically
DEMOTED = "demoted"


def _pools_put(pools, li, arr, acc):
    """Collect one layer's updated page array inside a traced fn that
    must handle BOTH pool forms: the per-layer list (default) appends to
    `acc` (the caller returns it via _pools_result), the NATIVE stacked
    [L, ...] array (megakernel="multi") takes a dynamic-update-slice in
    place — no per-step restack. Returns the (possibly new) pools."""
    if isinstance(pools, (list, tuple)):
        acc.append(arr)
        return pools
    return pools.at[li].set(arr)


def _rows_layer(a):
    """The module that serves a layer whose page group keeps ONE row a
    token (latent rows, or per-head [K ; V] beside index keys), None for
    a layer of separate K and V pools."""
    if a.latent is not None:
        return latent
    return sparse_heads if a.indexer is not None else None


def _pools_result(pools, acc):
    """The value a traced fn returns for its updated pools: the
    collected per-layer list, or the stacked array itself (already
    updated in place by _pools_put)."""
    return acc if isinstance(pools, (list, tuple)) else pools


class SchedulerError(RuntimeError):
    """Base of the scheduler's typed errors."""


class EngineBusyError(SchedulerError):
    """Backpressure: the admission queue is at queue_limit. The caller
    should shed load or retry later — nothing was enqueued."""


class UnknownRequestError(SchedulerError, KeyError):
    """A uid this engine has never issued (or one already forgotten)."""

    def __str__(self):              # KeyError repr-quotes its arg
        return self.args[0] if self.args else ""


class RequestNotFinishedError(SchedulerError):
    """result() on a request that is still queued/prefilling/decoding."""


class RequestFailedError(SchedulerError):
    """result() on a request that was retired with an error; carries the
    RequestFailure record as .failure."""

    def __init__(self, failure):
        self.failure = failure
        super().__init__(str(failure))


class RequestCancelledError(RequestFailedError):
    """result() on a request retired by cancel()."""


class DeadlineExceededError(SchedulerError):
    """Recorded error for a request whose deadline/TTL expired before it
    finished."""


class RequestFailure:
    """Typed per-request error record: WHICH request died, at WHAT stage,
    with WHAT error — while the engine kept stepping."""

    __slots__ = ("uid", "stage", "error", "message", "step",
                 "tokens_generated")

    def __init__(self, uid, stage, exc, step, tokens_generated=0):
        self.uid = uid
        self.stage = stage              # admit | prefill | decode |
        #                                 deadline | cancel
        self.error = type(exc).__name__
        self.message = str(exc)
        self.step = step                # engine step count at failure
        self.tokens_generated = tokens_generated

    def __repr__(self):
        return (f"RequestFailure(uid={self.uid}, stage={self.stage!r}, "
                f"error={self.error}, step={self.step})")

    def __str__(self):
        return (f"request {self.uid} failed at stage {self.stage!r} "
                f"(engine step {self.step}): {self.error}: {self.message}")


class Request:
    """One in-flight generation request (host-side bookkeeping only)."""

    __slots__ = ("uid", "ids", "t0", "max_new_tokens", "eos_token_id",
                 "state", "slot", "pages", "shared_idx", "cow_reserve",
                 "filled", "resume", "tok", "out", "result",
                 "pages_shared", "deadline", "ttl_steps", "born_step",
                 "error", "tenant", "priority", "draft_k",
                 "spec_drafted", "spec_accepted", "demote", "seated_step",
                 "idle_steps", "adapter", "adapter_released",
                 "sampling", "counts", "gstate", "more_pages")

    def __init__(self, uid, ids, max_new_tokens, eos_token_id,
                 deadline=None, ttl_steps=None, born_step=0,
                 tenant="default", priority=0, draft_k=0, sampling=None):
        self.uid = uid
        self.ids = ids                  # np.int64 [t0]
        self.t0 = int(ids.size)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.state = QUEUED
        self.slot = None
        self.pages = []                 # page ids, one per table index
        #                                 (page group 0, a full group)
        self.more_pages = {}            # group index -> {table index:
        #                                 page id} of every OTHER page
        #                                 group (serving.PageGroup)
        self.shared_idx = set()         # table indices that are READ-ONLY
        self.cow_reserve = None         # page reserved for the one
        #                                 possible copy-on-write
        self.filled = 0                 # prompt tokens already in cache
        self.resume = 0                 # first position prefill processes
        self.tok = None                 # next token id to feed
        self.out = []                   # generated token ids
        self.result = None              # np.int64 [t0 + n_generated]
        self.pages_shared = 0
        self.deadline = deadline        # absolute time.monotonic() cutoff
        self.ttl_steps = ttl_steps      # engine-step budget (deterministic)
        self.born_step = born_step      # engine step count at submission
        self.error = None               # RequestFailure when retired bad
        self.tenant = tenant            # admission-policy tenant name
        self.priority = int(priority)   # higher admits (and preempts)
        #                                 first; strict across tenants
        self.draft_k = int(draft_k)     # current per-request draft
        #                                 length (adaptive speculation)
        self.spec_drafted = 0           # drafts offered to verification
        self.spec_accepted = 0          # drafts the target accepted
        self.demote = None              # tier-restore record while the
        #                                 request is DEMOTED
        self.seated_step = born_step    # engine step of the last seat
        #                                 (admission/import/restore) —
        #                                 the demotion victim LRU key
        self.idle_steps = 0             # consecutive engine steps this
        #                                 seated decode request waited
        #                                 without emitting (the
        #                                 demote-on-idle trigger)
        self.adapter = None             # LoRA adapter NAME (None = base
        #                                 weights; inference/adapters.py)
        self.adapter_released = False   # pool ref dropped (terminal
        #                                 transition ran); the NAME
        #                                 stays for salvage/export
        self.sampling = sampling if sampling is not None else GREEDY
        self.counts = {}                # token -> occurrences among
        #                                 GENERATED tokens (the penalty
        #                                 state; prompt tokens never
        #                                 count). Survives preemption
        #                                 (the ids-fold keeps `out`'s
        #                                 history here) and rides
        #                                 export_request for resume.
        self.gstate = 0                 # grammar automaton state (host-
        #                                 authoritative; advanced per
        #                                 emitted token in _push_token)


class PrefixCache:
    """Content-addressed read-only KV pages, LRU-evicted under pressure.

    Full prompt pages are keyed by a CHAIN key — nested tuples
    (parent_key, page_tokens) — so a page only matches when its entire
    prompt prefix matches, never just the page's own tokens. A secondary
    index maps every strict prefix of a cached page's tokens to that
    page, which lets a request whose prompt DIVERGES MID-PAGE share the
    page read-only (the engine copy-on-writes it at the first divergent
    write). The cache holds its own allocator reference per page
    (refcount), so cached pages survive their creator's retirement and
    free only on eviction.
    """

    def __init__(self, page_size):
        self.p = page_size
        self._entries = collections.OrderedDict()   # chain_key -> page
        self._children = {}      # chain_key -> {page: tokens tuple}
        self._by_page = {}       # page -> chain_key
        self.hits = 0            # pages served from cache (counted by
        self.misses = 0          # the scheduler at ADMISSION, so failed
        #                          admission retries don't inflate them)
        self.on_evict = None     # callback(chain_key) fired when an
        #                          entry leaves the cache (the engine
        #                          retracts it from the fleet prefix
        #                          index; advisory — errors swallowed
        #                          by the installer's wrapper)

    def __len__(self):
        return len(self._entries)

    def match(self, ids):
        """Longest cached cover of a prefix of `ids` (1-D np array).
        Returns (pages, covered): `pages` to install at table indices
        0..len-1, `covered` counted in tokens. The LAST page may cover
        tokens through the end of the prompt even when the prompt ends
        mid-page (partial-index hit) — the scheduler re-runs the final
        token and copy-on-writes that page before any write."""
        p = self.p
        key = ()
        pages = []
        j = 0
        while (j + 1) * p <= ids.size:
            k2 = (key, tuple(int(t) for t in ids[j * p:(j + 1) * p]))
            page = self._entries.get(k2)
            if page is None:
                break
            self._entries.move_to_end(k2)
            pages.append(page)
            key = k2
            j += 1
        covered = j * p
        rem = tuple(int(t) for t in ids[j * p:])
        if rem and len(rem) < p:
            # mid-page divergence: any cached child page whose token
            # chunk STARTS WITH the remaining prompt can be shared (and
            # will be copy-on-written). Children of a chain node are the
            # observed continuations — typically a handful.
            for page, tokens in self._children.get(key, {}).items():
                if tokens[:len(rem)] == rem:
                    owner = self._by_page.get(page)
                    if owner is not None:
                        self._entries.move_to_end(owner)
                    pages.append(page)
                    covered = ids.size
                    break
        return pages, covered

    def insert(self, parent_key, tokens, page, allocator):
        """Register `page` as the cached KV for `tokens` under
        `parent_key`; the cache takes its own allocator reference.
        Returns the page's chain key (parent for the next page). No-op
        (returning the key) when an entry already exists."""
        toks = tuple(int(t) for t in tokens)
        key = (parent_key, toks)
        if key in self._entries:
            self._entries.move_to_end(key)
            return key
        allocator.share(page)
        self._entries[key] = page
        self._children.setdefault(parent_key, {})[page] = toks
        self._by_page[page] = key
        return key

    def chain_key(self, parent_key, tokens):
        return (parent_key, tuple(int(t) for t in tokens))

    def continuation(self, ids, k):
        """Predict up to `k` tokens FOLLOWING `ids` from the cached page
        chains — the prefix-cache-seeded DRAFTER's walk (speculative.py
        PrefixCacheDrafter). Every full page of `ids` must be cached
        (the chain is content-addressed, so a single mismatch means no
        other request ever served this context); the remaining partial
        tail then selects a cached child page whose tokens extend it,
        and full-page children keep the walk descending. Returns an
        int64 array, possibly empty (cold cache / divergent context)."""
        p = self.p
        ids = np.asarray(ids)
        key = ()
        for j in range(ids.size // p):
            key = (key, tuple(int(t) for t in ids[j * p:(j + 1) * p]))
            if key not in self._entries:
                return np.empty((0,), np.int64)
        rem = tuple(int(t) for t in ids[(ids.size // p) * p:])
        out = []
        while len(out) < k:
            nxt = None
            for tokens in self._children.get(key, {}).values():
                if len(tokens) > len(rem) and tokens[:len(rem)] == rem:
                    nxt = tokens
                    break
            if nxt is None:
                break
            out.extend(nxt[len(rem):])
            # cached children are always full pages: descend the chain
            key = (key, nxt)
            rem = ()
        return np.asarray(out[:k], np.int64)

    def evict(self, n_pages, allocator, protect=()):
        """Free up to `n_pages` cache-only pages (refcount 1), oldest
        first, skipping `protect`. Returns the number freed.

        O(1) amortized: entries pop from the LRU head; an entry that
        cannot be evicted right now — protected for the current
        admission, refcount > 1 because a running request still reads
        it, or under a PENDING EXPORT TICKET (a KV handoff, prefix
        ship, or tier demote in flight names the page; the ticket's
        commit drops a reference, so a concurrent free here would hand
        the page to a new owner mid-transfer) — is BY DEFINITION in
        use, so it is moved to the MRU end rather than rescanned by
        every future eviction (the old linear scan walked every pinned
        chain again on each call). Each entry is examined at most once
        per call."""
        freed = 0
        scanned = 0
        limit = len(self._entries)
        while freed < n_pages and scanned < limit and self._entries:
            key = next(iter(self._entries))
            page = self._entries[key]
            scanned += 1
            if page in protect or allocator.refcount(page) != 1 or \
                    allocator.is_exporting(page):
                self._entries.move_to_end(key)
                continue
            self._drop(key, page)
            allocator.free([page])
            freed += 1
        return freed

    def clear(self, allocator=None):
        if self.on_evict is not None:
            for key in list(self._entries):
                self.on_evict(key)
        if allocator is not None:
            for key, page in list(self._entries.items()):
                if allocator.refcount(page) > 0:
                    allocator.free([page])
        self._entries.clear()
        self._children.clear()
        self._by_page.clear()

    def _drop(self, key, page):
        del self._entries[key]
        self._by_page.pop(page, None)
        kids = self._children.get(key[0])
        if kids is not None:
            kids.pop(page, None)
            if not kids:
                del self._children[key[0]]
        if self.on_evict is not None:
            self.on_evict(key)


@jax.jit
def _tok_merge(toks, vals, mask):
    """The device token vector with the host's `vals` where `mask`."""
    return jnp.where(mask, vals, toks)


class _FusedBlock:
    """One in-flight fused dispatch (decode_block > 1): which requests
    rode it, plus the device futures the host has not yet fetched. The
    carries (tok/lens/act/rem/key) stay ON DEVICE so the next block can
    be dispatched from them without a host round trip (double-buffered
    pipelining)."""

    __slots__ = ("w", "K", "pf_items", "dec_items", "tables", "eos_dev",
                 "first", "toks", "emitted", "tok_fin", "lens_fin",
                 "act_fin", "rem_fin", "has_prefill", "has_decode",
                 "chained", "dlens", "aid", "mode", "extras")

    def __init__(self, w, K):
        self.w = w
        self.K = K
        self.pf_items = []          # [(Request, chunk-end position)]
        self.dec_items = []         # [Request]
        self.mode = "greedy"        # _block_mode of the participants
        self.extras = ()            # device sampling inputs (see
        #                             _build_cb_fused; () in greedy)
        self.tables = None          # device [w, mp] (reused by chains)
        self.eos_dev = None         # device [w] eos ids (-1 = none)
        self.first = None           # device [w] first tokens (prefill)
        self.toks = None            # device [K, w] sampled tokens
        self.emitted = None         # device [K, w] bool: token is real
        self.tok_fin = self.lens_fin = self.act_fin = self.rem_fin = None
        self.has_prefill = False
        self.has_decode = False
        self.chained = False
        self.dlens = None           # np [K, w] drafts offered per pass
        #                             per slot (speculative blocks only)
        self.aid = None             # device [w] adapter pool-slot ids
        #                             (None = adapter-free block: the
        #                             plain compiled program ran)


class _Dispatched:
    """One step program of the decode_block == 1 path that has been
    dispatched and whose tokens the host has not fetched and booked yet
    (`_resolve`). A row is (request, the slot it held at dispatch): by
    the time the program is resolved a request may have left its slot."""

    __slots__ = ("decode", "rows", "uids", "toks", "mode", "w", "logits",
                 "top", "positions", "last")

    def __init__(self, decode, rows, toks=None, mode="greedy", w=1,
                 logits=None, top=None, positions=None, last=False):
        self.decode = decode        # a decode step, else a prefill chunk
        self.rows = rows            # [(Request, slot)]
        self.uids = frozenset(r.uid for r, _ in rows)
        self.toks = toks            # the engine's device token vector as
        #                             this program left it (a greedy
        #                             program); else the host selects,
        #                             under `mode` at width `w`, from:
        self.mode, self.w = mode, w
        self.logits = logits        # device [w, V] ("proc", adapters)
        self.top = top              # device (topv, topi) ("sampled")
        self.positions = positions  # the new tokens' PRNG counters
        self.last = last            # a prompt's last chunk: it holds
        #                             the request's first token


class ContinuousBatchingEngine(LLMEngine):
    """Request-at-a-time serving over the paged-KV engine.

    Extra knobs on top of LLMEngine:
      prefill_chunk: prompt tokens processed per prefill step (default
        page_size). Long prompts spread over several steps, interleaved
        with decode steps so in-flight decodes never stall for a whole
        prompt.
      slot_buckets: compiled decode widths (default powers of two up to
        max_batch). A step runs at the smallest bucket covering the
        highest live slot.
      prefix_cache: enable content-addressed prompt-page sharing.
      decode_block: K > 1 runs the hot loop DEVICE-RESIDENT — one
        compiled dispatch covers a ragged prefill phase plus K decode
        steps (on-device sampling, per-slot EOS/budget flags); the host
        intervenes every K tokens to retire/admit/refill, and in a
        pure-decode steady state dispatches block N+1 before fetching
        block N's tokens. Greedy outputs stay byte-identical to K=1;
        deadlines/TTLs round UP to block boundaries and fault points
        fire once per block (docs/serving.md).
      ragged_kernel: force (True/False) the Pallas ragged-prefill
        kernel; default None = kernel on TPU, dense gathered math under
        interpret/CPU.
      megakernel: decode megakernel knob (ops/pallas/
        decode_megakernel). None (default) = auto: the per-layer
        megakernel on TPU when the (per-shard) geometry supports it,
        the existing fused op-chain under interpret/CPU; True/"layer"
        forces the per-layer megakernel (interpret mode on CPU — the
        parity fallback, byte-identical greedy to the op-chain path);
        "multi" is WHOLE-STEP mode: one invocation runs ALL layers
        plus the final norm, the vocab-tiled lm_head and an on-kernel
        greedy argmax (weights — lm_head included — stream across
        phase boundaries; KV pools stored NATIVELY stacked [L, ...]);
        False forces off. Composes with speculate= (the verify pass
        rides the kernel's tq>1 schedule) and with tp>1 under
        tp_mode="exact" (per-shard segments, vocab-parallel head,
        psum-free greedy select) — see docs/serving.md "Megakernel
        decode" for the composition matrix.
      speculate: T >= 2 turns on SPECULATIVE DECODING — each decode scan
        step becomes a verify pass over T feed tokens (pending token +
        up to T-1 drafts) scored in ONE multi-token-q ragged-paged-
        attention invocation, accept/reject computed inside the scan
        carries (accepted length advances lens on device; rejected
        drafts cost nothing — writes are length-gated, no KV scrub).
        Greedy outputs are byte-identical to the non-speculative engine.
        See docs/serving.md "Speculative decoding".
      drafter: "ngram" (default; prompt-lookup), "prefix" (prefix-cache-
        seeded chains), or a speculative.Drafter instance (e.g.
        ModelDrafter for a small draft model).
      spec_adaptive: per-request draft length shrinks (halve on a
        zero-accept pass) / grows (double on a clean sweep) within
        [1, T-1] on trailing acceptance.
      tenants: {name: {"share": s, "priority": p}} admission policy —
        priority strict-orders admission AND allows decode-slot
        preemption of strictly-lower-priority running requests (victim
        work re-queues, never lost); share weights fair-share virtual
        time (1/share per emitted token) among equal priorities, so
        speculation's variable yield is charged fairly.
      kv_tier: "host"/"disk" (or a tiering.KVTierStore) enables KV
        TIERING — demote_request parks a cold request's device pages
        in host RAM (spilling to disk past tier_host_cap_mb, under
        tier_dir) in the CRC-stamped handoff format; restore_request /
        the per-step restore sweep re-seats it byte-identically.
        oversubscribe (default on when a tier is set) lets admission
        demote the longest-resident running request when the queue
        head cannot fit, so live requests can exceed the device pool
        (docs/serving.md "Prefix-aware routing & KV tiering").
      queue_limit: bounded admission queue — add_request past this depth
        raises EngineBusyError (typed backpressure) instead of growing
        an unbounded backlog. None (default) = unbounded.
      default_deadline_ms: deadline applied to requests submitted
        without one (None = no deadline).
      sample_k: size of the top-K survivor set every sampled selection
        draws from (default 8; 1 <= sample_k <= 128). In whole-step
        megakernel mode the set is computed by the in-kernel running
        top-K merge and the [w, V] logits never materialize; top_p /
        min_p act within the survivor set (exact whenever the nucleus
        fits — docs/serving.md "Sampling & structured decoding").
        A request's top_k must be <= sample_k. Sampling itself is per
        request: add_request(sampling=SamplingParams(...)); a request
        without one decodes greedily.

    Failure posture: a request that trips a fault (injected or real) at
    a per-request boundary — admission allocation, a prefill chunk, its
    slice of a decode step, deadline expiry — is retired ALONE with a
    RequestFailure record (pages and prefix-cache refs reclaimed); the
    engine keeps stepping every other request. Only a failure inside a
    donated-buffer compiled call still takes the pools down (KV is
    gone), and even then queued requests survive the rebuild.
    """

    @_span("setup.engine")
    def __init__(self, model, max_len=1024, page_size=128, max_batch=8,
                 prefill_chunk=None, slot_buckets=None, prefix_cache=True,
                 queue_limit=None, default_deadline_ms=None, sample_k=8,
                 decode_block=1, ragged_kernel=None,
                 megakernel=None, speculate=None, drafter="ngram",
                 spec_adaptive=True, tenants=None, kv_tier=None,
                 tier_dir=None, tier_host_cap_mb=None, oversubscribe=None,
                 tier_idle_steps=None, telemetry=None, adapters=None,
                 **kw):
        # before the base builds the pools: a window group's pool holds
        # the further pages of the one chunk in flight
        self.prefill_chunk = int(prefill_chunk or page_size)
        super().__init__(model, max_len=max_len, page_size=page_size,
                         max_batch=max_batch, **kw)
        if not self.desc.plain:
            # what this layer description cannot do yet is refused HERE,
            # typed, not answered wrongly later
            windows = any(g.window is not None for g in self.groups)
            for on, what in (
                    (speculate not in (None, False, 1), "speculate="),
                    (kv_tier is not None, "kv_tier="),
                    (adapters not in (None, False), "adapters="),
                    (int(decode_block) > 1, "decode_block > 1"),
                    (megakernel not in (None, False), "megakernel="),
                    (prefix_cache and (
                        windows or len(self.groups) > 1
                        or any(g.index_width for g in self.groups)),
                     "prefix_cache=True (prefix sharing across window "
                     "layers, several page groups or a group with index "
                     "keys; pass prefix_cache=False)")):
                if on:
                    raise UnsupportedByDescription(
                        f"{what} is written for a plain description "
                        "(every layer the same dense block with full "
                        "causal attention); this model's layers differ "
                        "(page groups "
                        f"{[g.window for g in self.groups]}, routed "
                        f"experts: {self.desc.has_experts})")
        # telemetry=: a telemetry.Telemetry instance (or True to build
        # one) threaded through every lifecycle transition — per-request
        # spans (submit/seat/TTFT/blocks/spec passes/demote/handoff/
        # retire), latency histograms, chrome-trace + Prometheus + JSONL
        # exports. None (default) keeps a single-branch fast path at
        # every site; greedy outputs are byte-identical on vs off
        # (pinned in tests). All timestamps are captured at host points
        # the engine already visits — zero extra device syncs. See
        # docs/observability.md.
        self._tel = None
        self._tel_src = "engine"
        self.telemetry = None
        if telemetry is True:
            from .telemetry import Telemetry
            telemetry = Telemetry()
        if telemetry is not None and telemetry is not False:
            self.attach_telemetry(telemetry)
        # speculate=T (>= 2): speculative decoding — every decode scan
        # step becomes a VERIFY PASS over T feed tokens (the pending
        # token + up to T-1 drafter proposals) scored through ONE
        # multi-token-q ragged-paged-attention invocation, with greedy/
        # sampled acceptance computed inside the lax.scan carries:
        # accepted length advances `lens` on device, rejected drafts
        # need no KV scrub (writes are length-gated — `lens` simply does
        # not advance over them). Host intervention stays at block
        # boundaries: draft before dispatch, replay tokens after.
        # Greedy outputs are byte-identical to the non-speculative
        # engine (acceptance under greedy is deterministic); sampled
        # mode keeps the target distribution (deterministic drafters are
        # the q=delta case of rejection sampling) but draws a different
        # key stream. See docs/serving.md "Speculative decoding".
        if speculate is True:
            # int(True) == 1 would silently degenerate to plain decode
            raise ValueError(
                "speculate takes the VERIFY WIDTH (an int >= 2: the "
                "pending token + up to width-1 drafts per pass), not "
                "True")
        self._spec = 0 if speculate in (None, False) else int(speculate)
        if self._spec == 1:
            self._spec = 0              # T=1 degenerates to plain decode
        if self._spec < 0:
            raise ValueError(f"speculate must be >= 2, got {speculate}")
        if self._spec:
            if self._spec > max_len:
                raise ValueError(
                    f"speculate={self._spec} exceeds max_len={max_len}")
            # (the PR 6 "megakernel is single-token-q" gate is GONE:
            # the verify pass rides the megakernel's tq>1 schedule —
            # see _cb_spec_verify_math_mk; byte-identity pinned in
            # tests/test_megakernel_v2.py)
        self.spec_adaptive = bool(spec_adaptive)
        # decode_block=K > 1: device-resident multi-step decode — ONE
        # compiled dispatch runs a ragged-prefill phase plus K decode
        # steps (on-device sampling, per-slot EOS/budget flags); the
        # host only intervenes at block boundaries. K=1 keeps the
        # original one-program-per-step path. See docs/serving.md
        # "Block-granularity scheduling".
        self.decode_block = max(1, int(decode_block))
        # ragged_kernel: fused-prefill attention backend. None (default)
        # = the Pallas ragged kernel on TPU, the dense gathered path
        # under interpret/CPU (the dense path is what is byte-identical
        # to the per-step engine); True/False force either.
        self.ragged_kernel = ragged_kernel
        # megakernel: decode-layer Pallas megakernel — auto ("layer")
        # on TPU, off under interpret/CPU unless forced. Weights are
        # repacked ONCE here into the streamed layout (views/cheap
        # reshapes for aligned geometries; "multi" additionally stacks
        # them [L, ...] so one invocation streams every layer).
        # megakernel + tp > 1 composes via per-shard SEGMENTS (PR 12):
        # column-parallel q/k/v/gate/up packed per shard, local-head
        # attention, the exact-mode gathers running BETWEEN kernel
        # invocations — see _mk_walk and decode_megakernel seg=.
        self.megakernel = self._resolve_megakernel(megakernel)
        self._mk_head = False           # whole-step mode: final norm +
        self._mk_vl = 0                 # lm_head + argmax in-kernel
        self.mk_tile_plan = None        # set by _build_mk_pack
        # what a layer call of the paged decode attention kernel does at
        # a full batch: one grid step a slot, a loop over the slot's
        # live pages, operands by the pool's type (static;
        # health()["paged_decode"])
        self.paged_decode = None
        if not self.megakernel and not all(g.row_width
                                           for g in self.groups):
            self.paged_decode = {
                "grid_steps_per_layer": self.max_batch, "pages": "live",
                "mm_operand_dtype": jnp.dtype(
                    mxu_operands(self.kv_dtype)[0]).name}
        # what a chunk of a latent layer's attention runs, the full and
        # the window geometry: the Pallas kernel's query block, pages a
        # grid step and VMEM limit, or the XLA key blocks (static;
        # health()["latent_prefill"]); the kernel's plan by page group
        self._latent_plans = latent.prefill_plans(self)
        self.latent_prefill = latent.prefill_facts(self, self._latent_plans)
        self.latent_prefill_live_steps = 0
        if self.megakernel:
            with _span("setup.engine.mk_pack"):
                self._build_mk_pack()
        if self.megakernel == "multi":
            # NATIVE stacked KV pools: "multi" consumes the whole [L,...]
            # stack every step, so store it stacked — the per-scan-step
            # jnp.stack restack PR 6 documented (XLA traffic ~ pool size
            # inside the fused block) is gone; every compiled path
            # handles both forms (list per layer / one stacked array)
            with _span("setup.engine.kv_pool"):
                self.k_pages = jnp.stack(self.k_pages)
                self.v_pages = jnp.stack(self.v_pages)
                if self._tpc is not None:
                    self.k_pages = self._tpc.place_pools(self.k_pages)
                    self.v_pages = self._tpc.place_pools(self.v_pages)
        if slot_buckets is None:
            slot_buckets = []
            w = 1
            while w < max_batch:
                slot_buckets.append(w)
                w *= 2
        self._slot_buckets = tuple(sorted(
            {min(int(w), max_batch) for w in slot_buckets} | {max_batch}))
        self.sample_k = int(sample_k)
        if not 1 <= self.sample_k <= 128:
            raise ValueError(
                f"sample_k must be in [1, 128] (the in-kernel top-K "
                f"fold rides the megakernel's [R, 128] select scratch), "
                f"got {sample_k}")
        self._prefix = PrefixCache(page_size) if prefix_cache else None
        self._drafter = (resolve_drafter(drafter, self._prefix)
                         if self._spec else None)
        # multi-tenant admission policy: tenants={name: {"share": s,
        # "priority": p}}. Admission orders the queue by (priority desc,
        # fair-share virtual time asc, arrival); a strictly-higher-
        # priority candidate that cannot fit PREEMPTS the lowest-
        # priority running request (its work re-queues, not fails).
        # Virtual time charges 1/share per emitted token, so
        # speculation's variable token yield is charged exactly like
        # plain decode and cannot starve low-share tenants.
        self._tenant_cfg = {}
        for name, cfg in (tenants or {}).items():
            share = float(cfg.get("share", 1.0))
            if share <= 0:
                raise ValueError(
                    f"tenant {name!r} share must be > 0, got {share}")
            self._tenant_cfg[name] = {
                "share": share, "priority": int(cfg.get("priority", 0))}
        self._tenant_vt = {}            # tenant -> tokens / share
        #   (first sight BASELINES at the minimum recorded vt — a
        #    late-joining tenant competes from the current service
        #    floor instead of monopolizing admission while it "catches
        #    up" from zero against long-running incumbents)
        self._tenant_tokens = collections.Counter()

        self.queue_limit = (None if queue_limit is None
                            else int(queue_limit))
        self.default_deadline_ms = default_deadline_ms
        self._queue = collections.deque()
        self._requests = {}
        self._slots = [None] * max_batch
        self._tables_np = np.zeros((max_batch, self.max_pages_per_seq),
                                   np.int32)
        self._lens_np = np.zeros(max_batch, np.int32)
        self._tok_np = np.zeros(max_batch, np.int64)
        self._next_uid = 0
        self._prefer_decode = False
        self._cb_step_fns = {}
        self._cb_prefill_fn = None
        self._cb_fused_fns = {}
        self._program_built = False     # a step program was built and
        #                                 has not been called yet
        self._pf_dummies = {}
        self._pending = None            # the ONE dispatched program whose
        #                                 tokens are not booked yet: a
        #                                 _FusedBlock (decode_block > 1)
        #                                 or a _Dispatched (the per-step
        #                                 path); _sync_pending resolves it
        # the selected token of every slot, ON THE DEVICE: each greedy
        # step program and each prompt's last chunk writes the rows it
        # ran, the next decode step reads its input tokens from it. The
        # host writes into it only where the host is the source of a
        # slot's pending token (`_tok_on_dev` False: an imported or
        # restored request, a token the host selected)
        self._tok_dev = jnp.zeros((max_batch,), jnp.int32)
        self._tok_on_dev = np.zeros(max_batch, bool)
        self._copy_fn = None

        # observability (tests assert on these)
        self.steps = 0
        self.decode_steps = 0
        self.prefill_steps = 0
        self.admissions = 0
        self.slot_reuses = 0
        self.cow_copies = 0
        self.failure_count = 0
        self.cancellations = 0
        self.deadline_expiries = 0
        self.fused_blocks = 0
        self.chained_blocks = 0         # blocks dispatched BEFORE the
        #                                 previous block's readback
        # the per-step path's run-ahead (docs/serving.md "Dispatch
        # ahead"): programs dispatched while another was unresolved,
        # programs resolved BEFORE the next dispatch by reason, rows
        # that ran one step past an EOS and were discarded
        self.ahead_dispatched = 0
        self.ahead_resolved_first = collections.Counter()
        self.ahead_overrun_rows = 0
        self.preemptions = 0            # decode-slot preemptions (work
        #                                 re-queued, not failed)
        self.handoffs_out = 0           # KV-page exports committed away
        self.handoffs_in = 0            # KV-page imports seated here
        self._handoffs_out = {}         # uid -> pending export token
        # KV tiering (inference/tiering.py): kv_tier="host"/"disk" (or a
        # KVTierStore) enables demote_request/restore_request — a cold
        # request's device pages move to host RAM (then disk) in the
        # CRC-stamped page-export format and restore on demand at a
        # block boundary, byte-identical. oversubscribe (default: on
        # whenever a tier is configured) lets ADMISSION demote the
        # longest-resident lowest-priority running request when the
        # queue head cannot fit, so live requests' page needs may
        # exceed the device pool (docs/serving.md "Prefix-aware routing
        # & KV tiering"). Demoted requests restore with priority over
        # fresh admissions (no starvation). kv.demote / kv.restore are
        # the fault points; a corrupt tier entry or injected restore
        # fault retires exactly ONE request (stage "restore").
        from .tiering import resolve_tier
        self._tier = resolve_tier(kv_tier, tier_dir, tier_host_cap_mb)
        self.oversubscribe = (self._tier is not None
                              if oversubscribe is None
                              else bool(oversubscribe))
        # tier_idle_steps=N: DEMOTE-ON-IDLE (ROADMAP item 2 follow-up)
        # — a seated decode request that sits through N consecutive
        # engine steps WITHOUT emitting a token (it was blocked behind
        # other work, e.g. the K=1 prefill-priority steps of a long
        # prompt) demotes its pages to the tier even without admission
        # pressure, provided queued work exists to use the freed
        # capacity (demoting into an empty queue would just thrash the
        # restore sweep). Restore is byte-identical (the PR 11
        # contract, unit-pinned). In fused-block mode (decode_block>1
        # or speculate) every decode slot advances every block, so the
        # counter never accumulates — the knob is a K=1 scheduling
        # policy by construction.
        self.tier_idle_steps = (None if tier_idle_steps is None
                                else int(tier_idle_steps))
        if self.tier_idle_steps is not None:
            if self.tier_idle_steps < 1:
                raise ValueError(
                    f"tier_idle_steps must be >= 1, got {tier_idle_steps}")
            if self._tier is None:
                raise ValueError(
                    "tier_idle_steps needs a KV tier (kv_tier=) to "
                    "demote into")
        self.idle_demotions = 0         # demote-on-idle firings
        self._demoted = collections.OrderedDict()   # uid -> Request
        self.demotions = 0
        self.restores = 0
        self.restore_failures = 0       # restore-stage retirements
        self.demote_errors = 0          # failed demote attempts (the
        #                                 victim kept serving)
        self.pages_demoted = 0          # device pages currently parked
        #                                 in the tier (the oversub gauge)
        # fleet prefix index (inference/prefix_index.py): attached by
        # the router (attach_prefix_index); publish/retract are
        # ADVISORY — wrapped so an index failure can never fail a
        # request (the index.publish fault point proves it in chaos)
        self._prefix_index = None
        self._replica = None
        self.index_publishes = 0
        self.index_publish_errors = 0
        self.prefix_exports = 0         # prefix-page chains shipped out
        self.prefix_imports = 0         # chains seated from a ship
        self.spec_passes = 0            # verify passes that ran
        self.spec_emitted = 0           # decode tokens emitted by them
        self.spec_drafted_total = 0     # drafts offered
        self.spec_accepted_total = 0    # drafts accepted
        self.draft_errors = 0           # real (non-injected) drafter
        #                                 exceptions, degraded to dlen=0
        self.sampled_requests = 0       # admitted with do_sample=True
        self._spec_sampled_offered = 0  # drafts offered to SAMPLED
        self._spec_sampled_accepted = 0  # verify passes / accepted
        self._trivial_gram = None       # lazily-built always-allow
        #                                 automaton (grammar id 0 in
        #                                 packed proc batches)
        self._slot_used = [False] * max_batch
        # routing counters of a description with routed experts:
        # accumulated ON THE DEVICE inside the decode step program (rows
        # each held expert received, experts touched, steps), folded
        # into these host totals only when health() reads them — no
        # sync a step. int32 on the device: health() zeroes them as it
        # reads, so they overflow only if nobody asks for some 2^21
        # steps on end.
        # the same for a description with an indexer: keys visible to and
        # keys attended by its layers' decode queries, as (high, low 24
        # bits) int32 pairs: a step adds up to slots x max_len a layer
        self._counted = self.desc.has_experts or self.desc.has_indexer
        self._route_dev = self._route_zeros() if self._counted else None
        self._route_totals = None
        # multi-LoRA adapter serving (inference/adapters.py): adapters=
        # {"rank": R, "max_adapters": N, "pool_pages": P, "page_elems":
        # E} (True = defaults) builds a page-granular ADAPTER POOL
        # beside the KV pool — LoRA A/B factor stacks on device, the
        # KV allocator's refcount/LRU/backpressure discipline for the
        # pages. add_request(adapter=name) threads a pool-slot id into
        # the slot state; adapter-carrying dispatches run ADAPTER-AWARE
        # compiled variants (the no-adapter programs are untouched, so
        # an adapter-free engine — or an adapter-free batch — is
        # byte-identical to pre-adapter serving), applying the grouped
        # low-rank delta after the shared q/k/v/gate/up/down
        # projections. Adapter requests skip the prefix cache (their
        # KV bytes are adapter-specific; content addressing is by
        # tokens alone) and, under megakernel=, fall back per-dispatch
        # to the op-chain delta (counted in adapter_mk_fallbacks;
        # docs/serving.md "Multi-LoRA & the model zoo").
        self._apool = None
        self._adapter_registry = {}     # name -> path (lazy hot-load)
        self.adapter_requests = collections.Counter()   # name -> reqs
        self.adapter_tokens = collections.Counter()     # name -> tokens
        self.adapter_mk_fallbacks = 0   # adapter dispatches that left
        #                                 the megakernel for the op chain
        self._cb_step_ad_fns = {}
        self._cb_prefill_ad_fn = None
        if adapters is not None and adapters is not False:
            from .adapters import AdapterPool, engine_target_dims
            if self.tp > 1 and self.tp_mode != "exact":
                raise ValueError(
                    "adapters with tp > 1 require tp_mode='exact': the "
                    "down-projection delta needs the full activation "
                    "row, which psum mode never materializes")
            acfg = {} if adapters is True else dict(adapters)
            self._apool = AdapterPool(
                self.cfg.num_hidden_layers,
                engine_target_dims(self.cfg),
                rank=acfg.pop("rank", 4), **acfg)
            self._apool.place(self._tpc)

    # -- public ------------------------------------------------------------
    @staticmethod
    def _block_mode(requests):
        """Compiled-program family a dispatch needs for these
        participants: 'proc' when any request needs the materialized
        logit-processor chain, 'sampled' when any samples, else
        'greedy' (the untouched all-greedy program — no PRNG, no
        extra inputs)."""
        mode = "greedy"
        for r in requests:
            sp = r.sampling
            if sp.needs_processors:
                return "proc"
            if sp.do_sample:
                mode = "sampled"
        return mode

    def _row_params(self, rows, mode):
        """Per-row sampling inputs for a 'sampled'/'proc' dispatch,
        assembled FRESH from the participants each time (no persistent
        per-slot state to seat/release): rows is a list of
        Request-or-None, one entry per batch row; empty rows keep
        neutral defaults and never emit. Returns the numpy arrays in
        the exact order the compiled programs unpack them."""
        n = len(rows)
        seeds = np.zeros(n, np.uint32)
        dos = np.zeros(n, bool)
        temp = np.ones(n, np.float32)
        tkk = np.zeros(n, np.int32)
        tpp = np.ones(n, np.float32)
        minp = np.zeros(n, np.float32)
        for i, r in enumerate(rows):
            if r is None:
                continue
            sp = r.sampling
            seeds[i] = sp.seed
            dos[i] = sp.do_sample
            temp[i] = sp.temperature
            tkk[i] = sp.top_k
            tpp[i] = sp.top_p
            minp[i] = sp.min_p
        ex = [seeds, dos, temp, tkk, tpp, minp]
        if mode == "proc":
            V = self.cfg.vocab_size
            rep = np.ones(n, np.float32)
            pres = np.zeros(n, np.float32)
            frq = np.zeros(n, np.float32)
            counts = np.zeros((n, V), np.int32)
            gid = np.zeros(n, np.int32)
            gstate = np.zeros(n, np.int32)
            if self._trivial_gram is None or \
                    self._trivial_gram.vocab != V:
                self._trivial_gram = TokenMaskAutomaton.trivial(V)
            grams = [self._trivial_gram]   # gid 0 = no grammar
            for i, r in enumerate(rows):
                if r is None:
                    continue
                sp = r.sampling
                rep[i] = sp.repetition_penalty
                pres[i] = sp.presence_penalty
                frq[i] = sp.frequency_penalty
                for t, c in r.counts.items():
                    counts[i, t] = c
                if sp.grammar is not None:
                    gid[i] = len(grams)
                    grams.append(sp.grammar)
                    gstate[i] = r.gstate
            S = max(g.n_states for g in grams)
            gtab = np.zeros((len(grams), S, V), np.int32)
            gmask = np.zeros((len(grams), S, V), bool)
            gmask[0] = True                # trivial: everything allowed
            for i, g in enumerate(grams):
                gtab[i, :g.n_states] = np.asarray(g.table)
                gmask[i, :g.n_states] = np.asarray(g.mask)
            ex += [rep, pres, frq, counts, gid, gstate, gtab, gmask]
        return tuple(ex)

    def _block_extras(self, blk):
        """Device-resident sampling inputs for a fused block (order
        matches _build_cb_fused's unpack; () for greedy blocks)."""
        if blk.mode == "greedy":
            return ()
        rows = [None] * blk.w
        for r, _end in blk.pf_items:
            rows[r.slot] = r
        for r in blk.dec_items:
            rows[r.slot] = r
        return tuple(jnp.asarray(a)
                     for a in self._row_params(rows, blk.mode))

    def _select_tokens(self, rows, positions, mode, logits=None,
                       topv=None, topi=None):
        """Host-side token selection for the per-step (decode_block=1)
        and chunked-prefill paths: the SAME select_from_topk math the
        fused scan compiles, applied eagerly to one dispatch's rows —
        so per-step and fused engines emit bit-identical streams.
        positions[i] is the absolute sequence position row i's new
        token will occupy (= its PRNG counter). Pass either the
        materialized logits or the decode math's folded (topv, topi)
        candidate rows."""
        if mode == "greedy":
            return np.asarray(jnp.argmax(logits, axis=-1))
        ex = self._row_params(rows, mode)
        seeds, dos, temp, tkk, tpp, minp = ex[:6]
        if logits is not None:
            lg = jnp.asarray(logits)
            if mode == "proc":
                rep, pres, frq, counts, gid, gstate, gmask = (
                    ex[6], ex[7], ex[8], ex[9], ex[10], ex[11], ex[13])
                lg = apply_penalties(
                    lg.astype(jnp.float32), jnp.asarray(counts),
                    jnp.asarray(rep), jnp.asarray(pres),
                    jnp.asarray(frq))
                lg = jnp.where(jnp.asarray(gmask)[gid, gstate], lg, NEG)
            topv, topi = jax.lax.top_k(lg, self.sample_k)
            topv = topv.astype(jnp.float32)
            topi = topi.astype(jnp.int32)
        keys = fold_keys(jnp.asarray(seeds),
                         jnp.asarray(np.asarray(positions, np.int32)))
        toks = select_from_topk(topv, topi, keys, jnp.asarray(dos),
                                jnp.asarray(temp), jnp.asarray(tkk),
                                jnp.asarray(tpp), jnp.asarray(minp))
        return np.asarray(toks)

    def add_request(self, ids, max_new_tokens=32, eos_token_id=None,
                    deadline_ms=None, ttl_steps=None, tenant=None,
                    priority=None, adapter=None, sampling=None):
        """Queue one prompt (1-D int sequence). Returns a request uid.

        adapter: name of a loaded LoRA adapter (inference/adapters.py)
          this request decodes under — the grouped low-rank delta rides
          every prefill chunk, decode step and verify pass the request
          touches, so a mixed batch is byte-identical to per-adapter
          dedicated engines. A name not yet in the pool hot-loads from
          the registry (register_adapter/load_adapter); an unknown name
          raises UnknownAdapterError typed. The adapter is refcounted
          for the request's whole life (LRU eviction never pulls it out
          from under live traffic).

        deadline_ms: wall-clock budget from NOW; a request still
          unfinished when it expires retires with a DeadlineExceededError
          record (queued requests are shed without ever running).
        ttl_steps: the same contract counted in ENGINE STEPS instead of
          wall time — deterministic, the form chaos tests use.
        sampling: a SamplingParams (inference/sampling.py) — or a
          to_spec() dict — giving THIS request's sampling behavior:
          do_sample/temperature/top_k/top_p/min_p under a per-request
          `(seed, position)` key stream (reproducible regardless of
          batch composition, decode_block, preemption, failover or tp),
          repetition/presence/frequency penalties, stop sequences, and
          grammar-constrained decoding (TokenMaskAutomaton). None is
          greedy. Mixed greedy/sampled batches are first-class.
          Penalties/grammar require the materialized processor path and
          cannot compose with speculate= (typed ValueError here, not a
          silent fallback).
        tenant: admission-policy tenant name (fair-share virtual time is
          tracked per tenant; unregistered tenants get share 1.0).
        priority: admission priority (higher first, strict); defaults to
          the tenant's registered priority, else 0. A queued request of
          strictly higher priority may PREEMPT a running lower-priority
          one when the engine is full — the victim re-queues with its
          generated tokens folded into its prompt, nothing is lost.
        Raises EngineBusyError (typed backpressure, nothing enqueued)
        when the admission queue is at queue_limit.
        """
        ids = np.asarray(ids, np.int64).ravel()
        if ids.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if ids.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt length {ids.size} + max_new_tokens "
                f"{max_new_tokens} = {ids.size + max_new_tokens} exceeds "
                f"this engine's max_len={self.max_len}")
        if self.queue_limit is not None and \
                len(self._queue) >= self.queue_limit:
            raise EngineBusyError(
                f"admission queue full: {len(self._queue)} queued "
                f"requests at queue_limit={self.queue_limit} "
                f"({sum(1 for s in self._slots if s)} running); retry "
                "later or raise queue_limit")
        if adapter is not None:
            self._resolve_adapter(adapter)   # raises typed; may hot-load
        sp = (SamplingParams.from_spec(sampling) if sampling is not None
              else GREEDY)
        if sp.do_sample and sp.top_k > self.sample_k:
            raise ValueError(
                f"sampling.top_k={sp.top_k} exceeds this engine's "
                f"sample_k={self.sample_k} — the sampled path selects "
                "from the top-sample_k survivor set (raise sample_k= "
                "at engine build)")
        if self._spec and sp.needs_processors:
            raise ValueError(
                "logit processors (penalties / grammar) do not compose "
                "with speculate= — the verify pass scores positions "
                "whose processor state depends on in-pass emissions; "
                "run this request on a non-speculative engine")
        if sp.grammar is not None and \
                sp.grammar.vocab != self.cfg.vocab_size:
            raise ValueError(
                f"grammar automaton vocab {sp.grammar.vocab} != model "
                f"vocab {self.cfg.vocab_size}")
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        tenant = tenant or "default"
        if priority is None:
            priority = self._tenant_cfg.get(tenant, {}).get("priority", 0)
        r = Request(self._next_uid, ids, max_new_tokens, eos_token_id,
                    deadline=deadline,
                    ttl_steps=None if ttl_steps is None else int(ttl_steps),
                    born_step=self.steps, tenant=tenant, priority=priority,
                    draft_k=max(1, self._spec - 1) if self._spec else 0,
                    sampling=sp)
        if sp.do_sample:
            self.sampled_requests += 1
        if adapter is not None:
            self._apool.acquire(adapter)
            r.adapter = adapter
            self.adapter_requests[adapter] += 1
        self._next_uid += 1
        self._requests[r.uid] = r
        self._queue.append(r)
        if self._tel is not None:
            self._tel.req_start(self._tel_src, r.uid, prompt_len=r.t0,
                                max_new=r.max_new_tokens)
        return r.uid

    def cancel(self, uid):
        """Cancel a request. Queued: shed before it ever runs. In-flight:
        retired now, slot/pages/prefix-refs reclaimed. Returns True if
        this call cancelled it, False if it had already finished (or
        failed). Unknown uids raise UnknownRequestError."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        self._sync_pending()    # it may just have finished on the device
        if r.state in (DONE, FAILED, CANCELLED, MIGRATED):
            return False
        if r.state == QUEUED:
            self._queue.remove(r)
        self._fail_request(
            r, "cancel", SchedulerError(f"request {uid} cancelled"),
            state=CANCELLED)
        self.cancellations += 1
        return True

    def step(self):
        """One engine iteration (see _step_impl for the scheduling
        model). With telemetry attached, the whole iteration's wall
        time lands in the `block_ms` histogram — this wrapper IS the
        block-boundary host point, so the measurement costs two
        monotonic reads and nothing on the telemetry=None fast path
        (a single branch)."""
        with _span("cb.step", step=self.steps):
            if self._tel is None:
                return self._step_impl()
            t0 = time.monotonic()
            moved = self._step_impl()
            if moved:
                self._tel.block((time.monotonic() - t0) * 1e3)
            return moved

    def _step_impl(self):
        """One engine iteration. Returns False when there is nothing to
        do.

        decode_block == 1 (default): shed expired deadlines, admit what
        fits, then dispatch ONE compiled program — a prefill chunk or a
        decode step (alternating when both have work, so long prompts
        don't stall live decodes) — and THEN fetch and book (`_resolve`)
        the program the previous step() dispatched: the device runs
        this step's program while the host reads the last one's tokens,
        admits, prepares and dispatches again. Tokens of a program
        become visible at the next step(), at drain() or at any call
        that resolves (`_sync_pending`); step() returns True while a
        program is unresolved. The program to dispatch is chosen from
        what the host knows without the tokens in flight (`_runnable`).
        When its participants need the host's knowledge of those tokens
        (`_resolve_first`: processors, sampling, a stop sequence, an
        adapter, a deadline, an armed fault point, a KV tier) the step
        resolves first and dispatches after, and resolves its own
        program before it returns: the order this path always had, bit
        for bit. docs/serving.md "Dispatch ahead".

        decode_block == K > 1: one BLOCK — a single compiled dispatch
        covering a ragged prefill phase (every prefilling slot advances
        one chunk) plus K device-resident decode steps with on-device
        sampling and per-slot EOS/budget retirement flags; the host
        intervenes only here, at the block boundary. In a pure-decode
        steady state the next block is dispatched BEFORE this block's
        tokens are fetched (double-buffered readback), so host
        bookkeeping overlaps device compute.

        Per-request isolation: a fault raised at a request boundary
        (its admission, its prefill chunk, its slice of the decode
        batch/block) retires THAT request with a RequestFailure record
        and the step carries on. In fused mode faults are checked at
        host sync points, i.e. once per block per request.

        speculate=T routes through the fused path at EVERY decode_block
        (a decode_block=1 spec block is one verify pass): the verify
        scan, its on-device accept/reject carries, and the host draft
        boundary all live there."""
        if self.decode_block > 1 or self._spec:
            return self._fused_step()
        while True:
            with _span("cb.admit"):
                self._expire_deadlines()
                self._restore_sweep()
                self._idle_demote_sweep()
                self._admit()
            prefills, decodes = self._runnable()
            if not prefills and not decodes:
                if self._pending is None:
                    return self._idle_or_raise()
                self._sync_pending()    # nothing to put behind it
                return True
            prefill = bool(prefills) and (
                not decodes or not self._prefer_decode)
            why = self._resolve_first([prefills[0]] if prefill else decodes)
            # a program that waits for the host's knowledge of the tokens
            # in flight: book them, then choose again (a row may just
            # have ended, a seat may just have come free)
            if why is None or not self._resolved_for(why):
                break
        self.steps += 1
        try:
            cur = None
            if prefill:
                r = prefills[0]
                try:
                    fault_point("cb.prefill", detail=f"uid={r.uid}")
                    cur = self._prefill_step(r)
                except InjectedFault as e:
                    self._fail_request(r, "prefill", e)
                self.prefill_steps += 1
                self._prefer_decode = True
                for rd in decodes:
                    # a prefill-priority step is a WAITED step for every
                    # seated decode request (the demote-on-idle clock;
                    # _push_token resets it on the next emitted token)
                    if rd.state == DECODE:
                        rd.idle_steps += 1
            else:
                live = []
                for r in decodes:
                    try:
                        fault_point("cb.decode", detail=f"uid={r.uid}")
                        live.append(r)
                    except InjectedFault as e:
                        self._fail_request(r, "decode", e)
                if live:
                    cur = self._decode_step(live)
                self.decode_steps += 1
                self._prefer_decode = False
            self._count_pages()
            # the device has `cur` queued behind the program it runs:
            # NOW fetch and book that one
            prev, self._pending = self._pending, cur
            if prev is not None:
                self.ahead_dispatched += cur is not None
                self._resolve(prev)
            if why is not None:
                self._resolved_for(why)
        except Exception:
            self._abort_in_flight()
            raise
        return True

    def _runnable(self):
        """(prefills, decodes) in slot order, as the NEXT program may
        take them: the host's state as of the last resolve plus what it
        knows of the program in flight without its tokens. A prompt
        whose last chunk is dispatched decodes next, whatever its first
        token is; a row whose token in flight is its budget's last is
        left out."""
        ahead = self._pending.uids if self._pending is not None else ()
        prefills, decodes = [], []
        for r in self._slots:
            if r is None:
                continue
            if r.state == PREFILL and r.filled < r.t0:
                prefills.append(r)
            elif r.state in (PREFILL, DECODE) and \
                    len(r.out) + (r.uid in ahead) < r.max_new_tokens:
                decodes.append(r)
        return prefills, decodes

    def _resolve_first(self, rows):
        """Why the program over `rows` may not be in flight while the
        next one is chosen and dispatched (its tokens, or when they are
        booked, decide something on the host), or None: then the engine
        dispatches the next program before it fetches this one's
        tokens. Read off the participants, never set."""
        if _faults_armed():
            return "faults"     # fault points fire at host sync points,
            #                     once per request per resolved step
        if self._tier is not None:
            return "tier"       # demotion victims and the idle clocks
            #                     are read off resolved state
        for r in rows:
            sp = r.sampling
            if sp.needs_processors:
                return "proc"   # penalties and grammar state advance on
                #                 the host, token by token
            if sp.do_sample:
                return "sampled"    # the host draws from the folded
                #                     candidates (the named next step)
            if sp.stop:
                return "stop"   # a stop sequence retires on the host
            if r.adapter is not None:
                return "adapter"    # the adapter programs return logits
            if r.deadline is not None or r.ttl_steps is not None:
                return "deadline"   # expiry is promised between two
                #                     resolved steps
        return None

    def drain(self):
        """Run until every queued/in-flight request retires. Returns
        {uid: output} for requests completed by this call (an empty dict
        on an idle engine — never a hang, never a KeyError). Requests
        that retired with an error are NOT in the dict; read them via
        failures()/result()."""
        finished = {}
        before = {u for u, r in self._requests.items() if r.state == DONE}
        while self.step():
            pass
        for uid, r in self._requests.items():
            if r.state == DONE and uid not in before:
                finished[uid] = r.result
        return finished

    def result(self, uid):
        """Output array for a finished request: [prompt + generated],
        trimmed at the request's own EOS (inclusive).

        Typed failures instead of KeyError/None: UnknownRequestError for
        a uid this engine never issued, RequestNotFinishedError while
        still in flight, RequestCancelledError / RequestFailedError
        (carrying the RequestFailure record) for error retirements."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        if r.state in (PREFILL, DECODE):
            self._sync_pending()    # its last token may be in flight
        if r.state == CANCELLED:
            raise RequestCancelledError(r.error)
        if r.state == FAILED:
            raise RequestFailedError(r.error)
        if r.state == MIGRATED:
            raise RequestNotFinishedError(
                f"request {uid} migrated to another engine via KV "
                "handoff — read its result there (the router's ledger "
                "tracks the move)")
        if r.state != DONE:
            raise RequestNotFinishedError(
                f"request {uid} is {r.state}, not done")
        return r.result

    def status(self, uid):
        """State string for a uid: queued/prefill/decode/done/failed/
        cancelled."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        return r.state

    def failures(self):
        """{uid: RequestFailure} for every request retired with an error
        (cancellations included)."""
        return {u: r.error for u, r in self._requests.items()
                if r.error is not None}

    def pending(self):
        """uids still queued or in flight (demoted included — a parked
        request restores and finishes), submission order."""
        return [u for u, r in self._requests.items()
                if r.state in (QUEUED, PREFILL, DECODE, DEMOTED)]

    def __len__(self):
        """Number of requests still queued or in flight."""
        return sum(1 for r in self._requests.values()
                   if r.state in (QUEUED, PREFILL, DECODE, DEMOTED))

    def queue_head_uid(self):
        """The uid an idle-engine EngineFullError is complaining about:
        the admission queue head (next to be picked), else the
        demoted-restore head (a parked request whose fresh-page need
        cannot be met — same capacity contract). None when neither
        exists. Routers use this to attribute stuck-head failures."""
        if self._queue:
            return self._pick_next().uid
        return next(iter(self._demoted)) if self._demoted else None

    def headroom(self):
        """O(1) routing snapshot — the subset of health() a router's
        admission path polls once per request. health() walks the full
        request history (it Counters every request this engine has ever
        seen) and is for monitors; this is for the hot path."""
        return {"queued": len(self._queue),
                "running": sum(1 for s in self._slots if s is not None),
                "slots_total": self.max_batch,
                # sums over the page groups (one group: its own)
                "pages_free": sum(g.allocator.available
                                  for g in self.groups),
                "pages_total": sum(g.n_pages for g in self.groups),
                # oversubscription gauges: device pages parked in the
                # KV tier, and how many requests are parked (a router
                # weighs these against raw pages_free)
                "pages_demoted": self.pages_demoted,
                "demoted": len(self._demoted)}

    def health(self):
        """One serving-health snapshot (cheap; safe to poll): queue and
        slot occupancy, page-pool headroom, prefix-cache state, and the
        lifetime counters a monitor alarms on."""
        states = collections.Counter(
            r.state for r in self._requests.values())
        groups = [{"window": g.window, "layers": len(g.layers),
                   "kind": g.kind,
                   "row_width": g.row_width,
                   "index_width": g.index_width,
                   "kv_heads": g.n_kv_heads, "pages_total": g.n_pages,
                   "pages_free": g.allocator.available,
                   "pages_used": g.used,
                   "used_page_steps": g.used_page_steps,
                   "freed_behind_window": g.freed_behind_window,
                   "kv_tokens_read": g.kv_tokens_read,
                   "kv_pages_walked": g.kv_pages_walked,
                   "prefill_pairs": g.prefill_pairs}
                  for g in self.groups]
        experts = sparse = None
        route = self._route_read() if self._counted else None
        if self.desc.has_experts:
            experts = {"rows": route["rows"].tolist(),
                       "touched": route["touched"].tolist(),
                       "decode_steps": int(route["steps"])}
        if self.desc.has_indexer:
            # over the decode queries of the layers with an indexer:
            # the index scan scores whole table pages, so `scored` holds
            # dead keys too; queries / indexer layers = rows decoded
            visible, attended, scored, queries = (
                int(route[k][0]) * (1 << 24) + int(route[k][1])
                for k in SPARSE_COUNTS)
            sparse = {"keys_visible": visible, "keys_attended": attended,
                      "index_keys_scored": scored,
                      "decode_queries": queries}
        # one timestamped sample of the always-on counters, beside
        # profiler.span_totals() (docs/observability.md): a reader that
        # knows two moments differences the samples nearest them
        counters = {"steps": self.steps,
                    "prefill_steps": self.prefill_steps,
                    "ahead.dispatched": self.ahead_dispatched,
                    "ahead.overrun_rows": self.ahead_overrun_rows,
                    "ahead.resolved_first": sum(
                        self.ahead_resolved_first.values())}
        for why, n in self.ahead_resolved_first.items():
            counters[f"ahead.resolved_first.{why}"] = n
        for i, g in enumerate(groups):
            counters[f"group{i}.used_page_steps"] = g["used_page_steps"]
            counters[f"group{i}.pages_total"] = g["pages_total"]
            counters[f"group{i}.window"] = g["window"] or 0
            counters[f"group{i}.freed_behind_window"] = \
                g["freed_behind_window"]
            counters[f"group{i}.kv_tokens_read"] = g["kv_tokens_read"]
            counters[f"group{i}.kv_pages_walked"] = g["kv_pages_walked"]
            counters[f"group{i}.prefill_pairs"] = g["prefill_pairs"]
        if experts is not None:
            counters["experts.decode_steps"] = experts["decode_steps"]
            counters["experts.rows"] = experts["rows"]
            counters["experts.touched"] = experts["touched"]
        if sparse is not None:
            for k, v in sparse.items():
                counters[f"sparse.{k}"] = v
        if self.latent_prefill is not None:
            counters["latent.prefill_live_steps"] = \
                self.latent_prefill_live_steps
        record_counters("engine", counters)
        return {
            "queued": len(self._queue),
            "running": sum(1 for s in self._slots if s is not None),
            "slots_total": self.max_batch,
            "queue_limit": self.queue_limit,
            # sums over the page groups; per group under "page_groups"
            "pages_free": sum(g["pages_free"] for g in groups),
            "pages_total": sum(g["pages_total"] for g in groups),
            "page_groups": groups,
            "experts": experts,
            "sparse": sparse,
            "prefix_pages": 0 if self._prefix is None else len(self._prefix),
            "prefix_hits": 0 if self._prefix is None else self._prefix.hits,
            "done": states[DONE],
            "failed": states[FAILED],
            "cancelled": states[CANCELLED],
            "steps": self.steps,
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            "admissions": self.admissions,
            "failures": self.failure_count,
            "deadline_expiries": self.deadline_expiries,
            "cow_copies": self.cow_copies,
            "decode_block": self.decode_block,
            "fused_blocks": self.fused_blocks,
            "chained_blocks": self.chained_blocks,
            # the per-step path's run-ahead (docs/serving.md "Dispatch
            # ahead"): programs dispatched while another was unresolved,
            # programs resolved before the next dispatch by reason, rows
            # that ran one step past an EOS and were discarded. As
            # everything here: the state as of the last resolve
            "ahead": {"dispatched": self.ahead_dispatched,
                      "resolved_first": dict(self.ahead_resolved_first),
                      "overrun_rows": self.ahead_overrun_rows},
            # active decode-kernel mode: "off" = per-op XLA chain,
            # "layer"/"multi" = the Pallas decode megakernel;
            # whole_step = the "multi" head fold (final norm + lm_head
            # + greedy argmax inside the same invocation)
            "megakernel": self.megakernel if self.megakernel else "off",
            "megakernel_whole_step": self._mk_head,
            # MXU operand type of every weight-matmul tile, decided at
            # build from the activation and weight dtypes
            # (quantized_matmul.mm_operand_dtype): static, a fact
            "mm_operand_dtype": self.mm_operand_dtype,
            # the megakernel's tile plan (decode_megakernel.mm_tile_plan):
            # per projection [bk, bn] and the grid steps of one layer
            # call at a full batch; None on the op-chain path. Static
            "mk_tile_plan": self.mk_tile_plan,
            # the op chain's twin of it: what the paged decode attention
            # kernel (ops/pallas/paged_attention.py) does a layer call at
            # a full batch; None where no layer calls it (the
            # megakernel, a description whose every layer keeps one row
            # a token). Static
            "paged_decode": self.paged_decode,
            # what a latent layer's prefill chunk runs, by geometry: the
            # kernel `paged_latent_chunk_attention` (its query block,
            # pages a grid step, VMEM limit) or the XLA key blocks; None
            # without a latent layer. Static
            "latent_prefill": self.latent_prefill,
            # tensor parallelism (inference/tp.py): shard count, tail
            # mode, and whether the per-token reduce rides int8
            "tp": self.tp,
            "tp_mode": self.tp_mode,
            "tp_compress": self.tp_compress,
            # speculative decoding: verify width, drafter, and the
            # accept telemetry the adaptive-K policy runs on
            "speculate": self._spec,
            "drafter": (self._drafter.name if self._drafter is not None
                        else None),
            "spec_passes": self.spec_passes,
            "spec_emitted": self.spec_emitted,
            "spec_accept_rate": (
                self.spec_accepted_total / self.spec_drafted_total
                if self.spec_drafted_total else 0.0),
            "spec_tokens_per_pass": (
                self.spec_emitted / self.spec_passes
                if self.spec_passes else 0.0),
            "draft_errors": self.draft_errors,
            # on-device sampling: per-request sampled admissions, the
            # candidate-fold width, and sampled speculation's own
            # acceptance rate (its ceiling is set by temperature, unlike
            # the greedy rate)
            "sampled_requests": self.sampled_requests,
            "sample_k": self.sample_k,
            "spec_sampled_accept_rate": (
                self._spec_sampled_accepted / self._spec_sampled_offered
                if self._spec_sampled_offered else 0.0),
            # disaggregated prefill/decode: KV-page handoffs through
            # this engine (docs/serving.md)
            "handoffs_out": self.handoffs_out,
            "handoffs_in": self.handoffs_in,
            # KV tiering (docs/serving.md "Prefix-aware routing & KV
            # tiering"): demote/restore traffic, the oversubscription
            # gauge, and the tier store's own accounting
            "kv_tier": self._tier.kind if self._tier is not None else None,
            "demoted": len(self._demoted),
            "pages_demoted": self.pages_demoted,
            "demotions": self.demotions,
            "restores": self.restores,
            "restore_failures": self.restore_failures,
            "demote_errors": self.demote_errors,
            "tier": self._tier.stats() if self._tier is not None else None,
            # fleet prefix index: publish traffic + prefix-page ships
            "index_publishes": self.index_publishes,
            "index_publish_errors": self.index_publish_errors,
            "prefix_exports": self.prefix_exports,
            "prefix_imports": self.prefix_imports,
            # multi-LoRA adapter serving (inference/adapters.py): pool
            # occupancy + per-adapter request/token counters (None =
            # engine built without an adapter pool)
            "adapters": (dict(self._apool.stats(),
                              mk_fallbacks=self.adapter_mk_fallbacks,
                              requests=dict(self.adapter_requests),
                              tokens=dict(self.adapter_tokens))
                         if self._apool is not None else None),
            # multi-tenant admission: preemptions + per-tenant service
            "preemptions": self.preemptions,
            "tenants": {
                t: {"tokens": self._tenant_tokens[t],
                    "vt": round(self._tenant_vt.get(t, 0.0), 3),
                    "share": self._tenant_cfg.get(t, {}).get("share", 1.0),
                    "queued": sum(1 for q in self._queue
                                  if q.tenant == t),
                    "running": sum(1 for s in self._slots
                                   if s is not None and s.tenant == t)}
                for t in sorted(set(self._tenant_tokens)
                                | set(self._tenant_cfg)
                                | {q.tenant for q in self._queue}
                                | {s.tenant for s in self._slots
                                   if s is not None})},
        }

    def generate(self, *args, **kw):
        """Inherited static-batch generate(). With native stacked pools
        (megakernel="multi") the base engine's prefill/step programs
        expect per-layer pool lists, so the stack is unpacked around the
        call (once per generate(), not per step) and restored after —
        unless a mid-flight failure already rebuilt the pools (the CB
        _reset_kv restacks them itself)."""
        self._sync_pending()
        if self.megakernel != "multi":
            return super().generate(*args, **kw)
        L = self.cfg.num_hidden_layers
        self.k_pages = [self.k_pages[i] for i in range(L)]
        self.v_pages = [self.v_pages[i] for i in range(L)]
        try:
            return super().generate(*args, **kw)
        finally:
            if isinstance(self.k_pages, list):
                self.k_pages = jnp.stack(self.k_pages)
                self.v_pages = jnp.stack(self.v_pages)
                if self._tpc is not None:
                    # restacked host-side: re-place so the next sharded
                    # dispatch is zero-copy instead of resharding
                    self.k_pages = self._tpc.place_pools(self.k_pages)
                    self.v_pages = self._tpc.place_pools(self.v_pages)

    def generate_many(self, prompts, max_new_tokens=32, eos_token_id=None):
        """Submit a list of (ragged) prompts and drain. Returns a list of
        1-D arrays in submission order. Greedy outputs are byte-identical
        to one-at-a-time LLMEngine.generate() calls."""
        if not isinstance(max_new_tokens, (list, tuple)):
            max_new_tokens = [max_new_tokens] * len(prompts)
        if len(max_new_tokens) != len(prompts):
            raise ValueError(
                f"max_new_tokens list has {len(max_new_tokens)} entries "
                f"for {len(prompts)} prompts")
        uids = [self.add_request(p, n, eos_token_id)
                for p, n in zip(prompts, max_new_tokens)]
        self.drain()
        return [self.result(u) for u in uids]

    # -- admission ---------------------------------------------------------
    def _pages_needed(self, t0, max_new_tokens):
        # cache high-water: positions 0..t0+mnt-2 written, attention at
        # the last step reads lens+1 = t0+mnt-1 positions
        return -(-max(t0, t0 + max_new_tokens - 1) // self.page_size)

    def _vt(self, tenant):
        """Fair-share virtual time for a tenant; a tenant first seen
        NOW starts at the minimum recorded vt (stride-scheduling entry
        rule) so newcomers compete from the current service floor
        rather than winning every slot until they out-consume
        long-running incumbents."""
        vt = self._tenant_vt.get(tenant)
        if vt is None:
            vt = min(self._tenant_vt.values(), default=0.0)
            self._tenant_vt[tenant] = vt
        return vt

    def _pick_next(self):
        """Admission-policy queue head: priority (desc, strict), then
        fair-share virtual time (asc — the least-served tenant per
        share), then arrival order. FIFO degenerates back out when no
        tenants/priorities are configured (all keys tie)."""
        return min(self._queue,
                   key=lambda r: (-r.priority, self._vt(r.tenant),
                                  r.uid))

    def _preemption_victim(self, cand):
        """A running request the candidate may evict: strictly LOWER
        priority only (strictness makes preemption cycles impossible —
        the victim re-queues at its own priority and can never preempt
        back), and only when evicting lower-priority work could
        actually seat the candidate (FEASIBILITY: its page need — plus
        the worst-case CoW reserve — must fit in free pages + the
        victims' EXCLUSIVELY-held pages; a refcount-shared page —
        prefix-cache or co-held by another request — does not return
        to the free list when one holder releases it, so it is not
        counted, conservatively). Without the check, one oversized
        high-priority request would cascade through every victim,
        destroy all in-flight progress, and still fail. Among victims,
        the most-served tenant's newest request loses the least
        completed work."""
        running = [s for s in self._slots if s is not None]
        lower = [s for s in running if s.priority < cand.priority]
        if not lower:
            return None
        need = self._pages_needed(cand.t0, cand.max_new_tokens) + 1
        reclaimable = self.allocator.available + sum(
            sum(1 for p in s.pages if self.allocator.refcount(p) == 1)
            + (1 if s.cow_reserve is not None else 0)
            for s in lower)
        if need > reclaimable:
            return None
        return min(lower,
                   key=lambda r: (r.priority, -self._vt(r.tenant),
                                  -r.uid))

    def _release_slot(self, r):
        """Reclaim a running request's slot, pages, and CoW reserve —
        the ONE slot-release sequence shared by retirement, failure,
        and preemption (shared pages drop only this request's
        reference; cache/other holders keep theirs)."""
        if r.slot is not None:
            self._slots[r.slot] = None
            r.slot = None
        if r.pages:
            self.allocator.free(r.pages)
            r.pages = []
        for gi, held in r.more_pages.items():
            if held:
                self.groups[gi].allocator.free(list(held.values()))
        r.more_pages = {}
        if r.cow_reserve is not None:
            self.allocator.free([r.cow_reserve])
            r.cow_reserve = None
        r.shared_idx = set()

    def _preempt(self, r):
        """Decode-slot preemption (the PR 2 retirement machinery minus
        the failure record): reclaim the victim's slot/pages/CoW
        reserve, fold its generated tokens into its prompt, and re-queue
        it — on re-admission it re-prefills the folded context (usually
        through its own published prefix-cache pages) and continues;
        greedy continuations are byte-identical to an uninterrupted
        run. `result()` still returns [original prompt + all generated
        tokens]."""
        if self._tel is not None:
            self._tel.req_event(self._tel_src, r.uid, "preempt",
                                folded=len(r.out))
        self._release_slot(r)
        if r.out:
            r.ids = np.concatenate([r.ids, np.asarray(r.out, np.int64)])
            r.t0 = r.ids.size
            r.max_new_tokens -= len(r.out)
            r.out = []
        r.tok = None
        r.filled = r.resume = 0
        r.state = QUEUED
        self._queue.append(r)
        self.preemptions += 1

    def _price_admission(self, r):
        """The ONE page-pricing rule for seating `r` through the prefix
        cache: returns (shared, resume, need, cow, fresh) where `fresh`
        is the pages a seat actually claims — raw need minus the cached
        chain, plus the CoW reserve when the divergence point falls
        inside a shared page. Both consumers (_admit and the
        _idle_demote_sweep capacity gate) MUST price through here, or
        the gate demotes victims for heads admission would seat."""
        # adapter requests NEVER share (or publish) prefix-cache pages:
        # the cache is content-addressed by TOKENS alone, but an
        # adapter request's KV bytes carry its adapter's k/v deltas —
        # sharing across adapters (or with base) would silently serve
        # another model's cache (docs/serving.md)
        shared, covered = ([], 0) \
            if self._prefix is None or r.adapter is not None else \
            self._prefix.match(r.ids)
        resume = min(covered, r.t0 - 1)
        # group 0 claims here only if it is a full group; a window
        # group claims as it writes (_group_prepare)
        need = (self._pages_needed(r.t0, r.max_new_tokens)
                if self.groups[0].window is None else 0)
        n_shared = len(shared)
        cow = 1 if n_shared and resume // self.page_size < n_shared \
            else 0
        return shared, resume, need, cow, need - n_shared + cow

    def _admit(self):
        while self._queue:
            r = self._pick_next()
            slot = next((i for i, s in enumerate(self._slots) if s is None),
                        None)
            if slot is None:
                victim = self._preemption_victim(r)
                if victim is not None:
                    if self._resolved_for("preempt"):
                        continue       # the victim is chosen, and its
                        #                tokens folded, from resolved state
                    self._preempt(victim)
                    continue           # re-evaluate with the freed slot
                if self._demote_for(r):
                    continue           # oversubscription freed a slot
                return
            shared, resume, need, cow, fresh = self._price_admission(r)
            n_shared = len(shared)
            if fresh > self.allocator.available and self._prefix:
                self._prefix.evict(fresh - self.allocator.available,
                                   self.allocator, protect=set(shared))
            if fresh > self.allocator.available and shared:
                # sharing can cost MORE than a cold prefill in a tight
                # pool (the CoW reserve, plus matched pages protected
                # from eviction) — fall back to an unshared admission
                # before concluding the request doesn't fit
                shared, resume, cow = [], 0, 0
                n_shared = 0
                fresh = need
                if fresh > self.allocator.available and self._prefix:
                    self._prefix.evict(fresh - self.allocator.available,
                                       self.allocator)
            more_full = [g for g in self.groups[1:] if g.window is None]
            n_log = self._pages_needed(r.t0, r.max_new_tokens)
            if any(n_log > g.allocator.available for g in more_full):
                # a further full group holds the same pages per sequence
                # as group 0 and shares nothing: wait like page pressure
                fresh = max(fresh, self.allocator.available + 1)
            if fresh > self.allocator.available:
                # page pressure: a strictly-higher-priority candidate may
                # preempt a lower-priority running request to free its
                # pages — one victim per attempt, then re-evaluate
                victim = self._preemption_victim(r)
                if victim is not None:
                    if self._resolved_for("preempt"):
                        continue
                    self._preempt(victim)
                    continue
                if self._demote_for(r):
                    continue        # oversubscription freed pages
                return              # wait for retirements (policy order)
            self._queue.remove(r)
            # claim pages under a guard: an allocation failure here
            # (injected page.alloc fault, or a real race) releases every
            # page this request already claimed and retires ONLY this
            # request — the pool stays consistent and admission moves on
            pages = []
            more = {g.index: {} for g in self.groups[1:]}
            try:
                fault_point("cb.admit", detail=f"uid={r.uid}")
                for pg in shared:
                    pages.append(self.allocator.share(pg))
                for _ in range(need - n_shared):
                    pages.append(self.allocator.alloc())
                r.cow_reserve = self.allocator.alloc() if cow else None
                for g in more_full:
                    for idx in range(n_log):
                        more[g.index][idx] = g.allocator.alloc()
            except Exception as e:
                if pages:
                    self.allocator.free(pages)
                for gi, held in more.items():
                    if held:
                        self.groups[gi].allocator.free(list(held.values()))
                self._fail_request(r, "admit", e)
                continue
            if self._prefix is not None:
                if shared:
                    self._prefix.hits += len(shared)
                else:
                    self._prefix.misses += 1
            r.pages = pages
            r.more_pages = more
            r.shared_idx = set(range(n_shared))
            r.pages_shared = n_shared
            r.slot = slot
            r.resume = r.filled = resume
            r.state = PREFILL
            r.seated_step = self.steps
            self._slots[slot] = r
            self._tok_on_dev[slot] = False
            self._tables_np[slot] = 0
            self._tables_np[slot, :len(pages)] = pages
            for gi, held in more.items():
                for idx, pg in held.items():
                    self._tables_np[slot, self.groups[gi].col0 + idx] = pg
            self._lens_np[slot] = 0
            self.admissions += 1
            if self._tel is not None:
                self._tel.req_event(self._tel_src, r.uid, "seat",
                                    slot=slot, shared_pages=n_shared)
            if self._slot_used[slot]:
                self.slot_reuses += 1
            self._slot_used[slot] = True

    def _reclaim_pages(self, n):
        """generate()'s pool-pressure hook: idle prefix-cache pages are
        reclaimable."""
        if self._prefix is None:
            return 0
        return self._prefix.evict(n, self.allocator)

    # -- window groups: claim as written, free behind the window -----------
    def _group_prepare(self, r, lo_pos, hi_pos):
        """Before a program writes positions [lo_pos, hi_pos) of `r`:
        every window group claims the pages those positions fall in.
        The group's pool cannot run out (PageGroup sizes it for every
        seat's resting pages plus one chunk in flight)."""
        p = self.page_size
        for g in self.groups:
            if g.window is None:
                continue
            held = r.more_pages.setdefault(g.index, {})
            for idx in range(lo_pos // p, (hi_pos - 1) // p + 1):
                if idx not in held:
                    held[idx] = g.allocator.alloc()
                    self._tables_np[r.slot, g.col0 + idx] = held[idx]
            assert len(held) <= g.bound(hi_pos - lo_pos), \
                (len(held), g.window, lo_pos, hi_pos)

    def _group_release(self, r, next_pos):
        """After the write: the next query of `r` sits at `next_pos` and
        sees keys >= next_pos - window + 1, so a page whose last
        position lies below that is behind EVERY query still to come —
        free it (its table entry is never read again: the kernels walk
        from the window's first page)."""
        p = self.page_size
        for g in self.groups:
            held = r.more_pages.get(g.index)
            if g.window is None or not held:
                continue
            for idx in [i for i in held
                        if (i + 1) * p <= next_pos - g.window + 1]:
                # (the table keeps the dead entry: a transfer of this row
                # may still be reading the host array)
                g.allocator.free([held.pop(idx)])
                g.freed_behind_window += 1

    def _count_pages(self):
        """One step ran: every group's pages in use, summed for the
        `pages_used_share_*` means (health())."""
        for g in self.groups:
            g.used_page_steps += g.used

    # -- copy-on-write -----------------------------------------------------
    def _build_copy(self):
        def copy(kps, vps, src, dst):
            if isinstance(kps, (list, tuple)):
                return ([k.at[dst].set(k[src]) for k in kps],
                        [v.at[dst].set(v[src]) for v in vps])
            # native stacked pools (megakernel="multi"): one page copy
            # across every layer's [L, ...] slice
            return (kps.at[:, dst].set(kps[:, src]),
                    vps.at[:, dst].set(vps[:, src]))

        _, R, POOL = self._tp_specs()
        return self._jit_tp(copy, in_specs=(POOL, POOL, R, R),
                            out_specs=(POOL, POOL),
                            donate_argnums=(0, 1))

    def _cow(self, r, idx):
        """First divergent write into a shared page: copy its KV into
        the request's reserved page and swap the table entry; the shared
        original stays read-only for its other holders."""
        old = int(self._tables_np[r.slot, idx])
        new = r.cow_reserve
        assert new is not None, "copy-on-write without a reserved page"
        r.cow_reserve = None
        if self._copy_fn is None:
            self._copy_fn = self._build_copy()
        self.k_pages, self.v_pages = self._copy_fn(
            self.k_pages, self.v_pages, jnp.int32(old), jnp.int32(new))
        self._tables_np[r.slot, idx] = new
        r.pages[idx] = new
        r.shared_idx.discard(idx)
        self.allocator.free([old])           # drop r's reference only
        self.cow_copies += 1

    def _make_writable(self, r, lo_pos, hi_pos):
        """Copy-on-write every shared page overlapping write positions
        [lo_pos, hi_pos)."""
        p = self.page_size
        for idx in range(lo_pos // p, (hi_pos - 1) // p + 1):
            if idx in r.shared_idx:
                self._cow(r, idx)

    # -- prefill -----------------------------------------------------------
    def _build_cb_prefill(self, chunk, with_adapters=False):
        """One prompt chunk of ONE sequence: write its KV into the
        sequence's pages, then attend over the sequence's whole gathered
        context (shared prefix pages included) with causal masking.
        Static shape: [1, chunk]; t_start/t_end ride as traced scalars
        so every chunk of every prompt reuses ONE compiled program. It
        returns the chunk's last logits row AND the engine's token
        vector `toks` with the greedy token of that row written at
        `slot` when the chunk is the prompt's last (`_tp_greedy_token`:
        bitwise the host's argmax of the row), so a greedy prompt's
        first token never leaves the device before it is fed.
        with_adapters=True builds the ADAPTER-AWARE variant (aid [1] —
        the request's pool slot; an adapter request's prompt KV must
        carry the delta too, or its cache would diverge from a
        dedicated engine's)."""
        p = self.page_size
        mp = self.pages_per_seq
        layer_group = self.desc.layer_group

        def prefill(W, ids, k_pages_all, v_pages_all, table, t_start,
                    t_end, toks=None, slot=0, AD=None, aid=None):
            ad = None if AD is None else (AD, aid)
            with phase("embed"):
                h = jnp.take(W["emb"], ids, axis=0).astype(
                    jnp.float32 if self.f32_stream else self.kv_dtype)
                pos = t_start + jnp.arange(chunk, dtype=jnp.int32)
                pos_ids = pos[None, :]
            new_k, new_v = [], []
            for li, wset in enumerate(W["layers"]):
                a = self.desc.layers[li].attn
                g = self.groups[layer_group[li]]
                # this layer's group: its columns of the page table, its
                # pool shape (the LOCAL kv heads under tp, which only a
                # plain description runs)
                tab = table[0, g.col0:g.col0 + mp]
                nkv = a.n_kv_heads // self.tp
                oob = jnp.int32(g.n_pages * p)
                ad_li = None if ad is None else \
                    self._ad_sel(AD, aid, li)
                rows_layer = _rows_layer(a)
                if rows_layer is not None:
                    # rows (and index keys) written, then attention over
                    # the LIVE key blocks (inference/latent.py, the
                    # absorbed form; inference/sparse_heads.py)
                    attn, kp, vp = rows_layer.prefill_layer(
                        self, W, wset, h, k_pages_all[li],
                        v_pages_all[li], tab, pos, t_end, li)
                    k_pages_all = _pools_put(k_pages_all, li, kp, new_k)
                    v_pages_all = _pools_put(v_pages_all, li, vp, new_v)
                    h = self._layer_tail(W, wset, h, attn, li=li)
                    continue
                q, k, v = self._layer_qkv(W, wset, h, pos_ids, ad=ad_li,
                                          li=li)
                with phase("kv_write"):
                    slots = tab[pos // p] * p + pos % p
                    # padded tail positions (>= the true prompt end) write
                    # NOTHING — scatter-drop, so cached pages stay garbage-
                    # free and shared pages are never touched
                    slots = jnp.where(pos < t_end, slots, oob)
                    vp = v_pages_all[li].reshape(-1, nkv, a.v_dim)
                    vp = vp.at[slots].set(v[0].astype(self.kv_dtype),
                                          mode="drop")
                    vp = vp.reshape(g.n_pages, p, nkv, a.v_dim)
                    k_pool = k_pages_all[li].shape  # flat or by head
                    kp = k_pages_all[li].reshape((-1,) + k_pool[2:])
                    kp = kp.at[slots].set(
                        k[0].astype(self.kv_dtype).reshape(
                            (chunk,) + k_pool[2:]), mode="drop")
                    kp = kp.reshape(k_pool)
                    k_pages_all = _pools_put(k_pages_all, li, kp, new_k)
                    v_pages_all = _pools_put(v_pages_all, li, vp, new_v)
                with phase("attend"):
                    if not self.desc.plain:
                        # a KV head's query heads as rows against the
                        # UNREPEATED K and V, over the pages the chunk
                        # can see under a running softmax
                        attn = sparse_heads.attend_chunk(
                            q[0], kp, vp, tab, pos, t_end, a, p,
                            wset.get("sink"), self.interpret)[None]
                    else:
                        attn = self._attend_chunk_dense(
                            q, kp, vp, tab, pos, a)
                h = self._layer_tail(W, wset, h, attn, ad=ad_li, li=li)
            with phase("head"):
                h = self._norm(h, W["norm"], W["eps"])
                last = jnp.clip(t_end - 1 - t_start, 0, chunk - 1)
                h_last = jax.lax.dynamic_index_in_dim(h, last, axis=1)
                loc = (_mm_f32 if self.f32_stream else _mm)(
                    h_last, W["head"], self.interpret)[:, 0]
                if toks is None:        # a caller that only compiles it
                    toks = jnp.zeros((self.max_batch,), jnp.int32)
                first = self._tp_greedy_token(loc)[0].astype(toks.dtype)
                toks = jnp.where(t_start + chunk >= t_end,
                                 toks.at[slot].set(first), toks)
            return (self._gather_logits(loc), toks,
                    _pools_result(k_pages_all, new_k),
                    _pools_result(v_pages_all, new_v))

        W, R, POOL = self._tp_specs()
        if with_adapters:
            def prefill_ad(W, AD, aid, ids, k_pages_all, v_pages_all,
                           table, t_start, t_end):
                logits, _toks, kps, vps = prefill(
                    W, ids, k_pages_all, v_pages_all, table, t_start,
                    t_end, AD=AD, aid=aid)
                return logits, kps, vps

            ADsp = (self._apool.specs() if self._tpc is not None
                    else None)
            return self._jit_tp(prefill_ad,
                                in_specs=(W, ADsp, R, R, POOL, POOL,
                                          R, R, R),
                                out_specs=(R, POOL, POOL),
                                donate_argnums=(4, 5))
        return self._jit_tp(prefill,
                            in_specs=(W, R, POOL, POOL, R, R, R, R, R),
                            out_specs=(R, R, POOL, POOL),
                            donate_argnums=(2, 3))

    def _attend_chunk_dense(self, q, kp, vp, tab, pos, a):
        """A plain description's chunk attention, as every mode it is
        byte-compared with runs it: every logical page of the slot
        gathered out of the pool ([pages*p, h_kv, d]; keys past the
        causal horizon carry finite garbage and mask to exact zero
        weight), K and V repeated to the query heads, one softmax over
        [heads, chunk, keys]. q [1, chunk, H, d] -> [1, chunk, H, d]."""
        p, mp = self.page_size, self.pages_per_seq
        nkv = a.n_kv_heads // self.tp
        ck = kp[tab].reshape(mp * p, nkv, a.qk_dim)
        cv = vp[tab].reshape(mp * p, nkv, a.v_dim)
        ck = expand_kv_heads(ck, q.shape[2])
        cv = expand_kv_heads(cv, q.shape[2])
        logits = jnp.einsum("qhd,khd->hqk", q[0], ck) \
            / math.sqrt(a.qk_dim)
        kpos = jnp.arange(mp * p, dtype=jnp.int32)[None, None, :]
        logits = jnp.where(kpos <= pos[None, :, None], logits, -1e30)
        w = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", w, cv)[None]

    def _prefill_step(self, r):
        with _span("cb.prefill.prepare"):
            chunk = self.prefill_chunk
            start = r.filled
            end = min(start + chunk, r.t0)
            self._make_writable(r, start, end)
            self._group_prepare(r, start, end)
            ids_chunk = np.zeros((1, chunk), np.int64)
            ids_chunk[0, :end - start] = r.ids[start:end]
            if r.adapter is not None:
                # (not an adapter_mk_fallbacks site: chunked prefill is
                # always the op chain — there is no megakernel to leave)
                if self._cb_prefill_ad_fn is None:
                    self._cb_prefill_ad_fn = self._build_cb_prefill(
                        chunk, with_adapters=True)
                    self._program_built = True
                fn = self._cb_prefill_ad_fn
                pre = (self.weights, self._apool.device,
                       jnp.asarray(np.asarray(
                           [self._apool.slot(r.adapter)], np.int32)))
            else:
                if self._cb_prefill_fn is None:
                    self._cb_prefill_fn = self._build_cb_prefill(chunk)
                    self._program_built = True
                fn = self._cb_prefill_fn
                pre = (self.weights,)
            mode = self._block_mode([r])
            greedy = mode == "greedy" and r.adapter is None
            slot = r.slot
        with self._first_call_span():
            t0 = time.perf_counter()
            with _span("cb.prefill_chunk"):
                # a COPY of the row: the transfer may read the host
                # buffer after the call returns, and the row changes as
                # the next chunk's pages are claimed
                chunk_args = (
                    jnp.asarray(ids_chunk), self.k_pages, self.v_pages,
                    jnp.asarray(self._tables_np[slot:slot + 1].copy()),
                    jnp.int32(start), jnp.int32(r.t0))
                if r.adapter is not None:
                    logits, self.k_pages, self.v_pages = fn(
                        *pre, *chunk_args)
                    toks = None
                else:
                    logits, toks, self.k_pages, self.v_pages = fn(
                        *pre, *chunk_args, self._tok_dev, jnp.int32(slot))
            if self._tel is not None:
                self._tel.observe("prefill_chunk_ms",
                                  (time.perf_counter() - t0) * 1e3)
                self._tel.req_event(self._tel_src, r.uid, "prefill_chunk",
                                    filled=end)
        # what the host knows without the chunk's result is booked HERE,
        # at dispatch; the first token at _resolve
        r.filled = end
        seen = np.arange(start + 1, end + 1)    # keys a causal query sees
        for g in self.groups:
            # (query, key) pairs x layers this chunk's attention covers:
            # what its products must compute, whatever computes them
            g.prefill_pairs += len(g.layers) * int(
                seen.sum() if g.window is None
                else np.minimum(seen, g.window).sum())
            plan = self._latent_plans.get(g.index)
            if plan is not None:
                # grid steps of the latent chunk kernel that compute
                self.latent_prefill_live_steps += len(g.layers) * \
                    latent_live_steps(start, r.t0, chunk, self.page_size,
                                      plan["tq"], plan["pages_per_step"],
                                      g.window)
        self._group_release(r, end)
        last = end >= r.t0
        if last:
            # prompt complete: publish full prompt pages to the prefix
            # cache (before the first decode write, so concurrent
            # requests share); the first generated token enters
            # position t0
            self._publish_prefix(r)
            self._lens_np[slot] = r.t0
            if greedy:
                self._tok_dev = toks
                self._tok_on_dev[slot] = True
        return _Dispatched(False, [(r, slot)], last=last, mode=mode,
                           toks=toks if greedy else None,
                           logits=None if greedy else logits)

    def _first_call_span(self):
        """`setup.first_call` around the dispatch (and fetch) of a
        program the caller has just built: python tracing, lowering, the
        executable from the cache or the compiler, the first run. A
        no-op for every later call of that program."""
        if not self._program_built:
            return _NULL_SPAN
        self._program_built = False
        return _span("setup.first_call")

    def _publish_prefix(self, r):
        """Make a completed prompt's FULL pages shareable (the partial
        tail page stays private — decode writes land there). With a
        fleet prefix index attached, every full-page prefix digest is
        published alongside — advisory (an index failure never fails
        the request). Adapter requests publish NOTHING — their KV
        bytes carry the adapter's deltas, and the cache is content-
        addressed by tokens alone (see _price_admission)."""
        if self._prefix is None or r.adapter is not None:
            return
        key = ()
        dig = None
        p = self.page_size
        for j in range(r.t0 // p):
            chunk = r.ids[j * p:(j + 1) * p]
            key = self._prefix.insert(key, chunk, r.pages[j],
                                      self.allocator)
            if self._prefix_index is not None:
                from .prefix_index import EMPTY_DIGEST, chain_digest
                dig = chain_digest(EMPTY_DIGEST if dig is None else dig,
                                   chunk)
                try:
                    self._prefix_index.publish(self._replica, dig, j + 1)
                    self.index_publishes += 1
                except Exception:
                    # index.publish fault or a store hiccup: the index
                    # is a routing hint — serving never depends on it
                    self.index_publish_errors += 1

    # -- fleet prefix index (inference/prefix_index.py) ----------------------
    def attach_prefix_index(self, index, replica):
        """Wire this engine into a fleet prefix index under the name
        `replica`: prefill/import publishes full-page prefix digests,
        cache eviction retracts them, and a weight flip or pool rebuild
        drops every claim (the cache died with it). The router calls
        this once per replica at fleet construction."""
        self._prefix_index = index
        self._replica = replica
        if self._prefix is not None:
            self._prefix.on_evict = self._on_prefix_evict
        return self

    def _on_prefix_evict(self, chain_key):
        if self._prefix_index is None:
            return
        from .prefix_index import chain_key_digest
        try:
            self._prefix_index.retract(self._replica,
                                       chain_key_digest(chain_key))
        except Exception:
            self.index_publish_errors += 1

    # -- multi-LoRA adapters (inference/adapters.py) -------------------------
    def register_adapter(self, name, path):
        """Registry write WITHOUT loading: the adapter hot-loads from
        `path` on the first add_request(adapter=name). Deploying a
        fine-tune = this call on every replica (EngineRouter.
        load_adapter / the fleet RPC surface fan it out)."""
        if self._apool is None:
            raise AdapterError(
                "this engine was built without an adapter pool "
                "(adapters=); see docs/serving.md 'Multi-LoRA & the "
                "model zoo'")
        self._adapter_registry[name] = str(path)
        return name

    def load_adapter(self, name, source):
        """Hot-load a LoRA adapter into the pool under `name` (source:
        a directory written by adapters.save_adapter, or an adapter
        dict). `adapter.load` is the fault point and fires PRE-install
        — a failed/corrupt load raises typed, leaves the pool untouched
        (zero page leak), and the engine keeps serving on base weights
        (counted in the pool's load_errors). The load wall lands in the
        `adapter_load_ms` telemetry histogram. Returns the pool slot."""
        from .adapters import load_adapter_file
        if self._apool is None:
            raise AdapterError(
                "this engine was built without an adapter pool "
                "(adapters=); see docs/serving.md 'Multi-LoRA & the "
                "model zoo'")
        t0 = time.monotonic()
        try:
            fault_point("adapter.load", detail=f"name={name}")
            if isinstance(source, dict):
                ad = source
            else:
                ad = load_adapter_file(
                    source, expect_dims=self._apool.dims,
                    expect_layers=self._apool.n_layers)
            slot = self._apool.install(name, ad)
        except Exception:
            self._apool.load_errors += 1
            raise
        if not isinstance(source, dict):
            self._adapter_registry[name] = str(source)
        dt_ms = (time.monotonic() - t0) * 1e3
        self._apool.last_load_ms = dt_ms
        if self._tel is not None:
            self._tel.observe("adapter_load_ms", dt_ms)
            self._tel.registry.count("adapter_loads")
        return slot

    def evict_adapter(self, name):
        """Explicit pool eviction (LRU handles the implicit case);
        refuses typed while live requests hold the adapter. The
        `adapter_evict` counter rides telemetry."""
        if self._apool is None:
            raise AdapterError("this engine has no adapter pool "
                               "(adapters=)")
        slot = self._apool.evict(name)
        # the lazy-load registry entry goes WITH the pool slot — an
        # evicted fine-tune must not resurrect itself on the next
        # request naming it (register_adapter re-arms lazy loading)
        self._adapter_registry.pop(name, None)
        if self._tel is not None:
            self._tel.registry.count("adapter_evict")
        return slot

    def pin_adapter(self, name, pinned=True):
        """Pin (or unpin) a loaded adapter against LRU eviction — the
        autoscale controller keeps hot fine-tunes pool-resident on
        their affinity replicas this way."""
        if self._apool is None:
            raise AdapterError("this engine has no adapter pool "
                               "(adapters=)")
        if pinned:
            self._apool.pin(name)
        else:
            self._apool.unpin(name)
        return pinned

    def _resolve_adapter(self, name):
        """Pool slot for `name`, hot-loading from the registry when not
        resident; typed UnknownAdapterError otherwise."""
        if self._apool is None:
            raise AdapterError(
                "add_request(adapter=...) needs an engine built with "
                "an adapter pool (adapters=)")
        if not self._apool.has(name):
            path = self._adapter_registry.get(name)
            if path is None:
                raise UnknownAdapterError(
                    f"adapter {name!r} is neither loaded nor "
                    f"registered (loaded: {sorted(self._apool.names())}, "
                    f"registered: {sorted(self._adapter_registry)})")
            self.load_adapter(name, path)
        return self._apool.slot(name)

    def _release_adapter(self, r):
        """Drop a retiring request's pool reference ONCE — but keep
        the NAME on the request: failover salvage reads export_request
        AFTER the failure transition, and a nulled name would resume
        the continuation on base weights silently (wrong model, no
        error)."""
        if r.adapter is not None and self._apool is not None \
                and not r.adapter_released:
            self._apool.release(r.adapter)
            r.adapter_released = True

    def _ad_sel(self, AD, aid, li):
        """The per-layer LoRA selection tuple the traced layer math
        consumes (adapters.lora_apply): factor stacks for layer `li`,
        the per-row pool-slot ids, per-row alpha/r scales, and the
        aid > 0 gate that keeps adapter-free rows bit-exact."""
        return (AD["a"][li], AD["b"][li], aid, AD["scale"][aid], aid > 0)

    def _slot_aid(self, requests, w):
        """Per-slot adapter pool-slot ids (0 = base weights) for a
        dispatch over `requests`; None when the batch carries no
        adapter (the caller then runs the untouched no-adapter
        program)."""
        if self._apool is None:
            return None
        aid = np.zeros(w, np.int32)
        any_ad = False
        for r in requests:
            if r.adapter is not None and r.slot is not None \
                    and r.slot < w:
                aid[r.slot] = self._apool.slot(r.adapter)
                any_ad = True
        return aid if any_ad else None

    # -- telemetry (inference/telemetry.py) ----------------------------------
    def attach_telemetry(self, tel, src=None):
        """Wire this engine into a Telemetry object under source name
        `src` (defaults to the telemetry's own name; the router passes
        the replica name so fleet traces stay attributable). Request
        traces are keyed (src, uid) — an engine REBUILD under the same
        src must re-attach, which drops the dead engine's live traces
        (its uid space restarts). Detach with attach_telemetry(None)."""
        if tel is None:
            self._tel = None
            self.telemetry = None
            return self
        self._tel = tel
        self.telemetry = tel
        self._tel_src = src or getattr(tel, "name", None) or "engine"
        tel.reset_live(self._tel_src)
        return self

    # -- decode ------------------------------------------------------------
    def _resolve_megakernel(self, val):
        """megakernel= knob -> False / "layer" / "multi". Auto (None)
        turns the per-layer megakernel on only where it is the fast
        path AND the geometry reslices cleanly: real TPU, lane-multiple
        head/hidden dims (megakernel_supported). Forcing True on CPU
        runs it in interpret mode — the parity fallback the tests pin
        against the op-chain path."""
        from ..ops.pallas.decode_megakernel import megakernel_supported
        if not self.desc.plain:
            return False        # the op-chain programs (forcing it on
            #                     was refused at construction)
        # under tp the kernel runs per shard on LOCAL head/ffn slices —
        # those are the dims Mosaic has to reslice cleanly
        ffn = self.cfg.intermediate_size
        ffn_l = ffn // self.tp if ffn % self.tp == 0 else ffn
        ok = megakernel_supported(self.nh_l, self.nh_kv_l, self.hd,
                                  self.cfg.hidden_size, ffn_l)
        if val is None:
            if not ok or self.interpret:
                return False
            if self.tp > 1 and (self.tp_mode != "exact"
                                or self.cfg.intermediate_size % self.tp):
                # auto must never FORCE a tp-incomposable config into
                # the typed _build_mk_pack rejection — psum-mode or an
                # awkward ffn silently keeps the op-chain path, exactly
                # as these configs ran before the megakernel composed
                # with tp at all; forcing "layer"/"multi" still raises
                return False
            return "layer"
        if val is False:
            return False
        if val in (True, "layer"):
            mode = "layer"
        elif val == "multi":
            mode = "multi"
        else:
            raise ValueError(
                f"megakernel must be None, False, True, 'layer' or "
                f"'multi', got {val!r}")
        # forcing on a real TPU with a non-lane-aligned geometry would
        # die deep in Mosaic lowering — fail HERE with the reason
        # (interpret mode has no such constraint: CPU parity always ok)
        if not self.interpret and not ok:
            raise ValueError(
                f"megakernel={mode!r} forced on TPU but the geometry "
                f"(nh={self.nh}, nh_kv={self.nh_kv}, hd={self.hd}, "
                f"hidden={self.cfg.hidden_size}, "
                f"ffn={self.cfg.intermediate_size}) fails "
                "megakernel_supported (head/hidden/ffn dims must be "
                "lane multiples); use the auto default or a supported "
                "geometry")
        return mode

    def _build_mk_pack(self):
        """Repack the weight snapshot into the megakernel's streamed
        layout (once at build / weight flip; ~zero-copy for aligned
        geometries). tp > 1 packs the column-parallel projections per
        shard (q/k/v/gate/up + the vocab-parallel lm_head) and keeps
        the exact-mode row pair (o/down) full-replicated — the same
        weight placement the op-chain tp engine uses, so byte-identity
        with tp=1 survives. megakernel="multi" additionally builds the
        WHOLE-STEP head pack (final norm + lm_head + greedy argmax in
        the same schedule)."""
        from ..ops.pallas.decode_megakernel import (layer_tile_plan,
                                                    pack_decode_layer,
                                                    pack_lm_head,
                                                    stack_packed)
        W = self.weights
        if self.tp > 1:
            if self.tp_mode != "exact":
                raise ValueError(
                    "megakernel with tp > 1 requires tp_mode='exact': "
                    "the psum tail's row-parallel reduce cannot ride "
                    "the packed schedule bit-exactly — the exact mode's "
                    "gathers run BETWEEN kernel segments instead")
            if self.cfg.intermediate_size % self.tp:
                raise ValueError(
                    f"megakernel with tp={self.tp} needs the ffn dim "
                    f"({self.cfg.intermediate_size}) divisible by tp "
                    "(column-parallel gate/up shard per-shard tile "
                    "grids)")
        packed = [pack_decode_layer(ws, cdtype=self.kv_dtype, tp=self.tp)
                  for ws in W["layers"]]
        # which blocks the walk streams and how many grid steps a layer
        # call takes at a full batch (static; health()["mk_tile_plan"])
        self.mk_tile_plan = layer_tile_plan(packed[0], self.max_batch,
                                            self.tp)
        mk = (stack_packed(packed) if self.megakernel == "multi"
              else packed)
        head_w = (W["head"][0] if isinstance(W["head"], tuple)
                  else W["head"])
        vocab = head_w.shape[1]
        # whole-step head fold: "multi" mode only (per-layer mode keeps
        # the op-chain norm/head); an awkward vocab under tp falls back
        # to the op-chain head
        self._mk_head = (self.megakernel == "multi"
                         and (self.tp == 1 or vocab % self.tp == 0))
        self._mk_vl = vocab // self.tp if vocab % self.tp == 0 else vocab
        mk_head = (pack_lm_head(W["head"], W["norm"],
                                cdtype=self.kv_dtype, tp=self.tp)
                   if self._mk_head else None)
        if self._tpc is not None:
            specs = self._tpc.mk_spec_tree(mk)
            W["mk"] = self._tpc.place(mk, specs)
            self._w_specs["mk"] = specs
            if mk_head is not None:
                hspecs = self._tpc.mk_spec_tree(mk_head)
                W["mk_head"] = self._tpc.place(mk_head, hspecs)
                self._w_specs["mk_head"] = hspecs
        else:
            W["mk"] = mk
            if mk_head is not None:
                W["mk_head"] = mk_head

    def _mk_walk(self, W, h, k_pages_all, v_pages_all, tables, lens,
                 act_i, cos_sel, sin_sel, tq=1, wmask=None, head_k=None):
        """The megakernel layer walk shared by plain decode (tq=1) and
        the speculative verify pass (tq=T): runs the whole stack as one
        invocation ("multi", tp=1), per-layer invocations ("layer",
        tp=1), or the per-shard qkv/tail/down SEGMENTS with exact-mode
        gathers between them (tp>1). Returns (h, k_rows, v_rows, tok,
        maxv, logits_local): tok/maxv/logits are None unless the
        whole-step head fold ran. head_k=None (greedy): tok is the
        combined GLOBAL greedy argmax, maxv its logit, logits_local
        this shard's vocab columns. head_k=K>1 (the sampling fold):
        tok/maxv become the GLOBAL [rows, K] top-K (ids, f32 logits) —
        combined across vocab shards gather-free — and logits_local is
        None: the kernel drops the [R, V] output entirely."""
        from ..ops.pallas.decode_megakernel import decode_megakernel
        kw = dict(nh=self.nh_l, nh_kv=self.nh_kv_l, hd=self.hd,
                  eps=self.cfg.rms_norm_eps, interpret=self.interpret)
        head = W.get("mk_head") if self._mk_head else None
        head_v = self._mk_vl
        fold = head is not None and head_k is not None and head_k > 1
        tok = maxv = logits = None
        if self.tp == 1:
            if self.megakernel == "multi":
                out = decode_megakernel(
                    h, W["mk"], k_pages_all, v_pages_all, tables, lens,
                    act_i, cos_sel, sin_sel, tq=tq, wmask=wmask,
                    head=head, head_v=head_v if head else None,
                    head_k=head_k if fold else None, **kw)
                if fold:
                    h, k_all, v_all, tok, maxv = out
                elif head is not None:
                    h, k_all, v_all, tok, maxv, logits = out
                else:
                    h, k_all, v_all = out
            else:
                k_all, v_all = [], []
                for li, mset in enumerate(W["mk"]):
                    h, kn, vn = decode_megakernel(
                        h, mset, k_pages_all[li], v_pages_all[li],
                        tables, lens, act_i, cos_sel, sin_sel, tq=tq,
                        wmask=wmask, **kw)
                    k_all.append(kn)
                    v_all.append(vn)
        else:
            # per-shard segments: column-parallel QKV + local-head
            # attention, gather heads, replicated O + column-parallel
            # MLP front, gather columns, replicated down (+ the vocab-
            # parallel head slice on the last layer in whole-step mode).
            # The gathers are the SAME exact-mode reassembly the
            # op-chain tp engine performs — pure data movement.
            R = h.shape[0]
            Fl = self.cfg.intermediate_size // self.tp
            L = self.cfg.num_hidden_layers
            mk = W["mk"]
            stacked = not isinstance(mk, (list, tuple))
            k_all, v_all = [], []
            for li in range(L):
                mset = ({k: v[li] for k, v in mk.items()} if stacked
                        else mk[li])
                attn_l, kn, vn = decode_megakernel(
                    h, mset, k_pages_all[li], v_pages_all[li], tables,
                    lens, act_i, cos_sel, sin_sel, seg="qkv", tq=tq,
                    wmask=wmask, **kw)
                k_all.append(kn)
                v_all.append(vn)
                attn_f = self._tpc.gather_heads(
                    attn_l.reshape(R, self.nh_l, self.hd)).reshape(
                    R, self.nh * self.hd)
                h, act_l = decode_megakernel(
                    h, mset, seg="tail", attn_in=attn_f, mlp_v=Fl, **kw)
                act_f = self._tpc.gather_cols(act_l)
                if li == L - 1 and head is not None:
                    if fold:
                        h, tok, maxv = decode_megakernel(
                            h, mset, seg="down", act_in=act_f,
                            head=head, head_v=head_v, head_k=head_k,
                            **kw)
                    else:
                        h, tok, maxv, logits = decode_megakernel(
                            h, mset, seg="down", act_in=act_f,
                            head=head, head_v=head_v, **kw)
                else:
                    h = decode_megakernel(h, mset, seg="down",
                                          act_in=act_f, **kw)
            if tok is not None:
                if fold:
                    # vocab-parallel sampling fold: combine the shards'
                    # LOCAL top-K pairs gather-free — bitwise equal to
                    # lax.top_k over the full gathered logits (shard-
                    # major concat keeps the id-asc tie order)
                    maxv, tok = self._tpc.topk_of_local_topk(
                        maxv, tok, self._mk_vl, head_k)
                else:
                    # vocab-parallel whole-step select: combine the
                    # shards' (max, argmax) pairs psum-free — bitwise
                    # equal to argmax over the full gathered logits
                    tok = self._tpc.argmax_of_local_max(maxv, tok,
                                                        self._mk_vl)
        return h, k_all, v_all, tok, maxv, logits

    def _mk_scatter(self, k_pages_all, v_pages_all, k_all, v_all,
                    slots_raw, ok):
        """Write the kernel-returned current-row k/v into the page
        pools — the SAME bytes (same positions, same gating) the
        op-chain path scatters. slots_raw: [rows] flat pool-row index
        per feed row; ok: [rows] write gate (active slots at tq=1, the
        verify write mask at tq>1). Handles all four pool/row forms:
        per-layer lists, natively stacked pools, stacked kernel rows."""
        p = self.page_size
        shape = (self.nh_kv_l, self.hd)
        npp = self.n_pages * p

        def put(pool, rows, slots):
            flat = pool.reshape(npp, *shape)
            flat = flat.at[slots].set(
                rows.reshape(-1, *shape).astype(self.kv_dtype),
                mode="drop")
            return flat.reshape(self.n_pages, p, *shape)

        if isinstance(k_all, list):
            slots = jnp.where(ok, slots_raw, jnp.int32(npp))
            if isinstance(k_pages_all, (list, tuple)):
                new_k = [put(k_pages_all[li], k_all[li], slots)
                         for li in range(len(k_all))]
                new_v = [put(v_pages_all[li], v_all[li], slots)
                         for li in range(len(v_all))]
                return new_k, new_v
            for li in range(len(k_all)):    # stacked pools, listed rows
                k_pages_all = k_pages_all.at[li].set(
                    put(k_pages_all[li], k_all[li], slots))
                v_pages_all = v_pages_all.at[li].set(
                    put(v_pages_all[li], v_all[li], slots))
            return k_pages_all, v_pages_all
        # stacked rows [L, rows, NK] + stacked pools: ONE flat scatter
        # with per-layer offsets (inactive/ungated rows drop GLOBALLY —
        # layer li's oob must not alias layer li+1's page 0)
        L = k_all.shape[0]
        base = jnp.arange(L, dtype=jnp.int32)[:, None] * jnp.int32(npp)
        gidx = jnp.where(ok[None, :], base + slots_raw[None, :],
                         jnp.int32(L * npp))
        rows = slots_raw.shape[0]

        def put_all(pools, new_all):
            flat = pools.reshape(L * npp, *shape)
            flat = flat.at[gidx.reshape(-1)].set(
                new_all.reshape(L * rows, *shape).astype(self.kv_dtype),
                mode="drop")
            return flat.reshape(L, self.n_pages, p, *shape)

        return (put_all(k_pages_all, k_all), put_all(v_pages_all, v_all))

    def _cb_decode_math_mk(self, W, tok, k_pages_all, v_pages_all,
                           tables, lens, active, w, topk=None):
        """Megakernel decode step: each layer (or, in "multi" mode, the
        whole stack PLUS the final norm, lm_head and greedy argmax)
        runs as ONE Pallas invocation — matmuls, norms, rope and paged
        attention fused, weights streamed through VMEM. The kernel
        attends with the current token's k/v substituted into its page
        block and returns them for the SAME scatter the op-chain path
        performs, so the page pool contents stay byte-identical between
        the two paths.

        topk=K (the sampling fold): returns (topv [w, K] f32, topi
        [w, K] i32, new_k, new_v) from the kernel's in-kernel running
        top-K merge — the [w, V] logits never exist (whole-step mode);
        "layer" mode and the no-head fallback materialize + lax.top_k
        (same bits — the fold is selection only)."""
        p = self.page_size
        with phase("embed"):
            h = jnp.take(W["emb"], tok, axis=0).astype(
                self.kv_dtype)                                 # [w, H]
            cos_sel = W["cos"][lens].astype(h.dtype)
            sin_sel = W["sin"][lens].astype(h.dtype)
            act_i = active.astype(jnp.int32)
        # the kernel is ONE device operation under its own name
        # (`_decode_megakernel`): no phase can look inside it
        h, k_all, v_all, tok_g, maxv, loc = self._mk_walk(
            W, h, k_pages_all, v_pages_all, tables, lens, act_i,
            cos_sel, sin_sel, head_k=topk)
        with phase("kv_write"):
            slots_raw = (tables[jnp.arange(w), lens // p] * p + lens % p)
            new_k, new_v = self._mk_scatter(k_pages_all, v_pages_all,
                                            k_all, v_all, slots_raw, active)
        with phase("head"):
            if topk is not None:
                if tok_g is None:      # "layer" mode / head fold off
                    hN = _rms(h[:, None], W["norm"], W["eps"])
                    loc = _mm(hN, W["head"], self.interpret)[:, 0]
                    maxv, tok_g = self._tp_topk(loc, topk)
                return maxv, tok_g, new_k, new_v
            if loc is None:
                hN = _rms(h[:, None], W["norm"], W["eps"])
                loc = _mm(hN, W["head"], self.interpret)[:, 0]
                tok_g = self._tp_greedy_token(loc)
            return self._gather_logits(loc), tok_g, new_k, new_v

    def _cb_decode_math(self, W, tok, k_pages_all, v_pages_all, tables,
                        lens, active, w, ad=None, topk=None,
                        expert_rows=None, sparse_counts=None):
        """One decode step at slot-bucket width w, fully traceable
        (shared by the per-step jit and the fused multi-step scan, so
        both paths run byte-identical math): one token for every slot,
        inactive slots write nothing (scatter-drop) and skip attention
        compute/DMA via the kernel's active mask. With megakernel= on,
        the per-layer op chain is replaced by the fused Pallas
        megakernel (same math, same page writes).

        ad: (AD, aid) adapter selection for an adapter-carrying batch —
        the grouped LoRA delta rides the op chain (a megakernel engine
        FALLS BACK to the op-chain delta for these dispatches — counted
        in adapter_mk_fallbacks; megakernel/op-chain byte-identity is
        pinned, so the mixed-batch contract survives the mode split).

        Returns (logits, tok, new_k, new_v): logits the FULL-vocab row
        (gathered under a vocab-parallel head — unused consumers are
        DCE'd), tok the greedy argmax token (what the whole-step kernel
        emits directly; computed psum-free under tp). Greedy callers
        use tok, sampled callers logits — bitwise the same choice.

        topk=K (sampled fold): returns (topv [w, K], topi [w, K],
        new_k, new_v) instead — the per-row top-K logits and vocab ids
        in lax.top_k order (value desc, id asc on ties). Under the
        whole-step megakernel these come from the IN-KERNEL running
        top-K merge and the [w, V] logits are never materialized; the
        op-chain path computes lax.top_k of the same logits (the fold
        is selection-only, so both are bitwise identical)."""
        if self.megakernel and ad is None:
            return self._cb_decode_math_mk(W, tok, k_pages_all,
                                           v_pages_all, tables, lens,
                                           active, w, topk=topk)
        AD, aid = ad if ad is not None else (None, None)
        p = self.page_size
        mp = self.pages_per_seq
        layer_group = self.desc.layer_group
        with phase("embed"):
            h = jnp.take(W["emb"], tok[:, None], axis=0).astype(
                jnp.float32 if self.f32_stream else self.kv_dtype)
            pos_ids = lens[:, None]
        new_k, new_v = [], []
        for li, wset in enumerate(W["layers"]):
            a = self.desc.layers[li].attn
            g = self.groups[layer_group[li]]
            # this layer's group: its columns of the page table, its
            # pool shape (the LOCAL kv heads under tp, plain only)
            tab = tables[:, g.col0:g.col0 + mp]
            nkv = a.n_kv_heads // self.tp
            oob = jnp.int32(g.n_pages * p)
            ad_li = None if ad is None else self._ad_sel(AD, aid, li)
            rows_layer = _rows_layer(a)
            if rows_layer is not None:
                # one row a token, index keys in the second pool
                # (inference/latent.py, inference/sparse_heads.py)
                attn, kp, vp, counts = rows_layer.decode_layer(
                    self, W, wset, h, k_pages_all[li], v_pages_all[li],
                    tab, lens, active, li)
                if sparse_counts is not None:
                    sparse_counts.append(counts)
                k_pages_all = _pools_put(k_pages_all, li, kp, new_k)
                v_pages_all = _pools_put(v_pages_all, li, vp, new_v)
                h = self._layer_tail(W, wset, h, attn, li=li,
                                     expert_rows=expert_rows)
                continue
            q, k, v = self._layer_qkv(W, wset, h, pos_ids, ad=ad_li,
                                      li=li)
            with phase("kv_write"):
                slots = (tab[jnp.arange(w), lens // p] * p + lens % p)
                slots = jnp.where(active, slots, oob)
                vp = v_pages_all[li].reshape(-1, nkv, a.v_dim)
                vp = vp.at[slots].set(v[:, 0].astype(self.kv_dtype),
                                      mode="drop")
                vp = vp.reshape(g.n_pages, p, nkv, a.v_dim)
                k_pool = k_pages_all[li].shape      # flat or by head
                kp = k_pages_all[li].reshape((-1,) + k_pool[2:])
                kp = kp.at[slots].set(
                    k[:, 0].astype(self.kv_dtype).reshape((w,) + k_pool[2:]),
                    mode="drop")
                kp = kp.reshape(k_pool)
                k_pages_all = _pools_put(k_pages_all, li, kp, new_k)
                v_pages_all = _pools_put(v_pages_all, li, vp, new_v)
            with phase("attend"):
                attn = paged_attention(
                    q[:, 0], kp, vp, tab,
                    jnp.where(active, lens + 1, 0),
                    interpret=self.interpret,
                    active=active.astype(jnp.int32),
                    window=a.window, sinks=wset.get("sink"),
                    k_flat=g.k_flat)
            h = self._layer_tail(W, wset, h, attn[:, None], ad=ad_li,
                                 li=li, expert_rows=expert_rows)
        with phase("head"):
            h = self._norm(h, W["norm"], W["eps"])
            loc = (_mm_f32 if self.f32_stream else _mm)(
                h, W["head"], self.interpret)[:, 0]
            if topk is not None:
                topv, topi = self._tp_topk(loc, topk)
                return (topv, topi,
                        _pools_result(k_pages_all, new_k),
                        _pools_result(v_pages_all, new_v))
            return (self._gather_logits(loc), self._tp_greedy_token(loc),
                    _pools_result(k_pages_all, new_k),
                    _pools_result(v_pages_all, new_v))

    def _cb_spec_verify_math(self, W, feed, k_pages_all, v_pages_all,
                             tables, lens, active, rem, dlen, w,
                             ad=None, topk=None):
        """ONE speculative VERIFY pass at slot width w: slot b feeds T
        tokens (its pending token + up to T-1 drafts) at global
        positions lens[b] + [0, T), writing their KV length-gated and
        scoring every position through the multi-token-q ragged
        paged-attention kernel (spec_verify_attention). Rows are
        BIT-IDENTICAL to T sequential `_cb_decode_math` steps on the
        interpret path — the greedy byte-identity contract — because
        matmul/norm rows are position-independent and the ragged kernel
        walks the same per-page online softmax as the decode kernel.

        Write gating IS the rollback story: feed position j writes only
        when j == 0 (the committed pending token) or j <= dlen[b] (a
        real draft) and j < min(T, rem[b]) (the budget cap). A rejected
        draft's KV stays in the pool but `lens` never advances over it,
        so the next pass (or the next plain step) overwrites it and no
        attention ever reads it — no scrub, no extra pass.

        feed: [w, T] int; returns (logits [w, T, V], g_tok [w, T]
        greedy argmax rows, new_k, new_v) — the same contract as
        _cb_decode_math, per feed position. With megakernel= on, the
        verify pass rides the kernel's tq>1 schedule instead
        (_cb_spec_verify_math_mk): same substituted block contents,
        same ragged causal mask, same pool bytes. ad: adapter selection
        — verify rows carry the SLOT's adapter (every feed position of
        slot b shares aid[b]), riding the op-chain delta exactly like
        plain decode (megakernel engines fall back here for adapter
        batches). topk=K: returns (topv [w, T, K], topi [w, T, K],
        new_k, new_v) per feed position — same fold contract as
        _cb_decode_math(topk=K)."""
        if self.megakernel and ad is None:
            return self._cb_spec_verify_math_mk(
                W, feed, k_pages_all, v_pages_all, tables, lens, active,
                rem, dlen, w, topk=topk)
        AD, aid = ad if ad is not None else (None, None)
        p = self.page_size
        T = feed.shape[1]
        h = jnp.take(W["emb"], feed, axis=0).astype(self.kv_dtype)
        j = jnp.arange(T, dtype=jnp.int32)[None, :]               # [1, T]
        pos = lens[:, None] + j                                   # [w, T]
        # ungated tail positions may point past the request's page
        # claim; clamp for the table/rope GATHERS only (their rows are
        # discarded — emission never reaches them)
        pos_c = jnp.minimum(pos, jnp.int32(self.max_len - 1))
        cap = jnp.minimum(jnp.int32(T), rem)[:, None]
        write_ok = jnp.logical_and(
            active[:, None],
            jnp.logical_and(j < cap, j <= dlen[:, None]))
        oob = jnp.int32(self.n_pages * p)
        new_k, new_v = [], []
        for li, wset in enumerate(W["layers"]):
            ad_li = None if ad is None else self._ad_sel(AD, aid, li)
            q, k, v = self._layer_qkv(W, wset, h, pos_c, ad=ad_li)
            slots = tables[jnp.arange(w)[:, None], pos_c // p] * p \
                + pos_c % p
            slots = jnp.where(write_ok, slots, oob)
            kp = k_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
            vp = v_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
            kp = kp.at[slots].set(k.astype(self.kv_dtype), mode="drop")
            vp = vp.at[slots].set(v.astype(self.kv_dtype), mode="drop")
            kp = kp.reshape(self.n_pages, p, self.nh_kv_l, self.hd)
            vp = vp.reshape(self.n_pages, p, self.nh_kv_l, self.hd)
            k_pages_all = _pools_put(k_pages_all, li, kp, new_k)
            v_pages_all = _pools_put(v_pages_all, li, vp, new_v)
            attn = spec_verify_attention(
                q, kp, vp, tables, lens,
                active=active.astype(jnp.int32),
                interpret=self.interpret)
            h = self._layer_tail(W, wset, h, attn, ad=ad_li)
        h = _rms(h, W["norm"], W["eps"])
        loc = _mm(h, W["head"], self.interpret)
        if topk is not None:
            topv, topi = self._tp_topk(loc, topk)
            return (topv, topi,
                    _pools_result(k_pages_all, new_k),
                    _pools_result(v_pages_all, new_v))
        return (self._gather_logits(loc), self._tp_greedy_token(loc),
                _pools_result(k_pages_all, new_k),
                _pools_result(v_pages_all, new_v))

    def _cb_spec_verify_math_mk(self, W, feed, k_pages_all, v_pages_all,
                                tables, lens, active, rem, dlen, w,
                                topk=None):
        """The verify pass on the MEGAKERNEL's tq>1 schedule: feed rows
        flatten slot-major into the matmul phases, the ATTN phase runs
        the ragged kernel's causal mask with every WRITE-GATED feed
        token's k/v substituted into its page block, and in whole-step
        mode the final norm + lm_head + per-position greedy argmax ride
        the same invocation. The engine then performs the identical
        write-gated scatter, so pool bytes — including rejected drafts'
        rows — match the op-chain path bit-for-bit."""
        p = self.page_size
        T = feed.shape[1]
        R = w * T
        h = jnp.take(W["emb"], feed.reshape(-1), axis=0).astype(
            self.kv_dtype)                                     # [R, H]
        j = jnp.arange(T, dtype=jnp.int32)[None, :]
        pos = lens[:, None] + j
        pos_c = jnp.minimum(pos, jnp.int32(self.max_len - 1))
        cap = jnp.minimum(jnp.int32(T), rem)[:, None]
        write_ok = jnp.logical_and(
            active[:, None],
            jnp.logical_and(j < cap, j <= dlen[:, None]))
        cos_sel = W["cos"][pos_c.reshape(-1)].astype(h.dtype)
        sin_sel = W["sin"][pos_c.reshape(-1)].astype(h.dtype)
        wm = write_ok.reshape(R).astype(jnp.int32)
        h, k_all, v_all, tok_g, maxv, loc = self._mk_walk(
            W, h, k_pages_all, v_pages_all, tables, lens,
            active.astype(jnp.int32), cos_sel, sin_sel, tq=T, wmask=wm,
            head_k=topk)
        slots_raw = (tables[jnp.arange(w)[:, None], pos_c // p] * p
                     + pos_c % p).reshape(R)
        new_k, new_v = self._mk_scatter(k_pages_all, v_pages_all,
                                        k_all, v_all, slots_raw,
                                        write_ok.reshape(R))
        if topk is not None:
            if tok_g is None:      # "layer" mode / head fold off
                hN = _rms(h[:, None], W["norm"], W["eps"])
                loc = _mm(hN, W["head"], self.interpret)[:, 0]
                maxv, tok_g = self._tp_topk(loc, topk)
            return (maxv.reshape(w, T, -1), tok_g.reshape(w, T, -1),
                    new_k, new_v)
        if loc is None:
            hN = _rms(h[:, None], W["norm"], W["eps"])
            loc = _mm(hN, W["head"], self.interpret)[:, 0]
            tok_g = self._tp_greedy_token(loc)
        logits = self._gather_logits(loc)
        return (logits.reshape(w, T, -1), tok_g.reshape(w, T),
                new_k, new_v)

    def _build_cb_step(self, w, with_adapters=False, mode="greedy"):
        # "sampled" returns the folded top-sample_k candidate rows
        # instead of logits — under the whole-step megakernel the
        # [w, V] row never materializes even at decode_block=1. "proc"
        # (and the adapter-carrying program) keeps the logits return;
        # the host runs the processor chain + select eagerly
        # (_select_tokens) — same math, same bits.
        # "greedy" returns NO logits: the engine's token vector over all
        # slots (`tok`, read at [:w]) comes back with the greedy token
        # of every active row written, and the next step reads its
        # inputs from it (the other modes take the host's [w] tokens).
        fold = mode == "sampled"
        greedy = mode == "greedy"
        sK = self.sample_k

        def put(tok, tok_g, active):
            with phase("head"):     # the write into the token vector
                return tok.at[:w].set(
                    jnp.where(active, tok_g.astype(tok.dtype), tok[:w]))

        if self._counted:
            # a description with routed experts or an indexer: the same
            # step, which also carries the routing and selection
            # counters through (donated, added to on the device,
            # returned) — `route` None starts them
            def step(W, tok, k_pages_all, v_pages_all, tables, lens,
                     active, route=None):
                rows, sparse = [], []
                out = self._cb_decode_math(
                    W, tok[:w], k_pages_all, v_pages_all, tables, lens,
                    active, w, topk=sK if fold else None,
                    expert_rows=rows, sparse_counts=sparse)
                if route is None:
                    route = self._route_zeros()
                route = dict(route)
                # the counters are by-products of the phase they count
                if rows:
                    with phase("ffn"):
                        rows = jnp.stack(rows)  # [expert layers, held]
                        route.update(
                            rows=route["rows"] + rows,
                            touched=route["touched"] + jnp.sum(
                                rows > 0, axis=1, dtype=jnp.int32),
                            steps=route["steps"] + 1)
                with phase("attend"):
                    for name, add in zip(SPARSE_COUNTS, zip(*sparse)):
                        low = route[name][1] + sum(add)
                        route[name] = jnp.stack(
                            [route[name][0] + (low >> 24),
                             low & ((1 << 24) - 1)]).astype(jnp.int32)
                if fold:
                    return out + (route,)
                logits, tok_g, kps, vps = out
                if greedy:
                    return put(tok, tok_g, active), kps, vps, route
                return logits, kps, vps, route

            return jax.jit(step, donate_argnums=(2, 3, 7))

        def step(W, tok, k_pages_all, v_pages_all, tables, lens, active):
            out = self._cb_decode_math(
                W, tok[:w], k_pages_all, v_pages_all, tables, lens,
                active, w, topk=sK if fold else None)
            if fold:
                topv, topi, kps, vps = out
                return topv, topi, kps, vps
            logits, tok_g, kps, vps = out
            if greedy:
                return put(tok, tok_g, active), kps, vps
            return logits, kps, vps

        def step_ad(W, AD, aid, tok, k_pages_all, v_pages_all, tables,
                    lens, active):
            logits, _tok, kps, vps = self._cb_decode_math(
                W, tok, k_pages_all, v_pages_all, tables, lens, active,
                w, ad=(AD, aid))
            return logits, kps, vps

        Wsp, R, POOL = self._tp_specs()
        if with_adapters:
            ADsp = (self._apool.specs() if self._tpc is not None
                    else None)
            return self._jit_tp(step_ad,
                                in_specs=(Wsp, ADsp, R, R, POOL, POOL,
                                          R, R, R),
                                out_specs=(R, POOL, POOL),
                                donate_argnums=(4, 5))
        return self._jit_tp(step,
                            in_specs=(Wsp, R, POOL, POOL, R, R, R),
                            out_specs=((R, R, POOL, POOL) if fold
                                       else (R, POOL, POOL)),
                            donate_argnums=(2, 3))

    def _route_zeros(self):
        """The routing counters at zero: rows each held expert received
        [expert layers, held], experts touched [expert layers], decode
        steps — all summed over the steps since health() last read."""
        shape = [(layer.ffn.held[1] - layer.ffn.held[0])
                 for layer in self.desc.layers
                 if layer.ffn.kind == "experts"]
        zeros = {}
        if shape:
            zeros.update(
                rows=jnp.zeros((len(shape), shape[0]), jnp.int32),
                touched=jnp.zeros((len(shape),), jnp.int32),
                steps=jnp.zeros((), jnp.int32))
        if self.desc.has_indexer:
            zeros.update({k: jnp.zeros((2,), jnp.int32)
                          for k in SPARSE_COUNTS})
        return zeros

    def _route_read(self):
        """Fold the device's routing counters into the host totals and
        zero them (the one place they are fetched: health())."""
        if self._route_totals is None:
            zeros = self._route_zeros()
            self._route_totals = {k: np.zeros(v.shape, np.int64)
                                  for k, v in zeros.items()}
        for k, v in jax.device_get(self._route_dev).items():
            self._route_totals[k] += v
        self._route_dev = self._route_zeros()
        return self._route_totals

    def _decode_step(self, decodes):
        with _span("cb.decode.prepare"):
            for r in decodes:
                # the token fed this step writes KV at position lens
                pos = int(self._lens_np[r.slot])
                self._make_writable(r, pos, pos + 1)
                self._group_prepare(r, pos, pos + 1)
            w = next(b for b in self._slot_buckets
                     if b > max(r.slot for r in decodes))
            active = np.zeros(w, bool)
            for r in decodes:
                if r.slot < w:
                    active[r.slot] = True
            mode = self._block_mode(decodes)
            aid = self._slot_aid(decodes, w)
            fold = mode == "sampled" and aid is None
            greedy = mode == "greedy" and aid is None
            if aid is not None:
                # adapter-carrying batch: the ADAPTER-AWARE program (the
                # plain program stays untouched — and with megakernel=
                # on, this dispatch IS the documented op-chain fallback;
                # same for the sampling fold, which keeps the
                # materialized arm)
                if self.megakernel:
                    self.adapter_mk_fallbacks += 1
                fn = self._cb_step_ad_fns.get(w)
                if fn is None:
                    fn = self._build_cb_step(w, with_adapters=True)
                    self._cb_step_ad_fns[w] = fn
                    self._program_built = True
                args = (self.weights, self._apool.device,
                        jnp.asarray(aid))
            else:
                fn = self._cb_step_fns.get((w, mode))
                if fn is None:
                    fn = self._build_cb_step(w, mode=mode)
                    self._cb_step_fns[(w, mode)] = fn
                    self._program_built = True
                args = (self.weights,)
            if greedy:
                # the input tokens are on the device already, but for
                # the rows whose pending token the host chose or carried
                # in (imported, restored, selected on the host)
                late = [r for r in decodes
                        if not self._tok_on_dev[r.slot]]
                if late:
                    vals = np.zeros(self.max_batch, np.int32)
                    mask = np.zeros(self.max_batch, bool)
                    for r in late:
                        vals[r.slot], mask[r.slot] = r.tok, True
                    self._tok_dev = _tok_merge(
                        self._tok_dev, jnp.asarray(vals), jnp.asarray(mask))
                    self._tok_on_dev[mask] = True
                tok = self._tok_dev
            else:
                for r in decodes:
                    self._tok_np[r.slot] = r.tok
                tok = jnp.asarray(self._tok_np[:w].copy())
            # the new token of the row fed at position lens occupies
            # position lens+1 — its PRNG counter (BEFORE the increment)
            positions = self._lens_np[:w] + 1
        with self._first_call_span(), _span("cb.decode_step"), \
                _span("cb.decode.dispatch"):
            # returns without waiting for the device. Every host array
            # is handed over as a COPY: the transfer may read the buffer
            # after the call returns, and nothing waits for the program
            # before these rows change again
            out = fn(
                *args, tok, self.k_pages, self.v_pages,
                jnp.asarray(self._tables_np[:w].copy()),
                jnp.asarray(self._lens_np[:w].copy()), jnp.asarray(active),
                *([self._route_dev] if self._counted else []))
            if self._counted:           # the counters ride along
                *out, self._route_dev = out
            *head, self.k_pages, self.v_pages = out
        # what the host knows without the tokens is booked HERE, at
        # dispatch: every active row advanced by one position
        for r in decodes:
            self._lens_np[r.slot] += 1
            self._group_release(r, int(self._lens_np[r.slot]))
        p = self.page_size
        ctx = self._lens_np[[r.slot for r in decodes]]
        for g in self.groups:
            # cached tokens this step's queries read, layer by layer: the
            # whole context, or what the window leaves of it
            seen = ctx if g.window is None else np.minimum(ctx, g.window)
            g.kv_tokens_read += len(g.layers) * int(seen.sum())
            if not g.row_width:
                # K and V pools by page: the paged decode kernel walks a
                # seat's live pages [first, last), the page of its
                # oldest visible key to the page of its newest
                g.kv_pages_walked += len(g.layers) * int(
                    (-(-ctx // p) - (ctx - seen) // p).sum())
        if greedy:
            self._tok_dev = head[0]
        return _Dispatched(True, [(r, r.slot) for r in decodes], mode=mode,
                           w=w, positions=positions,
                           toks=head[0] if greedy else None,
                           top=tuple(head) if fold else None,
                           logits=None if greedy or fold else head[0])

    def _resolve(self, rec):
        """Fetch a dispatched program's tokens (the host blocks here
        until THAT program is done; the device meanwhile runs whatever
        was dispatched behind it) and book them through the same
        functions as ever: `_push_token`, retirement. A row whose
        request left while the program was in flight is skipped: it was
        cancelled, or it met its EOS in the program before this one and
        ran one step too many, whose token is discarded and whose KV
        write landed in its own page inside its budget (programs run in
        dispatch order, so a page freed here is not written by an older
        program after a newer owner's)."""
        if not rec.decode:
            (r, slot), = rec.rows
            if not rec.last or r.state != PREFILL or r.slot is None:
                return          # no token yet / cancelled in flight
            with _span("cb.prefill.first_token"):
                if rec.toks is not None:
                    tok = np.asarray(rec.toks)[slot]
                else:
                    tok = self._select_tokens([r], [r.t0], rec.mode,
                                              logits=rec.logits)[0]
                r.state = DECODE
                self._push_token(r, tok)
            return
        with _span("cb.decode.fetch"):
            if rec.toks is not None:
                toks = np.asarray(rec.toks)
            else:
                rows = [None] * rec.w
                for r, slot in rec.rows:
                    rows[slot] = r
                topv, topi = rec.top or (None, None)
                toks = self._select_tokens(
                    rows, rec.positions, rec.mode, logits=rec.logits,
                    topv=topv, topi=topi)
        with _span("cb.decode.push"):
            for r, slot in rec.rows:
                if r.state != DECODE or r.slot is None:
                    self.ahead_overrun_rows += r.state == DONE
                    continue
                if rec.toks is None:        # the host is its source now
                    self._tok_on_dev[slot] = False
                self._push_token(r, toks[slot])

    # -- fused multi-step decode (device-resident blocks) ------------------
    def _idle_or_raise(self):
        """Nothing running and nothing admitted: either truly idle
        (False) or the queue head / demoted head cannot fit an IDLE
        engine — a real capacity bug, not back-pressure."""
        if self._queue:
            head = self._pick_next()
            need = self._pages_needed(head.t0, head.max_new_tokens)
            raise EngineFullError(
                f"request {head.uid} cannot be admitted into an idle "
                f"engine: needs {need} KV pages but only "
                f"{self.allocator.available} of "
                f"{self.allocator.n_pages} are free (page pool "
                "pinned?)")
        if self._demoted:
            uid = next(iter(self._demoted))
            d = self._requests[uid].demote
            need = d["n_pages"] - len(d["shared"])
            raise EngineFullError(
                f"demoted request {uid} cannot restore into an idle "
                f"engine: needs {need} KV pages but only "
                f"{self.allocator.available} of "
                f"{self.allocator.n_pages} are free (page pool "
                "pinned?)")
        return False

    def _build_cb_fused(self, w, with_prefill, with_decode,
                        with_adapters=False, mode="greedy"):
        """ONE compiled program for a whole scheduling block at slot
        width w: a ragged prefill phase — every prefilling slot advances
        one chunk at its OWN offset, in one dispatch — followed by
        decode_block device-resident decode steps (lax.scan over the
        same per-step math) with on-device sampling and per-slot
        EOS/budget retirement flags. The host only sees the block's
        outputs: sampled tokens, an emitted mask, and the final carries
        (which the next block can consume WITHOUT a host round trip —
        see _chain_block).

        mode selects the per-block sampling program (see _block_mode):

        * "greedy"  — no extra inputs; tokens are the decode math's own
          argmax. No PRNG anywhere in the program.
        * "sampled" — six extra [w] arrays ride after eos_ids (seeds
          u32, do_sample bool, temperature/top_p/min_p f32, top_k i32).
          Tokens come from select_from_topk over the top-sample_k
          (value, id) rows — the IN-KERNEL fold, so the [w, V] logits
          are never materialized; an adapter-carrying block takes
          lax.top_k of the materialized logits (bitwise-identical
          candidates either way). Every token's key is
          fold_in(key(seed), absolute_position) — no split chain, so
          the stream is invariant to batch composition, block size and
          megakernel mode.
        * "proc"    — the sampled inputs plus penalty/grammar state
          (repetition/presence/frequency [w] f32, counts [w, V] i32,
          grammar id/state [w] i32 and the stacked [G, S, V] automaton
          table/mask). Logits materialize in f32, ride the processor
          chain (penalties, then the grammar mask), then the same
          top-k select. counts/gstate advance in the scan carry;
          their final values are DISCARDED — the host recomputes them
          authoritatively in _push_token.

        Ragged prefill attention: the Pallas ragged kernel
        (per-slot q_start/ctx_len scalar prefetch) on TPU; under
        interpret/CPU the dense gathered form, which is what stays
        byte-identical to the per-step engine's chunk prefill."""
        chunk = self.prefill_chunk
        K = self.decode_block
        p = self.page_size
        mp = self.max_pages_per_seq
        sK = self.sample_k
        NEX = {"greedy": 0, "sampled": 6, "proc": 14}[mode]
        use_kernel = (self.ragged_kernel is True) or \
            (self.ragged_kernel is None and not self.interpret)

        def prefill_phase(W, ids, k_pages_all, v_pages_all, tables,
                          starts, ends, pf_act, ad=None):
            h = jnp.take(W["emb"], ids, axis=0).astype(self.kv_dtype)
            pos = starts[:, None] + jnp.arange(chunk, dtype=jnp.int32)
            oob = jnp.int32(self.n_pages * p)
            ctx = jnp.minimum(starts + chunk, ends)
            new_k, new_v = [], []
            for li, wset in enumerate(W["layers"]):
                ad_li = None if ad is None else \
                    self._ad_sel(ad[0], ad[1], li)
                q, k, v = self._layer_qkv(W, wset, h, pos, ad=ad_li)
                slots = tables[jnp.arange(w)[:, None], pos // p] * p \
                    + pos % p
                # inactive slots and padded tails write NOTHING
                ok_w = jnp.logical_and(pos < ends[:, None],
                                       pf_act[:, None])
                slots = jnp.where(ok_w, slots, oob)
                kp = k_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
                vp = v_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
                kp = kp.at[slots].set(k.astype(self.kv_dtype),
                                      mode="drop")
                vp = vp.at[slots].set(v.astype(self.kv_dtype),
                                      mode="drop")
                kp = kp.reshape(self.n_pages, p, self.nh_kv_l, self.hd)
                vp = vp.reshape(self.n_pages, p, self.nh_kv_l, self.hd)
                k_pages_all = _pools_put(k_pages_all, li, kp, new_k)
                v_pages_all = _pools_put(v_pages_all, li, vp, new_v)
                if use_kernel:
                    attn = ragged_paged_attention(
                        q, kp, vp, tables, ctx, starts,
                        active=pf_act.astype(jnp.int32),
                        interpret=self.interpret)
                else:
                    ck = kp[tables].reshape(w, mp * p, self.nh_kv_l,
                                            self.hd)
                    cv = vp[tables].reshape(w, mp * p, self.nh_kv_l,
                                            self.hd)
                    ck = expand_kv_heads(ck, self.nh_l)
                    cv = expand_kv_heads(cv, self.nh_l)
                    logits = jnp.einsum("bqhd,bkhd->bhqk", q, ck) \
                        / math.sqrt(self.hd)
                    kpos = jnp.arange(mp * p)[None, None, None, :]
                    qpos = pos[:, None, :, None]
                    logits = jnp.where(kpos <= qpos, logits, -1e30)
                    wts = jax.nn.softmax(logits.astype(jnp.float32),
                                         -1).astype(q.dtype)
                    attn = jnp.einsum("bhqk,bkhd->bqhd", wts, cv)
                h = self._layer_tail(W, wset, h, attn, ad=ad_li)
            h = _rms(h, W["norm"], W["eps"])
            last = jnp.clip(ends - 1 - starts, 0, chunk - 1)
            h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)
            logits = self._lm_head(W, h_last)
            return (logits[:, 0], _pools_result(k_pages_all, new_k),
                    _pools_result(v_pages_all, new_v))

        def decode_scan(W, k_pages_all, v_pages_all, tables, tok, lens,
                        act, rem, eos_ids, ex, ad=None):
            proc = mode == "proc"
            if proc:
                (seeds, dos, temp, tkk, tpp, minp, rep, pres, frq,
                 counts0, gid, gstate0, gtab, gmask) = ex
            elif mode == "sampled":
                seeds, dos, temp, tkk, tpp, minp = ex

            def body(carry, _):
                if proc:
                    tok, lens, act, rem, counts, gstate, kps, vps = carry
                else:
                    tok, lens, act, rem, kps, vps = carry
                if mode == "sampled" and ad is None:
                    # the sampling fold: top-sample_k (value, id) rows
                    # straight from the decode math — under the whole-
                    # step megakernel the IN-KERNEL running merge, so
                    # the [w, V] logits are never materialized
                    topv, topi, kps, vps = self._cb_decode_math(
                        W, tok, kps, vps, tables, lens, act, w, topk=sK)
                    gtok = None
                else:
                    logits, gtok, kps, vps = self._cb_decode_math(
                        W, tok, kps, vps, tables, lens, act, w, ad=ad)
                if mode == "greedy":
                    # the greedy token came out of the decode math
                    # itself (whole-step mode: the kernel's running
                    # argmax; tp: argmax-of-local-max) — bitwise equal
                    # to argmax over the gathered logits, which DCE
                    # then prunes from the compiled scan
                    nxt = gtok
                else:
                    if proc:
                        lg = apply_penalties(
                            logits.astype(jnp.float32), counts,
                            rep, pres, frq)
                        lg = jnp.where(gmask[gid, gstate], lg, NEG)
                        topv, topi = jax.lax.top_k(lg, sK)
                        topi = topi.astype(jnp.int32)
                    elif gtok is not None:
                        # materialized arm (adapter fallback) —
                        # bitwise the fold's candidates
                        topv, topi = jax.lax.top_k(logits, sK)
                        topv = topv.astype(jnp.float32)
                        topi = topi.astype(jnp.int32)
                    # counter-based stream: the token entering position
                    # lens+1 is ALWAYS drawn with fold_in(seed, lens+1)
                    nxt = select_from_topk(
                        topv, topi, fold_keys(seeds, lens + 1), dos,
                        temp, tkk, tpp, minp)
                nxt = jnp.where(act, nxt.astype(tok.dtype), tok)
                emit = act
                rem = jnp.where(act, rem - 1, rem)
                lens = jnp.where(act, lens + 1, lens)
                # retire ON DEVICE at the request's own EOS (-1 sentinel
                # never matches: token ids are non-negative) or budget —
                # a retired slot stops writing KV and skips attention
                # compute/DMA for the REST of the block
                act = jnp.logical_and(
                    act, jnp.logical_and(rem > 0, nxt != eos_ids))
                if proc:
                    counts = counts.at[jnp.arange(w), nxt].add(
                        jnp.where(emit, jnp.int32(1), jnp.int32(0)))
                    gstate = jnp.where(emit, gtab[gid, gstate, nxt],
                                       gstate)
                    return ((nxt, lens, act, rem, counts, gstate,
                             kps, vps), (nxt, emit))
                return (nxt, lens, act, rem, kps, vps), (nxt, emit)

            if proc:
                carry0 = (tok, lens, act, rem, counts0, gstate0,
                          k_pages_all, v_pages_all)
                (tok, lens, act, rem, _, _, kps, vps), (toks, emitted) \
                    = jax.lax.scan(body, carry0, None, length=K)
            else:
                carry0 = (tok, lens, act, rem, k_pages_all, v_pages_all)
                (tok, lens, act, rem, kps, vps), (toks, emitted) = \
                    jax.lax.scan(body, carry0, None, length=K)
            return toks, emitted, tok, lens, act, rem, kps, vps

        T = self._spec                  # verify width (0 = spec off)
        iT = (jnp.arange(T, dtype=jnp.int32)[None, :] if T else None)
        iD = (jnp.arange(max(T - 1, 0), dtype=jnp.int32)[None, :]
              if T else None)

        def spec_scan(W, k_pages_all, v_pages_all, tables, tok, lens,
                      act, rem, eos_ids, ex, drafts, dlen, ad=None):
            """K VERIFY passes with accept/reject inside the scan
            carries: each pass feeds [tok, drafts_s] (T tokens), samples
            the target's token at every position, and commits the
            longest draft prefix the target agrees with plus the
            target's own next token. `lens` advances by the emitted
            count (length-gated writes make rejection free — nothing to
            scrub), `rem`/`act` retire on budget/EOS exactly like the
            plain scan. `dlen` is PER PASS [K, w] (a short drafter
            continuation offers fewer — possibly zero — drafts in later
            passes; zero-padding is never counted as an offered draft).
            Outputs [K, w, T] tokens + an emitted mask; the host replays
            them through the same `_push_token` path.

            Sampled verify is SAMPLE-AND-MATCH: the target's token g_j
            at feed position j is drawn with the position key
            fold_in(seed, lens+1+j) — the SAME key the unspeculated
            stream would use for that position — and draft j is
            accepted iff it EQUALS g_j. That is rejection sampling for
            the q=delta(draft) proposal (accept prob = p(draft); the
            emitted token is distributed exactly p either way), and it
            makes the committed stream byte-identical to the
            unspeculated sampled stream at the same key schedule.
            ("proc" never reaches here — speculation + processors is
            rejected at add_request.)"""
            if mode == "sampled":
                seeds, dos, temp, tkk, tpp, minp = ex

                def bt(a):             # [w] -> [w*T] slot-major
                    return jnp.broadcast_to(
                        a[:, None], (w, T)).reshape(-1)

            def body(carry, xs):
                drafts_s, dlen_s = xs
                tok, lens, act, rem, kps, vps = carry
                feed = jnp.concatenate([tok[:, None], drafts_s], axis=1)
                if mode == "sampled" and ad is None:
                    topv, topi, kps, vps = self._cb_spec_verify_math(
                        W, feed, kps, vps, tables, lens, act, rem,
                        dlen_s, w, topk=sK)
                    gtok = None
                else:
                    logits, gtok, kps, vps = self._cb_spec_verify_math(
                        W, feed, kps, vps, tables, lens, act, rem,
                        dlen_s, w, ad=ad)
                if mode == "sampled":
                    if gtok is not None:
                        topv, topi = jax.lax.top_k(logits, sK)
                        topv = topv.astype(jnp.float32)
                        topi = topi.astype(jnp.int32)
                    pos = (lens[:, None] + jnp.int32(1) + iT).reshape(-1)
                    g = select_from_topk(
                        topv.reshape(w * T, -1),
                        topi.reshape(w * T, -1),
                        fold_keys(bt(seeds), pos), bt(dos), bt(temp),
                        bt(tkk), bt(tpp), bt(minp))
                    g = g.reshape(w, T).astype(tok.dtype)
                else:
                    g = gtok.astype(tok.dtype)
                # accepted prefix: draft i matches the target's token at
                # its position AND every earlier draft matched (greedy =
                # deterministic argmax agreement; sampled = the q=delta
                # case of rejection sampling, distribution-exact)
                match = jnp.logical_and(drafts_s == g[:, :T - 1],
                                        iD < dlen_s[:, None])
                # i32-pinned reductions: under the package's global x64,
                # integer sum/cumsum otherwise accumulate to i64 and the
                # scan carry dtypes stop matching
                n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32),
                                            axis=1, dtype=jnp.int32),
                                axis=1, dtype=jnp.int32)
                cap = jnp.minimum(jnp.int32(T), rem)
                n_emit = jnp.minimum(n_acc + jnp.int32(1), cap)
                is_eos = g == eos_ids[:, None].astype(tok.dtype)
                eos_before = jnp.cumsum(is_eos.astype(jnp.int32),
                                        axis=1, dtype=jnp.int32) \
                    - is_eos.astype(jnp.int32)
                # emit the prefix up to the first EOS (inclusive) within
                # the accepted+bonus window — exactly where the per-step
                # engine's _push_token sequence would stop
                emit = jnp.logical_and(
                    jnp.logical_and(iT < n_emit[:, None],
                                    eos_before == jnp.int32(0)),
                    act[:, None])
                n_fin = jnp.sum(emit.astype(jnp.int32), axis=1,
                                dtype=jnp.int32)
                last = jnp.maximum(n_fin - jnp.int32(1), jnp.int32(0))
                nxt = jnp.take_along_axis(g, last[:, None], axis=1)[:, 0]
                nxt = jnp.where(act, nxt, tok)
                lens = jnp.where(act, lens + n_fin, lens)
                rem = jnp.where(act, rem - n_fin, rem)
                hit_eos = jnp.any(jnp.logical_and(emit, is_eos), axis=1)
                act = jnp.logical_and(
                    act, jnp.logical_and(rem > 0,
                                         jnp.logical_not(hit_eos)))
                return (nxt, lens, act, rem, kps, vps), (g, emit)

            carry0 = (tok, lens, act, rem, k_pages_all, v_pages_all)
            (tok, lens, act, rem, kps, vps), (toks, emitted) = \
                jax.lax.scan(body, carry0,
                             (drafts, dlen))   # [K,w,T-1] / [K,w]
            return toks, emitted, tok, lens, act, rem, kps, vps

        def fused(W, k_pages_all, v_pages_all, tables, pf_ids, pf_act,
                  pf_start, pf_end, tok, lens, act, rem, eos_ids,
                  *rest, ad=None):
            ex = rest[:NEX]
            drafts, dlen = ((rest[NEX], rest[NEX + 1]) if T
                            else (None, None))
            first = toks = emitted = None
            if with_prefill:
                pf_logits, k_pages_all, v_pages_all = prefill_phase(
                    W, pf_ids, k_pages_all, v_pages_all, tables,
                    pf_start, pf_end, pf_act, ad=ad)
                if mode == "greedy":
                    first = jnp.argmax(pf_logits, axis=-1)
                else:
                    seeds, dos, temp, tkk, tpp, minp = ex[:6]
                    lg = pf_logits
                    if mode == "proc":
                        rep, pres, frq = ex[6:9]
                        counts0, gid, gstate0 = ex[9], ex[10], ex[11]
                        gtab, gmask = ex[12], ex[13]
                        lg = apply_penalties(lg.astype(jnp.float32),
                                             counts0, rep, pres, frq)
                        lg = jnp.where(gmask[gid, gstate0], lg, NEG)
                    topv, topi = jax.lax.top_k(lg, sK)
                    topv = topv.astype(jnp.float32)
                    topi = topi.astype(jnp.int32)
                    # the chunk's last token sits at position pf_end-1;
                    # the token it emits enters position pf_end — its
                    # key counter, same schedule as the decode scan
                    first = select_from_topk(
                        topv, topi, fold_keys(seeds, pf_end), dos,
                        temp, tkk, tpp, minp)
            if with_decode:
                if T:
                    (toks, emitted, tok, lens, act, rem,
                     k_pages_all, v_pages_all) = spec_scan(
                        W, k_pages_all, v_pages_all, tables, tok, lens,
                        act, rem, eos_ids, ex, drafts, dlen, ad=ad)
                else:
                    (toks, emitted, tok, lens, act, rem,
                     k_pages_all, v_pages_all) = decode_scan(
                        W, k_pages_all, v_pages_all, tables, tok, lens,
                        act, rem, eos_ids, ex, ad=ad)
            return (first, toks, emitted, tok, lens, act, rem,
                    k_pages_all, v_pages_all)

        Wsp, R, POOL = self._tp_specs()
        out_specs = (R, R, R, R, R, R, R, POOL, POOL)
        if with_adapters:
            # adapter-aware block: (AD, aid) ride right after W; same
            # carries, same outputs — the plain program is untouched
            def fused_ad(W, AD, aid, k_pages_all, v_pages_all, tables,
                         pf_ids, pf_act, pf_start, pf_end, tok, lens,
                         act, rem, eos_ids, *rest):
                return fused(W, k_pages_all, v_pages_all, tables,
                             pf_ids, pf_act, pf_start, pf_end, tok,
                             lens, act, rem, eos_ids, *rest,
                             ad=(AD, aid))

            ADsp = (self._apool.specs() if self._tpc is not None
                    else None)
            in_specs = (Wsp, ADsp, R, POOL, POOL) \
                + (R,) * (10 + NEX + (2 if T else 0))
            return self._jit_tp(fused_ad, in_specs=in_specs,
                                out_specs=out_specs,
                                donate_argnums=(3, 4))
        # positional arg specs: mode extras ride after eos_ids,
        # drafts/dlen after those (only when speculating)
        in_specs = (Wsp, POOL, POOL) + (R,) * (10 + NEX
                                               + (2 if T else 0))
        return self._jit_tp(fused, in_specs=in_specs,
                            out_specs=out_specs, donate_argnums=(1, 2))

    def _get_fused(self, w, with_prefill, with_decode,
                   with_adapters=False, mode="greedy"):
        key = (w, with_prefill, with_decode, with_adapters, mode)
        fn = self._cb_fused_fns.get(key)
        if fn is None:
            fn = self._build_cb_fused(w, with_prefill, with_decode,
                                      with_adapters, mode=mode)
            self._cb_fused_fns[key] = fn
            self._program_built = True
        return fn

    def _fused_step(self):
        """One block-granular engine iteration (decode_block > 1):
        process the previous block if one is still in flight, dispatch
        the next, fetch and apply tokens. In a pure-decode steady state
        the NEXT block is dispatched from this block's device carries
        BEFORE this block's tokens are fetched, so the host's readback +
        bookkeeping overlaps the device's compute."""
        try:
            if self._pending is not None:
                blk = self._pending
                self._pending = None
            else:
                blk = self._dispatch_block()
                if blk is None:
                    return False
                if blk is True:        # every participant faulted at
                    return True        # the sync point; still a step
            if self._can_chain(blk):
                self._pending = self._chain_block(blk)
            self._process_block(blk)
        except InjectedFault:
            raise                      # faults fire at dispatch only
        except Exception:
            self._pending = None
            self._abort_in_flight()
            raise
        return True

    def _dispatch_block(self):
        """Host sync point: shed deadlines, admit, check fault points
        (block granularity — once per request per block), then dispatch
        ONE fused program. Returns a _FusedBlock, True when every
        participant faulted, or None when idle."""
        self._expire_deadlines()
        self._restore_sweep()
        self._admit()
        prefills = [r for r in self._slots if r and r.state == PREFILL]
        decodes = [r for r in self._slots if r and r.state == DECODE]
        if not prefills and not decodes:
            self._idle_or_raise()      # raises on a stuck queue head
            return None
        live_pf, live_dec = [], []
        for r in prefills:
            try:
                fault_point("cb.prefill", detail=f"uid={r.uid}")
                live_pf.append(r)
            except InjectedFault as e:
                self._fail_request(r, "prefill", e)
        for r in decodes:
            try:
                fault_point("cb.decode", detail=f"uid={r.uid}")
                live_dec.append(r)
            except InjectedFault as e:
                self._fail_request(r, "decode", e)
        if not live_pf and not live_dec:
            self.steps += 1
            return True
        K = self.decode_block
        chunk = self.prefill_chunk
        top = max(r.slot for r in live_pf + live_dec)
        w = next(b for b in self._slot_buckets if b > top)
        blk = _FusedBlock(w, K)
        pf_ids = np.zeros((w, chunk), np.int64)
        pf_act = np.zeros(w, bool)
        pf_start = np.zeros(w, np.int32)
        pf_end = np.zeros(w, np.int32)
        for r in live_pf:
            start = r.filled
            end = min(start + chunk, r.t0)
            self._make_writable(r, start, end)
            pf_ids[r.slot, :end - start] = r.ids[start:end]
            pf_act[r.slot] = True
            pf_start[r.slot] = start
            pf_end[r.slot] = r.t0
            blk.pf_items.append((r, end))
        act = np.zeros(w, bool)
        rem = np.zeros(w, np.int32)
        eos = np.full(w, -1, np.int32)
        T = self._spec
        if T:
            # host side of the draft/verify boundary: the drafter
            # proposes an OPTIMISTIC continuation of S*T tokens per
            # request, sliced into per-pass drafts — pass s's slice is
            # only exactly-positioned if every earlier pass fully
            # accepted; otherwise it mostly mismatches and that pass
            # degrades to one (target-chosen) token, never to a wrong
            # one. dlen is PER PASS: a short continuation offers fewer
            # (or zero) drafts in later passes — zero-pad is never
            # charged as an offered draft (it would punish a short-but-
            # right drafter and collapse adaptive draft_k).
            drafts_np = np.zeros((K, w, T - 1), np.int64)
            dlen_np = np.zeros((K, w), np.int32)
        for r in live_dec:
            if T:
                try:
                    fault_point("cb.draft", detail=f"uid={r.uid}")
                except InjectedFault as e:
                    self._fail_request(r, "draft", e)
                    continue
                want = min(r.draft_k, T - 1)
                cont = np.empty((0,), np.int64)
                if want > 0:
                    t_draft = (time.monotonic()
                               if self._tel is not None else None)
                    try:
                        cont = np.asarray(self._drafter.timed_propose(
                            np.concatenate(
                                [r.ids, np.asarray(r.out, np.int64)]),
                            K * (want + 1),
                            sampling=r.sampling), np.int64).ravel()
                    except Exception:
                        # a broken drafter degrades speculation for this
                        # request, never its correctness (verification
                        # emits the target's token regardless)
                        self.draft_errors += 1
                        cont = np.empty((0,), np.int64)
                    if t_draft is not None:
                        self._tel.observe(
                            "draft_ms",
                            (time.monotonic() - t_draft) * 1e3)
                # a fully-accepted pass emits want drafts + the bonus
                # token, so consecutive passes stride want+1 through the
                # continuation — striding by T instead would misalign
                # every pass after the first whenever adaptive K has
                # shrunk want below T-1, even under perfect drafting
                stride = want + 1
                for s in range(K):
                    seg = cont[s * stride:s * stride + want]
                    drafts_np[s, r.slot, :seg.size] = seg
                    dlen_np[s, r.slot] = seg.size
                try:
                    # the verify boundary proper: AFTER this request's
                    # drafter ran, BEFORE it joins the verify dispatch
                    # (docs/robustness.md) — retires one request with
                    # the same stage the plain decode boundary uses
                    fault_point("cb.verify", detail=f"uid={r.uid}")
                except InjectedFault as e:
                    self._fail_request(r, "decode", e)
                    continue
            pos = int(self._lens_np[r.slot])
            # the block writes KV at positions [pos, pos+K) while the
            # slot stays active (speculation widens that to K verify
            # passes of up to T tokens each); CoW every shared page it
            # can touch NOW (the only shareable page decode can reach is
            # the prompt's partial tail page, so this copies exactly
            # what the per-step path would)
            span = K * T if T else K
            hi = min(pos + span, r.t0 + r.max_new_tokens - 1)
            self._make_writable(r, pos, max(hi, pos + 1))
            self._tok_np[r.slot] = r.tok
            act[r.slot] = True
            rem[r.slot] = r.max_new_tokens - len(r.out)
            if r.eos_token_id is not None:
                eos[r.slot] = r.eos_token_id
            blk.dec_items.append(r)
        if T and not blk.dec_items and not live_pf:
            self.steps += 1            # every decoder faulted at draft
            return True
        blk.has_prefill = bool(live_pf)
        blk.has_decode = bool(blk.dec_items)
        blk.mode = self._block_mode(
            [r for r, _end in blk.pf_items] + blk.dec_items)
        blk.extras = self._block_extras(blk)
        aid = self._slot_aid(live_pf + blk.dec_items, w)
        ad_args = ()
        if aid is not None:
            if self.megakernel and blk.has_decode:
                # only decode/verify dispatches ever RUN the megakernel
                # — a prefill-only block left nothing
                self.adapter_mk_fallbacks += 1
            blk.aid = jnp.asarray(aid)
            ad_args = (self._apool.device, blk.aid)
        fn = self._get_fused(w, blk.has_prefill, blk.has_decode,
                             aid is not None, blk.mode)
        blk.tables = jnp.asarray(self._tables_np[:w])
        blk.eos_dev = jnp.asarray(eos)
        if T:
            blk.dlens = dlen_np
        spec_args = ((jnp.asarray(drafts_np), jnp.asarray(dlen_np))
                     if T else ())
        with self._first_call_span(), _span("cb.block"):
            (blk.first, blk.toks, blk.emitted, blk.tok_fin, blk.lens_fin,
             blk.act_fin, blk.rem_fin, self.k_pages,
             self.v_pages) = fn(
                self.weights, *ad_args, self.k_pages, self.v_pages,
                blk.tables,
                jnp.asarray(pf_ids), jnp.asarray(pf_act),
                jnp.asarray(pf_start), jnp.asarray(pf_end),
                jnp.asarray(self._tok_np[:w]),
                jnp.asarray(self._lens_np[:w]),
                jnp.asarray(act), jnp.asarray(rem), blk.eos_dev,
                *blk.extras, *spec_args)
        self.fused_blocks += 1
        # steps advance by the block's DEVICE micro-steps so TTL budgets
        # stay comparable with the per-step engine (expiry itself is
        # only checked here, at block boundaries — rounded UP). A spec
        # block's K micro-steps are VERIFY PASSES (1..T tokens each):
        # TTLs count passes, not tokens.
        self.steps += len(live_pf) + (K if blk.has_decode else 0)
        self.prefill_steps += len(live_pf)
        self.decode_steps += K if blk.has_decode else 0
        return blk

    def _can_chain(self, blk):
        """Pipeline only in the pure-decode steady state where the next
        block's inputs cannot depend on this block's tokens: no prefill
        anywhere, nothing queued, no deadline/TTL holder (their expiry
        is promised at SINGLE block boundaries), no armed fault points
        (faults fire at host sync points), no copy-on-write pending, and
        at least one request that must outlive this block."""
        if blk.K <= 1 or not blk.has_decode or blk.has_prefill:
            return False
        if self._spec:
            # the drafter runs on the HOST against the newest context;
            # a chained block would re-verify stale drafts (correct but
            # useless speculation) — dispatch from the sync point instead
            return False
        if self._queue or self._pending is not None:
            return False
        if self._demoted:
            # restores happen at the host sync point a chain skips; a
            # parked request must not wait out another's whole budget
            return False
        if any(s is not None and s.state == PREFILL for s in self._slots):
            return False
        if _faults_armed():
            return False
        if blk.mode == "proc":
            # penalty counts and grammar state advance on the HOST in
            # _push_token; a chained block would run the processor
            # chain against stale state
            return False
        ok = False
        for r in blk.dec_items:
            if r.state != DECODE:
                continue
            if r.deadline is not None or r.ttl_steps is not None:
                return False
            if r.shared_idx:
                return False
            if r.sampling.stop:
                # stop sequences retire on the HOST; a chained block
                # would keep writing KV into pages the retirement frees
                return False
            if r.max_new_tokens - len(r.out) > blk.K:
                ok = True
        return ok

    def _chain_block(self, blk):
        """Dispatch block N+1 straight from block N's device carries —
        before N's tokens are fetched. No host state crosses: tables,
        eos ids, tok/lens/act/rem all ride on device."""
        chunk = self.prefill_chunk
        w = blk.w
        nxt = _FusedBlock(w, blk.K)
        nxt.dec_items = blk.dec_items
        nxt.tables = blk.tables
        nxt.eos_dev = blk.eos_dev
        nxt.has_decode = True
        nxt.chained = True
        nxt.mode = blk.mode             # sampled params are static
        nxt.extras = blk.extras         # across a chain; the PRNG
        #                                 counters ride the device lens
        nxt.aid = blk.aid               # adapter ids are static across
        ad_args = ()                    # a chain (admission happens at
        if blk.aid is not None:         # host sync points only)
            if self.megakernel:
                self.adapter_mk_fallbacks += 1
            ad_args = (self._apool.device, blk.aid)
        fn = self._get_fused(w, False, True, blk.aid is not None,
                             blk.mode)
        dummy = self._pf_dummies.get(w)
        if dummy is None:
            dummy = (jnp.asarray(np.zeros((w, chunk), np.int64)),
                     jnp.asarray(np.zeros(w, bool)),
                     jnp.asarray(np.zeros(w, np.int32)),
                     jnp.asarray(np.zeros(w, np.int32)))
            self._pf_dummies[w] = dummy
        with self._first_call_span(), _span("cb.block_chain"):
            (nxt.first, nxt.toks, nxt.emitted, nxt.tok_fin, nxt.lens_fin,
             nxt.act_fin, nxt.rem_fin, self.k_pages,
             self.v_pages) = fn(
                self.weights, *ad_args, self.k_pages, self.v_pages,
                blk.tables,
                *dummy, blk.tok_fin, blk.lens_fin, blk.act_fin,
                blk.rem_fin, blk.eos_dev, *blk.extras)
        self.fused_blocks += 1
        self.chained_blocks += 1
        self.steps += blk.K
        self.decode_steps += blk.K
        return nxt

    def _process_block(self, blk):
        """Fetch a block's tokens (the only blocking readback) and
        replay them through the SAME retirement bookkeeping the
        per-step path uses — host and device agree on EOS/budget by
        construction, so _push_token retires exactly where the device's
        active flag dropped."""
        first = np.asarray(blk.first) if blk.has_prefill else None
        if blk.has_decode:
            toks = np.asarray(blk.toks)
            emitted = np.asarray(blk.emitted)
        for r, end in blk.pf_items:
            if r.state != PREFILL or r.slot is None:
                continue               # cancelled while in flight
            r.filled = end
            if self._tel is not None:
                self._tel.req_event(self._tel_src, r.uid,
                                    "prefill_chunk", filled=end)
            if end >= r.t0:
                # prompt complete: publish pages, then its first token
                # (sampled ON DEVICE from the final chunk's logits)
                self._publish_prefix(r)
                self._lens_np[r.slot] = r.t0
                r.state = DECODE
                self._push_token(r, int(first[r.slot]))
        if blk.has_decode and self._spec:
            # speculative block: toks/emitted are [K, w, T] — replay
            # each pass's emitted prefix through the SAME _push_token
            # retirement path, then feed the acceptance stats to the
            # per-request adaptive-K policy
            T = self._spec
            for s in range(toks.shape[0]):
                for r in blk.dec_items:
                    if r.state != DECODE or r.slot is None:
                        continue       # retired at an earlier pass /
                        #                cancelled while in flight
                    em = emitted[s, r.slot]
                    n = int(em.sum())
                    if n == 0:
                        continue
                    # drafts past the request's remaining budget can
                    # never be accepted (the device caps emission at
                    # rem) — don't charge them as rejected, or a
                    # perfect drafter reads below 1.0 at every
                    # end-of-budget pass
                    rem_r = r.max_new_tokens - len(r.out)
                    offered = min(int(blk.dlens[s, r.slot]),
                                  max(rem_r - 1, 0))
                    accepted = min(max(0, n - 1), offered)
                    self.spec_passes += 1
                    self.spec_emitted += n
                    self.spec_drafted_total += offered
                    self.spec_accepted_total += accepted
                    r.spec_drafted += offered
                    r.spec_accepted += accepted
                    if r.sampling.do_sample:
                        # sampled speculation (sample-and-match): its
                        # own acceptance telemetry, since its rate is
                        # governed by the temperature, not just drafter
                        # quality
                        self._spec_sampled_offered += offered
                        self._spec_sampled_accepted += accepted
                    if self._tel is not None:
                        self._tel.req_event(
                            self._tel_src, r.uid, "spec_pass",
                            offered=offered, accepted=accepted,
                            emitted=n)
                    if self.spec_adaptive and offered:
                        # shrink fast on a complete miss, grow on a
                        # clean sweep; the window [1, T-1] keeps at
                        # least one draft in flight so recovery costs
                        # one cheap pass, not a policy reset
                        if accepted >= offered and n > offered:
                            r.draft_k = min(T - 1, max(1, r.draft_k * 2))
                        elif accepted == 0:
                            r.draft_k = max(1, r.draft_k // 2)
                    slot = r.slot
                    for i in range(T):
                        if not em[i]:
                            continue
                        self._lens_np[slot] += 1
                        self._push_token(r, int(toks[s, slot, i]))
                        if r.state != DECODE:
                            break      # EOS/budget retirement mid-pass
        elif blk.has_decode:
            for k in range(blk.K):
                for r in blk.dec_items:
                    if r.state != DECODE or r.slot is None:
                        continue       # retired at an earlier k /
                        #                cancelled while in flight
                    if not emitted[k, r.slot]:
                        continue
                    self._lens_np[r.slot] += 1
                    self._push_token(r, int(toks[k, r.slot]))

    def _push_token(self, r, tok):
        tok = int(tok)
        r.out.append(tok)
        r.tok = tok
        if r.sampling.needs_processors:
            # host-authoritative processor state: the device scan's
            # carries are recomputed here so preemption/export/chaining
            # boundaries can never desynchronize them
            r.counts[tok] = r.counts.get(tok, 0) + 1
            g = r.sampling.grammar
            if g is not None:
                r.gstate = int(g.advance(r.gstate, tok))
        r.idle_steps = 0                # progress: the demote-on-idle
        #                                 clock restarts
        if self._tel is not None and len(r.out) == 1:
            # the TTFT host point: the first generated token became
            # visible to the host (an imported continuation arrives
            # with tokens already committed, so this never re-fires)
            self._tel.req_first_token(self._tel_src, r.uid)
        # fair-share accounting: 1/share virtual time per emitted token,
        # so a speculating tenant's higher per-pass yield is charged
        # exactly like plain decode
        share = self._tenant_cfg.get(r.tenant, {}).get("share", 1.0)
        self._tenant_vt[r.tenant] = self._vt(r.tenant) + 1.0 / share
        self._tenant_tokens[r.tenant] += 1
        if r.adapter is not None:
            self.adapter_tokens[r.adapter] += 1
        if (r.eos_token_id is not None and tok == r.eos_token_id) or \
                len(r.out) >= r.max_new_tokens:
            self._retire(r)
        elif r.sampling.stop and stop_hit(r.out, r.sampling.stop):
            # stop sequences retire HERE, on the host: the device scan
            # is ignorant of them (which is why _can_chain refuses to
            # chain a block whose participants carry any)
            self._retire(r)

    # -- replica boundary: in-flight export + weight flip --------------------
    def export_request(self, uid):
        """Resume spec for one request — everything a DIFFERENT engine
        needs to continue it from its last committed token: the folded
        prompt (original ids + tokens generated so far — exactly the
        preemption fold, so a greedy continuation is byte-identical to
        an uninterrupted run), the REMAINING budget, and the admission
        identity (eos/tenant/priority/deadline/remaining TTL). Only
        meaningful for LIVE requests (queued/prefill/decode) and
        engine-stage failures — the states failover re-queues; a
        finished request's output must be read via result(), never
        regenerated from a spec (`state` rides along so callers can
        tell, and submit_resume rejects a spent budget)."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        self._sync_pending()    # the fold must hold every token emitted
        prompt = (np.concatenate([r.ids, np.asarray(r.out, np.int64)])
                  if r.out else r.ids.copy())
        ttl = r.ttl_steps
        if ttl is not None:
            ttl = max(0, ttl - (self.steps - r.born_step))
        return {
            "uid": uid,
            "state": r.state,
            "prompt": prompt,
            "generated": len(r.out),
            "max_new_tokens": r.max_new_tokens - len(r.out),
            "eos_token_id": r.eos_token_id,
            "tenant": r.tenant,
            "priority": r.priority,
            "ttl_steps": ttl,
            "deadline": r.deadline,        # absolute monotonic cutoff
            "adapter": r.adapter,          # LoRA adapter name (the
            #                                importer resolves it in
            #                                ITS pool/registry)
            # sampled continuation: the params + key stream ride the
            # spec verbatim. The PRNG counter is IMPLICIT — keys fold
            # from absolute positions, and the folded prompt preserves
            # them — so the resumed sampled tail is byte-identical to
            # the uninterrupted stream. counts/gstate ride explicitly:
            # the folded prompt would otherwise reclassify generated
            # tokens as prompt for penalty/grammar purposes.
            "sampling": (None if r.sampling is GREEDY
                         else r.sampling.to_spec()),
            "counts": dict(r.counts),
            "gstate": r.gstate,
        }

    def export_inflight(self):
        """Resume specs for every request still queued or in flight
        (submission order; demoted requests ride too — failover
        recomputes them elsewhere, their tier entry dies with the
        replica) — the payload a router salvages when this replica is
        declared dead."""
        self._sync_pending()
        return [self.export_request(u)
                for u, r in self._requests.items()
                if r.state in (QUEUED, PREFILL, DECODE, DEMOTED)]

    def submit_resume(self, spec):
        """Admit an export_request spec into THIS engine. The folded
        prompt re-prefills (usually through published prefix pages) and
        the continuation proceeds under the remaining budget — greedy
        outputs byte-identical to the uninterrupted run (the preemption
        contract, pinned in tests). Returns this engine's uid for it."""
        deadline_ms = None
        if spec.get("deadline") is not None:
            # absolute -> relative; an already-expired deadline admits
            # and is shed by the next _expire_deadlines sweep (the
            # same outcome the original engine would have reached)
            deadline_ms = max(
                0.0, (spec["deadline"] - time.monotonic()) * 1e3)
        uid = self.add_request(
            spec["prompt"], max_new_tokens=spec["max_new_tokens"],
            eos_token_id=spec["eos_token_id"], deadline_ms=deadline_ms,
            ttl_steps=spec["ttl_steps"], tenant=spec["tenant"],
            priority=spec["priority"], adapter=spec.get("adapter"),
            sampling=spec.get("sampling"))
        if spec.get("counts") or spec.get("gstate"):
            r = self._requests[uid]
            r.counts = {int(t): int(c)
                        for t, c in (spec.get("counts") or {}).items()}
            r.gstate = int(spec.get("gstate") or 0)
        gen = int(spec.get("generated") or 0)
        if gen and self._tel is not None:
            # a resumed continuation: the folded prompt already holds
            # `gen` committed tokens, so the first token THIS engine
            # emits is not the request's TTFT (that was observed where
            # the original first token appeared) — the marker makes
            # req_first_token keep the span timestamp but skip the
            # ttft_ms observation, so fleet counts stay == retired
            self._tel.req_event(self._tel_src, uid, "resume",
                                committed=gen)
        return uid

    # -- KV-page handoff (disaggregated prefill/decode) ----------------------
    def _kv_geometry(self):
        """The cache-geometry stamp every page-image payload carries
        (and every import verifies) — ONE definition for the handoff,
        tier-demote, and prefix-ship paths."""
        return {"page_size": self.page_size, "nh_kv": self.nh_kv,
                "hd": self.hd, "layers": self.cfg.num_hidden_layers,
                "kv_dtype": str(jnp.dtype(self.kv_dtype))}

    def _package_pages(self, token, spec, lens, pages, device=False):
        """CRC-stamped page-image payload — the one assembly shared by
        KV handoff, tier demotion, and prefix shipping: per-layer K/V
        blobs for `pages`, the cache geometry, checksums. Pools index
        identically in both forms (per-layer list, or the natively
        stacked [L, ...] array of megakernel="multi").

        device=True is the negotiated ICI-class path (handoff.
        DeviceTransport): blobs stay DEVICE arrays — no host readback,
        no per-page CRC walk (the bytes never cross a host boundary;
        the metadata CRC still stamps). Only valid when the importer
        shares this engine's JAX runtime — `handoff.negotiate` is what
        decides that."""
        from .handoff import DeviceTransport, checksum_payload
        idx = np.asarray(pages, np.int64)
        k_blobs, v_blobs = [], []
        for li in range(self.cfg.num_hidden_layers):
            if device:
                k_blobs.append(DeviceTransport.gather(self.k_pages[li],
                                                      idx))
                v_blobs.append(DeviceTransport.gather(self.v_pages[li],
                                                      idx))
            else:
                k_blobs.append(np.asarray(self.k_pages[li][idx]))
                v_blobs.append(np.asarray(self.v_pages[li][idx]))
        payload = {
            "token": token, "spec": spec, "lens": lens,
            "geometry": self._kv_geometry(),
            "k": k_blobs, "v": v_blobs}
        if device:
            payload["transport"] = "device"
        return checksum_payload(payload)

    def _sync_pending(self):
        """The ONE resolve point: fetch and book the program still in
        flight (a chained fused block, or the per-step path's program
        dispatched ahead), so host state (lens, generated tokens,
        retirements) is current before anything reads or hands it
        over."""
        while self._pending is not None:
            blk = self._pending
            self._pending = None
            if isinstance(blk, _Dispatched):
                try:
                    self._resolve(blk)
                except Exception:
                    self._abort_in_flight()
                    raise
            else:
                self._process_block(blk)

    def _resolved_for(self, why):
        """Resolve the program in flight, if there is one, because
        `why` reads the host's state; True if there was one (counted in
        `ahead.resolved_first`)."""
        if self._pending is None:
            return False
        self.ahead_resolved_first[why] += 1
        self._sync_pending()
        return True

    def export_kv_pages(self, uid, device=False, transport=None):
        """Package a post-prefill request for migration to ANOTHER
        engine with zero recompute: resume identity (the export_request
        spec), cache length, and the raw K/V bytes of every page that
        holds committed context, CRC-stamped (inference/handoff.py).

        The source keeps serving the request until release_handoff();
        abort_handoff() cancels cleanly. Only DECODE-state requests
        carry a coherent KV image (mid-prefill pages are half-written;
        queued requests have none) — others raise ValueError, and the
        caller falls back to the spec-requeue salvage path (recompute,
        never lost). `kv.export` is the fault point.

        device=True: the negotiated device-domain export (see
        _package_pages) — page blobs stay on device, `transport.device`
        is its own fault point (an injected failure makes the router
        fall back to the host-bounce path, pinned in tests).

        transport=: the NEGOTIATED label for this export when the
        host-format payload rides something other than the caller's
        memory (the fleet's store transport) — it stamps the payload
        and both telemetry legs, so a trace shows the transport that
        actually ran, not "host" for every non-device path."""
        self._require_plain("KV page export")
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        # apply any in-flight chained block FIRST: it can retire this
        # request (EOS/budget), and the state check must see that
        self._sync_pending()
        if r.state != DECODE or r.slot is None:
            raise ValueError(
                f"export_kv_pages: request {uid} is {r.state!r} — only "
                "a decode-state request carries a complete KV image "
                "(use export_request for the spec-requeue path)")
        fault_point("kv.export", detail=f"uid={uid}")
        if device:
            fault_point("transport.device", detail=f"uid={uid}")
        p = self.page_size
        lens = int(self._lens_np[r.slot])
        n_used = -(-lens // p)
        used = [int(pg) for pg in r.pages[:n_used]]
        token = self.allocator.export_begin(used)
        spec = self.export_request(uid)
        # absolute monotonic deadlines don't survive a host boundary
        # (StoreKVTransport's whole point): ship the REMAINING budget
        # and let the importer rebase it on its own clock — the same
        # conversion submit_resume does for the failover path
        if spec.get("deadline") is not None:
            spec["deadline_remaining_ms"] = max(
                0.0, (spec["deadline"] - time.monotonic()) * 1e3)
            spec["deadline"] = None
        self._handoffs_out[uid] = token
        label = transport or ("device" if device else "host")
        if self._tel is not None:
            self._tel.req_event(self._tel_src, uid, "kv_export",
                                pages=len(used), transport=label)
        try:
            payload = self._package_pages(token, spec, lens, used,
                                          device=device)
        except Exception:
            # post-ticket packaging failure (a real device gather /
            # placement error, not the pre-ticket fault points): close
            # the ticket here — the request keeps serving, and the
            # caller's fallback must not find a stale token pinning
            # these pages out of eviction
            self.abort_handoff(uid)
            raise
        if not device:
            # "device" is the only value verify_payload special-cases
            # (metadata-only CRC); any other label keeps the full page
            # CRC walk and just rides through to the importer's
            # import_seat telemetry leg
            payload["transport"] = label
        return payload

    def abort_handoff(self, uid):
        """Cancel a pending export: the request keeps serving HERE."""
        token = self._handoffs_out.pop(uid, None)
        if token is not None:
            self.allocator.export_abort(token)

    def release_handoff(self, uid):
        """Source-side commit of a completed handoff: the request now
        lives on the importing engine. Its used pages' transfer refs
        drop via the allocator ticket, the remainder (unused budget
        tail, CoW reserve) through the normal slot release; the request
        retires MIGRATED (result() must be read from the importer)."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        self._sync_pending()    # it may have retired since the export
        token = self._handoffs_out.pop(uid, None)
        if token is None:
            raise ValueError(
                f"release_handoff: no pending export for request {uid}")
        if r.state != DECODE:
            # retired (EOS/budget/fault) since the export — its pages
            # are already released, the ticket must not free them again;
            # the coordinator resolves the duplicate (deliver from HERE,
            # cancel the imported copy — exactly-once either way)
            self.allocator.export_abort(token)
            raise ValueError(
                f"release_handoff: request {uid} is {r.state!r} (it "
                "retired after the export) — handoff aborted, read the "
                "result from this engine")
        used = set(self.allocator.export_pages(token))
        self.allocator.export_commit(token)
        r.pages = [pg for pg in r.pages if pg not in used]
        r.state = MIGRATED
        self._release_slot(r)
        self._release_adapter(r)
        self.handoffs_out += 1
        if self._tel is not None:
            # "migrated" pairs with "kv_export" -> handoff_ms histogram
            self._tel.req_event(self._tel_src, uid, "migrated")
            self._tel.req_done(self._tel_src, uid, MIGRATED,
                               n_tokens=len(r.out))

    def import_kv_pages(self, payload):
        """Admit an export_kv_pages payload into THIS engine: CRC +
        geometry verify, claim pages under the transfer token (a token
        already imported here RAISES — no silent aliasing), write the
        KV bytes into the pools, seat the request directly in DECODE
        state, and republish its full prompt pages to the prefix cache
        (parity with a locally-prefilled request). Greedy continuation
        is byte-identical to an uninterrupted single-engine run — the
        imported bytes ARE the exported bytes (pinned in tests).

        Raises EngineBusyError when no slot is free (the handoff
        coordinator holds and retries — nothing is claimed), KVHandoff-
        Error on integrity failures, EngineFullError propagating from
        the page claim. Any failure after the claim rolls the import
        back (pages freed, token NOT burned). `kv.import` is the fault
        point."""
        self._require_plain("KV page import")
        from .handoff import KVHandoffError, verify_payload
        self._sync_pending()    # seats are read off resolved state
        fault_point("kv.import", detail=f"token={payload.get('token')}")
        g = payload["geometry"]
        mine = self._kv_geometry()
        if {k: g.get(k) for k in mine} != mine:
            raise KVHandoffError(
                f"handoff geometry mismatch: payload {g} vs engine "
                f"{mine} (disaggregated pools must share model + cache "
                "geometry)")
        spec = payload["spec"]
        remaining = int(spec["max_new_tokens"])
        if remaining <= 0:
            raise ValueError(
                "import_kv_pages: spent generation budget (the source "
                "should deliver the finished result, not migrate it)")
        gen = int(spec["generated"])
        prompt = np.asarray(spec["prompt"], np.int64).ravel()
        ids = prompt[:prompt.size - gen]
        out = [int(t) for t in prompt[prompt.size - gen:]]
        if not out:
            raise ValueError(
                "import_kv_pages: no committed first token — migrate "
                "at first-token or later (that is the handoff point)")
        t0 = int(ids.size)
        mnt_total = remaining + gen
        if t0 + mnt_total > self.max_len:
            raise ValueError(
                f"prompt {t0} + total budget {mnt_total} exceeds "
                f"max_len={self.max_len}")
        ad_name = spec.get("adapter")
        if ad_name is not None:
            # resolved (hot-loading from the registry if needed) BEFORE
            # the CRC sweep/page claim: an adapter this engine cannot
            # serve must cost the coordinator a cheap typed refusal
            self._resolve_adapter(ad_name)
        sp_spec = spec.get("sampling")
        sp = (SamplingParams.from_spec(sp_spec)
              if sp_spec is not None else GREEDY)
        # the same sampled-continuation refusals add_request makes —
        # BEFORE the CRC sweep/page claim, like the adapter resolve
        if sp.do_sample and sp.top_k > self.sample_k:
            raise ValueError(
                f"import_kv_pages: top_k={sp.top_k} exceeds this "
                f"engine's sample_k={self.sample_k} candidate fold")
        if self._spec and sp.needs_processors:
            raise ValueError(
                "import_kv_pages: logit processors cannot ride "
                "speculative decoding (engine has speculate= on)")
        if sp.grammar is not None and \
                sp.grammar.vocab != self.cfg.vocab_size:
            raise ValueError(
                f"import_kv_pages: grammar vocab {sp.grammar.vocab} "
                f"!= model vocab {self.cfg.vocab_size}")
        lens = int(payload["lens"])
        p = self.page_size
        n_used = -(-lens // p)
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        need = self._pages_needed(t0, mnt_total)
        if slot is None:
            # slot AND page availability are checked BEFORE the CRC
            # sweep: backpressure must cost the coordinator a cheap
            # refusal, not a full-payload checksum pass
            raise EngineBusyError(
                f"import_kv_pages: no free slot ({self.max_batch} "
                "running); retry after a retirement")
        if need > self.allocator.available:
            raise EngineFullError(
                f"import_kv_pages: needs {need} KV pages but only "
                f"{self.allocator.available} of "
                f"{self.allocator.n_pages} are free; retry after a "
                "retirement")
        verify_payload(payload)
        pages = self.allocator.import_begin(payload["token"], need)
        r = None
        try:
            idx = jnp.asarray(np.asarray(pages[:n_used], np.int64))
            for li in range(self.cfg.num_hidden_layers):
                kc = jnp.asarray(payload["k"][li], self.kv_dtype)
                vc = jnp.asarray(payload["v"][li], self.kv_dtype)
                if isinstance(self.k_pages, (list, tuple)):
                    self.k_pages[li] = self.k_pages[li].at[idx].set(kc)
                    self.v_pages[li] = self.v_pages[li].at[idx].set(vc)
                else:               # natively stacked pools ("multi")
                    self.k_pages = self.k_pages.at[li, idx].set(kc)
                    self.v_pages = self.v_pages.at[li, idx].set(vc)
            if self._tpc is not None:
                # at-set outside the compiled paths may drop the mesh
                # layout; re-place so the next dispatch is zero-copy
                self.k_pages = self._tpc.place_pools(self.k_pages)
                self.v_pages = self._tpc.place_pools(self.v_pages)
            deadline = spec.get("deadline")     # same-host payloads
            if spec.get("deadline_remaining_ms") is not None:
                # cross-host payload: rebase the shipped remaining
                # budget on THIS host's monotonic clock
                deadline = (time.monotonic()
                            + spec["deadline_remaining_ms"] / 1e3)
            r = Request(self._next_uid, ids, mnt_total,
                        spec["eos_token_id"],
                        deadline=deadline,
                        ttl_steps=spec.get("ttl_steps"),
                        born_step=self.steps,
                        tenant=spec.get("tenant") or "default",
                        priority=int(spec.get("priority") or 0),
                        draft_k=max(1, self._spec - 1) if self._spec
                        else 0)
            r.out = out
            r.tok = out[-1]
            r.pages = pages
            r.slot = slot
            r.filled = r.resume = t0
            r.state = DECODE
            r.sampling = sp
            if sp.do_sample:
                self.sampled_requests += 1
            if sp.needs_processors:
                cts = spec.get("counts")
                if cts:
                    r.counts = {int(t): int(c) for t, c in cts.items()}
                else:
                    # older payloads: reconstruct from the committed
                    # tokens (counts cover GENERATED tokens only)
                    for t in out:
                        r.counts[t] = r.counts.get(t, 0) + 1
                if sp.grammar is not None:
                    gs = spec.get("gstate")
                    if gs is None:
                        gs = 0
                        for t in out:
                            gs = int(sp.grammar.advance(gs, t))
                    r.gstate = int(gs)
            self._next_uid += 1
            self._requests[r.uid] = r
            self._slots[slot] = r
            self._tok_on_dev[slot] = False      # r.tok rides the payload
            self._tables_np[slot] = 0
            self._tables_np[slot, :len(pages)] = pages
            self._lens_np[slot] = lens
            if ad_name is not None:
                r.adapter = ad_name
                self._apool.acquire(ad_name)
                self.adapter_requests[ad_name] += 1
            self._publish_prefix(r)
            self.allocator.import_commit(payload["token"])
        except Exception:
            # roll the import back whole: pages freed, token NOT
            # burned (a retry may target this engine again), slot and
            # request maps untouched by the partial seat
            if r is not None:
                self._release_adapter(r)
                if self._requests.get(r.uid) is r:
                    del self._requests[r.uid]
                if self._slots[slot] is r:
                    self._slots[slot] = None
            self.allocator.import_abort(payload["token"])
            raise
        self.admissions += 1
        self.handoffs_in += 1
        if self._tel is not None:
            self._tel.req_start(self._tel_src, r.uid, prompt_len=t0,
                                max_new=remaining)
            self._tel.req_event(self._tel_src, r.uid, "import_seat",
                                slot=slot, lens=lens,
                                committed_tokens=gen,
                                transport=payload.get("transport",
                                                      "host"))
        if self._slot_used[slot]:
            self.slot_reuses += 1
        self._slot_used[slot] = True
        return r.uid

    # -- KV tiering (HBM -> host RAM -> disk; inference/tiering.py) ----------
    def demote_request(self, uid):
        """Move a decode-state request's device pages into the KV tier
        (host RAM, spilling to disk — `kv_tier=`): its EXCLUSIVE pages'
        bytes export under an allocator ticket in the CRC-stamped
        handoff format and the device copies free; prefix-cache-shared
        pages stay resident (they are deduplicated HBM other requests
        read — the request keeps its references, so eviction cannot
        pull them out from under the pending restore). The slot frees,
        the request parks in DEMOTED state, and a later
        restore_request / restore sweep re-seats it byte-identically.
        `kv.demote` is the fault point (fires BEFORE the ticket opens —
        a demote failure leaves the request serving untouched)."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        if self._tier is None:
            raise ValueError(
                "demote_request: no KV tier configured (kv_tier=)")
        self._sync_pending()
        if r.state != DECODE or r.slot is None:
            raise ValueError(
                f"demote_request: request {uid} is {r.state!r} — only a "
                "decode-state request carries a complete KV image")
        if uid in self._handoffs_out:
            raise ValueError(
                f"demote_request: request {uid} has a pending KV-page "
                "handoff export (settle it first)")
        fault_point("kv.demote", detail=f"uid={uid}")
        p = self.page_size
        lens = int(self._lens_np[r.slot])
        n_used = -(-lens // p)
        # pages KEPT resident: prefix-cache-shared ones (shared_idx)
        # AND the request's own prompt pages it PUBLISHED to the cache
        # (refcount 2: request + cache, but not in shared_idx) — the
        # cache pins those in HBM either way, so exporting their bytes
        # would free nothing, store a redundant tier copy, and make
        # restore claim duplicates of pages still resident
        kept = {}
        for i in range(n_used):
            pg = r.pages[i]
            if i in r.shared_idx or (self._prefix is not None
                                     and pg in self._prefix._by_page):
                kept[i] = pg
        excl_idx = [i for i in range(n_used) if i not in kept]
        excl_pages = [r.pages[i] for i in excl_idx]
        token = self.allocator.export_begin(excl_pages)
        try:
            self._tier.put(token, self._package_pages(
                token, self.export_request(uid), lens, excl_pages))
        except Exception:
            # tier write failed (disk error): close the ticket, the
            # request keeps serving from its device pages
            self.allocator.export_abort(token)
            raise
        n_total = len(r.pages)
        tail = r.pages[n_used:]
        self.allocator.export_commit(token)      # drops the exported refs
        if tail:
            self.allocator.free(tail)
        if r.cow_reserve is not None:
            self.allocator.free([r.cow_reserve])
            r.cow_reserve = None
        self._slots[r.slot] = None
        r.slot = None
        r.demote = {"token": token, "lens": lens, "n_pages": n_total,
                    "excl_idx": excl_idx, "shared": kept,
                    # the ORIGINAL read-only labeling — kept pages the
                    # request owns (self-published) seat back unshared
                    "shared_idx": sorted(r.shared_idx)}
        r.pages = [kept[i] for i in sorted(kept)]
        r.shared_idx = set()
        r.state = DEMOTED
        self._demoted[uid] = r
        self.demotions += 1
        self.pages_demoted += n_total - len(kept)
        if self._tel is not None:
            self._tel.req_event(self._tel_src, uid, "demote",
                                pages=n_total - len(kept))
        return token

    def restore_request(self, uid):
        """Re-seat a DEMOTED request: claim fresh device pages under
        the tier token (burned on commit — one tier entry seats at most
        one continuation), write the exported bytes back, re-link the
        kept shared pages at their table indices, and continue in
        DECODE state — greedy output byte-identical to a never-demoted
        run (pinned in tests across decode_block 1/8).

        Raises EngineBusyError (no free slot) / EngineFullError (pages,
        after prefix-cache eviction) as plain backpressure — nothing
        claimed, retry later. A CORRUPT tier entry or an injected
        `kv.restore` fault retires exactly THIS request with a typed
        stage="restore" RequestFailure (tier entry dropped, kept refs
        freed, zero page leak) and returns False; the engine keeps
        stepping everyone else."""
        r = self._requests.get(uid)
        if r is None:
            raise UnknownRequestError(f"unknown request uid {uid}")
        if r.state != DEMOTED or r.demote is None:
            raise ValueError(
                f"restore_request: request {uid} is {r.state!r}, not "
                "demoted")
        self._sync_pending()    # seats are read off resolved state
        d = r.demote
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            raise EngineBusyError(
                f"restore_request: no free slot ({self.max_batch} "
                "running); retry after a retirement")
        shared = d["shared"]
        n_fresh = d["n_pages"] - len(shared)
        if n_fresh > self.allocator.available and self._prefix:
            self._prefix.evict(n_fresh - self.allocator.available,
                               self.allocator,
                               protect=set(shared.values()))
        if n_fresh > self.allocator.available:
            raise EngineFullError(
                f"restore_request: needs {n_fresh} KV pages but only "
                f"{self.allocator.available} of "
                f"{self.allocator.n_pages} are free; retry after a "
                "retirement")
        try:
            fault_point("kv.restore", detail=f"uid={uid}")
            payload = self._tier.get(d["token"])
        except Exception as e:
            # corrupt/lost tier entry or injected fault: THIS request
            # retires alone (the PR 2 isolation contract) — tier entry
            # dropped, kept shared refs freed via the release path
            self.restore_failures += 1
            self._fail_request(r, "restore", e)
            return False
        pages = self.allocator.import_begin(d["token"], n_fresh)
        try:
            excl_idx = d["excl_idx"]
            if excl_idx:
                idx = jnp.asarray(np.asarray(pages[:len(excl_idx)],
                                             np.int64))
                for li in range(self.cfg.num_hidden_layers):
                    kc = jnp.asarray(payload["k"][li], self.kv_dtype)
                    vc = jnp.asarray(payload["v"][li], self.kv_dtype)
                    if isinstance(self.k_pages, (list, tuple)):
                        self.k_pages[li] = \
                            self.k_pages[li].at[idx].set(kc)
                        self.v_pages[li] = \
                            self.v_pages[li].at[idx].set(vc)
                    else:           # natively stacked pools ("multi")
                        self.k_pages = self.k_pages.at[li, idx].set(kc)
                        self.v_pages = self.v_pages.at[li, idx].set(vc)
                if self._tpc is not None:
                    self.k_pages = self._tpc.place_pools(self.k_pages)
                    self.v_pages = self._tpc.place_pools(self.v_pages)
            table = [None] * d["n_pages"]
            for i, pg in shared.items():
                table[i] = pg
            fi = 0
            for i in excl_idx:
                table[i] = pages[fi]
                fi += 1
            for i in range(d["n_pages"]):
                if table[i] is None:
                    table[i] = pages[fi]
                    fi += 1
            r.pages = table
            r.shared_idx = set(d["shared_idx"])
            r.slot = slot
            r.state = DECODE
            r.seated_step = self.steps
            r.idle_steps = 0            # a fresh seat restarts the
            #                             demote-on-idle clock
            self._slots[slot] = r
            self._tok_on_dev[slot] = False      # r.tok waited in the tier
            self._tables_np[slot] = 0
            self._tables_np[slot, :len(table)] = table
            self._lens_np[slot] = d["lens"]
            self.allocator.import_commit(d["token"])
        except Exception:
            # roll the restore back whole: claimed pages freed, token
            # NOT burned, the request stays DEMOTED for a retry
            if self._slots[slot] is r:
                self._slots[slot] = None
            r.slot = None
            r.state = DEMOTED
            r.pages = [shared[i] for i in sorted(shared)]
            r.shared_idx = set()
            self.allocator.import_abort(d["token"])
            raise
        self._tier.delete(d["token"])
        self._demoted.pop(uid, None)
        self.pages_demoted -= n_fresh
        r.demote = None
        self.restores += 1
        if self._tel is not None:
            # pairs with the "demote" event -> restore_ms histogram
            self._tel.req_event(self._tel_src, uid, "restore",
                                pages=n_fresh)
        return True

    def _drop_demoted(self, r):
        """Forget a DEMOTED request's tier entry and bookkeeping (it is
        retiring: cancel/deadline/failure/pool rebuild). Its kept
        shared-page references free through the normal release path."""
        d = r.demote
        if d is None:
            return
        try:
            self._tier.delete(d["token"])
        except Exception:
            pass
        self._demoted.pop(r.uid, None)
        self.pages_demoted -= d["n_pages"] - len(d["shared"])
        r.demote = None

    def _restore_sweep(self):
        """Re-seat demoted requests (oldest demotion first) while slots
        are free. Demoted requests outrank FRESH admissions — they
        already earned service, so a steady queue cannot starve a
        parked conversation — but under queue pressure only one
        restores per step (the queue keeps draining; admission may
        demote again, round-robining the device pool through the
        oversubscribed set). Returns True when any restore ran (success
        or typed failure — both are progress)."""
        did = False
        while self._demoted:
            if not any(s is None for s in self._slots):
                break
            uid = next(iter(self._demoted))
            try:
                self.restore_request(uid)
            except (EngineBusyError, EngineFullError):
                break               # capacity backpressure: next step
            did = True
            if self._queue:
                break               # one per step under queue pressure
        return did

    def _idle_demote_sweep(self):
        """DEMOTE-ON-IDLE (tier_idle_steps=N): park any seated decode
        request that has waited N consecutive steps without emitting,
        so its slot and device pages serve the QUEUED work it was
        blocked alongside. Gated on a non-empty admission queue —
        without waiting work, demoting would only bounce the request
        through the restore sweep. Restore is byte-identical (the
        PR 11 contract); a demote failure (kv.demote fault, tier write
        error) leaves the victim serving and counts demote_errors."""
        if self._tier is None or not self.tier_idle_steps or \
                not self._queue:
            return
        # only when the queue head actually CANNOT seat: with a free
        # slot and pages to spare, _admit (which runs next) seats it
        # without anyone paying a demote/restore round trip. The gate
        # must price the head the way _admit does — prefix-shared
        # pages plus the CoW page, not the raw page count — or a head
        # whose prompt is mostly cache-covered demotes a victim _admit
        # never needed (eviction headroom stays _admit's business: a
        # demote that eviction would have avoided is a tight-pool
        # corner, not the every-step thrash this gate exists to stop)
        head = self._pick_next()
        if any(s is None for s in self._slots):
            if self._pages_needed(head.t0, head.max_new_tokens) \
                    <= self.allocator.available:
                return                  # fits even without sharing —
                #                         skip the prefix match (fresh
                #                         <= need always, so this is
                #                         the common-case early out
                #                         that keeps the hot path to
                #                         ONE match per step, _admit's)
            _, _, _, _, fresh = self._price_admission(head)
            if fresh <= self.allocator.available:
                return
        # one victim per step (the _demote_for rhythm): admission
        # re-evaluates with the freed capacity, and the restore sweep
        # trickles parked requests back one per step — demoting the
        # whole idle set at once would be pure churn
        victims = [r for r in self._slots
                   if r is not None and r.state == DECODE
                   and r.idle_steps >= self.tier_idle_steps
                   and r.uid not in self._handoffs_out]
        if not victims:
            return
        victim = max(victims, key=lambda r: r.idle_steps)
        try:
            self.demote_request(victim.uid)
            self.idle_demotions += 1
        except Exception:
            self.demote_errors += 1

    def _demote_for(self, cand):
        """Oversubscription: demote the longest-resident running
        request at or below the candidate's priority so the candidate
        can seat — its pages move to the tier instead of being thrown
        away (preemption's recompute) or blocking admission. One victim
        per attempt; the admission loop re-evaluates. Requests with a
        pending handoff export are never victims (the ticket names
        their pages)."""
        if self._tier is None or not self.oversubscribe:
            return False
        victims = [s for s in self._slots
                   if s is not None and s.state == DECODE
                   and s.priority <= cand.priority
                   and s.uid not in self._handoffs_out]
        if not victims:
            return False
        victim = min(victims,
                     key=lambda s: (s.priority, s.seated_step, s.uid))
        try:
            self.demote_request(victim.uid)
            return True
        except Exception:
            # kv.demote fault or tier write failure: the victim keeps
            # serving; admission waits instead
            self.demote_errors += 1
            return False

    # -- prefix-page shipping (cache-aware routing's transfer path) ----------
    def export_prefix_pages(self, ids, device=False):
        """Package this engine's cached full-page chain covering a
        prefix of `ids` for import into ANOTHER engine's prefix cache —
        the router's alternative to re-prefilling when the best-prefix
        replica lacks headroom. Returns None when no full page of `ids`
        is cached (a stale index hint). The chain pages ride under an
        export ticket holding its OWN references (the cache keeps
        serving them here, and PrefixCache.evict skips ticketed pages);
        the caller MUST settle the ticket: finish_prefix_export after a
        landed import, abort_prefix_export otherwise. device=True is
        the negotiated same-runtime ship (no host bounce — see
        _package_pages)."""
        self._require_plain("prefix page export")
        if self._prefix is None:
            raise ValueError("export_prefix_pages: prefix cache disabled")
        ids = np.asarray(ids, np.int64).ravel()
        self._sync_pending()
        p = self.page_size
        key = ()
        pages = []
        for j in range(ids.size // p):
            k2 = self._prefix.chain_key(key, ids[j * p:(j + 1) * p])
            page = self._prefix._entries.get(k2)
            if page is None:
                break
            pages.append(page)
            key = k2
        if not pages:
            return None
        fault_point("kv.export", detail=f"prefix:{len(pages)}")
        if device:
            fault_point("transport.device", detail="prefix")
        for pg in pages:
            self.allocator.share(pg)         # the ticket's own refs
        try:
            token = self.allocator.export_begin(pages)
        except Exception:
            self.allocator.free(pages)
            raise
        covered = len(pages) * p
        try:
            payload = self._package_pages(
                token, {"state": "prefix",
                        "prompt": ids[:covered].copy()},
                covered, pages, device=device)
        except Exception:
            # post-ticket packaging failure: the caller never receives
            # the token, so abort_prefix_export is OURS to run — the
            # ticket's share() refs would otherwise never drop (a hard
            # page leak on every failed device-path ship)
            self.abort_prefix_export(token)
            raise
        self.prefix_exports += 1
        return payload

    def finish_prefix_export(self, token):
        """Settle a landed prefix ship: the ticket's references drop
        (the cache keeps its own — local serving is unaffected)."""
        self.allocator.export_commit(token)

    def abort_prefix_export(self, token):
        """Cancel a failed prefix ship: close the ticket and drop its
        references — cache state is untouched."""
        pages = list(self.allocator.export_pages(token))
        self.allocator.export_abort(token)
        self.allocator.free(pages)

    def import_prefix_pages(self, payload):
        """Seat a shipped prefix-page chain into THIS engine's prefix
        cache: CRC + geometry verify, claim fresh pages under the
        transfer token (burned on commit — a replayed ship raises),
        write the KV bytes, register the chain content-addressed, and
        publish it to the fleet index. A request admitted next shares
        these pages exactly as if this engine had prefilled them.
        Returns the number of pages seated."""
        self._require_plain("prefix page import")
        from .handoff import KVHandoffError, verify_payload
        if self._prefix is None:
            raise ValueError("import_prefix_pages: prefix cache disabled")
        self._sync_pending()
        fault_point("kv.import", detail="prefix")
        g = payload["geometry"]
        mine = self._kv_geometry()
        if {k: g.get(k) for k in mine} != mine:
            raise KVHandoffError(
                f"prefix-ship geometry mismatch: payload {g} vs engine "
                f"{mine}")
        verify_payload(payload)
        prompt = np.asarray(payload["spec"]["prompt"], np.int64).ravel()
        p = self.page_size
        n = int(payload["lens"]) // p
        if n * p != int(payload["lens"]) or prompt.size < n * p:
            raise KVHandoffError(
                f"prefix payload lens {payload['lens']} is not "
                f"{n} full pages of the shipped prompt ({prompt.size} "
                "tokens)")
        if n > self.allocator.available and self._prefix:
            self._prefix.evict(n - self.allocator.available,
                               self.allocator)
        pages = self.allocator.import_begin(payload["token"], n)
        try:
            idx = jnp.asarray(np.asarray(pages, np.int64))
            for li in range(self.cfg.num_hidden_layers):
                kc = jnp.asarray(payload["k"][li], self.kv_dtype)
                vc = jnp.asarray(payload["v"][li], self.kv_dtype)
                if isinstance(self.k_pages, (list, tuple)):
                    self.k_pages[li] = self.k_pages[li].at[idx].set(kc)
                    self.v_pages[li] = self.v_pages[li].at[idx].set(vc)
                else:               # natively stacked pools ("multi")
                    self.k_pages = self.k_pages.at[li, idx].set(kc)
                    self.v_pages = self.v_pages.at[li, idx].set(vc)
            if self._tpc is not None:
                self.k_pages = self._tpc.place_pools(self.k_pages)
                self.v_pages = self._tpc.place_pools(self.v_pages)
        except Exception:
            self.allocator.import_abort(payload["token"])
            raise
        self.allocator.import_commit(payload["token"])
        # register the chain; a link already cached HERE keeps the
        # local page (the imported copy's reference just drops below)
        from .prefix_index import EMPTY_DIGEST, chain_digest
        key = ()
        dig = EMPTY_DIGEST
        for j in range(n):
            chunk = prompt[j * p:(j + 1) * p]
            k2 = self._prefix.chain_key(key, chunk)
            if k2 not in self._prefix._entries:
                self._prefix.insert(key, chunk, pages[j], self.allocator)
            key = k2
            if self._prefix_index is not None:
                dig = chain_digest(dig, chunk)
                try:
                    self._prefix_index.publish(self._replica, dig, j + 1)
                    self.index_publishes += 1
                except Exception:
                    self.index_publish_errors += 1
        self.allocator.free(pages)      # drop the import refs; the
        self.prefix_imports += 1        # cache keeps its own
        return n

    def install_weights(self, new):
        """Zero-downtime flip, gated at a BLOCK BOUNDARY: no slot may
        hold in-flight KV (cache contents computed under the old
        weights would silently corrupt continuations), so callers drain
        or migrate running requests first — EngineBusyError here is the
        backpressure signal, not a failure. DEMOTED requests count as
        busy too: their tier bytes are old-weight KV. Queued (not yet
        admitted) requests HOLD through the flip and run under the new
        weights. The prefix cache is dropped with the old weights (its
        pages are old-weight KV); the megakernel repack is rebuilt."""
        self._sync_pending()    # a slot may just have emptied
        busy = [r.uid for r in self._slots if r is not None]
        busy += list(self._demoted)
        if busy:
            raise EngineBusyError(
                f"install_weights with {len(busy)} request(s) in flight "
                f"(uids {busy}): their KV was computed under the OLD "
                "weights — drain or migrate them first (the router's "
                "hot_swap does)")
        super().install_weights(new)
        if self._prefix is not None:
            self._prefix.clear(self.allocator)
        if self.megakernel:
            self._build_mk_pack()
        return self

    # -- retirement / failure ----------------------------------------------
    def _expire_deadlines(self):
        """Shed every request whose wall-clock deadline or step TTL has
        passed: queued ones before they run, in-flight ones with their
        slot/pages reclaimed. Runs at the top of each step()."""
        now = None
        # live requests only (queue + slots + demoted) — NOT the full
        # request history, which grows for the life of the engine
        live = list(self._queue) + [s for s in self._slots
                                    if s is not None] \
            + list(self._demoted.values())
        for r in live:
            expired = False
            if r.ttl_steps is not None and \
                    self.steps - r.born_step >= r.ttl_steps:
                expired = True
                why = (f"ttl of {r.ttl_steps} engine steps exhausted "
                       f"(submitted at step {r.born_step}, now "
                       f"{self.steps})")
            elif r.deadline is not None:
                if now is None:
                    now = time.monotonic()
                if now >= r.deadline:
                    expired = True
                    why = f"wall-clock deadline passed at step {self.steps}"
            if not expired:
                continue
            if r.state == QUEUED:
                self._queue.remove(r)
            self._fail_request(r, "deadline", DeadlineExceededError(why))
            self.deadline_expiries += 1

    def _fail_request(self, r, stage, exc, state=FAILED):
        """Retire ONE request with a typed error record; reclaim its
        slot, pages, CoW reserve, prefix-cache references, and (for a
        DEMOTED request) its tier entry. The engine keeps stepping
        everyone else."""
        if r.demote is not None:
            self._drop_demoted(r)
        r.error = RequestFailure(r.uid, stage, exc, self.steps,
                                 tokens_generated=len(r.out))
        r.state = state
        self._release_slot(r)
        self._release_adapter(r)
        self.failure_count += 1
        if self._tel is not None:
            self._tel.req_done(self._tel_src, r.uid, state,
                               n_tokens=len(r.out), stage=stage,
                               error=type(exc).__name__)

    def _retire(self, r):
        r.result = np.concatenate([r.ids,
                                   np.asarray(r.out, np.int64)])
        r.state = DONE
        self._release_slot(r)
        self._release_adapter(r)
        if self._tel is not None:
            self._tel.req_done(self._tel_src, r.uid, DONE,
                               n_tokens=len(r.out))

    def _abort_in_flight(self):
        """A donated-buffer call died mid-flight: the pools are gone and
        with them every in-flight sequence's KV and the prefix cache.
        Rebuild empty; queued (not yet admitted) requests survive."""
        self._pending = None           # its buffers died with the pools
        self._reset_kv()

    def _reset_kv(self):
        """Any pool rebuild (including one triggered by an inherited
        generate() call failing) invalidates every in-flight sequence's
        KV AND the content-addressed cache — the fresh allocator will
        re-issue the cached page ids, so stale entries would alias other
        requests' pages."""
        tel = getattr(self, "_tel", None)
        for uid, r in list(getattr(self, "_demoted", {}).items()):
            # the pool rebuild killed the kept shared pages too; the
            # tier bytes alone cannot re-seat (their shared-page table
            # entries are gone) — typed engine-stage failure, like any
            # in-flight request
            self._drop_demoted(r)
            self._release_adapter(r)
            r.pages = []
            r.shared_idx = set()
            r.state = FAILED
            if r.error is None:
                r.error = RequestFailure(
                    r.uid, "engine",
                    SchedulerError("KV pools rebuilt mid-flight "
                                   "(compiled call failed)"),
                    getattr(self, "steps", 0),
                    tokens_generated=len(r.out))
            self.failure_count += 1
            if tel is not None:
                tel.req_done(self._tel_src, r.uid, FAILED,
                             n_tokens=len(r.out), stage="engine")
        for i, r in enumerate(getattr(self, "_slots", [])):
            if r is not None:
                self._release_adapter(r)
                r.state = FAILED
                if r.error is None:
                    r.error = RequestFailure(
                        r.uid, "engine",
                        SchedulerError("KV pools rebuilt mid-flight "
                                       "(compiled call failed)"),
                        getattr(self, "steps", 0),
                        tokens_generated=len(r.out))
                self.failure_count += 1
                if tel is not None:
                    tel.req_done(self._tel_src, r.uid, FAILED,
                                 n_tokens=len(r.out), stage="engine")
                r.pages = []          # pool is being rebuilt: page ids
                r.more_pages = {}     # are meaningless, nothing to free
                r.cow_reserve = None
                r.shared_idx = set()
                r.slot = None
                self._slots[i] = None
        self._pending = None
        if getattr(self, "_tok_on_dev", None) is not None:
            self._tok_on_dev[:] = False
            # (it may be the result of the call that failed)
            self._tok_dev = jnp.zeros((self.max_batch,), jnp.int32)
        prefix = getattr(self, "_prefix", None)
        if prefix is not None:
            prefix.clear()                   # allocator is reset below
        super()._reset_kv()
        if getattr(self, "_route_dev", None) is not None:
            self._route_dev = self._route_zeros()   # donated with the call
        if getattr(self, "megakernel", None) == "multi":
            # restore the native stacked [L, ...] pool form (re-placed
            # on the mesh so the next sharded dispatch is zero-copy)
            self.k_pages = jnp.stack(self.k_pages)
            self.v_pages = jnp.stack(self.v_pages)
            if self._tpc is not None:
                self.k_pages = self._tpc.place_pools(self.k_pages)
                self.v_pages = self._tpc.place_pools(self.v_pages)
