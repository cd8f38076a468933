"""LLM serving engine: paged KV cache + int8 weight-only decode.

The deployment arc the reference serves with fused_multi_transformer
(ref: paddle/fluid/operators/fused/fused_multi_transformer_op.cu.h inline
KV cache + masked MHA; fused_multi_transformer_int8_op.cu): a decode
engine whose KV cache lives in fixed-size PAGES with a free-list
allocator, so sequences of different lengths share one pool (continuous
batching shape; PAPERS.md ragged paged attention), and whose matmuls can
run int8 weight-only (ops/pallas/quantized_matmul).

Pieces:
  - PageAllocator: free-list over [n_pages, page_size, h, d] K/V pools
  - LLMEngine(model, ...): snapshots LLaMA weights (optionally int8),
    prefills prompts densely and scatters their KV into pages, then runs
    ONE jitted decode step per token: ragged per-sequence positions,
    rope at each sequence's own offset, KV written to its page slot, and
    attention via the Pallas paged_attention kernel
  - generate(): the host loop (greedy or temperature/top-k/top-p
    sampling, shared with models.generation._sample)
"""
import collections
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from ..failsafe import fault_point
from ..profiler import RecordEvent, phase
from ..tensor.tensor import Tensor
from ..autograd import tape
from ..models.llama import _rope_cache
from ..ops import latent_attention as la
from ..ops.moe import routed_experts
from .description import UnsupportedByDescription, describe
from ..ops.pallas.paged_attention import (expand_kv_heads,
                                          paged_attention,
                                          paged_attention_reference)
from ..ops.pallas.quantized_matmul import (mm_operand_dtype,
                                           quantized_matmul,
                                           quantize_weights)


class EngineFullError(RuntimeError):
    """A request cannot be served right now: the KV page pool (or the
    slot budget) is exhausted. Callers that hold a queue (the
    continuous-batching scheduler) treat this as "wait for retirements";
    a direct generate() call surfaces it with the sizes that collided."""


class PageAllocator:
    """Free-list page allocator with refcounts (the serving engine's KV
    memory manager).

    Refcounts exist for prefix caching: a page holding a shared prompt
    prefix is referenced by several sequences at once (plus the prefix
    cache itself) and must return to the free list only when the LAST
    reference drops. alloc() hands out a page at refcount 1; share()
    takes an extra reference; free() drops one reference per page and
    recycles at zero. Double-frees and shares of free pages raise
    instead of corrupting the free list.
    """

    def __init__(self, n_pages):
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, -1, -1))
        self._ref = [0] * n_pages
        self.total_allocs = 0   # fresh pages handed out (prefix-cache
        #                         tests assert shared prefixes shrink it)
        # cross-engine page transfer bookkeeping (KV handoff,
        # docs/serving.md "Disaggregated prefill/decode"): exports are
        # TICKETED so a transfer is either committed (source refs
        # dropped) or aborted (nothing changed), and imports BURN the
        # ticket token so the same page chain can never be imported
        # twice (two requests silently aliasing one exported KV image).
        self._exports = {}       # token -> tuple(pages) pending export
        self._imports = {}       # token -> list(pages) pending import
        # burned tokens (committed imports), BOUNDED: double-import
        # protection only has to cover transfers whose retry could
        # still be in flight — an unbounded set would grow one uuid per
        # handoff for the life of a decode worker
        self._imported = collections.OrderedDict()
        self._imported_cap = 4096

    # -- cross-engine transfer (the KV-handoff substrate) -------------------
    def export_begin(self, pages):
        """Open a transfer ticket for `pages` (all must be live). The
        pages stay owned by this allocator until export_commit; abort
        leaves everything untouched. Returns the ticket token."""
        import uuid
        pages = tuple(int(p) for p in pages)
        for p in pages:
            if not (0 <= p < self.n_pages) or self._ref[p] <= 0:
                raise RuntimeError(
                    f"export_begin of page {p}: not a live page "
                    f"(refcount {self._ref[p] if 0 <= p < self.n_pages else 'n/a'})")
        token = uuid.uuid4().hex
        self._exports[token] = pages
        return token

    def export_pages(self, token):
        """The page tuple under a pending export ticket."""
        pages = self._exports.get(token)
        if pages is None:
            raise RuntimeError(
                f"export_pages of unknown/closed transfer {token!r}")
        return pages

    def is_exporting(self, page):
        """True while `page` sits under ANY pending export ticket.
        Reclaimers (PrefixCache.evict) must skip such pages even at
        refcount 1: the ticket's commit will drop a reference, and a
        concurrent free would hand the page to a new owner while the
        transfer still names it."""
        return any(page in pages for pages in self._exports.values())

    def export_commit(self, token):
        """Close the ticket and drop THIS transfer's reference on each
        page (ownership moved to the importer's copy); shared holders
        (prefix cache, co-readers) keep theirs."""
        pages = self._exports.pop(token, None)
        if pages is None:
            raise RuntimeError(
                f"export_commit of unknown/closed transfer {token!r}")
        self.free(pages)

    def export_abort(self, token):
        """Cancel a pending export: ticket closed, pages untouched."""
        if self._exports.pop(token, None) is None:
            raise RuntimeError(
                f"export_abort of unknown/closed transfer {token!r}")

    def import_begin(self, token, n):
        """Claim `n` fresh pages to receive the transfer `token`.
        A token already imported (or mid-import) RAISES — silently
        aliasing one exported KV image into two requests is how a
        retried handoff corrupts an innocent request's attention.
        Nothing is claimed when the pool cannot cover `n`."""
        if token in self._imported or token in self._imports:
            raise RuntimeError(
                f"double import of transfer {token!r}: this page chain "
                "was already imported here (a retried handoff must "
                "abort the first import or target another engine)")
        if n > self.available:
            raise EngineFullError(
                f"import of {n} KV pages needs {n} free pages but only "
                f"{self.available} of {self.n_pages} are free")
        pages = []
        self._imports[token] = pages
        try:
            for _ in range(n):
                pages.append(self.alloc())
        except Exception:
            self.import_abort(token)
            raise
        return list(pages)

    def import_commit(self, token):
        """Burn the token (double-import protection) and keep the
        pages — the importer's request now owns them."""
        if token not in self._imports:
            raise RuntimeError(
                f"import_commit of unknown transfer {token!r}")
        del self._imports[token]
        self._imported[token] = True
        while len(self._imported) > self._imported_cap:
            self._imported.popitem(last=False)

    def import_abort(self, token):
        """Roll a failed import back: claimed pages return to the free
        list and the token is NOT burned (the handoff may be retried
        here after the failure is resolved)."""
        pages = self._imports.pop(token, None)
        if pages is None:
            raise RuntimeError(
                f"import_abort of unknown transfer {token!r}")
        if pages:
            self.free(pages)

    def alloc(self):
        fault_point("page.alloc")
        if not self._free:
            raise EngineFullError(
                f"KV page pool exhausted: 1 page needed, 0 of "
                f"{self.n_pages} available — all pages are in use "
                "(retire sequences or build the engine with a larger "
                "max_batch*max_len budget)")
        p = self._free.pop()
        self._ref[p] = 1
        self.total_allocs += 1
        return p

    def share(self, page):
        """Take an additional reference on an ALLOCATED page (prefix
        sharing). Returns the page id for chaining."""
        if self._ref[page] <= 0:
            raise RuntimeError(
                f"share() of free page {page} (refcount "
                f"{self._ref[page]}, never allocated or already "
                "recycled)")
        self._ref[page] += 1
        return page

    def refcount(self, page):
        return self._ref[page]

    def free(self, pages):
        """Drop one reference per listed page; pages reaching zero
        return to the free list."""
        for p in pages:
            if self._ref[p] <= 0:
                raise RuntimeError(
                    f"double free of page {p}: refcount is already "
                    f"{self._ref[p]} (every holder has released it)")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)

    @property
    def available(self):
        return len(self._free)


# canonical per-layer names (inference/description.py): the projections
# int8 may replace, and the leaves that stay float32 whatever the
# engine's weight_dtype (the router's product is float32; a sink is one
# float per head)
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
_F32_KEYS = ("router", "router_bias", "sink", "ix_wq", "ix_wk", "ix_kn_w",
             "ix_kn_b", "ix_ww")


class PageGroup:
    """The layers that share one page-pool shape, one page table and one
    freeing policy (`description.GroupKey`). Per-head K and V ("heads"):
    a key pool and a value pool. LATENT layers: ONE row a token for all
    heads ([c_kv ; k_r], padded to the 128 lanes: a gather of 576-wide
    rows ran 3.3 times slower on the v5e than of 640-wide ones, PERF.md
    PR 30) and no value pool. Where its layers have an indexer, either
    kind keeps the indexer's key a token in a second pool on the same
    page table, and a per-head group then keeps a token's K and V as ONE
    row too ([K ; V] of all its KV heads, `ops/sparse_attention.kv_row`):
    decode gathers the SELECTED tokens' rows, and one gathered row then
    brings both (docs/serving.md "Per-head groups with index keys").
    A FULL group (window None) keeps every token of a sequence: its pages
    are priced and claimed at admission, as the engine always did. A
    WINDOW group claims a page when a position is first written and
    frees it once its last token has fallen behind the window of every
    query still to come, so a sequence never holds more than
    `bound(tokens written at once)` of them."""

    def __init__(self, index, key, layers, page_size, max_batch,
                 pages_per_seq, chunk, plain=True):
        self.index = index
        self.kind = key.kind
        self.n_kv_heads, self.qk_dim, self.v_dim = key[1:4]
        self.window, self.index_width = key.window, key.index_width
        self.latent = key.kind == "latent"
        # one row a token: a latent group's, or [K ; V] beside index keys
        self.row_width = (
            self.qk_dim if self.latent
            else self.n_kv_heads * (self.qk_dim + self.v_dim)
            if self.index_width else 0)
        self.row_pad = -(-self.row_width // 128) * 128
        self.layers = tuple(layers)
        self.page_size = page_size
        self.col0 = index * pages_per_seq   # its columns of the table
        # a key width that is no multiple of the 128 lanes is stored
        # FLAT, a page [page, kv heads * width]: shaped [.., kv heads,
        # 192] XLA gives the step program's pool parameter one layout
        # and its pool result another and copies the whole pool between
        # them every step (PERF.md, PR 26). A plain description keeps
        # the shape every mode reads.
        self.k_flat = not plain and not self.row_width \
            and self.qk_dim % 128 != 0
        if self.window is None:
            self.n_pages = max_batch * pages_per_seq
        else:
            # every seat's resting pages plus the further pages of the
            # ONE chunk in flight
            self.n_pages = max_batch * self.bound(1) \
                + -(-chunk // page_size)
        self.allocator = PageAllocator(self.n_pages)
        self.freed_behind_window = 0    # lifetime, pages
        self.kv_tokens_read = 0         # lifetime: cached tokens x layers
        #                                 that decode queries attended to
        self.kv_pages_walked = 0        # lifetime: pages x layers the
        #                                 paged decode kernel visited
        self.prefill_pairs = 0          # lifetime: (query, key) pairs x
        #                                 layers of the prefill chunks
        self.used_page_steps = 0        # sum over steps of pages in use

    def bound(self, tokens):
        """Most pages one sequence holds while `tokens` positions are
        written in one program: ceil((window + tokens) / page) + 1."""
        return -(-(self.window + tokens) // self.page_size) + 1

    @property
    def used(self):
        return self.n_pages - self.allocator.available

    def pool_shapes(self):
        """(keys, values) of one layer; a group of rows has (rows, index
        keys), the second empty where its layers have no indexer."""
        if self.row_width:
            return ((self.n_pages, self.page_size, self.row_pad),
                    (self.n_pages, self.page_size, self.index_width)
                    if self.index_width else (0,))
        h = self.n_kv_heads
        k = ((self.n_pages, self.page_size, h * self.qk_dim) if self.k_flat
             else (self.n_pages, self.page_size, h, self.qk_dim))
        return k, (self.n_pages, self.page_size, h, self.v_dim)


def _snapshot(model, quant, weight_dtype=None, quant_scales=None):
    """Pull the model's serving_parameters() out of the Layer tree into
    plain arrays under the engine's canonical names.
    quant='int8' replaces the projection weights of every layer (and
    the lm_head) with (int8, scales) pairs; quant_scales (a
    quantization.ptq.CalibrationResult) swaps the absmax-from-weights
    scales for PTQ-calibrated ones, leaf by leaf — a leaf the
    calibration lacks keeps the absmax fallback, and a scale vector of
    the wrong width fails typed before anything installs.

    Lazy-aware: a model built under framework.LazyGuard (meta init) is
    materialized HERE, one leaf at a time, straight to `weight_dtype` —
    the serving analog of SpmdTrainer.init_state. A 7B checkpoint-scale
    model therefore reaches the chip as 13.5 GB of bf16 (or 6.7 GB int8)
    without ever holding the 27 GB eager-f32 tree that cannot fit the
    16 GB v5e."""
    from ..framework.misc import materialize_lazy
    wdt = weight_dtype  # validated jnp.dtype (or None) from LLMEngine

    def take(param, f32=False):
        w = materialize_lazy(param)  # no-op for eagerly-built params
        if f32:
            return w.astype(jnp.float32)
        if wdt is not None and jnp.issubdtype(w.dtype, jnp.floating):
            w = w.astype(wdt)
        return w

    def maybe_q(param, li=None, proj=None):
        # int8 quantizes from the natively-materialized values (NOT from a
        # weight_dtype-rounded copy: scales should see full init precision)
        if quant == "int8":
            w = materialize_lazy(param)
            sc_cal = (quant_scales.weight_scale(li, proj)
                      if quant_scales is not None else None)
            if sc_cal is not None:
                from ..quantization.ptq import quantize_with_scales
                return quantize_with_scales(w.astype(jnp.float32), sc_cal)
            wq, sc = quantize_weights(w.astype(jnp.float32))
            return (wq, sc)
        return take(param)

    params = model.serving_parameters()
    layers = [
        {name: (maybe_q(param, li, name) if name in _QUANT_KEYS
                else take(param, f32=name in _F32_KEYS))
         for name, param in layer.items()}
        for li, layer in enumerate(params["layers"])]
    return dict(emb=take(params["emb"]), norm=take(params["norm"]),
                head=maybe_q(params["head"], None, "head"),
                layers=layers, eps=model.serving_description().eps)


def _mm(x, w, interpret):
    """x @ w where w is either a dense array or an (int8, scales) pair."""
    if isinstance(w, tuple):
        wq, sc = w
        flat = x.reshape(-1, x.shape[-1])
        out = quantized_matmul(flat, wq, sc, out_dtype=x.dtype,
                               interpret=interpret)
        return out.reshape(*x.shape[:-1], -1)
    return x @ w.astype(x.dtype)


def _mm_f32(x, w, interpret):
    """x @ w with a float32 result: the operands reach the MXU in the
    weight's own dtype, the sums are float32 and are NOT rounded back to
    it (what a float32 residual stream adds its updates from)."""
    if isinstance(w, tuple):
        return _mm(x, w, interpret).astype(jnp.float32)
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * w.astype(x.dtype)


def _layer_norm(x, w, eps):
    """`ModelDescription.norm` "layer": the mean taken off, then the
    RMSNorm of what is left; a weight and no bias."""
    x32 = x.astype(jnp.float32)
    return _rms(x32 - jnp.mean(x32, axis=-1, keepdims=True), w,
                eps).astype(x.dtype)


_NORMS = {"rms": _rms, "layer": _layer_norm}


class LLMEngine:
    """Paged-KV decode engine for any model that describes itself
    (inference/description.py): the engine reads the per-layer
    description and the canonical parameter names, never a model class.

    max_batch sequences, each up to max_len tokens, share a pool of
    (max_batch * max_len / page_size) pages per full-attention layer;
    layers of another shape or with a sliding window form page groups of
    their own (PageGroup).
    """

    def __init__(self, model, max_len=1024, page_size=128, max_batch=8,
                 quant=None, batch_buckets=None,
                 weight_dtype=None, flash_prefill_min=256,
                 tp=1, tp_mode="exact", tp_compress=None,
                 quant_scales=None):
        self.desc = desc = describe(model)
        if quant not in (None, "int8"):
            raise ValueError(f"unsupported quant {quant!r}")
        if quant_scales is not None and quant != "int8":
            raise ValueError(
                "quant_scales (PTQ calibration) only applies with "
                "quant='int8' — the scales feed the int8 snapshot")
        if weight_dtype is not None:
            asked = weight_dtype
            try:
                weight_dtype = jnp.dtype(weight_dtype)
            except TypeError:
                weight_dtype = None  # unparseable ("fp16") fails the same way
            if weight_dtype not in (jnp.dtype(jnp.bfloat16),
                                    jnp.dtype(jnp.float32),
                                    jnp.dtype(jnp.float16)):
                raise ValueError(
                    f"unsupported weight_dtype {asked!r}; expected "
                    f"bfloat16/float16/float32")
        model.eval()
        cfg = model.config
        self.cfg = cfg
        self.page_size = page_size
        self.max_len = max_len
        self.max_batch = max_batch
        # logical pages of one sequence; the page table a program takes
        # is [rows, max_pages_per_seq] = every group's columns side by
        # side (one group: the same thing)
        self.pages_per_seq = -(-max_len // page_size)
        self.max_pages_per_seq = self.pages_per_seq * len(desc.groups)
        # the shapes every mode of a PLAIN description reads; for another
        # description they are the first layer's, and the modes that read
        # them are refused at construction
        # the residual stream's dtype. A plain description keeps the
        # cache's (bf16 on the chip), as every mode was written and
        # measured. Any other carries it in float32 — the norms' inputs,
        # the router's input and the logits are then not rounded to bf16
        # at every layer — with bf16 operands and float32 sums in every
        # product: routed experts turn a rounding into a DIFFERENT
        # EXPERT, which a float32 reference then reads as a wrong token
        # (PERF.md, PR 26: one check in nine failed with a bf16 stream)
        self.f32_stream = not desc.plain
        a0 = desc.layers[0].attn
        self.nh = a0.n_heads
        self.hd = a0.qk_dim
        # GQA checkpoints: the paged cache keeps the kv head count
        self.nh_kv = a0.n_kv_heads
        for layer in desc.layers:
            if layer.attn.n_heads % layer.attn.n_kv_heads:
                raise ValueError(
                    f"num_attention_heads ({layer.attn.n_heads}) must be "
                    f"a multiple of num_key_value_heads "
                    f"({layer.attn.n_kv_heads})")
        if desc.norm not in _NORMS:
            raise UnsupportedByDescription(
                f"ModelDescription.norm {desc.norm!r}: the engine has "
                f"{sorted(_NORMS)}")
        self._norm = _NORMS[desc.norm]
        for layer in desc.layers:
            a = layer.attn
            rows = a.latent is not None or a.indexer is not None
            if rows and (layer.parallel or desc.norm != "rms"
                         or a.rope_dim == 0):
                raise UnsupportedByDescription(
                    "a latent layer or a layer with an indexer norms its "
                    "own input with an RMSNorm and rotates: it is served "
                    "in a sequential block, under `norm` \"rms\", with "
                    "rope_dim > 0")
        if not desc.plain:
            if int(tp or 1) > 1:
                raise UnsupportedByDescription(
                    "tp > 1 shards a plain dense block by heads and "
                    "columns; this model's layer description (layers of "
                    "several kinds, routed experts, key width != value "
                    "width) is served on one chip only")
            if quant is not None and desc.has_experts:
                raise UnsupportedByDescription(
                    "quant='int8' has no grouped int8 product for routed "
                    "experts yet; serve this description unquantized")
            if quant is not None and (desc.has_indexer or any(
                    layer.attn.latent is not None
                    for layer in desc.layers)):
                raise UnsupportedByDescription(
                    "quant='int8' does not cover a latent layer's "
                    "projections or an indexer; serve this description "
                    "unquantized")
        # tensor parallelism: tp > 1 runs every compiled dispatch under
        # shard_map on a 1-D "mp" mesh — heads + KV pools sharded over
        # heads, matmuls column/row-parallel (inference/tp.py). The
        # traced math below uses the LOCAL head counts (nh_l/nh_kv_l ==
        # the globals at tp=1), so one code path serves both.
        self.tp = int(tp or 1)
        if self.tp > 1:
            if self.nh % self.tp or self.nh_kv % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide both num_attention_heads "
                    f"({self.nh}) and num_key_value_heads ({self.nh_kv}) "
                    "— heads shard evenly, GQA groups never split")
            from .tp import TPContext
            self._tpc = TPContext(self.tp, tp_mode, tp_compress)
        else:
            self._tpc = None
        self.tp_mode = tp_mode if self.tp > 1 else None
        self.tp_compress = tp_compress if self.tp > 1 else None
        self.nh_l = self.nh // self.tp
        self.nh_kv_l = self.nh_kv // self.tp
        self.quant = quant
        # Pallas kernels are interpreted on the CPU backend only (CI);
        # on a chip they compile or the engine fails
        self.interpret = jax.default_backend() == "cpu"
        # prompts at/above this padded length prefill through the flash
        # kernel instead of dense scores (see _attn_prefill)
        self.flash_prefill_min = int(flash_prefill_min)
        self._flash = None
        self.quant_scales = quant_scales
        # set-up phases as always-on spans (profiler.span_totals();
        # docs/observability.md): host time, so device work dispatched
        # inside one may finish after it
        with RecordEvent("setup.engine.weights"):
            self.weights = _snapshot(model, quant, weight_dtype,
                                     quant_scales)
        # the type the weight matmuls' operands reach the MXU in: the
        # kernels' own rule on this engine's activation and weight
        # dtypes (static; health()["mm_operand_dtype"])
        head = self.weights["head"]
        self.mm_operand_dtype = jnp.dtype(mm_operand_dtype(
            self.weights["emb"].dtype,
            (head[0] if isinstance(head, tuple) else head).dtype)).name
        dtype = (jnp.bfloat16 if jax.default_backend() != "cpu"
                 else jnp.float32)
        self.kv_dtype = dtype
        self._new_pools()
        self._step_fn = None
        self._prefill_fns = {}
        self._loop_fns = {}
        # batch buckets (OPT-IN): generate() pads the request batch up to
        # the nearest bucket so varying batch sizes reuse a handful of
        # compiled prefill/step programs instead of one per size. Off by
        # default: padding changes the shape jax.random draws over, so
        # sampled generations would differ from the unpadded run for the
        # same seed (greedy decoding is batch-size invariant).
        self._batch_buckets = (tuple(sorted(set(
            min(int(x), max_batch) for x in batch_buckets)))
            if batch_buckets is not None else None)
        # rope tables ride inside the weight pytree so the jitted
        # prefill/step never closure-capture arrays (HLO-constant bloat):
        # one (cos, sin) pair per distinct (rotary width, base), which a
        # plain description has one of, under the names it always had
        # (an indexer rotates its own width on its layer's base: one
        # more pair where that width is not the layer's)
        # (a position-free layer, rope_dim 0, has no table)
        def rope_kinds(a):
            return [(a.rope_dim, a.rope_theta)] * bool(a.rope_dim) + (
                [(a.indexer.rope_dim, a.rope_theta)] if a.indexer else [])

        kinds = []
        for layer in desc.layers:
            for kind in rope_kinds(layer.attn):
                if kind not in kinds:
                    kinds.append(kind)

        def table_of(a, which):
            mine = rope_kinds(a)
            return kinds.index(mine[which]) if mine else None

        self._layer_rope = tuple(table_of(layer.attn, 0)
                                 for layer in desc.layers)
        self._index_rope = tuple(table_of(layer.attn, -1)
                                 for layer in desc.layers)
        tables = [_rope_cache(max_len, d, theta, jnp.float32)
                  for d, theta in kinds]
        if len(tables) == 1:
            self.weights["cos"], self.weights["sin"] = tables[0]
        else:
            self.weights["rope"] = tables
        if self._tpc is not None:
            # place weights + pools onto the mesh ONCE — every later
            # dispatch is zero-copy (jit would silently reshard per call
            # otherwise, moving the whole snapshot each step)
            self._w_specs = self._tpc.weight_specs(self.weights)
            self.weights = self._tpc.place(self.weights, self._w_specs)
            self.k_pages = self._tpc.place_pools(self.k_pages)
            self.v_pages = self._tpc.place_pools(self.v_pages)

    def _new_pools(self):
        """Fresh page groups (allocators, counters) and zeroed pools: one
        K and one V array per layer, shaped by the layer's group. Group 0
        is what `allocator` / `n_pages` always named."""
        desc, p = self.desc, self.page_size
        chunk = int(getattr(self, "prefill_chunk", 0) or p)
        layer_group = desc.layer_group
        self.groups = [
            PageGroup(gi, key,
                      [li for li, g in enumerate(layer_group) if g == gi],
                      p, self.max_batch, self.pages_per_seq, chunk,
                      plain=desc.plain)
            for gi, key in enumerate(desc.groups)]
        self.allocator = self.groups[0].allocator
        self.n_pages = self.groups[0].n_pages
        with RecordEvent("setup.engine.kv_pool"):
            self.k_pages, self.v_pages = [], []
            for gi in layer_group:
                group = self.groups[gi]
                with RecordEvent(f"setup.engine.kv_pool.group{gi}"):
                    k_shape, v_shape = group.pool_shapes()
                    self.k_pages.append(jnp.zeros(k_shape, self.kv_dtype))
                    self.v_pages.append(jnp.zeros(v_shape, self.kv_dtype))

    def _rope_of(self, W, li, indexer=False):
        """(cos, sin) tables of layer li, or of its indexer: [max_len,
        rotary width / 2]."""
        if "rope" in W:
            return W["rope"][(self._index_rope if indexer
                              else self._layer_rope)[li]]
        return W["cos"], W["sin"]

    def _require_plain(self, what):
        if not self.desc.plain:
            raise UnsupportedByDescription(
                f"{what} serves a plain description only (every layer "
                "the same dense block, full rotary causal attention, "
                "equal key and value widths); this model's layers differ "
                "— serve it through ContinuousBatchingEngine.add_request "
                "/ step at decode_block=1")

    # -- tensor parallelism (inference/tp.py) -------------------------------
    def _jit_tp(self, fn, in_specs, out_specs, donate_argnums=()):
        """jit(fn), or jit(shard_map(fn)) on the mp mesh when tp > 1.
        The traced fns are written against LOCAL head counts, so the
        same body serves both paths."""
        if self._tpc is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        return jax.jit(self._tpc.wrap(fn, in_specs, out_specs),
                       donate_argnums=donate_argnums)

    def _tp_specs(self):
        """(weight_spec, replicated, pool_spec) shorthand for builders —
        pool spec tracks the CURRENT pool form (per-layer list, or the
        natively stacked [L, ...] array of megakernel="multi").
        Meaningless (unused) at tp=1."""
        from .tp import POOL, REPL, STACKED_POOL
        stacked = not isinstance(self.k_pages, (list, tuple))
        return (self._w_specs if self._tpc is not None else None,
                REPL, STACKED_POOL if stacked else POOL)

    def _lm_head(self, W, h):
        """Final logits: h @ lm_head. Under tensor parallelism with a
        vocab-parallel head (inference/tp.py weight_specs) the local
        matmul covers this shard's vocab columns and the FULL row
        reassembles by an exact tiled gather — pure data movement, so
        the result is byte-identical to the replicated head. Callers on
        the greedy hot path should prefer _tp_greedy_token, which skips
        the gather entirely (argmax-of-local-max)."""
        mm = _mm_f32 if self.f32_stream else _mm
        return self._gather_logits(mm(h, W["head"], self.interpret))

    def _gather_logits(self, local_logits):
        """Reassemble full-vocab logits from the vocab-parallel head's
        local columns (exact tiled gather; identity at tp=1 or with a
        replicated head). Callers that only argmax should skip this —
        XLA dead-code-eliminates the gather when the result is unused."""
        if self._tpc is not None and self._tpc.head_sharded:
            return self._tpc.gather_cols(local_logits)
        return local_logits

    def _tp_greedy_token(self, local_logits):
        """Greedy next token from (possibly vocab-local) logits rows:
        plain argmax at tp=1 / replicated head; under the vocab-
        parallel head, the psum-free argmax-of-local-max combine —
        bitwise equal to argmax over the full gathered logits."""
        if self._tpc is None or not self._tpc.head_sharded:
            return jnp.argmax(local_logits, axis=-1).astype(jnp.int32)
        m = jnp.max(local_logits, axis=-1)
        a = jnp.argmax(local_logits, axis=-1).astype(jnp.int32)
        return self._tpc.argmax_of_local_max(
            m, a, local_logits.shape[-1])

    def _tp_topk(self, local_logits, k):
        """Top-K (f32 values, i32 vocab ids) rows from (possibly
        vocab-local) logits — the sampled-path sibling of
        _tp_greedy_token: plain lax.top_k at tp=1 / replicated head;
        under the vocab-parallel head, the gather-free topk-of-local-
        topk combine — bitwise equal to lax.top_k over the full
        gathered logits (shard-major concat preserves the id-asc tie
        order). Values return as f32 (an exact upcast) so both this
        path and the megakernel's f32 select scratch feed the selection
        math identical bits."""
        lv, li = jax.lax.top_k(local_logits, k)
        lv = lv.astype(jnp.float32)
        li = li.astype(jnp.int32)
        if self._tpc is None or not self._tpc.head_sharded:
            return lv, li
        return self._tpc.topk_of_local_topk(
            lv, li, local_logits.shape[-1], k)

    def _tp_gather_heads(self, x):
        """exact-mode TP: reassemble full heads before o_proj (identity
        at tp=1 and in psum mode, where wo is row-sharded instead)."""
        if self._tpc is None or self._tpc.mode != "exact":
            return x
        return self._tpc.gather_heads(x)

    def _tp_gather_cols(self, x):
        """exact-mode TP: reassemble full MLP activations before
        down_proj (identity at tp=1 / psum mode)."""
        if self._tpc is None or self._tpc.mode != "exact":
            return x
        return self._tpc.gather_cols(x)

    def _tp_reduce(self, x):
        """psum-mode TP: the per-token all-reduce closing a row-parallel
        pair (identity at tp=1 / exact mode)."""
        if self._tpc is None or self._tpc.mode != "psum":
            return x
        return self._tpc.reduce(x)

    # -- math ---------------------------------------------------------------
    def _attn_dense(self, q, k, v):
        """Prefill attention (causal, dense over the prompt). GQA kv
        arrives at nh_kv heads; the expansion here is TRANSIENT (prefill
        activations only) — the cache itself stays at nh_kv."""
        k = expand_kv_heads(k, q.shape[2])
        v = expand_kv_heads(v, q.shape[2])
        s = q.shape[1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(self.hd)
        tri = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(tri[None, None], logits, -1e30)
        w = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    def _attn_prefill(self, q, k, v, t_pad):
        """Prefill attention dispatch: long prompts ride the Pallas flash
        kernel (no [b, h, t, t] logits tensor — at a 2048-token prompt the
        dense path materializes 0.5 GB of f32 scores per 7B-geometry
        batch row); short prompts keep the dense path, where flash's
        256-padding would outweigh the tiling win. Gated on head dims the
        kernel tiles natively (lane multiples + the tested d=64 fallback)."""
        if t_pad >= self.flash_prefill_min and (
                self.hd == 64 or self.hd % 128 == 0):
            if self._flash is None:
                from ..ops.pallas.flash_attention import make_flash_attention
                self._flash = make_flash_attention(interpret=self.interpret)
            qh = q.shape[2]
            return self._flash(q, expand_kv_heads(k, qh),
                               expand_kv_heads(v, qh), True,
                               1.0 / math.sqrt(self.hd))
        return self._attn_dense(q, k, v)

    def _layer_qkv(self, W, wset, h, pos_ids, ad=None, li=0):
        # head-count comes from the matmul's own width (nh_l/nh_kv_l):
        # under shard_map the column-sharded wq/wk/wv produce this
        # shard's heads only, at tp=1 the full set — same code path.
        # ad: per-layer LoRA selection (inference/adapters.py) — the
        # grouped low-rank delta lands on the projection OUTPUTS
        # (pre-rope, pre-reshape), where-gated so adapter-free rows
        # keep their exact bits; None (the default, and the only value
        # the static-generate paths ever pass) is zero-cost.
        # li: the layer, for its AttentionSpec (key and value widths,
        # how many leading dims rotate and on which base, the value
        # scale); the modes that serve plain descriptions only leave it
        # at 0, every layer being the same.
        with phase("attn_proj"):
            a = self.desc.layers[li].attn
            b, t, H = h.shape
            x = self._norm(h, wset["ln1"], W["eps"])
            if self.f32_stream:         # operands in the cache's dtype
                x = x.astype(self.kv_dtype)
            if "wqkv" in wset:
                # one fused projection, columns q | k | v: a layout of the
                # weights, the mathematics is three projections
                qkv = _mm(x, wset["wqkv"], self.interpret)
                nq = a.n_heads * a.qk_dim
                nk = a.n_kv_heads * a.qk_dim
                q, k, v = qkv[..., :nq], qkv[..., nq:nq + nk], \
                    qkv[..., nq + nk:]
            else:
                q = _mm(x, wset["wq"], self.interpret)
                k = _mm(x, wset["wk"], self.interpret)
                v = _mm(x, wset["wv"], self.interpret)
                if self.f32_stream:
                    # the products END here: left to fuse the reshape to
                    # heads into them, the TPU compiler transposes the
                    # WEIGHT inside every program to get [.., heads, d]
                    # out of the product (4 x 134 MB a decode step at 128
                    # heads x 128: 1.7 of 21.7 ms; PERF.md 6, PR 36); so
                    # the few rows of activations are relaid instead
                    q, k, v = jax.lax.optimization_barrier((q, k, v))
            if ad is not None:
                from .adapters import lora_apply
                q = lora_apply(q, x, "wq", ad)
                k = lora_apply(k, x, "wk", ad)
                v = lora_apply(v, x, "wv", ad)
            q = q.reshape(b, t, -1, a.qk_dim)
            k = k.reshape(b, t, -1, a.qk_dim)
            v = v.reshape(b, t, -1, a.v_dim)
            if a.qk_norm:               # every head, before the rotation
                q = _rms(q, wset["q_hn"], W["eps"])
                k = _rms(k, wset["k_hn"], W["eps"])
            if a.value_scale != 1.0:
                v = v * jnp.asarray(a.value_scale, v.dtype)
            # GQA: k/v STAY at nh_kv heads — the paged cache stores the
            # checkpoint's kv width (1/rep the HBM of an expanded cache) and
            # the decode kernel maps q head i -> kv head i // rep natively
            if not a.rope_dim:          # a position-free layer
                return q, k, v
            cos, sin = self._rope_of(W, li)
            c = cos[pos_ids][..., None, :].astype(q.dtype)
            s = sin[pos_ids][..., None, :].astype(q.dtype)
            d2 = a.rope_dim // 2

            def rope(x_):
                x1, x2 = x_[..., :d2], x_[..., d2:a.rope_dim]
                out = [x1 * c - x2 * s, x2 * c + x1 * s]
                if a.rope_dim < a.qk_dim:       # partial rotary: the rest
                    out.append(x_[..., a.rope_dim:])        # passes through
                return jnp.concatenate(out, -1)

            return rope(q), rope(k), v

    def _layer_tail(self, W, wset, h, attn_out, ad=None, li=0,
                    expert_rows=None):
        # TP row-parallel pair (o_proj / down_proj): "exact" mode
        # gathers the sharded operand and runs the full matmul
        # replicated (byte-identical to tp=1 — the gather is pure data
        # movement); "psum" mode keeps the operand local against
        # row-sharded weights and all-reduces the partial outputs. At
        # tp=1 every hook is identity and this is the original chain.
        # ad: per-layer LoRA selection — deltas on gate/up (local
        # columns under tp, like the projections) and on down (after
        # the exact-mode gather, replicated like wd itself); adapters
        # require tp_mode="exact" (gated at engine build) because the
        # down delta needs the FULL activation row.
        # li: the layer, for its FFNSpec — a dense SwiGLU or the routed
        # experts held here (ops/moe.py); expert_rows, a list, collects
        # the rows each held expert received ([held] int32 per expert
        # layer) for the engine's routing counters. A PARALLEL layer
        # (LayerSpec.parallel) has one norm: its FFN reads the block's
        # input under `ln1`, as its projections did (the same expression
        # in the same program: the compiler keeps one), and attention
        # and FFN join the residual stream in one sum.
        spec = self.desc.layers[li]
        b, t = attn_out.shape[:2]
        # the two products whose results join the residual stream
        mm_out = _mm_f32 if self.f32_stream else _mm
        with phase("attn_proj"):   # the output projection
            attn_out = self._tp_gather_heads(attn_out)
            o = mm_out(attn_out.reshape(b, t, -1), wset["wo"], self.interpret)
            o = self._tp_reduce(o)
            if spec.parallel:
                res = h + o             # what the FFN's result joins
            else:
                res = h = h + o
        with phase("ffn"):
            x = self._norm(h, wset["ln1" if spec.parallel else "ln2"],
                           W["eps"])
            ffn = spec.ffn
            if ffn.kind == "experts":
                y, rows = routed_experts(
                    x.reshape(b * t, -1), wset["router"],
                    wset.get("router_bias"), wset["w_gu"], wset["w_d"],
                    ffn.held, ffn.top_k, interpret=self.interpret,
                    score=ffn.score)
                if expert_rows is not None:
                    expert_rows.append(rows)
                if ffn.shared_width:    # the shared expert, on every token
                    with jax.named_scope("shared_expert"):
                        y = y + la.swiglu(
                            x.reshape(b * t, -1), wset["ws_g"],
                            wset["ws_u"], wset["ws_d"])
                return res + y.reshape(b, t, -1)
            if self.f32_stream:
                x = x.astype(self.kv_dtype)
            g = _mm(x, wset["wg"], self.interpret)
            u = _mm(x, wset["wu"], self.interpret)
            if ad is not None:
                from .adapters import lora_apply
                g = lora_apply(g, x, "wg", ad)
                u = lora_apply(u, x, "wu", ad)
            act = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
            act = self._tp_gather_cols(act)
            d = mm_out(act, wset["wd"], self.interpret)
            if ad is not None:
                from .adapters import lora_apply
                d = lora_apply(d, act, "wd", ad)
            return res + self._tp_reduce(d)

    # -- prefill ------------------------------------------------------------
    def _build_prefill(self, t_pad):
        """Batched prefill over a PADDED prompt length (multiple of
        page_size, so at most max_len/page_size variants ever compile).
        Padded positions write garbage KV into slots past t0 — harmless:
        paged attention masks by lens, and each decode step overwrites its
        slot before reading it.

        Weights ride as an ARGUMENT pytree, never a closure capture:
        captured arrays lower to constants embedded in the HLO proto, and
        a whole-model constant blob makes compiles pathological (measured
        80s for a single 64 MB captured matmul vs 0.9s as an argument,
        builder-reported on an older stack — a full snapshot never
        finished at all)."""

        def prefill(W, ids, k_pages_all, v_pages_all, tables, t0):
            """W: weight pytree; ids [b, t_pad]; t0 = true prompt length
            (dynamic)."""
            b = ids.shape[0]
            h = jnp.take(W["emb"], ids, axis=0).astype(self.kv_dtype)
            pos_ids = jnp.broadcast_to(jnp.arange(t_pad)[None, :],
                                       (b, t_pad))
            new_k, new_v = [], []
            for li, wset in enumerate(W["layers"]):
                q, k, v = self._layer_qkv(W, wset, h, pos_ids)
                attn = self._attn_prefill(q, k, v, t_pad)
                h = self._layer_tail(W, wset, h, attn)
                # scatter every sequence's kv into its pages at once
                pos = jnp.arange(t_pad)[None, :]
                slots = (tables[jnp.arange(b)[:, None],
                                pos // self.page_size]
                         * self.page_size + pos % self.page_size)  # [b,t]
                kp = k_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
                vp = v_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
                kp = kp.at[slots].set(k.astype(self.kv_dtype))
                vp = vp.at[slots].set(v.astype(self.kv_dtype))
                new_k.append(kp.reshape(self.n_pages, self.page_size,
                                        self.nh_kv_l, self.hd))
                new_v.append(vp.reshape(self.n_pages, self.page_size,
                                        self.nh_kv_l, self.hd))
            h = _rms(h, W["norm"], W["eps"])
            h_last = jax.lax.dynamic_index_in_dim(h, t0 - 1, axis=1)
            logits = self._lm_head(W, h_last)
            return logits[:, 0], new_k, new_v

        W, R, POOL = self._tp_specs()
        return self._jit_tp(prefill,
                            in_specs=(W, R, POOL, POOL, R, R),
                            out_specs=(R, POOL, POOL),
                            donate_argnums=(2, 3))

    # -- decode step ----------------------------------------------------------
    def _step_math(self, W, tok, k_pages_all, v_pages_all, tables, lens):
        """One decode step, fully traceable (shared by the per-token jit
        and the device-side lax.scan loop). W: weight pytree (argument,
        not capture — see _build_prefill); tok [b]; lens [b] = tokens
        already in cache (position of this token). One token for EVERY
        slot; masked by caller."""
        p = self.page_size
        b = tok.shape[0]
        h = jnp.take(W["emb"], tok[:, None], axis=0).astype(self.kv_dtype)
        pos_ids = lens[:, None]                      # ragged positions
        new_k, new_v = [], []
        for li, wset in enumerate(W["layers"]):
            q, k, v = self._layer_qkv(W, wset, h, pos_ids)
            # write this token's kv at each sequence's slot
            slots = (tables[jnp.arange(b), lens // p] * p + lens % p)
            kp = k_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
            vp = v_pages_all[li].reshape(-1, self.nh_kv_l, self.hd)
            kp = kp.at[slots].set(k[:, 0].astype(self.kv_dtype))
            vp = vp.at[slots].set(v[:, 0].astype(self.kv_dtype))
            kp = kp.reshape(self.n_pages, p, self.nh_kv_l, self.hd)
            vp = vp.reshape(self.n_pages, p, self.nh_kv_l, self.hd)
            new_k.append(kp)
            new_v.append(vp)
            attn = paged_attention(q[:, 0], kp, vp, tables, lens + 1,
                                   interpret=self.interpret)
            h = self._layer_tail(W, wset, h, attn[:, None])
        h = _rms(h, W["norm"], W["eps"])
        logits = self._lm_head(W, h)
        return logits[:, 0], new_k, new_v

    def _build_step(self):
        def step(W, tok, k_pages_all, v_pages_all, tables, lens):
            return self._step_math(W, tok, k_pages_all, v_pages_all,
                                   tables, lens)

        W, R, POOL = self._tp_specs()
        return self._jit_tp(step, in_specs=(W, R, POOL, POOL, R, R),
                            out_specs=(R, POOL, POOL),
                            donate_argnums=(2, 3))

    def _build_decode_loop(self, n, do_sample, temperature, top_k, top_p):
        """Device-side decode: n steps as ONE dispatch (lax.scan over
        _step_math + sampling). Kills the per-token host→device round
        trip that dominates small-batch decode off-chip — the TPU analog
        of the reference's fused decode loop
        (ref: fused_multi_transformer_op.cu.h decode path, which exists
        to amortize per-token launch overhead on GPU). Runs all n steps
        (no early EOS exit inside the scan); generate() trims trailing
        post-EOS columns so greedy output matches the host loop."""
        from ..models.generation import _sample

        def loop(W, tok0, k_pages_all, v_pages_all, tables, lens0, key0):
            def body(carry, _):
                tok, kp, vp, lens, key = carry
                logits, kp, vp = self._step_math(W, tok, kp, vp, tables,
                                                 lens)
                key, sub = jax.random.split(key)
                nxt = _sample(logits, sub, do_sample, temperature, top_k,
                              top_p)
                return (nxt, kp, vp, lens + 1, key), nxt

            carry0 = (tok0, k_pages_all, v_pages_all, lens0, key0)
            (_, kp, vp, _, _), toks = jax.lax.scan(body, carry0, None,
                                                   length=n)
            return jnp.swapaxes(toks, 0, 1), kp, vp   # [b, n]

        W, R, POOL = self._tp_specs()
        return self._jit_tp(loop,
                            in_specs=(W, R, POOL, POOL, R, R, R),
                            out_specs=(R, POOL, POOL),
                            donate_argnums=(2, 3))

    def _reclaim_pages(self, n):
        """Hook: free up to n idle pages (no-op here; the continuous-
        batching engine overrides it to evict prefix-cache pages)."""
        return 0

    @staticmethod
    def _finish_eos(full, t0, eos_token_id):
        """Per-row EOS finishing: each row keeps its generated tokens up
        to and including ITS OWN first EOS; later columns are masked to
        eos_token_id, and the array is trimmed to the longest surviving
        row (a row that never emits EOS keeps its full budget). Shared by
        the host loop and the device (lax.scan) loop so both modes agree
        token-for-token."""
        if eos_token_id is None:
            return full
        gen = full[:, t0:]
        n = gen.shape[1]
        if n == 0:
            return full
        keep = []
        for row in gen:
            hit = np.flatnonzero(row == eos_token_id)
            keep.append(int(hit[0]) + 1 if hit.size else n)
        for i, k in enumerate(keep):
            gen[i, k:] = eos_token_id
        return full[:, :t0 + max(keep)]

    def _reset_kv(self):
        """Fresh pools + allocator — a failed call's donated buffers are
        gone, and so is every in-flight sequence's cache."""
        self._new_pools()
        if self._tpc is not None:
            self.k_pages = self._tpc.place_pools(self.k_pages)
            self.v_pages = self._tpc.place_pools(self.v_pages)

    # -- weight snapshots (zero-downtime hot-swap substrate) ----------------
    # Derived/config entries are rebuilt at install, never serialized:
    # rope tables and eps come from the config ("eps" as a python float
    # stays WEAK-typed inside _rms — a round-tripped f64 array would
    # promote the norm math and bit-drift greedy outputs), "mk" is the
    # megakernel repack.
    _DERIVED_WEIGHT_KEYS = ("cos", "sin", "eps", "mk")

    def export_weights(self):
        """The engine's serializable weight pytree: everything the model
        snapshot holds except derived entries (rope tables, megakernel
        repacks — rebuilt by install_weights)."""
        return {k: v for k, v in self.weights.items()
                if k not in self._DERIVED_WEIGHT_KEYS}

    def save_weights_snapshot(self, path, step=None):
        """Atomic CRC32-manifest save of the CURRENT weights (the
        artifact a later hot-swap loads and verifies)."""
        from ..distributed import checkpoint as ckpt
        ckpt.save_snapshot(self.export_weights(), path, step=step)
        return path

    def load_weights_snapshot(self, path):
        """Load + verify (CRC32, tree structure, per-leaf shapes) a
        snapshot against THIS engine's weight tree without installing
        it. Raises CheckpointCorruptError before the engine is touched;
        the flip itself is install_weights."""
        from ..distributed import checkpoint as ckpt
        return ckpt.load_snapshot_for(self.export_weights(), path)

    def install_weights(self, new):
        """Flip the serving weights to `new` (an export_weights-shaped
        pytree, e.g. from load_weights_snapshot). The jitted programs
        take weights as an ARGUMENT pytree, so the flip needs no
        recompilation — the next dispatch simply runs the new values.
        Derived entries (rope tables) are preserved; subclasses rebuild
        theirs (megakernel repack) and gate the flip at a safe point."""
        cur = self.export_weights()
        if (jax.tree_util.tree_structure(cur)
                != jax.tree_util.tree_structure(new)):
            raise ValueError(
                "install_weights: snapshot tree structure does not match "
                "this engine's weights (different quant/layer layout?)")
        self.weights.update(new)
        if self._tpc is not None:
            # re-place the fresh (host/unsharded) leaves onto the mesh;
            # already-placed leaves (rope tables) are a no-op
            self.weights = self._tpc.place(self.weights, self._w_specs)
        return self

    # -- public -------------------------------------------------------------
    def generate(self, input_ids, max_new_tokens=32, eos_token_id=None,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 seed=0, device_loop=False):
        """Decode with greedy or top-k/top-p sampling. input_ids: [b, t0]
        equal-length prompts. Returns [b, t0+n].

        device_loop=True runs the whole decode as ONE compiled lax.scan
        dispatch (_build_decode_loop) instead of one jit call per token —
        the throughput mode when host→device latency is non-trivial. All
        max_new_tokens steps execute (EOS trims the OUTPUT, it cannot
        stop the scan early), so the host loop remains the better mode
        when generations usually terminate long before the budget."""
        from ..models.generation import _sample
        self._require_plain("generate() (the static-batch path)")
        ids = np.asarray(input_ids.numpy() if isinstance(input_ids, Tensor)
                         else input_ids)
        b_real, t0 = ids.shape
        if b_real > self.max_batch:
            raise ValueError(
                f"batch of {b_real} prompts exceeds this engine's "
                f"max_batch={self.max_batch}; split the batch or build "
                "the engine with a larger max_batch")
        if t0 + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt length {t0} + max_new_tokens {max_new_tokens} "
                f"= {t0 + max_new_tokens} exceeds this engine's "
                f"max_len={self.max_len}")
        # pad the batch up to the nearest bucket (compile reuse); padded
        # rows replay row 0 and are dropped before returning
        b = b_real
        if self._batch_buckets:
            b = next((x for x in self._batch_buckets if x >= b_real),
                     self.max_batch)
            if b != b_real:
                ids = np.concatenate(
                    [ids, np.repeat(ids[:1], b - b_real, axis=0)], axis=0)

        # allocate pages for each sequence (padded-prefill garbage slots
        # included, so allocate through the padded length)
        t_pad = min(-(-t0 // self.page_size) * self.page_size, self.max_len)
        n_rest = max_new_tokens - 1
        # device loop: bucket the scan length to the next multiple of 32
        # so varying budgets reuse a handful of compiled loops (same idea
        # as batch_buckets); padded steps run and write KV past the real
        # budget, so pages are allocated through the BUCKETED length and
        # the output is trimmed back to n_rest
        n_loop = 0
        if device_loop and n_rest > 0:
            n_loop = min(-(-n_rest // 32) * 32, self.max_len - t0 - 1)
        need = -(-max(t_pad, t0 + 1 + max(n_rest, n_loop))
                 // self.page_size)
        if need * b > self.allocator.available:
            # idle cache-held pages (continuous-batching engines) are
            # reclaimable — try before declaring the pool full
            self._reclaim_pages(need * b - self.allocator.available)
        if need * b > self.allocator.available:
            # checked UP FRONT so a too-large request fails whole — not
            # halfway through the per-sequence alloc loop with pages
            # already claimed and an opaque pool error mid-flight
            raise EngineFullError(
                f"engine full: this call needs {need * b} KV pages "
                f"({b} sequences x {need} pages) but only "
                f"{self.allocator.available} of {self.allocator.n_pages} "
                "are free; finish or retire in-flight sequences first")
        tables_np = np.zeros((b, self.max_pages_per_seq), np.int32)
        seq_pages = []
        try:
            for i in range(b):
                pages = []
                seq_pages.append(pages)      # registered BEFORE filling:
                for _ in range(need):        # a failing alloc (injected
                    pages.append(self.allocator.alloc())  # or racing)
                tables_np[i, :need] = pages  # frees the partial claim
        except Exception:
            for pages in seq_pages:
                if pages:
                    self.allocator.free(pages)
            raise
        tables = jnp.asarray(tables_np)

        prefill = self._prefill_fns.get(t_pad)
        if prefill is None:
            prefill = self._build_prefill(t_pad)
            self._prefill_fns[t_pad] = prefill
        if self._step_fn is None:
            self._step_fn = self._build_step()

        ids_pad = np.zeros((b, t_pad), np.int64)
        ids_pad[:, :t0] = ids
        key = jax.random.key(seed)
        ok = False
        try:
            logits, k_pages, v_pages = prefill(
                self.weights, jnp.asarray(ids_pad), self.k_pages,
                self.v_pages, tables, t0)
            key, sub = jax.random.split(key)
            tok = _sample(logits, sub, do_sample, temperature, top_k, top_p)
            lens = jnp.full((b,), t0, jnp.int32)
            out = [np.asarray(tok)[:, None]]
            if device_loop and n_rest > 0:
                lkey = (n_loop, do_sample, float(temperature), int(top_k),
                        float(top_p))
                loop = self._loop_fns.get(lkey)
                if loop is None:
                    loop = self._build_decode_loop(*lkey)
                    self._loop_fns[lkey] = loop
                toks, k_pages, v_pages = loop(
                    self.weights, tok, k_pages, v_pages, tables, lens, key)
                toks = np.asarray(toks)[:, :n_rest]      # drop bucket pad
                # per-row EOS is applied by _finish_eos on the assembled
                # array below — the scan itself always runs every step
                out.extend(toks[:, i:i + 1] for i in range(toks.shape[1]))
            else:
                # per-row done mask: a row that hits ITS OWN EOS is
                # finished even while other rows keep decoding (the old
                # loop only stopped on an all-rows-same-column EOS, so
                # one live row kept every finished row stepping)
                done = np.zeros(b_real, bool)
                if eos_token_id is not None:
                    done |= np.asarray(tok)[:b_real] == eos_token_id
                for _ in range(n_rest):
                    if eos_token_id is not None and done.all():
                        break
                    logits, k_pages, v_pages = self._step_fn(
                        self.weights, tok, k_pages, v_pages, tables, lens)
                    key, sub = jax.random.split(key)
                    tok = _sample(logits, sub, do_sample, temperature,
                                  top_k, top_p)
                    lens = lens + 1
                    out.append(np.asarray(tok)[:, None])
                    if eos_token_id is not None:
                        done |= out[-1][:b_real, 0] == eos_token_id
            ok = True
        finally:
            if ok:
                self.k_pages, self.v_pages = k_pages, v_pages
                for pages in seq_pages:
                    self.allocator.free(pages)
            else:
                # donated buffers may be gone mid-flight: rebuild the pool
                self._reset_kv()
        full = np.concatenate([ids] + out, axis=1)[:b_real]
        # trim each row at its own EOS (post-EOS columns -> eos token)
        return self._finish_eos(full, t0, eos_token_id)
