"""Tensor-parallel serving support: one engine spanning a device mesh.

A single-chip `LLMEngine` caps the servable model at one HBM and the
per-replica throughput at one chip's FLOPs (ROADMAP item 1; the
Gemma-on-TPU serving comparison in PAPERS.md makes sharded decode over
the ICI mesh the perf/$ case for TPU serving). This module holds the
mesh/sharding plumbing that lets every compiled serving dispatch —
prefill, chunked CB prefill, the per-step decode, the fused multi-step
block, the speculative verify pass — run unchanged under `shard_map`
on a 1-D "mp" (model-parallel) mesh:

  - ATTENTION HEADS and the paged-KV pools shard over heads: shard s
    holds q heads [s*nh/tp, (s+1)*nh/tp) and the matching kv heads, and
    ITS OWN slice of every KV page. Page tables, lens, and the page
    allocator stay replicated host state — paging decisions are
    head-independent. The paged-attention / ragged kernels run
    PER-SHARD on their local heads with no cross-shard traffic (head
    independence is what makes KV the perfectly shardable half of
    serving memory).
  - MATMULS follow the reference's ColumnParallelLinear /
    RowParallelLinear split (fleet/meta_parallel mp_layers + mp_ops):
    wq/wk/wv and gate/up are column-parallel (output channels sharded,
    int8 per-channel scales riding along), wo and down are the
    row-parallel pair.

Two tail modes, because exactness and wire-optimality pull apart:

  tp_mode="exact" (default): the row-parallel pair is REASSEMBLED
    instead of reduced — attention outputs all_gather over heads before
    a replicated o_proj, MLP activations all_gather over columns before
    a replicated down_proj. Every matmul then runs at exactly the
    unsharded shapes on exactly the unsharded values, so greedy outputs
    are byte-identical to the tp=1 engine (the repo's exactness bar,
    pinned in tests/test_tp_decode.py). The cost: wo/wd compute and
    residency are replicated (the gather moves the same bytes the psum
    would).
  tp_mode="psum": true Megatron row-parallel — wo/wd shard rows, each
    shard computes a partial output, one per-token all-reduce per pair
    (the fwd side of mp_ops._mp_allreduce; the bwd-identity half is
    irrelevant at inference). tp_compress="int8" rides PR 4's
    comm_compress.quantized_psum so the per-token reduce moves int8 +
    per-chunk scales (~4x fewer wire bytes); the EF residual is dropped
    (inference is stateless — there is no next step to carry it into).
    f32 association differs from the single-chip dot, so outputs are
    CLOSE (rtol-pinned), not byte-identical — the TPU perf mode.

On the CPU/interpret mesh the collectives run over XLA host devices —
the same programs, the same specs, byte-for-byte the math the TPU mesh
runs — which is what lets the tier-1 suite pin tp=2/4 behavior without
a pod. See docs/serving.md "Sharded decode & disaggregated prefill".
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

AXIS = "mp"                    # the serving model-parallel mesh axis
REPL = P()                     # replicated spec (tables, lens, tokens…)
POOL = P(None, None, AXIS, None)   # [n_pages, page, heads, hd] pools
# natively stacked pools (megakernel="multi"): [L, n_pages, page, heads,
# hd] — heads still the sharded axis
STACKED_POOL = P(None, None, None, AXIS, None)


class TPContext:
    """Mesh + spec + collective bundle for one tensor-parallel engine.

    tp: shard count (must divide both nh and nh_kv — heads shard
      evenly; GQA groups never split across shards because nh/nh_kv is
      preserved per shard).
    mode: "exact" | "psum" (module docstring).
    compress: None | "int8" — quantize the psum-mode all-reduce
      (rejected under "exact": there is no reduce to compress).
    """

    def __init__(self, tp, mode="exact", compress=None, devices=None):
        tp = int(tp)
        if tp < 2:
            raise ValueError(f"TPContext needs tp >= 2, got {tp}")
        if mode not in ("exact", "psum"):
            raise ValueError(
                f"tp_mode must be 'exact' or 'psum', got {mode!r}")
        if compress not in (None, "int8"):
            raise ValueError(
                f"tp_compress must be None or 'int8', got {compress!r}")
        if compress is not None and mode != "psum":
            raise ValueError(
                "tp_compress rides the per-token all-reduce, which only "
                "exists under tp_mode='psum' (the 'exact' mode gathers "
                "instead of reducing)")
        devs = list(devices if devices is not None else jax.devices())
        if len(devs) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices but only {len(devs)} are "
                f"visible (backend {jax.default_backend()!r}); on CPU "
                "set jax_num_cpu_devices before the backend starts")
        self.tp = tp
        self.mode = mode
        self.compress = compress
        self.mesh = Mesh(np.array(devs[:tp]), (AXIS,))
        # vocab-parallel lm_head: set by weight_specs when the vocab
        # divides evenly — the head columns shard over "mp" and logits
        # reassemble (exact) or reduce to an argmax gather-free
        self.head_sharded = False

    # -- spec construction --------------------------------------------------
    def _col(self, w):
        """Column-parallel weight spec: [in, out] sharded on out; int8
        (w, scales) pairs shard the per-output-channel scales along."""
        return (P(None, AXIS), P(AXIS)) if isinstance(w, tuple) \
            else P(None, AXIS)

    def _tail(self, w):
        """The row-parallel pair's spec: sharded rows under "psum"
        (scales are per-OUTPUT-channel — replicated when rows shard),
        fully replicated under "exact"."""
        if self.mode == "psum":
            return (P(AXIS, None), P()) if isinstance(w, tuple) \
                else P(AXIS, None)
        return (P(), P()) if isinstance(w, tuple) else P()

    def weight_specs(self, weights):
        """PartitionSpec pytree mirroring an LLMEngine weight snapshot
        (_snapshot_llama shape + the rope tables)."""
        layers = [dict(ln1=P(), ln2=P(),
                       wq=self._col(ws["wq"]), wk=self._col(ws["wk"]),
                       wv=self._col(ws["wv"]), wo=self._tail(ws["wo"]),
                       wg=self._col(ws["wg"]), wu=self._col(ws["wu"]),
                       wd=self._tail(ws["wd"]))
                  for ws in weights["layers"]]
        spec = {k: P() for k in weights if k not in ("layers", "head")}
        spec["layers"] = layers
        # VOCAB-PARALLEL lm_head (both modes): the head is column-
        # parallel over the vocab whenever tp divides it — each shard
        # streams 1/tp of the largest single weight on the decode path.
        # Greedy select runs argmax-of-local-max (an all_gather of two
        # [b] rows, psum-free); full logits, where a caller needs them,
        # reassemble by an exact tiled gather — pure data movement, so
        # byte-identity with the replicated head survives. An awkward
        # vocab keeps the replicated fallback.
        head = weights["head"]
        vocab = (head[0] if isinstance(head, tuple) else head).shape[1]
        self.head_sharded = vocab % self.tp == 0
        if self.head_sharded:
            spec["head"] = (P(None, AXIS), P(AXIS)) \
                if isinstance(head, tuple) else P(None, AXIS)
        else:
            spec["head"] = (P(), P()) if isinstance(head, tuple) else P()
        return spec

    # -- placement ----------------------------------------------------------
    def place(self, tree, specs):
        """device_put every ARRAY leaf onto the mesh per its spec
        (python scalars — eps — pass through untouched so they stay
        weak-typed inside the traced math)."""
        def put(x, s):
            if not hasattr(x, "ndim"):
                return x
            return jax.device_put(x, NamedSharding(self.mesh, s))
        return jax.tree_util.tree_map(put, tree, specs)

    def place_pools(self, pools):
        """Per-layer pool list, or the natively stacked [L, ...] array
        of megakernel="multi" — heads are the sharded axis either way."""
        if not isinstance(pools, (list, tuple)):
            return jax.device_put(pools,
                                  NamedSharding(self.mesh, STACKED_POOL))
        return [jax.device_put(p, NamedSharding(self.mesh, POOL))
                for p in pools]

    # -- the shard_map wrapper ----------------------------------------------
    def wrap(self, fn, in_specs, out_specs):
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    # -- megakernel pack specs -----------------------------------------------
    _MK_COL = frozenset(("wq", "sq", "wk", "sk", "wv", "sv",
                         "wg", "sg", "wu", "su", "wh", "sh"))

    def mk_spec_tree(self, packed):
        """PartitionSpec tree mirroring a pack_decode_layer(tp=...) /
        pack_lm_head(tp=...) dict (per-layer list or stacked): column-
        parallel values + their per-channel scales shard their LAST
        axis (the per-shard-concatenated pack hands each shard its own
        padded tile grid); the replicated row pair (o/down), norms and
        the final-norm row stay P()."""
        def spec(key, arr):
            if key in self._MK_COL:
                return P(*([None] * (arr.ndim - 1) + [AXIS]))
            return P()

        if isinstance(packed, list):
            return [{k: spec(k, v) for k, v in lay.items()}
                    for lay in packed]
        return {k: spec(k, v) for k, v in packed.items()}

    # -- in-trace collectives (called from the engine's layer math) ---------
    def argmax_of_local_max(self, maxv, arg, v_local):
        """Global greedy token from per-shard (max logit, local argmax)
        pairs — the vocab-parallel head's PSUM-FREE select: all_gather
        two small rows, pick the FIRST shard holding the global max
        (exactly jnp.argmax's first-max-wins tie rule over the shard-
        concatenated logits), offset its local index by the shard's
        vocab base. Bitwise equal to argmax over the full logits."""
        ms = lax.all_gather(maxv, AXIS)                  # [tp, ...]
        ags = lax.all_gather(arg, AXIS)
        s = jnp.argmax(ms, axis=0)
        loc = jnp.take_along_axis(ags, s[None].astype(ags.dtype),
                                  axis=0)[0]
        return loc.astype(jnp.int32) \
            + s.astype(jnp.int32) * jnp.int32(v_local)

    def topk_of_local_topk(self, topv, topi, v_local, k):
        """Global top-k (value desc, vocab-id-asc ties) from per-shard
        top-k pairs — the vocab-parallel head's sampling-fold combine
        (ISSUE 18), gather-free over the [w, V] logits: all_gather the
        [*, k] local pairs (tiny), offset local ids by each shard's
        vocab base, and lax.top_k the shard-ordered [*, tp*k] concat.
        Ties resolve to the lower position = the lower GLOBAL vocab id,
        because shard blocks concatenate in vocab order and each block
        is already (value desc, id asc) — so the result is bitwise what
        lax.top_k over the full logits row would produce. Requires each
        shard to contribute its full local top-k (the engine's
        sample_k), which the megakernel head fold does."""
        vs = lax.all_gather(topv, AXIS)                 # [tp, ..., k]
        is_ = lax.all_gather(topi, AXIS)
        tp = vs.shape[0]
        base = (jnp.arange(tp, dtype=jnp.int32)
                * jnp.int32(v_local)).reshape(
            (tp,) + (1,) * (is_.ndim - 1))
        gids = is_.astype(jnp.int32) + base
        # [tp, ..., k] -> [..., tp*k] with shard-major column order
        vs = jnp.moveaxis(vs, 0, -2).reshape(
            topv.shape[:-1] + (tp * topv.shape[-1],))
        gids = jnp.moveaxis(gids, 0, -2).reshape(
            topi.shape[:-1] + (tp * topi.shape[-1],))
        gv, gpos = lax.top_k(vs, k)
        gi = jnp.take_along_axis(gids, gpos, axis=-1)
        return gv, gi.astype(jnp.int32)

    def gather_heads(self, x):
        """[..., nh_local, hd] -> [..., nh, hd]: reassemble the exact
        per-head attention outputs in shard (= original head) order —
        pure data movement, no arithmetic, so byte-identity survives."""
        return lax.all_gather(x, AXIS, axis=x.ndim - 2, tiled=True)

    def gather_cols(self, x):
        """[..., cols_local] -> [..., cols] (exact-mode MLP activation
        reassembly before the replicated down_proj)."""
        return lax.all_gather(x, AXIS, axis=x.ndim - 1, tiled=True)

    def reduce(self, x):
        """psum-mode row-parallel output reduce: the fwd-allreduce of
        mp_ops._mp_allreduce, optionally int8-quantized through PR 4's
        two-stage quantized_psum (EF residual dropped — inference)."""
        if self.compress == "int8":
            from ..distributed.comm_compress import quantized_psum
            y, _err = quantized_psum(x, AXIS, axis_size=self.tp)
            return y.astype(x.dtype)
        # the cached custom-vjp allreduce the training MP layers use —
        # at inference only its forward (lax.psum) ever runs
        from ..distributed.fleet.meta_parallel.parallel_layers.mp_ops \
            import _allreduce_fn
        return _allreduce_fn(AXIS)(x)
