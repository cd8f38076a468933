"""Compiled hybrid-parallel train step.

This is the TPU-native replacement for the reference's hot path (SURVEY §3.4:
1F1B steady state + per-op dispatch): ONE jitted SPMD program per step,
covering

  - TP   : mp_layers' explicit collectives over the 'model' axis
  - PP   : GPipe microbatch pipeline via lax.ppermute over the 'pipe' axis
           (single-program pipelining — the second option in SURVEY §7 "hard
           parts"; the host-driven 1F1B scheduler in meta_parallel covers the
           schedule-faithful path)
  - DP   : gradient psum over 'data' (+ 'sharding') axes
  - ZeRO : (ref: sharding/group_sharded_optimizer_stage2.py:53,
           group_sharded_stage3.py:59) three stages, all inside the one
           compiled program:
             stage 1/2 — params replicated; grads reduce-SCATTERED to the
               owning 'sharding' rank (lax.psum_scatter — true
               reduce-to-owner, not allreduce+slice); adam moments sharded;
               updated param slices exchanged back into the (donated)
               block in place. All of it in the local block's OWN shape,
               the owned slice along one axis of it (moment_axis): no
               flattened float32 copy, which on a TPU is a whole-tensor
               relayout.
             stage 3 — params STORED as flat per-rank chunks over
               'sharding'; all-gathered on use per pipeline stage (inside
               the layer scan, so with recompute only one stage's full
               params are ever live); AD through the gather yields the
               grad reduce-scatter automatically; the update runs on the
               local chunk and nothing is re-gathered after it.
  - recompute : jax.checkpoint around each pipeline stage

Decoder layers are stacked [L, ...] and sharded P('pipe') so every stage
holds L/S layers; XLA overlaps the ppermute ring with stage compute.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P, NamedSharding

from ..autograd import tape
from ..framework import random as frnd
from ..profiler import phase, record_counters
from ..tensor.tensor import Tensor
from ..distributed.mesh import spmd_axes
from ..distributed.comm_compress import resolve_chunk as _resolve_chunk
from ..distributed.fleet.meta_parallel.spmd import _Swap, param_spec
# fwd psum / bwd identity — the Megatron "allreduce pair" (mp_ops:40);
# used to share values across ranks without inflating the grad convention
from ..distributed.fleet.meta_parallel.parallel_layers.mp_ops import (
    _allreduce_fn as _untied_psum)


def _model_parts(model):
    """Adapters for supported CausalLM families."""
    from .llama import LlamaForCausalLM
    from .gpt import GPTForCausalLM
    if isinstance(model, LlamaForCausalLM):
        return (model.llama.embed_tokens, list(model.llama.layers),
                [model.llama.norm, model.lm_head], model.criterion.ce)
    if isinstance(model, GPTForCausalLM):
        return (model.gpt.embeddings, list(model.gpt.h),
                [model.gpt.ln_f, model.lm_head], model.ce)
    raise TypeError(f"unsupported flagship model {type(model)}")


def _named_params(layer):
    return list(layer.named_parameters())


def _local_shape(gshape, spec, mesh):
    """Per-device block shape of a global array under a PartitionSpec."""
    loc = list(gshape)
    for d, ax in enumerate(tuple(spec)):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        for a in axes:
            loc[d] //= mesh.shape[a]
    return tuple(loc)


FLAT = -1     # moment_axis(): no axis divides, padded flat chunks


def moment_axis(block_shape, n_shard):
    """THE rule for where the stage-1/2 AdamW moments of a local parameter
    block live, read off the block's shape alone. `None`: the whole block
    (nothing shards it). `k >= 0`: each 'sharding' rank owns the slice
    `[r * d/S, (r + 1) * d/S)` along axis k, the FIRST axis whose extent
    S divides (axis 0 wherever it does: a decoder stack's layers of this
    pipe stage, vocabulary rows); the reduce-to-owner, the owned slice of
    the parameter and the re-gather all run along k, in the block's own
    shape. `FLAT`: no axis divides, so the block is flattened, zero-padded
    to a multiple of S and owned in rank-1 chunks (a relayout on a TPU:
    update_layout() counts how often)."""
    if n_shard <= 1:
        return None
    for k, d in enumerate(block_shape):
        if d and d % n_shard == 0:
            return k
    return FLAT


def _padded_flat(x, n_shard):
    """x as rank 1, zero-padded to a multiple of n_shard (FLAT only)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n_shard
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    return flat


class SpmdTrainer:
    """Builds and runs the one-program hybrid step for a CausalLM model.

    Memory/speed knobs (defaults tuned for the flagship bench):
    - recompute_policy="save_attn" pins the flash-attention o/lse residuals
      (~(2d+4)·tokens bytes per layer) so backward never re-runs the
      attention forward kernel. On memory-edge configs that only just fit
      with full rematerialization, pass recompute_policy="full".
    - fuse_head_ce=True computes lm_head+CE chunk-wise (never materializes
      [N, vocab] logits); ce_chunk sets the row-chunk size.
    - matmul_precision defaults by param dtype (bf16 -> "default" native
      MXU passes, f32 -> "highest"); it does not affect the flash kernel,
      whose precision follows its operand dtype (see ops/pallas/_prec).
    """

    def __init__(self, model, mesh, lr=1e-3, betas=(0.9, 0.95), eps=1e-8,
                 weight_decay=0.01, micro_batch_size=None, recompute=False,
                 param_dtype=None, sharding_stage=2, pp_schedule="gpipe",
                 virtual_pp_degree=1, fuse_head_ce=True, ce_chunk=4096,
                 matmul_precision=None, recompute_policy="save_attn",
                 moment_dtype="float32", grad_compress=None,
                 compress_chunk=None, grad_accum=1, plan=None):
        # --- declarative plan (cost_model.Plan) -------------------------
        # The planner's output is the single source of truth for the
        # knobs it carries: when plan= is given (a Plan or its JSON
        # dict), its fields REPLACE the corresponding constructor
        # arguments, so a trainer built from a searched plan and one
        # built by hand with the same fields are identical by
        # construction.  The mesh must agree with plan.mesh_axes().
        self.plan = None
        if plan is not None:
            from ..cost_model import Plan
            if isinstance(plan, dict):
                plan = Plan.from_json(plan)
            mesh_shape = dict(mesh.shape)
            for axis, want in plan.mesh_axes().items():
                have = mesh_shape.get(axis, 1)
                if have != want:
                    raise ValueError(
                        f"mesh axis {axis!r} is {have} but the plan "
                        f"needs {want} (plan.mesh_axes()="
                        f"{plan.mesh_axes()}) — build the mesh with "
                        f"plan.build_mesh()")
            self.plan = plan
            sharding_stage = plan.sharding_stage
            grad_compress = plan.grad_compress
            grad_accum = plan.grad_accum
            micro_batch_size = plan.micro_batch_size
            pp_schedule = plan.pp_schedule
            virtual_pp_degree = plan.virtual_pp_degree
            recompute = plan.recompute
        if sharding_stage not in (1, 2, 3):
            raise ValueError(f"sharding_stage must be 1/2/3, got "
                             f"{sharding_stage}")
        if grad_compress not in (None, "int8"):
            raise ValueError(f"grad_compress must be None or 'int8', got "
                             f"{grad_compress!r}")
        if int(grad_accum) < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        if pp_schedule not in ("gpipe", "1f1b", "interleave"):
            raise ValueError(f"pp_schedule must be gpipe/1f1b/interleave, "
                             f"got {pp_schedule}")
        if pp_schedule == "interleave" and virtual_pp_degree < 2:
            raise ValueError("interleave needs virtual_pp_degree >= 2")
        if pp_schedule in ("gpipe", "1f1b") and virtual_pp_degree != 1:
            raise ValueError(f"{pp_schedule} uses virtual_pp_degree=1")
        self.model = model
        self.mesh = mesh
        self.lr = lr
        self.b1, self.b2 = betas
        self.eps = eps
        self.wd = weight_decay
        self.recompute = recompute
        self.micro_batch_size = micro_batch_size
        self.sharding_stage = sharding_stage
        # --- comm compression + deferred sync (docs/distributed_perf.md) ---
        # grad_compress="int8": gradient collectives over the batch-like
        # axes (data/sep psum, stage-1/2 sharding psum_scatter, stage-3
        # gather-on-use grad scatter) ride chunked int8 with per-chunk
        # scales; compression error is carried in state["ef"] and fed
        # back into the next step's gradients (EF-SGD), so the quality
        # cost is transient rounding, not accumulated drift. None (the
        # default) keeps every collective exact f32 — byte-identical to
        # prior behavior.
        self.grad_compress = grad_compress
        self.compress_chunk = _resolve_chunk(compress_chunk)
        # grad_accum=K: split the local batch into K microbatches, scan a
        # LOCAL value_and_grad over them (no collectives inside), and
        # sync gradients ONCE after the scan — the deferred-sync pattern
        # that hands XLA's latency-hiding scheduler one batch of
        # collectives to overlap with the tail of backward compute.
        self.grad_accum = int(grad_accum)
        self.pp_schedule = pp_schedule
        self.v_pp = virtual_pp_degree
        self.fuse_head_ce = fuse_head_ce
        self.ce_chunk = ce_chunk
        self.matmul_precision = matmul_precision
        if recompute_policy not in ("full", "save_attn"):
            raise ValueError(f"recompute_policy must be full/save_attn, got "
                             f"{recompute_policy}")
        self.recompute_policy = recompute_policy
        # adam moment storage dtype: bf16 halves optimizer-state HBM (the
        # update math stays f32 — read-upcast / write-downcast), the knob
        # that fits a ~1.3B model on one 16G chip (ref analog: the
        # multi_precision=False master-weightless mode of
        # python/paddle/optimizer/adamw.py)
        self._mdt = jnp.dtype(moment_dtype)

        self.S_pipe = mesh.shape.get("pipe", 1)
        if self.grad_accum > 1 and self.S_pipe > 1:
            raise ValueError(
                "grad_accum>1 is the non-pipeline deferred-sync path; "
                "with pipe>1 the microbatch loop (micro_batch_size=) "
                "already accumulates locally and syncs once per step")
        self.S_shard = mesh.shape.get("sharding", 1)
        self.S_sep = mesh.shape.get("sep", 1)
        self.batch_axes = tuple(a for a in ("data", "sharding")
                                if a in mesh.axis_names)
        self.data_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
        # context parallelism: 'sep' shards the SEQUENCE dim of activations
        # and labels; for parameters it behaves like a data axis (replicated
        # params, partial grads -> psum)
        self.sep_axes = tuple(a for a in ("sep",) if a in mesh.axis_names)
        # mesh axes a stage-3 chunk varies over (model-sharded params differ
        # per model rank; every sharding rank owns a distinct chunk)
        self._chunk_axes = tuple(a for a in ("model", "sharding")
                                 if a in mesh.axis_names)

        embed, decoders, tail, ce = _model_parts(model)
        assert len(decoders) % (self.S_pipe * self.v_pp) == 0, \
            "num layers must divide pp degree x virtual_pp_degree"
        self.embed = embed
        self.decoders = decoders
        self.tail = tail
        self.template = decoders[0]
        self.n_layers = len(decoders)
        self.per = self.n_layers // self.S_pipe       # layers per rank
        self.per_v = self.per // self.v_pp            # layers per chunk
        # Physical stacking order: P('pipe') splits dim0 contiguously, so
        # rank r's block must hold ITS chunks back-to-back. phys position
        # p = r*(v*per_v) + c*per_v + i  <->  logical layer
        # (c*S + r)*per_v + i  (interleave assignment; identity when v=1).
        self.phys_order = []
        for rr in range(self.S_pipe):
            for c in range(self.v_pp):
                for i in range(self.per_v):
                    self.phys_order.append((c * self.S_pipe + rr)
                                           * self.per_v + i)

        # ---- parameter bookkeeping ----------------------------------------
        # "outer" params: embed + tail (replicated over pipe)
        self.outer_layers = [embed] + tail
        self.outer_names = []
        self.outer_tensors = []
        self.outer_specs = []
        for li, l in enumerate(self.outer_layers):
            for n, p in _named_params(l):
                self.outer_names.append(f"outer{li}.{n}")
                self.outer_tensors.append(p)
                self.outer_specs.append(param_spec(p))
        # stacked decoder params
        self.layer_param_names = [n for n, _ in _named_params(self.template)]
        self.layer_param_tensors = [p for _, p in _named_params(self.template)]
        # Megatron-SP (SURVEY §5.7): model built with the sequence-parallel
        # linear pair tags its norm weights; their grads are PARTIAL over
        # 'model' (each rank saw only its sequence shard) and get psum'd
        self._sp_partial = [bool(getattr(p, "sequence_parallel", False))
                            for p in self.layer_param_tensors]
        self.sequence_parallel = any(self._sp_partial)
        if self.sequence_parallel:
            if self.sharding_stage == 3:
                raise NotImplementedError(
                    "sequence_parallel with sharding_stage=3 is not "
                    "supported: stage-3 chunk transposes do not complete "
                    "the 'model'-partial norm grads. Use stage 1/2.")
            if self.S_pipe > 1:
                raise NotImplementedError(
                    "sequence_parallel with pipeline parallelism is not "
                    "supported yet; use mp/dp/sharding/sep meshes.")
        self.stacked_specs = []
        for _, p in _named_params(self.template):
            base = param_spec(p)
            self.stacked_specs.append(P("pipe", *base))

        # stage-3 chunk geometry: per-device local block -> flat [chunk]
        S = max(self.S_shard, 1)
        self.outer_loc_shapes = [
            _local_shape(tuple(p.shape), s, mesh)
            for p, s in zip(self.outer_tensors, self.outer_specs)]
        self.outer_loc_n = [int(np.prod(s)) for s in self.outer_loc_shapes]
        self.outer_chunk = [(n + (-n) % S) // S for n in self.outer_loc_n]
        self.layer_loc_shapes = [
            _local_shape(tuple(p.shape), param_spec(p), mesh)
            for p in self.layer_param_tensors]
        self.layer_loc_n = [int(np.prod(s)) for s in self.layer_loc_shapes]
        self.layer_chunk = [(n + (-n) % S) // S for n in self.layer_loc_n]

        if param_dtype is not None:
            self._pdt = jnp.dtype(param_dtype)
        else:
            self._pdt = None
        if self.matmul_precision is None:
            # bf16/f16 params: native low-precision MXU passes. f32 params
            # keep the package's f32-parity "highest" — "default" would
            # silently run single-pass-bf16 matmuls on TPU.
            low = self._pdt is not None and self._pdt in (
                jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))
            self.matmul_precision = "default" if low else "highest"
        self._jitted = None

    # ---- specs -------------------------------------------------------------
    def _param_specs12(self):
        return {"outer": list(self.outer_specs),
                "stacked": list(self.stacked_specs)}

    def _chunk_spec_outer(self):
        return P(self._chunk_axes) if self._chunk_axes else P()

    def _chunk_spec_stacked(self):
        return (P("pipe", self._chunk_axes) if self._chunk_axes
                else P("pipe"))

    def _param_specs(self):
        if self.sharding_stage == 3:
            return {"outer": [self._chunk_spec_outer()
                              for _ in self.outer_tensors],
                    "stacked": [self._chunk_spec_stacked()
                                for _ in self.layer_param_tensors]}
        return self._param_specs12()

    def _opt_specs(self):
        if self.sharding_stage == 3:
            return jax.tree_util.tree_map(
                lambda s: {"m": s, "v": s},
                self._param_specs(), is_leaf=lambda x: isinstance(x, P))
        return {kind: [{"m": lay["spec"], "v": lay["spec"]}
                       for lay in lays]
                for kind, lays in self._moment_layouts().items()}

    def _state_specs(self):
        specs = {"params": self._param_specs(), "opt": self._opt_specs(),
                 "step": P()}
        if self.grad_compress is not None:
            # error-feedback residuals mirror the params tree exactly
            # (stage 1/2: local-block shaped; stage 3: chunk shaped), f32
            specs["ef"] = self._param_specs()
        return specs

    # ---- stage-1/2 moments: where they live (moment_axis) -----------------
    def _moment_layouts(self):
        """What follows from moment_axis() for every stage-1/2 parameter:
        its axis, its local block, the moments' per-device and global
        shapes and their PartitionSpec. Along an axis the moments are
        GLOBALLY param-shaped, the parameter's spec with 'sharding' joined
        to that axis; a FLAT chunk varies over the parameter's mesh axes
        and 'sharding'."""
        mesh, S = self.mesh, self.S_shard

        def layout(gshape, spec):
            block = _local_shape(gshape, spec, mesh)
            k = moment_axis(block, S)
            if k is None:
                return {"axis": k, "block": block, "local": block,
                        "shape": tuple(gshape), "spec": spec}
            named = [() if e is None else e if isinstance(e, tuple) else (e,)
                     for e in tuple(spec) + (None,) * (len(block) - len(spec))]
            if k == FLAT:
                chunk = -(-int(np.prod(block)) // S)
                axes = tuple(a for e in named for a in e) + ("sharding",)
                n = int(np.prod([mesh.shape[a] for a in axes]))
                return {"axis": k, "block": block, "local": (chunk,),
                        "shape": (chunk * n,), "spec": P(axes)}
            named[k] += ("sharding",)
            return {"axis": k, "block": block,
                    "local": block[:k] + (block[k] // S,) + block[k + 1:],
                    "shape": tuple(gshape),
                    "spec": P(*[e or None for e in named])}

        return {"outer": [layout(tuple(p.shape), s) for p, s in
                          zip(self.outer_tensors, self.outer_specs)],
                "stacked": [layout((self.n_layers,) + tuple(p.shape), s)
                            for p, s in zip(self.layer_param_tensors,
                                            self.stacked_specs)]}

    def _owned(self, x, k):
        """This 'sharding' rank's slice of a local block whose
        moment_axis() is k (inside shard_map)."""
        if k is None:
            return x
        if k == FLAT:
            x, k = _padded_flat(x, self.S_shard), 0
        chunk = x.shape[k] // self.S_shard
        return lax.dynamic_slice_in_dim(
            x, lax.axis_index("sharding") * chunk, chunk, axis=k)

    def _regathered(self, x, k, block, into=None):
        """Inverse of _owned: every rank's slice, back as the block.
        `into`, a block the caller gives up (the step's donated
        parameter), takes the slices IN PLACE: this rank's by
        dynamic_update_slice, each other rank's as it arrives by
        collective-permute. On the chip the result of a whole-block
        all_gather is copied once more on its way out of the program and
        the donated block once on its way in (PERF.md 6, PR 35: 15 ms of
        a 679 ms four-chip step); written slice by slice, neither."""
        if k is None:
            return x
        if k == FLAT:
            flat = lax.all_gather(x, "sharding", axis=0, tiled=True)
            return flat[:int(np.prod(block))].reshape(block)
        if into is None:
            return lax.all_gather(x, "sharding", axis=k, tiled=True)
        S, r, chunk = self.S_shard, lax.axis_index("sharding"), x.shape[k]
        into = lax.dynamic_update_slice_in_dim(into, x, r * chunk, axis=k)
        for j in range(1, S):
            got = lax.ppermute(x, "sharding",
                               [(i, (i + j) % S) for i in range(S)])
            into = lax.dynamic_update_slice_in_dim(
                into, got, ((r - j) % S) * chunk, axis=k)
        return into

    def update_layout(self):
        """How often the in-shape update engages: the parameter tensors
        and the share of (per-device) ELEMENTS whose AdamW update runs in
        the block's own shape (`in_shape_share`; of it along axis 0 and
        along another axis where ZeRO shards the moments) and through the
        flat fallback (`flat_share`; stage 3 stores flat chunks, all of
        it). _build() records it: profiler.counter_history("trainer")."""
        if self.sharding_stage == 3:
            lays = [{"axis": FLAT, "block": s} for s in
                    self.outer_loc_shapes + self.layer_loc_shapes]
        else:
            lays = sum(self._moment_layouts().values(), [])
        elems = {"whole": 0, "axis0": 0, "other_axis": 0, "flat": 0}
        tensors = dict(elems)
        for lay in lays:
            k = lay["axis"]
            how = ("whole" if k is None else "flat" if k == FLAT
                   else "axis0" if k == 0 else "other_axis")
            elems[how] += int(np.prod(lay["block"]))
            tensors[how] += 1
        total = max(sum(elems.values()), 1)
        return {"tensors": len(lays), "tensors_flat": tensors["flat"],
                "tensors_axis0": tensors["axis0"],
                "tensors_other_axis": tensors["other_axis"],
                "in_shape_share": 1.0 - elems["flat"] / total,
                "axis0_share": elems["axis0"] / total,
                "other_axis_share": elems["other_axis"] / total,
                "flat_share": elems["flat"] / total}

    # ---- stage-3 chunk <-> block conversion (runs inside shard_map) --------
    def _chunkify_outer(self, p_loc, i):
        S = self.S_shard
        n = self.outer_loc_n[i]
        chunk = self.outer_chunk[i]
        flat = p_loc.reshape(-1)
        pad = S * chunk - n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
        if S > 1:
            r = lax.axis_index("sharding")
            return lax.dynamic_slice_in_dim(flat, r * chunk, chunk)
        return flat

    def _chunkify_stacked(self, p_loc, i):
        S = self.S_shard
        n = self.layer_loc_n[i]
        chunk = self.layer_chunk[i]
        per = p_loc.shape[0]
        flat = p_loc.reshape(per, -1)
        pad = S * chunk - n
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((per, pad), flat.dtype)], axis=1)
        if S > 1:
            r = lax.axis_index("sharding")
            return lax.dynamic_slice_in_dim(flat, r * chunk, chunk, axis=1)
        return flat

    def _gather_chunks(self, chunk):
        """Stage-3 gather-on-use. With grad_compress the gather's AD
        TRANSPOSE — the ZeRO-3 grad reduce-scatter — moves int8 instead
        of f32 (comm_compress.all_gather_with_qscatter_grad); the forward
        param gather itself stays exact, so non-grad users
        (init/canonical/gather_params) are byte-identical either way."""
        if self.grad_compress == "int8":
            from ..distributed.comm_compress import (
                all_gather_with_qscatter_grad)
            return all_gather_with_qscatter_grad(
                chunk, "sharding", axis_size=self.S_shard,
                chunk=self.compress_chunk)
        return lax.all_gather(chunk, "sharding", axis=0, tiled=True)

    def _ungather_outer(self, chunk, i):
        n = self.outer_loc_n[i]
        if self.S_shard > 1:
            flat = self._gather_chunks(chunk)
        else:
            flat = chunk
        return flat[:n].reshape(self.outer_loc_shapes[i])

    def _ungather_layer(self, chunk, i):
        """chunk: [chunk_i] for ONE layer -> local block."""
        n = self.layer_loc_n[i]
        if self.S_shard > 1:
            flat = self._gather_chunks(chunk)
        else:
            flat = chunk
        return flat[:n].reshape(self.layer_loc_shapes[i])

    # ---- state ------------------------------------------------------------
    def _init_params12(self):
        from ..framework.misc import materialize_lazy
        cast = (lambda a: a.astype(self._pdt)
                if self._pdt is not None and jnp.issubdtype(a.dtype, jnp.floating)
                else a)

        def fetch(p):
            # LazyGuard models materialize HERE, one leaf at a time, cast
            # straight to param_dtype: peak extra HBM = one f32 leaf, not
            # a full second model copy (the 1.3B bench OOM of r5).
            if isinstance(p.data, jax.ShapeDtypeStruct):
                return cast(materialize_lazy(p))
            return cast(p.data)

        outer = [fetch(p) for p in self.outer_tensors]
        stacked = []
        for pi, name in enumerate(self.layer_param_names):
            arrs = []
            for li in self.phys_order:  # physical (chunk-major) order
                arrs.append(fetch(
                    dict(_named_params(self.decoders[li]))[name]))
            stacked.append(jnp.stack(arrs, axis=0))  # [L, ...]
        params = {"outer": outer, "stacked": stacked}
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
            params, self._param_specs12())

    def init_state(self):
        params12 = self._init_params12()

        if self.sharding_stage == 3:
            def to_chunks(p12):
                outer = [self._chunkify_outer(p, i)
                         for i, p in enumerate(p12["outer"])]
                stacked = [self._chunkify_stacked(p, i)
                           for i, p in enumerate(p12["stacked"])]
                opt = jax.tree_util.tree_map(
                    lambda a: {"m": jnp.zeros(a.shape, self._mdt),
                               "v": jnp.zeros(a.shape, self._mdt)},
                    {"outer": outer, "stacked": stacked},
                    is_leaf=lambda x: hasattr(x, "shape"))
                return {"outer": outer, "stacked": stacked}, opt

            smapped = shard_map(to_chunks, mesh=self.mesh,
                                in_specs=(self._param_specs12(),),
                                out_specs=(self._param_specs(),
                                           self._opt_specs()),
                                check_vma=False)
            params, opt = jax.jit(smapped)(params12)
            state = {"params": params, "opt": opt,
                     "step": jax.device_put(
                         jnp.zeros((), jnp.int32),
                         NamedSharding(self.mesh, P()))}
            if self.grad_compress is not None:
                state["ef"] = self._init_ef(params)
            return state

        # stage 1/2: AdamW moments created INSIDE the SPMD region, each
        # device its owned slice of the LOCAL (model/pipe-sharded) block
        # (_moment_layouts)
        layouts = self._moment_layouts()

        def init_fn():
            return {kind: [{k: jnp.zeros(lay["local"], self._mdt)
                            for k in ("m", "v")} for lay in lays]
                    for kind, lays in layouts.items()}

        smapped = shard_map(init_fn, mesh=self.mesh, in_specs=(),
                            out_specs=self._opt_specs(), check_vma=False)
        opt = jax.jit(smapped)()
        state = {"params": params12, "opt": opt,
                 "step": jax.device_put(
                         jnp.zeros((), jnp.int32),
                         NamedSharding(self.mesh, P()))}
        if self.grad_compress is not None:
            state["ef"] = self._init_ef(params12)
        return state

    def _init_ef(self, params):
        """Zero error-feedback residuals: f32, one per param leaf, the
        leaf's (global) shape and sharding spec."""
        specs = self._param_specs()
        return {kind: [jax.device_put(jnp.zeros(a.shape, jnp.float32),
                                      NamedSharding(self.mesh, s))
                       for a, s in zip(params[kind], specs[kind])]
                for kind in ("outer", "stacked")}

    # ---- mesh-independent canonical state (cross-mesh restore) -------------
    def canonical_state(self, state):
        """Convert a live state into its MESH-INDEPENDENT canonical form:
        params and AdamW moments as GLOBAL param-shaped arrays, decoder
        stacks in LOGICAL layer order, plus the step counter. Any
        SpmdTrainer built over any mesh / sharding stage / pipe schedule
        for the same model rebuilds its own state via
        state_from_canonical — the cross-mesh/cross-world checkpoint
        restore contract (VERDICT r4 missing #3; ref:
        python/paddle/distributed/fleet/elastic/manager.py:126,243
        restart-from-checkpoint under a CHANGED world,
        hybrid_parallel_pp_save_load.py)."""
        specs12 = self._param_specs12()
        layouts = self._moment_layouts()
        stage3 = self.sharding_stage == 3

        def unshard(st):
            pr, opt = st["params"], st["opt"]
            if stage3:
                outer = [self._ungather_outer(c, i)
                         for i, c in enumerate(pr["outer"])]
                stacked = []
                for i, c in enumerate(pr["stacked"]):  # [per, chunk_i]
                    if self.S_shard > 1:
                        flat = lax.all_gather(c, "sharding", axis=1,
                                              tiled=True)
                    else:
                        flat = c
                    stacked.append(flat[:, :self.layer_loc_n[i]].reshape(
                        (self.per,) + self.layer_loc_shapes[i]))
                mo = [{k: self._ungather_outer(opt["outer"][i][k], i)
                       for k in ("m", "v")}
                      for i in range(len(pr["outer"]))]
                ms = []
                for i in range(len(pr["stacked"])):
                    ent = {}
                    for k in ("m", "v"):
                        c = opt["stacked"][i][k]
                        if self.S_shard > 1:
                            c = lax.all_gather(c, "sharding", axis=1,
                                               tiled=True)
                        ent[k] = c[:, :self.layer_loc_n[i]].reshape(
                            (self.per,) + self.layer_loc_shapes[i])
                    ms.append(ent)
            else:
                outer, stacked = pr["outer"], pr["stacked"]
                mo, ms = [
                    [{k: self._regathered(ent[k], lay["axis"], lay["block"])
                      for k in ("m", "v")}
                     for ent, lay in zip(opt[kind], layouts[kind])]
                    for kind in ("outer", "stacked")]
            return {"params": {"outer": outer, "stacked": stacked},
                    "opt": {"outer": mo, "stacked": ms}, "step": st["step"]}

        moment_specs12 = {
            "outer": list(specs12["outer"]),
            "stacked": list(specs12["stacked"])}
        out_specs = {"params": specs12,
                     "opt": jax.tree_util.tree_map(
                         lambda s: {"m": s, "v": s}, moment_specs12,
                         is_leaf=lambda x: isinstance(x, P)),
                     "step": P()}
        smapped = shard_map(unshard, mesh=self.mesh,
                            in_specs=(self._state_specs(),),
                            out_specs=out_specs, check_vma=False)
        canon = jax.jit(smapped)(state)
        # physical (pipe-chunk-major) -> logical layer order
        idx = jnp.asarray(np.argsort(np.asarray(self.phys_order)), jnp.int32)
        reorder = lambda a: jnp.take(a, idx, axis=0)
        canon["params"]["stacked"] = [reorder(a)
                                      for a in canon["params"]["stacked"]]
        canon["opt"]["stacked"] = [
            {k: reorder(v) for k, v in ent.items()}
            for ent in canon["opt"]["stacked"]]
        # normalize Adam moments to the GLOBAL-MEAN-gradient convention:
        # the step's grads are per-rank-mean SUMS over every batch-like
        # axis (data/sharding/sep), so raw m scales with the axes' degree
        # product F (and v with F^2) — invisible to scale-invariant AdamW
        # but mesh-DEPENDENT. Canonical form divides it out;
        # state_from_canonical re-applies the target mesh's F.
        f = float(self._batch_rank_factor())
        if f != 1.0:
            for kind in ("outer", "stacked"):
                canon["opt"][kind] = [
                    {"m": (ent["m"].astype(jnp.float32) / f
                           ).astype(ent["m"].dtype),
                     "v": (ent["v"].astype(jnp.float32) / (f * f)
                           ).astype(ent["v"].dtype)}
                    for ent in canon["opt"][kind]]
        return canon

    def _batch_rank_factor(self):
        """Gradient-convention scale vs the global-mean gradient (see
        canonical_state). The jax.grad paths (non-pipe / GPipe) produce
        per-rank-mean SUMS over the batch-like axes — factor = product of
        the data/sharding/sep degrees. The hand-rolled 1F1B/interleave
        backward seeds its cotangent with 1/(M*n_batch_ranks*mp) already
        (see loss_and_grads), so its factor is 1."""
        if self.S_pipe > 1 and self.pp_schedule in ("1f1b", "interleave"):
            return 1
        f = 1
        for a in self.batch_axes + self.sep_axes:
            f *= int(self.mesh.shape[a])
        return f

    def state_from_canonical(self, canon):
        """Inverse of canonical_state on THIS trainer's mesh: re-chunk the
        global param-shaped arrays into this mesh's state (casting to this
        trainer's param/moment dtypes)."""
        specs12 = self._param_specs12()
        layouts = self._moment_layouts()
        stage3 = self.sharding_stage == 3

        cast_p = (lambda a: a.astype(self._pdt)
                  if self._pdt is not None
                  and jnp.issubdtype(a.dtype, jnp.floating) else a)
        # logical -> physical order for this mesh's pipe layout
        perm = jnp.asarray(np.asarray(self.phys_order), jnp.int32)
        put = lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s))
        params12 = {
            "outer": [put(cast_p(jnp.asarray(a)), sp) for a, sp in
                      zip(canon["params"]["outer"], specs12["outer"])],
            "stacked": [put(cast_p(jnp.take(jnp.asarray(a), perm, axis=0)),
                            sp)
                        for a, sp in zip(canon["params"]["stacked"],
                                         specs12["stacked"])]}
        # re-apply THIS mesh's batch-rank factor (see canonical_state)
        f = float(self._batch_rank_factor())
        scale = {"m": f, "v": f * f}
        cast_m = lambda a, k: (jnp.asarray(a).astype(jnp.float32)
                               * scale[k]).astype(self._mdt)
        mom12 = {
            "outer": [{k: put(cast_m(ent[k], k), sp) for k in ("m", "v")}
                      for ent, sp in zip(canon["opt"]["outer"],
                                         specs12["outer"])],
            "stacked": [{k: put(cast_m(jnp.take(jnp.asarray(ent[k]), perm,
                                                axis=0), k), sp)
                         for k in ("m", "v")}
                        for ent, sp in zip(canon["opt"]["stacked"],
                                           specs12["stacked"])]}

        def reshard(p12, m12, step):
            if stage3:
                params = {"outer": [self._chunkify_outer(p, i)
                                    for i, p in enumerate(p12["outer"])],
                          "stacked": [self._chunkify_stacked(p, i)
                                      for i, p in
                                      enumerate(p12["stacked"])]}
                opt = {"outer": [{k: self._chunkify_outer(ent[k], i)
                                  for k in ("m", "v")}
                                 for i, ent in enumerate(m12["outer"])],
                       "stacked": [{k: self._chunkify_stacked(ent[k], i)
                                    for k in ("m", "v")}
                                   for i, ent in
                                   enumerate(m12["stacked"])]}
            else:
                params = p12
                opt = {kind: [{k: self._owned(ent[k], lay["axis"])
                               for k in ("m", "v")}
                              for ent, lay in zip(m12[kind], layouts[kind])]
                       for kind in ("outer", "stacked")}
            out = {"params": params, "opt": opt, "step": step}
            if self.grad_compress is not None:
                # EF residuals are transient device state (sub-one-step
                # rounding error): canonical form drops them, restore
                # re-zeros them
                out["ef"] = {kind: [jnp.zeros(a.shape, jnp.float32)
                                    for a in params[kind]]
                             for kind in ("outer", "stacked")}
            return out

        mspec12 = jax.tree_util.tree_map(
            lambda s: {"m": s, "v": s},
            {"outer": list(specs12["outer"]),
             "stacked": list(specs12["stacked"])},
            is_leaf=lambda x: isinstance(x, P))
        smapped = shard_map(
            reshard, mesh=self.mesh,
            in_specs=(specs12, mspec12, P()),
            out_specs=self._state_specs(), check_vma=False)
        step = jnp.asarray(canon["step"], jnp.int32)
        return jax.jit(smapped)(params12, mom12, step)

    def save_checkpoint(self, state, path, step=None):
        """Sharded save in canonical (mesh-independent) form."""
        from ..distributed import checkpoint as _ckpt
        _ckpt.save_state(self.canonical_state(state), path, step=step)

    def load_checkpoint(self, path):
        """Restore a canonical checkpoint onto THIS trainer's mesh —
        regardless of the mesh/world it was saved from. Returns
        (state, index)."""
        from ..distributed import checkpoint as _ckpt
        template = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype),
            jax.eval_shape(self.canonical_state,
                           jax.eval_shape(self.init_state)),
            is_leaf=lambda x: hasattr(x, "shape"))
        canon, index = _ckpt.load_state(path, like=template)
        return self.state_from_canonical(canon), index

    # ---- the step ---------------------------------------------------------
    def _build(self, ids_shape):
        record_counters("trainer", self.update_layout())
        mesh = self.mesh
        axis_names = tuple(mesh.axis_names)
        S = self.S_pipe
        per = self.per
        outer_tensors = self.outer_tensors
        layer_tensors = self.layer_param_tensors
        embed, tail, template = self.embed, self.tail, self.template
        recompute = self.recompute
        batch_axes = self.batch_axes
        data_axes = self.data_axes
        sep_axes = self.sep_axes
        mb = self.micro_batch_size
        b1, b2, eps, wd = self.b1, self.b2, self.eps, self.wd
        mdt = self._mdt
        S_shard = self.S_shard
        stage3 = self.sharding_stage == 3
        sp_active = self.sequence_parallel and "model" in mesh.axis_names
        sp_flags = list(self._sp_partial)
        if sp_active:
            from ..distributed.fleet.utils.sequence_parallel_utils import (
                _scatter_seq_fn, _allgather_seq_slice_grad_fn)
            sp_scatter_raw = _scatter_seq_fn("model", 1)
            sp_gather_raw = _allgather_seq_slice_grad_fn("model", 1)

        def materialize_outer(outer):
            if not stage3:
                return outer
            return [self._ungather_outer(c, i) for i, c in enumerate(outer)]

        def apply_embed(outer, ids):
            with phase("embed"), \
                    _Swap(outer_tensors, materialize_outer(outer)), \
                    tape.no_grad():
                return embed(Tensor(ids)).data

        # Fused chunked head+CE: when the tail is [norms..., Linear w/o
        # bias] feeding a mean-over-tokens CE (both flagship families), the
        # [N, V] logits are never materialized — the head matmul + CE run
        # chunk-by-chunk in a checkpointed scan (ops/fused_ce.py). This is
        # what makes no-recompute batches fit in HBM at vocab 32k.
        lm_head = tail[-1]
        # The fused kernel computes exactly plain ignore-index mean CE; a
        # criterion configured with soft labels / smoothing / class
        # weights / a non-mean reduction has DIFFERENT semantics and must
        # ride the unfused path (ADVICE r3).
        _, _, _, ce_obj = _model_parts(self.model)
        plain_ce = (getattr(ce_obj, "soft_label", False) is False
                    and getattr(ce_obj, "label_smoothing", 0.0) == 0.0
                    and getattr(ce_obj, "weight", None) is None
                    and getattr(ce_obj, "reduction", "mean") == "mean"
                    and getattr(ce_obj, "use_softmax", True) is True
                    and getattr(ce_obj, "axis", -1) == -1)
        fused_tail = (getattr(lm_head, "bias", None) is None
                      and hasattr(lm_head, "weight")
                      and plain_ce
                      and self.fuse_head_ce)
        mp_axis = "model" if "model" in mesh.axis_names else None

        if fused_tail:
            from ..ops.fused_ce import fused_linear_ce
            from ..distributed.fleet.meta_parallel.parallel_layers.mp_ops \
                import _identity_fn
            ignore_index = getattr(ce_obj, "ignore_index", -100)

            def apply_tail_loss(outer, h, labels):
                with phase("loss"), \
                        _Swap(outer_tensors, materialize_outer(outer)), \
                        tape.no_grad():
                    if sp_active:
                        # tail is replicated computation: gather the
                        # sequence with the slice-transpose gather
                        h = sp_gather_raw(h)
                    out = Tensor(h) if not isinstance(h, Tensor) else h
                    for l in tail[:-1]:
                        out = l(out)
                    hh = out.data
                    if mp_axis is not None:
                        # column-parallel input contract (mp_ops._c_identity):
                        # identity fwd, psum-over-'model' bwd — dh must sum
                        # each vocab shard's partial
                        hh = _identity_fn(mp_axis)(hh)
                    w = lm_head.weight.data      # [H, V_local]
                    flat = hh.reshape(-1, hh.shape[-1])
                    total, _ = fused_linear_ce(
                        flat, w, labels.reshape(-1), axis=mp_axis,
                        chunk=self.ce_chunk, ignore_index=ignore_index)
                    # mean over ALL tokens (ignored rows contribute 0) —
                    # the same normalization as the unfused
                    # jnp.mean(criterion(...)) path
                    return total / jnp.float32(flat.shape[0])
        else:
            def apply_tail_loss(outer, h, labels):
                with phase("loss"), \
                        _Swap(outer_tensors, materialize_outer(outer)), \
                        tape.no_grad():
                    if sp_active:
                        h = sp_gather_raw(h)
                    out = h
                    for l in tail[:-1]:
                        out = l(Tensor(out) if not isinstance(out, Tensor) else out)
                    logits = tail[-1](out)
                    _, _, _, ce = _model_parts(self.model)
                    loss = ce(logits, Tensor(labels))
                    return jnp.mean(loss.data)

        if recompute or stage3:
            # stage 3 always remats the outer gathers so the full embedding
            # table is never saved for backward — only its chunks are.
            apply_embed = jax.checkpoint(apply_embed)
            if stage3 or not fused_tail:
                # fused tail already checkpoints per-chunk; the outer wrap
                # is only needed when the gathered lm_head W itself must
                # not be saved (stage 3's memory contract)
                apply_tail_loss = jax.checkpoint(apply_tail_loss)


        def _ckpt(fn):
            """Layer-body checkpoint. "save_attn" pins the flash kernel's
            named residuals (o/lse) so backward recompute re-runs only the
            cheap projections/elementwise, never the attention kernel."""
            if self.recompute_policy == "save_attn":
                pol = jax.checkpoint_policies.save_only_these_names(
                    "sdpa_res")
                return jax.checkpoint(fn, policy=pol)
            return jax.checkpoint(fn)

        def apply_stage(stacked_local, h):
            """Run this rank's `per` decoder layers over h.

            stage 1/2: stacked_local[i] = [per, *block] full local blocks.
            stage 3  : stacked_local[i] = [per, chunk_i]; each scan tick
            all-gathers ONE layer's params (gather-on-use; released after
            the tick — with recompute the backward regathers instead of
            keeping them)."""

            def body(carry, layer_params):
                if stage3:
                    layer_params = [self._ungather_layer(c, i)
                                    for i, c in enumerate(layer_params)]
                with _Swap(layer_tensors, list(layer_params)), tape.no_grad():
                    out = template(Tensor(carry)).data
                return out, None

            if recompute:
                body = _ckpt(body)
            h, _ = lax.scan(body, h, stacked_local)
            return h

        def loss_fn(params, ids, labels, key):
            outer = params["outer"]
            stacked = params["stacked"]  # local: [per, ...] or [per, chunk]
            with spmd_axes(axis_names), frnd.key_scope(key):
                emb = apply_embed(outer, ids)  # [B_loc, T, H]
                if sp_active:
                    # enter the sequence-parallel region: shard the
                    # (replicated-over-'model') embeddings by sequence
                    if emb.shape[1] % mesh.shape["model"]:
                        raise ValueError(
                            f"sequence_parallel needs the model-parallel "
                            f"degree {mesh.shape['model']} to divide the "
                            f"sequence length {emb.shape[1]} (pad the "
                            f"sequence to a multiple of the degree)")
                    emb = sp_scatter_raw(emb)
                if S == 1:
                    h = apply_stage(stacked, emb)
                    loss = apply_tail_loss(outer, h, labels)
                else:
                    stage = lax.axis_index("pipe")
                    B_loc, T = ids.shape[0], ids.shape[1]
                    m = mb or B_loc
                    M = B_loc // m
                    emb_m = emb.reshape(M, m, T, emb.shape[-1])
                    lab_m = labels.reshape(M, m, T)
                    state0 = jnp.zeros((m, T, emb.shape[-1]), emb.dtype)

                    def tick(carry, t):
                        state, acc = carry
                        inj = emb_m[jnp.clip(t, 0, M - 1)]
                        state = jnp.where((stage == 0) & (t < M), inj, state)
                        h = apply_stage(stacked, state)
                        t_out = t - (S - 1)
                        valid = (stage == S - 1) & (t_out >= 0) & (t_out < M)
                        lab = lab_m[jnp.clip(t_out, 0, M - 1)]
                        l = apply_tail_loss(outer, h, lab)
                        acc = acc + jnp.where(valid, l, 0.0)
                        nxt = lax.ppermute(
                            h, "pipe",
                            [(i, (i + 1) % S) for i in range(S)])
                        return (nxt, acc), None

                    (state, acc), _ = lax.scan(
                        tick, (state0, jnp.zeros((), jnp.float32)),
                        jnp.arange(M + S - 1))
                    # average over microbatches; share from the last stage
                    # with the IDENTITY-transpose psum: a tied psum here
                    # would hand every stage a xS_pipe cotangent, scaling
                    # stage-local (stacked) grads by the pipe degree —
                    # invisible to scale-invariant AdamW but breaking the
                    # mesh-independent canonical moment contract
                    loss = _untied_psum("pipe")(acc / M)
                # batch-mean across data/sharding (+ sequence) ranks
                with phase("loss"):
                    for ax in batch_axes + sep_axes:
                        loss = lax.pmean(loss, ax)
                    if "model" in axis_names and mesh.shape["model"] > 1:
                        # value-neutral re-share of the (already
                        # replicated) loss that DIVIDES the cotangent by
                        # the tp degree: /M then identity-transpose psum.
                        # (A plain pmean here is gradient-NEUTRAL: its
                        # internal tied psum multiplies the seed back by
                        # M.) This cancels the one tied psum inside the CE
                        # completion, making grads — and Adam moments —
                        # mesh-independent (the canonical checkpoint
                        # contract).
                        loss = _untied_psum("model")(
                            loss / mesh.shape["model"])
                return loss

        def _adamw_core(pl, gl, st, step, lr):
            """the AdamW math itself — moments, bias correction, decoupled
            decay — shared by all four (exact/int8 x stage12/stage3)
            variants so a fix here cannot drift between them. pl/gl are
            f32 views of this rank's owned slice."""
            m = b1 * st["m"].astype(jnp.float32) + (1 - b1) * gl
            v = b2 * st["v"].astype(jnp.float32) + (1 - b2) * gl * gl
            t = step.astype(jnp.float32)
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            pl = pl * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
            return pl, {"m": m.astype(mdt), "v": v.astype(mdt)}

        def _update12_scaffold(p, g, st, step, lr, scatter):
            """stage 1/2 scaffold shared by the exact and int8 paths, in
            the block's own shape along its moment_axis() k: reduce-to-
            owner via scatter(g f32, k) -> (owned grad slice, residual-or-
            None), core update on the owned slice of p (cast inside the
            elementwise update), the updated slices back into p's block
            in p's dtype. Nothing shards the update (k None): the core on
            the block as it is. Returns (p', moments, residual)."""
            k = moment_axis(p.shape, S_shard)
            gl, err = g, None
            if k is not None:
                with phase("grad_sync"):    # the sum, reduced to its owner
                    gl, err = scatter(g.astype(jnp.float32), k)
            with phase("optimizer"):
                pl, stn = _adamw_core(
                    self._owned(p, k).astype(jnp.float32),
                    gl.astype(jnp.float32), st, step, lr)
                return (self._regathered(pl.astype(p.dtype), k, p.shape,
                                         into=p), stn, err)

        def adamw_update12(p, g, st, step, lr):
            """stage 1/2: p is the full local block; g is psum'd over 'data'
            but still PARTIAL over 'sharding' — reduce-scatter completes the
            sum while handing each rank exactly its owned slice
            (ref: group_sharded_stage2.py grad reduce-to-owner hooks)."""
            def scatter(gf, k):
                if k == FLAT:
                    gf, k = _padded_flat(gf, S_shard), 0
                return lax.psum_scatter(gf, "sharding",
                                        scatter_dimension=k,
                                        tiled=True), None
            pn, stn, _ = _update12_scaffold(p, g, st, step, lr, scatter)
            return pn, stn

        def adamw_update3(p, g, st, step, lr):
            """stage 3: p IS the owned chunk; g arrived reduce-scattered by
            the AD transpose of the gather-on-use all_gather. Elementwise
            update, nothing re-gathered (ref: group_sharded_stage3.py:486)."""
            with phase("optimizer"):
                pl, stn = _adamw_core(p.astype(jnp.float32),
                                      g.astype(jnp.float32), st, step, lr)
                return pl.astype(p.dtype), stn

        adamw_update = adamw_update3 if stage3 else adamw_update12

        # ---- compressed gradient reduction (grad_compress="int8") ---------
        comp = self.grad_compress == "int8"
        cchunk = self.compress_chunk
        if comp:
            from ..distributed import comm_compress as _cc

            def compress_reduce(g, ef):
                """EF-add + chunked-int8 psum over the batch-like axes.

                Returns (reduced f32 grad, accumulated residual, repl):
                each stage's residual is divided by the replication degree
                already accumulated (errors computed AFTER reducing axis A
                are identical across A's ranks — next step every rank
                feeds them back, so the psum over A would scale them by
                |A| without the division)."""
                with phase("grad_sync"):
                    v = g.astype(jnp.float32) + ef
                    err_tot = jnp.zeros(v.shape, jnp.float32)
                    out, repl = v, 1
                    for ax in data_axes + sep_axes:
                        nax = int(mesh.shape[ax])
                        if nax == 1:
                            continue
                        out, err = _cc.quantized_psum(
                            out, ax, axis_size=nax, chunk=cchunk)
                        err_tot = err_tot + err / repl
                        repl *= nax
                    return out, err_tot, repl

            def adamw_update12_c(p, g, ef, st, step, lr):
                """stage 1/2 update with int8 DP psum + int8 'sharding'
                reduce-scatter; same scaffold + core as adamw_update12,
                plus the EF residual bookkeeping."""
                gr, err_tot, repl = compress_reduce(g, ef)

                def scatter(gf, k):
                    # the quantised collective scatters along dim 0: the
                    # owned axis goes to the front for it alone (axis 0:
                    # the same rows as a flat block's)
                    rows = (_padded_flat(gf, S_shard) if k == FLAT
                            else jnp.moveaxis(gf, k, 0))
                    y, err = _cc.quantized_psum_scatter(
                        rows, "sharding", axis_size=S_shard, chunk=cchunk)
                    if k == FLAT:
                        return y, err[:gf.size].reshape(gf.shape)
                    return jnp.moveaxis(y, 0, k), jnp.moveaxis(err, 0, k)
                pn, stn, err_s = _update12_scaffold(p, gr, st, step, lr,
                                                    scatter)
                if err_s is not None:
                    with phase("grad_sync"):
                        err_tot = err_tot + err_s / repl
                return pn, stn, err_tot

            def adamw_update3_c(p, g, ef, st, step, lr):
                """stage 3: g is the owned chunk (already reduce-scattered
                — in int8 when grad_compress is on, via the gather-on-use
                custom VJP); compress the remaining DP psum with EF."""
                gr, err_tot, _ = compress_reduce(g, ef)
                with phase("optimizer"):
                    pl, stn = _adamw_core(p.astype(jnp.float32), gr, st,
                                          step, lr)
                    return pl.astype(p.dtype), stn, err_tot

            adamw_update_c = adamw_update3_c if stage3 else adamw_update12_c

        # ---- 1F1B / interleaved schedule (hand-rolled bwd) ----------------
        use_1f1b = S > 1 and self.pp_schedule in ("1f1b", "interleave")
        if use_1f1b:
            from .pipeline_1f1b import build_1f1b_loss_and_grads
            v = self.v_pp
            per_v = self.per_v
            n_batch = 1
            for ax in batch_axes + sep_axes:
                n_batch *= mesh.shape[ax]

            def stage_fwd(chunk_list, h):
                def body(carry, layer_params):
                    if stage3:
                        layer_params = [self._ungather_layer(c, i)
                                        for i, c in enumerate(layer_params)]
                    with _Swap(layer_tensors, list(layer_params)), \
                            tape.no_grad():
                        out = template(Tensor(carry)).data
                    return out, None
                if recompute:
                    body = _ckpt(body)
                h, _ = lax.scan(body, h, chunk_list)
                return h

            def embed_fwd_1f1b(outer_p, ids_mb):
                return apply_embed(outer_p, ids_mb)

            def tail_loss_1f1b(outer_p, h, labels_mb):
                # f32 scalar: the schedule seeds its vjp with an f32
                # cotangent and accumulates losses in f32
                return apply_tail_loss(outer_p, h, labels_mb).astype(
                    jnp.float32)

            def loss_and_grads(params, ids, labels, key):
                B_loc, T = ids.shape
                m = mb or B_loc
                M = B_loc // m
                # logical hidden width = embedding table's last dim
                H = int(self.outer_tensors[0].shape[-1])
                run = build_1f1b_loss_and_grads(
                    S=S, v=v, per_v=per_v, stage_fwd=stage_fwd,
                    embed_fwd=embed_fwd_1f1b, tail_loss=tail_loss_1f1b,
                    n_micro=M, micro_bs=m, seq=T, hidden=H,
                    h_dtype=self._pdt or jnp.float32)
                ids_m = ids.reshape(M, m, T)
                lab_m = labels.reshape(M, m, T)
                # cotangent seed: microbatch + batch-rank mean, PLUS the
                # model-degree division (the tied psum inside the CE
                # completion multiplies every hand-rolled cotangent by the
                # tp degree — see loss_fn's model pmean for the jax.grad
                # analog)
                inv = jnp.asarray(
                    1.0 / (M * n_batch * mesh.shape.get("model", 1)),
                    jnp.float32)
                with spmd_axes(axis_names), frnd.key_scope(key):
                    loss, grads = run(params, ids_m, lab_m, inv)
                for ax in batch_axes + sep_axes:
                    loss = lax.pmean(loss, ax)
                return loss, grads
        elif self.grad_accum > 1:
            K_acc = self.grad_accum

            def loss_and_grads(params, ids, labels, key):
                # deferred sync: a lax.scan of LOCAL value_and_grad over K
                # microbatches — no GRADIENT collectives inside the scan
                # (loss_fn still pmeans the scalar loss and re-shares
                # untied params each iteration); the one batched gradient
                # sync happens after, where XLA's latency-hiding scheduler
                # can overlap it with the last microbatch's backward
                # (docs/distributed_perf.md)
                B_loc, T = ids.shape
                if B_loc % K_acc:
                    raise ValueError(
                        f"grad_accum={K_acc} must divide the per-rank "
                        f"batch {B_loc}")
                ids_k = ids.reshape(K_acc, B_loc // K_acc, T)
                lab_k = labels.reshape(K_acc, B_loc // K_acc, T)
                keys = jax.random.split(key, K_acc)

                def body(carry, xs):
                    acc_l, acc_g = carry
                    mb_ids, mb_lab, mb_key = xs
                    l, g = jax.value_and_grad(loss_fn)(params, mb_ids,
                                                       mb_lab, mb_key)
                    acc_g = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(jnp.float32), acc_g, g)
                    return (acc_l + l, acc_g), None

                zero_g = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (loss, grads), _ = lax.scan(
                    body, (jnp.zeros((), jnp.float32), zero_g),
                    (ids_k, lab_k, keys))
                # each slice's loss/grad is a slice-mean; averaging the K
                # equal slices reproduces the full-batch mean
                grads = jax.tree_util.tree_map(lambda a: a / K_acc, grads)
                return loss / K_acc, grads
        else:
            def loss_and_grads(params, ids, labels, key):
                return jax.value_and_grad(loss_fn)(params, ids, labels, key)

        def step_fn(state, ids, labels, key, lr):
            # the package's global matmul precision is "highest" (f32 API
            # parity for eager ops); the compiled training step wants the
            # native MXU rate for its dtype — bf16 passes for bf16 params
            with jax.default_matmul_precision(self.matmul_precision):
                return _step_fn(state, ids, labels, key, lr)

        def _step_fn(state, ids, labels, key, lr):
            params = state["params"]
            step = state["step"] + 1
            loss, grads = loss_and_grads(params, ids, labels, key)
            # grads partial over 'data' replicas: sum them (mean: loss is
            # already pmean'd so AD emits 1/N-scaled partials -> psum).
            # 'sharding'-axis completion happens in the update:
            # psum_scatter (stage 1/2) or the AD-inserted reduce-scatter of
            # the gather-on-use (stage 3). With grad_compress both of
            # those syncs ride chunked int8 inside the per-param update
            # (compress_reduce / quantized_psum_scatter) instead.
            with phase("grad_sync"):
                if not comp:
                    def reduce_grad(g):
                        for ax in data_axes + sep_axes:
                            g = lax.psum(g, ax)
                        return g

                    grads = jax.tree_util.tree_map(reduce_grad, grads)
                # Megatron-SP: norm weights saw only this rank's sequence
                # shard — complete their grads across the TP group (exact:
                # the model axis is not a compressed path)
                if sp_active:
                    grads["stacked"] = [
                        lax.psum(g, "model") if flag else g
                        for g, flag in zip(grads["stacked"], sp_flags)]
                # pipe-replicated outer params: sum partials across stages
                if S > 1:
                    grads["outer"] = [lax.psum(g, "pipe")
                                      for g in grads["outer"]]
            new_params = {"outer": [], "stacked": []}
            new_opt = {"outer": [], "stacked": []}
            if comp:
                new_ef = {"outer": [], "stacked": []}
                for kind in ("outer", "stacked"):
                    for p, g, ef, st in zip(params[kind], grads[kind],
                                            state["ef"][kind],
                                            state["opt"][kind]):
                        np_, nst, nef = adamw_update_c(p, g, ef, st, step,
                                                       lr)
                        new_params[kind].append(np_)
                        new_opt[kind].append(nst)
                        new_ef[kind].append(nef)
                return ({"params": new_params, "opt": new_opt,
                         "ef": new_ef, "step": step}, loss)
            for kind in ("outer", "stacked"):
                for p, g, st in zip(params[kind], grads[kind],
                                    state["opt"][kind]):
                    np_, nst = adamw_update(p, g, st, step, lr)
                    new_params[kind].append(np_)
                    new_opt[kind].append(nst)
            return ({"params": new_params, "opt": new_opt, "step": step},
                    loss)

        state_specs = self._state_specs()
        ids_spec = P(self.batch_axes if self.batch_axes else None,
                     "sep" if self.sep_axes else None)

        smapped = shard_map(
            step_fn, mesh=mesh,
            in_specs=(state_specs, ids_spec, ids_spec, P(), P()),
            out_specs=(state_specs, P()),
            check_vma=False,
        )
        return jax.jit(smapped, donate_argnums=(0,))

    def step(self, state, ids, labels, key=None, lr=None):
        if self._jitted is None:
            self._jitted = self._build(tuple(np.shape(ids)))
        if key is None:
            key = frnd.next_key()
        lr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        ids = ids.data if isinstance(ids, Tensor) else jnp.asarray(ids)
        labels = labels.data if isinstance(labels, Tensor) else jnp.asarray(labels)
        state, loss = self._jitted(state, ids, labels, key, lr)
        return state, loss

    # ---- observability -----------------------------------------------------
    def abstract_state(self):
        """ShapeDtypeStruct pytree of init_state() WITH shardings, built
        from parameter METADATA only — no initializer runs, so a model
        constructed under framework.LazyGuard (meta init) AOT-compiles
        7B/13B-scale recipes on a small host
        (examples/pretrain_llama_hybrid.py --aot_memory)."""
        mesh = self.mesh

        def sds(shape, dtype, spec):
            return jax.ShapeDtypeStruct(
                tuple(int(s) for s in shape), jnp.dtype(dtype),
                sharding=NamedSharding(mesh, spec))

        def pdt_of(dt):
            if self._pdt is not None and jnp.issubdtype(dt, jnp.floating):
                return self._pdt
            return dt

        specs = self._param_specs()
        chunk_mul = 1
        for a in self._chunk_axes:
            chunk_mul *= int(self.mesh.shape[a])

        if self.sharding_stage == 3:
            # global leaf = local chunk x product of the chunk axes
            p_outer = [sds((self.outer_chunk[i] * chunk_mul,),
                           pdt_of(jnp.dtype(p.dtype)), specs["outer"][i])
                       for i, p in enumerate(self.outer_tensors)]
            p_stacked = [sds((self.n_layers, self.layer_chunk[i] * chunk_mul),
                             pdt_of(jnp.dtype(p.dtype)),
                             specs["stacked"][i])
                         for i, p in enumerate(self.layer_param_tensors)]
            mo = [{k: sds(x.shape, self._mdt, sp) for k in ("m", "v")}
                  for x, sp in zip(p_outer, specs["outer"])]
            ms = [{k: sds(x.shape, self._mdt, sp) for k in ("m", "v")}
                  for x, sp in zip(p_stacked, specs["stacked"])]
        else:
            p_outer = [sds(p.shape, pdt_of(jnp.dtype(p.dtype)),
                           specs["outer"][i])
                       for i, p in enumerate(self.outer_tensors)]
            p_stacked = [sds((self.n_layers,) + tuple(p.shape),
                             pdt_of(jnp.dtype(p.dtype)),
                             specs["stacked"][i])
                         for i, p in enumerate(self.layer_param_tensors)]
            layouts = self._moment_layouts()
            mo, ms = [[{k: sds(lay["shape"], self._mdt, lay["spec"])
                        for k in ("m", "v")} for lay in layouts[kind]]
                      for kind in ("outer", "stacked")]
        out = {"params": {"outer": p_outer, "stacked": p_stacked},
               "opt": {"outer": mo, "stacked": ms},
               "step": sds((), jnp.int32, P())}
        if self.grad_compress is not None:
            out["ef"] = {
                "outer": [sds(x.shape, jnp.float32, sp) for x, sp in
                          zip(p_outer, specs["outer"])],
                "stacked": [sds(x.shape, jnp.float32, sp) for x, sp in
                            zip(p_stacked, specs["stacked"])]}
        return out

    def memory_analysis(self, state, ids, labels):
        """Compile-time per-device memory accounting of the step program
        (argument/output/temp/code bytes). The TPU answer to the reference's
        allocator stats (ref: fluid/memory/stats.cc) for the compiled path:
        ZeRO stage claims are judged against these numbers, not placement
        metadata. `state`/`ids`/`labels` may be ShapeDtypeStructs
        (abstract_state) — nothing is materialized."""
        if not isinstance(ids, jax.ShapeDtypeStruct):
            ids = ids.data if isinstance(ids, Tensor) else jnp.asarray(ids)
        if not isinstance(labels, jax.ShapeDtypeStruct):
            labels = (labels.data if isinstance(labels, Tensor)
                      else jnp.asarray(labels))
        if self._jitted is None:
            self._jitted = self._build(tuple(np.shape(ids)))
        key = jax.random.key(0)
        lr = jnp.asarray(self.lr, jnp.float32)
        compiled = self._jitted.lower(state, ids, labels, key, lr).compile()
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        return {
            "argument_size_in_bytes": ma.argument_size_in_bytes,
            "output_size_in_bytes": ma.output_size_in_bytes,
            "temp_size_in_bytes": ma.temp_size_in_bytes,
            "alias_size_in_bytes": ma.alias_size_in_bytes,
            "generated_code_size_in_bytes": ma.generated_code_size_in_bytes,
        }

    # ---- checkpoint bridge -------------------------------------------------
    def gather_params(self, state):
        """Return params in the logical (stage-1/2) layout regardless of
        sharding_stage (ref: group_sharded_stage3.py:617
        get_all_parameters)."""
        if self.sharding_stage != 3:
            return state["params"]

        def gather_fn(chunks):
            outer = [self._ungather_outer(c, i)
                     for i, c in enumerate(chunks["outer"])]
            stacked = []
            for i, c in enumerate(chunks["stacked"]):  # [per, chunk]
                blocks = jnp.stack([self._ungather_layer(c[j], i)
                                    for j in range(c.shape[0])])
                stacked.append(blocks)
            return {"outer": outer, "stacked": stacked}

        smapped = shard_map(gather_fn, mesh=self.mesh,
                            in_specs=(self._param_specs(),),
                            out_specs=self._param_specs12(),
                            check_vma=False)
        return jax.jit(smapped)(state["params"])

    def sync_to_model(self, state):
        """Write compiled-state params back into the eager model."""
        params12 = self.gather_params(state)
        outer = params12["outer"]
        for p, a in zip(self.outer_tensors, outer):
            p.data = a
        stacked = params12["stacked"]
        for pi, name in enumerate(self.layer_param_names):
            for phys, li in enumerate(self.phys_order):
                dict(_named_params(self.decoders[li]))[name].data = \
                    stacked[pi][phys]
