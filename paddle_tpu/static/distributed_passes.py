"""Distributed passes over the recorded Program IR + the static train step.

ref: python/paddle/distributed/fleet/meta_optimizers/raw_program_optimizer.py
(DP allreduce injection), sharding_optimizer.py:61 (ZeRO program surgery),
python/paddle/distributed/passes/. On the reference these are ProgramDesc
rewrites inserting c_allreduce_sum / slice-and-broadcast ops; here the
Program's replay is differentiated by jax.grad, so the passes rewrite the
program's GRADIENT PIPELINE — an introspectable op list applied between
the AD-produced grads and the optimizer update — and the partition spec
that shards optimizer state over the 'sharding' mesh axis:

  data_parallel_gradient_sync : grads <- pmean over 'data' (+'sharding')
  zero_sharding (stage 1/2)   : grads reduce-SCATTERED to the owning
      sharding rank (lax.psum_scatter), optimizer state stored/updated in
      per-rank chunks, updated params all-gathered — same compiled-step
      semantics as models/train_step.py's adamw_update12, derived here
      from ANY Optimizer's functional _rule.

`build_train_callable` assembles the full step (replay fwd -> grads ->
pipeline -> update) as a pure function the Executor jits (optionally under
shard_map over the global mesh).
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .passes import PassBase, register_pass
from ..distributed.mesh import in_spmd_region
from jax.lax import axis_size as _axis_size


@register_pass("data_parallel_gradient_sync")
class DataParallelGradientSyncPass(PassBase):
    """ref: raw_program_optimizer.py _insert_allreduce_ops."""

    def __init__(self, axis="data", op="avg"):
        self.axis = axis
        self.op = op

    def apply(self, program, **kwargs):
        program._grad_pipeline.append(
            {"op": f"c_allreduce_{self.op}", "axis": self.axis})
        return program


@register_pass("zero_sharding")
class ZeroShardingPass(PassBase):
    """ref: sharding_optimizer.py:61 (stage 1: state partition; stage 2:
    + grad reduce-to-owner; stage 3: + param chunks gathered on use)."""

    def __init__(self, axis="sharding", stage=2):
        if stage not in (1, 2, 3):
            raise ValueError(f"zero_sharding pass supports stage 1/2/3, "
                             f"got {stage}")
        self.axis = axis
        self.stage = stage

    def apply(self, program, **kwargs):
        program._shard_spec = {"axis": self.axis, "stage": self.stage}
        ops = {1: "c_allreduce_then_slice", 2: "c_reducescatter",
               3: "c_reducescatter"}
        program._grad_pipeline.append(
            {"op": ops[self.stage], "axis": self.axis})
        if self.stage == 3:
            program._grad_pipeline.append(
                {"op": "param_chunk_gather_on_use", "axis": self.axis})
        return program


@register_pass("gradient_merge")
class GradientMergePass(PassBase):
    """k-step gradient accumulation (ref: sharding_optimizer.py grad-merge
    + passes/auto_parallel_gradient_merge.py): grads are synced and
    ACCUMULATED each step; the optimizer applies the k-step mean only at
    merge boundaries (t % k == 0) — between boundaries params and
    optimizer state are untouched."""

    def __init__(self, k_steps=2, avg=True):
        if k_steps < 1:
            raise ValueError("gradient_merge needs k_steps >= 1")
        self.k = int(k_steps)
        self.avg = bool(avg)

    def apply(self, program, **kwargs):
        program._grad_merge = {"k": self.k, "avg": self.avg}
        program._grad_pipeline.append(
            {"op": f"gradient_merge(k={self.k})", "axis": None})
        return program


@register_pass("optimizer_state_offload")
class OptimizerStateOffloadPass(PassBase):
    """ref: sharding_optimizer.py offload (`_dp_as_optimizer_sharding` +
    OffloadHelper): optimizer state lives in HOST memory between steps —
    the Executor parks the state arrays on the host after every step and
    feeds them back in at the next one, freeing device HBM for
    activations/params."""

    def apply(self, program, **kwargs):
        program._offload_opt_state = True
        program._grad_pipeline.append(
            {"op": "optimizer_state_offload", "axis": None})
        return program


def _sync_grad(g, spec_list):
    for spec in spec_list:
        axis = spec["axis"]
        if not in_spmd_region(axis):
            continue
        if spec["op"].startswith("c_allreduce"):
            g = lax.pmean(g, axis)
    return g


def build_train_callable(program, optimizer, fetch_ids, shard_degree=1):
    """Pure train step over (feed, params, opt_state, t) implementing the
    pass-rewritten program.

    Returns (step, init_opt_state, state_is_chunked). With the
    zero_sharding pass applied (shard_degree > 1), optimizer state lives
    as FLAT PADDED arrays sharded over the 'sharding' axis — each rank
    holds and updates only its chunk between steps (the ZeRO state
    partition); params stay replicated (all-gathered after the chunk
    update)."""
    params = [p for p, _ in program._params_marked]
    base = program.build_callable(fetch_ids, with_grads=True)
    pipeline = list(program._grad_pipeline)
    # accumulate-time sync for gradient merge: the accumulator must be
    # REPLICATED (its shard_map spec is P()), so it is meaned over every
    # batch axis — 'data' via the recorded c_allreduce entries AND, under
    # stage 2/3 (whose sharding-axis completion normally hides inside the
    # boundary psum_scatter), an explicit 'sharding' mean. The boundary
    # psum_scatter of the replicated accumulator then reduces to a plain
    # owner-slice of it, keeping the update math unchanged.
    acc_pipeline = [s for s in pipeline if s["op"].startswith("c_allreduce")]
    shard = program._shard_spec
    chunked = shard is not None and shard_degree > 1
    stage3 = chunked and shard["stage"] == 3
    if chunked and shard["stage"] in (2, 3):
        acc_pipeline = acc_pipeline + [
            {"op": "c_allreduce_avg", "axis": shard["axis"]}]
    merge = getattr(program, "_grad_merge", None)
    k_merge = merge["k"] if merge else 1
    leaf_ids = program.leaf_ids()
    param_pos = [leaf_ids.index(id(p)) for p in params]

    def init_opt_state():
        states = []
        for p in params:
            st = {k: jnp.asarray(v.data if hasattr(v, "data") else v)
                  for k, v in optimizer._create_state(p).items()}
            if chunked:
                n = int(np.prod(p.data.shape))
                pad = (-n) % shard_degree
                st = {k: jnp.pad(v.reshape(-1).astype(jnp.float32),
                                 (0, pad)) for k, v in st.items()}
                if stage3:
                    # stage 3: the PARAM itself lives as per-rank chunks
                    # between steps (flat padded; the shard_map in_specs
                    # P('sharding') hands each rank its slice)
                    st["__w_chunk"] = jnp.pad(
                        p.data.reshape(-1).astype(jnp.float32), (0, pad))
            if k_merge > 1:
                # k-step accumulator of data-SYNCED grads: identical on
                # every rank, so its shard_map spec stays P()
                st["__gm_acc"] = jnp.zeros(tuple(p.data.shape), jnp.float32)
            states.append(st)
        return states

    def update_param(pos, p, leaves, g, st, t, lr, sync_dp=True):
        """Grad sync + (chunking) + optimizer rule for ONE param.
        Returns (new_full_w, new_state_dict)."""
        g = _sync_grad(g, pipeline if sync_dp else [])
        w = leaves[pos]
        dtype = p.data.dtype
        opt_st = {k: v for k, v in st.items() if not k.startswith("__")}
        if chunked and in_spmd_region(shard["axis"]):
            axis = shard["axis"]
            S = _axis_size(axis)
            shape = tuple(p.data.shape)
            n = int(np.prod(shape))
            pad = (-n) % S
            chunk = (n + pad) // S
            gf = g.reshape(-1).astype(jnp.float32)
            if pad:
                gf = jnp.concatenate([gf, jnp.zeros(pad, jnp.float32)])
            r = lax.axis_index(axis)
            if shard["stage"] in (2, 3):
                # reduce-to-owner: completes the cross-rank grad MEAN
                # (each rank's grad is its local-batch mean, so scale
                # by 1/S) while handing each rank its owned chunk
                gl = lax.psum_scatter(gf / S, axis,
                                      scatter_dimension=0, tiled=True)
            else:  # stage 1: grads already synced; slice own chunk
                gl = lax.dynamic_slice_in_dim(gf, r * chunk, chunk)
            if stage3:
                wl = st["__w_chunk"]
            else:
                wf = w.reshape(-1).astype(jnp.float32)
                if pad:
                    wf = jnp.concatenate([wf, jnp.zeros(pad, jnp.float32)])
                wl = lax.dynamic_slice_in_dim(wf, r * chunk, chunk)
            # opt state arrives as this rank's [chunk] shard (shard_map
            # in_specs P('sharding')) — updated in place, never gathered
            new_w, new_opt = optimizer._rule(wl, gl.astype(wl.dtype),
                                             opt_st, lr, t)
            out_st = dict(new_opt)
            if stage3:
                out_st["__w_chunk"] = new_w.astype(jnp.float32)
            wf2 = lax.all_gather(new_w, axis, axis=0, tiled=True)
            if pad:
                wf2 = wf2[:n]
            return wf2.reshape(shape).astype(dtype), out_st
        new_w, new_opt = optimizer._rule(w, g.astype(w.dtype), opt_st,
                                         lr, t)
        return new_w.astype(w.dtype), dict(new_opt)

    def step(feed_arrays, leaf_arrays, opt_states, t):
        lr = optimizer.get_lr()
        leaf_arrays = list(leaf_arrays)
        if stage3 and in_spmd_region(shard["axis"]):
            # gather-on-use: materialize full params from this step's
            # chunks before replaying the forward (the recorded-Program
            # analog of SpmdTrainer's stage-3 _ungather). The chunks OWN
            # the parameters under stage 3 — the executor feeds dummy
            # placeholders at param positions, and external writes into
            # prog.vars between steps are not observed
            axis = shard["axis"]
            for pos, p, st in zip(param_pos, params, opt_states):
                shape = tuple(p.data.shape)
                n = int(np.prod(shape))
                wf = lax.all_gather(st["__w_chunk"], axis, axis=0,
                                    tiled=True)[:n]
                leaf_arrays[pos] = wf.reshape(shape).astype(
                    leaf_arrays[pos].dtype)
        outs = base(feed_arrays, leaf_arrays)
        n_f = len(fetch_ids)
        fetches, grads = outs[:n_f], outs[n_f:]
        new_leaves = list(leaf_arrays)
        new_states = []
        for pos, p, g, st in zip(param_pos, params, grads, opt_states):
            if k_merge > 1:
                # accumulate the data-synced grad each step; the update
                # (incl. sharding collectives) runs only at boundaries
                acc = st["__gm_acc"] + _sync_grad(
                    g, acc_pipeline).astype(jnp.float32)
                boundary = (t % k_merge) == 0
                scale = float(k_merge) if merge["avg"] else 1.0

                def do_update(acc_in, _pos=pos, _p=p, _st=st):
                    g_eff = (acc_in / scale).astype(g.dtype)
                    # the inner optimizer advances once per MERGED step
                    # (Adam bias correction counts applied updates, not
                    # ministeps — GradientMergeOptimizer contract)
                    nw, nst = update_param(_pos, _p, new_leaves, g_eff,
                                           _st, t // k_merge, lr,
                                           sync_dp=False)
                    nst["__gm_acc"] = jnp.zeros_like(acc_in)
                    return nw, nst

                def skip_update(acc_in, _pos=pos, _st=st):
                    nst = {k: v for k, v in _st.items() if k != "__gm_acc"}
                    nst["__gm_acc"] = acc_in
                    return new_leaves[_pos], nst

                new_w, new_st = lax.cond(boundary, do_update, skip_update,
                                         acc)
            else:
                new_w, new_st = update_param(pos, p, new_leaves, g, st,
                                             t, lr)
            new_leaves[pos] = new_w
            new_states.append(new_st)
        return fetches, new_leaves, new_states, t + 1

    return step, init_opt_state, chunked
