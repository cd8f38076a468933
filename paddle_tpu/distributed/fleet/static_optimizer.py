"""Static-graph distributed optimizer tier.

ref: python/paddle/distributed/fleet/meta_optimizers/raw_program_optimizer.py
+ sharding_optimizer.py:61 — in the reference, fleet.distributed_optimizer
in static mode rewrites the ProgramDesc (inject c_allreduce after grads,
partition optimizer ops by owner). Here `minimize` applies the registered
Program passes (static/distributed_passes.py) and attaches the train-step
contract to the Program; static.Executor.run detects it, jits the step
(under shard_map over the global mesh when dp/sharding axes exist), keeps
optimizer state across runs (sharded chunks under ZeRO), and writes
updated params back into the recorded parameter tensors.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class StaticDistributedOptimizer:
    """Returned by fleet.distributed_optimizer(...) under static mode."""

    def __init__(self, optimizer, strategy):
        self.inner = optimizer
        self.strategy = strategy

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def minimize(self, loss, startup_program=None, program=None,
                 parameter_list=None, no_grad_set=None):
        from ... import static
        from ...static.passes import new_pass
        prog = program if program is not None \
            else static.default_main_program()
        if not prog._params_marked:
            prog.append_backward(loss, parameter_list)

        hc = getattr(self.strategy, "hybrid_configs", {}) or {}
        dp = int(hc.get("dp_degree", 1))
        sd = int(hc.get("sharding_degree", 1))
        if dp > 1 or sd > 1:
            # grads are means over the global batch: every batch axis
            # contributes a pmean (matches SpmdTrainer's data semantics)
            for axis, deg in (("data", dp), ("sharding", sd)):
                if deg > 1 and (sd == 1 or axis == "data"):
                    new_pass("data_parallel_gradient_sync",
                             axis=axis).apply(prog)
        sc = getattr(self.strategy, "sharding_configs", {}) or {}
        if sd > 1:
            stage = hc.get("sharding_stage")
            if stage is None and getattr(self.strategy, "sharding", False):
                stage = sc.get("stage")  # user-enabled sharding_configs
            stage = int(stage or 2)
            new_pass("zero_sharding", axis="sharding",
                     stage=stage).apply(prog)
        # k-step gradient accumulation (ref: sharding_optimizer grad-merge;
        # sharding_configs.accumulate_steps is the same knob spelled the
        # sharding way — honored when no explicit gradient_merge is set)
        if getattr(self.strategy, "gradient_merge", False):
            gm = getattr(self.strategy, "gradient_merge_configs", {}) or {}
            new_pass("gradient_merge", k_steps=int(gm.get("k_steps", 1)),
                     avg=bool(gm.get("avg", True))).apply(prog)
        elif (getattr(self.strategy, "sharding", False)
                and int(sc.get("accumulate_steps", 1) or 1) > 1):
            new_pass("gradient_merge",
                     k_steps=int(sc["accumulate_steps"])).apply(prog)
        # host-parked optimizer state (ref: sharding offload). Same gate
        # as the stage knob: sharding_configs take effect only with
        # strategy.sharding = True (the reference's activation contract).
        if getattr(self.strategy, "sharding", False) and sc.get("offload"):
            new_pass("optimizer_state_offload").apply(prog)
        prog._train = {"optimizer": self.inner, "shard_degree": sd,
                       "dp_degree": dp,
                       "offload": bool(getattr(prog, "_offload_opt_state",
                                               False))}
        return [], list(prog._params_marked)


def run_train_step(exe, prog, feed, fetch_ids, fetch_slots):
    """Executor backend for a pass-rewritten Program (called from
    static.Executor.run when prog._train is set)."""
    from ...static.distributed_passes import build_train_callable
    from ..mesh import global_mesh, spmd_axes
    from jax import shard_map

    info = prog._train
    opt = info["optimizer"]
    sd = info["shard_degree"]
    dp = info["dp_degree"]
    mesh = global_mesh()
    dist = dp > 1 or sd > 1

    key = (id(prog), prog._version, tuple(fetch_ids))
    cache = exe._cache.setdefault("__train__", {})
    stage3 = (sd > 1 and prog._shard_spec is not None
              and prog._shard_spec["stage"] == 3)
    param_ids = {id(p) for p, _ in prog._params_marked}

    def _gather_leaves(leaf_ids):
        """Step inputs per leaf. Under stage 3 the per-rank CHUNKS own the
        parameters (gathered on use inside the step), so param positions
        feed a tiny dummy instead of the full replicated array — external
        writes into prog.vars between steps are not observed."""
        out = []
        for vid in leaf_ids:
            t = prog.vars[vid].tensor
            if stage3 and vid in param_ids:
                out.append(jnp.zeros((1,), t.data.dtype))
            else:
                out.append(t.data)
        return out

    if key not in cache:
        step, init_state, chunked = build_train_callable(
            prog, opt, fetch_ids, shard_degree=sd)
        leaf_ids = prog.leaf_ids()
        leaves = _gather_leaves(leaf_ids)
        states = init_state()
        t0 = jnp.asarray(1, jnp.int32)
        if dist:
            axis_names = tuple(mesh.axis_names)
            batch_axes = tuple(a for a in ("data", "sharding")
                               if a in axis_names and mesh.shape[a] > 1)

            def wrapped(feeds, leaves, states, t):
                with spmd_axes(axis_names):
                    fetches, nl, ns, nt = step(feeds, leaves, states, t)
                    # fetches (loss etc.) are local-batch values; average
                    # across batch ranks so every device returns the
                    # global-batch value (replicated out_specs)
                    from jax import lax as _lax
                    for ax in batch_axes:
                        fetches = [_lax.pmean(f, ax) for f in fetches]
                    return fetches, nl, ns, nt

            feed_spec = P(batch_axes if batch_axes else None)
            st_spec = P("sharding") if chunked else P()
            # grad-merge accumulators hold data-SYNCED (replicated) grads
            # — they stay P() even when the optimizer state is chunked
            st_specs = [{k: (P() if k == "__gm_acc" else st_spec)
                         for k in s} for s in states]
            fn = shard_map(
                wrapped, mesh=mesh,
                in_specs=([feed_spec] * len(prog.feed_order),
                          [P()] * len(leaves), st_specs, P()),
                out_specs=([P()] * len(fetch_ids), [P()] * len(leaves),
                           st_specs, P()),
                check_vma=False)
        else:
            fn = step
        cache[key] = {"fn": jax.jit(fn), "states": states, "t": t0,
                      "leaf_ids": leaf_ids}
    ent = cache[key]

    leaf_ids = ent["leaf_ids"]
    leaves = _gather_leaves(leaf_ids)
    feeds = [jnp.asarray(feed[prog.vars[vid].name])
             for vid in prog.feed_order]
    fetches, new_leaves, new_states, new_t = ent["fn"](
        feeds, leaves, ent["states"], ent["t"])
    if info.get("offload"):
        # park the optimizer state on the HOST between steps (ref:
        # sharding_optimizer OffloadHelper): device HBM holds it only
        # while the step runs; the next call re-places it
        new_states = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), new_states)
    ent["states"] = new_states
    ent["t"] = new_t
    # write updated params back into the recorded tensors (the static
    # analog of the eager optimizer mutating p.data)
    for vid, arr in zip(leaf_ids, new_leaves):
        prog.vars[vid].tensor.data = arr
    out = []
    i = 0
    for slot in fetch_slots:
        out.append(np.asarray(fetches[i]))
        i += 1
    return out
