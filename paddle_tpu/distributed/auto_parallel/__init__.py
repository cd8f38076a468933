"""Semi-automatic parallelization.

ref: python/paddle/distributed/auto_parallel/ — Engine (engine.py:57),
ProcessMesh (process_mesh.py:45), dist attrs, Completer (completion.py),
Partitioner, Resharder (reshard.py, 2964 LoC).

TPU-native: those 19.5 kLoC collapse onto the XLA GSPMD partitioner. A
ProcessMesh is a jax Mesh; shard_tensor places arrays with NamedSharding;
the Completer (shard propagation) and Resharder (comm insertion for
mismatched shardings) are what XLA does when a jit-compiled program consumes
arrays with declared shardings. The Engine builds that jitted step.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...tensor.tensor import Tensor
from ...autograd import tape
from ...framework import random as frnd


class Placement:
    pass


class Replicate(Placement):
    def __repr__(self):
        return "Replicate()"


class Shard(Placement):
    def __init__(self, dim):
        self.dim = dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"


class Partial(Placement):
    def __repr__(self):
        return "Partial()"


class ProcessMesh:
    """ref: process_mesh.py:45 — an N-d array of ranks with dim names."""

    def __init__(self, mesh, dim_names=None, shape=None, process_ids=None):
        arr = np.asarray(mesh)
        self._shape = arr.shape
        self._dim_names = list(dim_names) if dim_names else [
            f"d{i}" for i in range(arr.ndim)]
        devices = np.asarray(jax.devices())[arr.reshape(-1)].reshape(arr.shape)
        self._jax_mesh = Mesh(devices, tuple(self._dim_names))

    @property
    def shape(self):
        return list(self._shape)

    @property
    def dim_names(self):
        return self._dim_names

    @property
    def jax_mesh(self):
        return self._jax_mesh

    @property
    def process_ids(self):
        return list(range(int(np.prod(self._shape))))


def _spec_from_placements(mesh, placements, ndim):
    axes = [None] * ndim
    for axis_name, pl in zip(mesh.dim_names, placements):
        if isinstance(pl, Shard):
            axes[pl.dim] = axis_name
    return P(*axes)


def shard_tensor(x, process_mesh, placements, dtype=None, stop_gradient=None):
    """ref: api shard_tensor — place the array with the given sharding; XLA
    propagates from here."""
    t = x if isinstance(x, Tensor) else Tensor(x)
    spec = _spec_from_placements(process_mesh, placements, t.ndim)
    t.data = jax.device_put(t.data, NamedSharding(process_mesh.jax_mesh, spec))
    t.dist_attr = tuple(spec)
    t.process_mesh = process_mesh
    return t


def dtensor_from_fn(fn, process_mesh, placements, *args, **kwargs):
    return shard_tensor(fn(*args, **kwargs), process_mesh, placements)


def reshard(x, process_mesh, placements):
    """ref: reshard.py:1007 Resharder. Outside an SPMD region: one
    device_put (XLA emits the collective traffic). INSIDE a shard_map
    region (x holds the local shard and carries dist_attr): the explicit
    collective chain from reshard.py — all_to_all for axis moves,
    all_gather to unshard, a free slice to shard, psum/psum_scatter for
    partials."""
    from ..mesh import in_spmd_region
    from .reshard import reshard_spec
    t = x if isinstance(x, Tensor) else Tensor(x)
    dst = tuple(_spec_from_placements(process_mesh, placements, t.ndim))
    src = getattr(t, "dist_attr", None)
    live = any(in_spmd_region(a) for a in process_mesh.dim_names)
    if live and src is not None:
        from ...ops import apply
        out = apply(lambda a: reshard_spec(a, src, dst), t, name="reshard")
        out.dist_attr = dst
        out.process_mesh = process_mesh
        return out
    return shard_tensor(t, process_mesh, placements)


def shard_layer(layer, process_mesh, shard_fn=None, input_fn=None,
                output_fn=None):
    """Annotate a layer's params via shard_fn(name, layer, mesh)."""
    if shard_fn is not None:
        for name, sub in layer.named_sublayers(include_self=True):
            shard_fn(name, sub, process_mesh)
    return layer


class Strategy:
    """auto_mode:
      "semi" — Completer places params, XLA GSPMD inserts collectives
               (the default; collectives implicit).
      "full" — Completer -> Planner (cluster-bandwidth cost rule) ->
               Partitioner: the loss jaxpr is interpreted on LOCAL
               shards inside shard_map with EXPLICIT reshard_spec
               collective chains at every spec conflict
               (ref: partitioner.py:38 + reshard.py:1007 + cost/)."""

    def __init__(self):
        self.auto_mode = "semi"
        self.cluster = None  # Cluster instance for the planner cost rule


class Engine:
    """ref: engine.py:57 — prepare/fit/evaluate driving a jit-compiled step
    whose parallelism comes from the declared shardings.

    The Completer analog (completion.py): params annotated via shard_tensor
    seed a shard-propagation pass over the traced loss jaxpr; the engine
    fills in shardings for every UNANNOTATED parameter, places them, and
    lets XLA GSPMD insert the collectives (the Resharder's job)."""

    def __init__(self, model=None, loss=None, optimizer=None, metrics=None,
                 strategy=None):
        self._model = model
        self._loss = loss
        self._optimizer = optimizer
        self._metrics = metrics or []
        self._strategy = strategy or Strategy()
        self._params = None
        self._jitted = None
        self._process_mesh = None
        self._input_placements = None
        self.completed_param_specs = None
        self._completed_all_specs = None

    def prepare(self, *args, input_placements=None, process_mesh=None,
                **kwargs):
        """input_placements: spec tuple (axis names / None per dim) for the
        input batch; process_mesh: the ProcessMesh to complete over."""
        self._params = list(self._model.parameters())
        if input_placements is not None:
            self._input_placements = [tuple(s) for s in input_placements]
        if process_mesh is not None:
            self._process_mesh = process_mesh
        return self

    def _compute_fn(self, params, key):
        model, loss_fn = self._model, self._loss

        def compute(arrs, x, y):
            for p, a in zip(params, arrs):
                p.data = a
            with tape.no_grad(), frnd.key_scope(key):
                out = model(Tensor(x))
                l = loss_fn(out, Tensor(y))
            return l.data

        return compute

    def _complete_and_place(self, x, y):
        """Run the Completer over the traced loss and place params
        accordingly (ref: completion.py Completer +
        engine._initialize)."""
        if self._params is None:
            self._params = list(self._model.parameters())
        params = self._params
        mesh = self._process_mesh
        seeds = {}
        for i, p in enumerate(params):
            attr = getattr(p, "dist_attr", None)
            if attr is not None:
                seeds[i] = tuple(attr)
        n = len(params)
        if self._input_placements:
            seeds[n] = self._input_placements[0]
        if mesh is None or not seeds:
            return
        from .completion import Completer
        compute = self._compute_fn(params, jax.random.key(0))
        example = [p.data for p in params] + [x, y]
        saved = [p.data for p in params]

        def flat(*argv):
            try:
                arrs = list(argv[:n])
                return compute(arrs, argv[n], argv[n + 1])
            finally:
                for p, s in zip(params, saved):
                    p.data = s

        specs = Completer(mesh.jax_mesh).complete(flat, example, seeds)
        self.completed_param_specs = specs[:n]
        self._completed_all_specs = list(specs)
        if self._strategy.auto_mode == "full":
            # explicit-partitioned path places shards inside shard_map —
            # keep params replicated host-side
            return
        for p, spec in zip(params, self.completed_param_specs):
            sharding = NamedSharding(
                mesh.jax_mesh, P(*spec) if spec is not None else P())
            p.data = jax.device_put(p.data, sharding)

    def _build_full(self, x, y):
        """Planner+Partitioner path (strategy.auto_mode == "full"): the
        once-annotated loss program is completed, planned against the
        cluster bandwidth table, partitioned onto the mesh with explicit
        reshard chains, and compiled as one shard_map step."""
        from jax import shard_map
        from .partitioner import Partitioner, _axes

        if self._process_mesh is None:
            raise ValueError(
                "auto_mode='full' needs Engine.prepare(process_mesh=...) "
                "before fit()")
        if getattr(self, "_completed_all_specs", None) is None:
            raise ValueError(
                "auto_mode='full' needs at least one sharding seed — "
                "annotate a parameter (param.dist_attr = spec / "
                "shard_tensor) or pass input_placements to prepare() so "
                "the Completer has something to propagate")
        params = self._params
        n = len(params)
        mesh = self._process_mesh.jax_mesh
        lr = self._optimizer.get_lr() if self._optimizer else 1e-3
        specs = self._completed_all_specs
        p_specs = [s if s is not None else (None,) * params[i].data.ndim
                   for i, s in enumerate(specs[:n])]
        xy_specs = [s for s in specs[n:]]
        xy_specs = [
            s if s is not None else (None,) * nd
            for s, nd in zip(xy_specs, (np.ndim(x), np.ndim(y)))]
        # mesh axes sharding the INPUTS: a param replicated over such an
        # axis saw only that rank's batch slice — its grad is partial and
        # gets psum'd; axes in the param's own spec hold distinct shards
        input_axes = set()
        for s in xy_specs:
            for a in s:
                if a is not None:
                    input_axes.update(a if isinstance(a, tuple) else (a,))
        grad_psum_axes = [
            tuple(sorted(input_axes - set(_axes(sp)))) for sp in p_specs]

        self.partitioner = Partitioner(mesh, self._strategy.cluster)
        model, loss_fn = self._model, self._loss
        saved = [p.data for p in params]

        def flat(*argv):
            # argv = param arrays..., x, y, rng key (key per STEP — a
            # baked trace-time key would freeze dropout masks)
            try:
                for p, a in zip(params, argv[:n]):
                    p.data = a
                with tape.no_grad(), frnd.key_scope(argv[n + 2]):
                    out = model(Tensor(argv[n]))
                    return loss_fn(out, Tensor(argv[n + 1])).data
            finally:
                for p, s in zip(params, saved):
                    p.data = s

        example = [p.data for p in params] + [x, y, frnd.next_key()]
        local_loss = self.partitioner.partition(
            flat, example, p_specs + xy_specs + [()])

        def step(parrs, xx, yy, key):
            def loss_of(pa):
                return local_loss(*pa, xx, yy, key)

            lv, grads = jax.value_and_grad(loss_of)(list(parrs))
            new = []
            for a, g, axes in zip(parrs, grads, grad_psum_axes):
                for ax in axes:
                    g = jax.lax.psum(g, ax)
                new.append(a - lr * g)
            return new, lv

        in_specs = ([P(*s) for s in p_specs],
                    P(*xy_specs[0]), P(*xy_specs[1]), P())
        out_specs = ([P(*s) for s in p_specs], P())
        smapped = shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)
        return jax.jit(smapped)

    def _build(self):
        params = self._params or list(self._model.parameters())
        model, loss_fn = self._model, self._loss
        lr = self._optimizer.get_lr() if self._optimizer else 1e-3
        mesh = self._process_mesh
        in_pl = self._input_placements

        def step(parrs, x, y, key):
            saved = [p.data for p in params]
            for p, a in zip(params, parrs):
                p.data = a
            try:
                if mesh is not None and in_pl:
                    x = jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh.jax_mesh, P(*in_pl[0])))

                def compute(arrs):
                    for p, a in zip(params, arrs):
                        p.data = a
                    with tape.no_grad(), frnd.key_scope(key):
                        out = model(Tensor(x))
                        l = loss_fn(out, Tensor(y))
                    return l.data

                lv, grads = jax.value_and_grad(compute)(list(parrs))
                new = [a - lr * g for a, g in zip(parrs, grads)]
                return new, lv
            finally:
                for p, s in zip(params, saved):
                    p.data = s

        return jax.jit(step)

    def fit(self, train_data, epochs=1, batch_size=1, steps_per_epoch=None,
            log_freq=10, verbose=1):
        from ...io import DataLoader, Dataset
        loader = DataLoader(train_data, batch_size=batch_size) \
            if isinstance(train_data, Dataset) else train_data
        params = self._params or list(self._model.parameters())
        first_epoch_iter = None
        full = self._strategy.auto_mode == "full"
        if self._jitted is None:
            # peek the first batch for tracing, then CHAIN it back so
            # one-shot iterators don't silently lose it
            import itertools
            it = iter(loader)
            first = next(it)
            first_epoch_iter = itertools.chain([first], it)
            if self.completed_param_specs is None:
                self._complete_and_place(first[0].data, first[1].data)
            if full:
                self._jitted = self._build_full(first[0].data,
                                                first[1].data)
            else:
                self._jitted = self._build()
        parrs = [p.data for p in params]
        history = []
        for epoch in range(epochs):
            epoch_iter = (first_epoch_iter if epoch == 0 and
                          first_epoch_iter is not None else loader)
            for step_i, batch in enumerate(epoch_iter):
                x, y = batch[0], batch[1]
                parrs, lv = self._jitted(parrs, x.data, y.data,
                                         frnd.next_key())
                if steps_per_epoch and step_i + 1 >= steps_per_epoch:
                    break
            history.append(float(jax.device_get(lv)))
            if verbose:
                print(f"[auto_parallel] epoch {epoch}: loss={history[-1]:.4f}")
        for p, a in zip(params, parrs):
            p.data = a
        return history

    def evaluate(self, eval_data, batch_size=1, steps=None):
        from ...io import DataLoader, Dataset
        loader = DataLoader(eval_data, batch_size=batch_size) \
            if isinstance(eval_data, Dataset) else eval_data
        losses = []
        with tape.no_grad():
            for i, batch in enumerate(loader):
                out = self._model(batch[0])
                losses.append(float(self._loss(out, batch[1]).numpy()))
                if steps and i + 1 >= steps:
                    break
        return {"loss": float(np.mean(losses))}


def to_static(layer, loader=None, loss=None, optimizer=None, strategy=None):
    return Engine(layer, loss, optimizer, strategy=strategy)
