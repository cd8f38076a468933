"""The stage-1/2 AdamW update in the parameters' own shape (PR 35).

`train_step.moment_axis` is the ONE rule for where a local block's moments
live: the whole block where nothing shards them, else the owned slice
along the first axis the 'sharding' degree divides, else padded flat
chunks. These tests hold the trainer to it on CPU meshes:

(a) N steps at sharding 1 / 2 / 4 x model 1 / 2 (and a pipe 2 case) equal
    a plain param-shaped AdamW written HERE, through `canonical_state`:
    bit-equal at 1 and 2 (two float32 addends commute), float32 rounding
    at 4. The step's summed gradient is read off a second trainer with
    beta1 = 0 and zero float32 moments, whose first moment after one step
    IS the gradient.
(b) a block no axis of which the degree divides takes the flat fallback,
    one whose axis 0 does not divide but another does takes that axis.
(c) `canonical_state` -> `state_from_canonical` round trip, a saved
    checkpoint and a cross-mesh restore 2 x 2 -> 4 x 1 -> 1.
(d) the step's jaxpr at both cells' meshes: under the `optimizer` and
    `grad_sync` scopes no rank-1 reshape of a parameter-sized operand and
    no concatenate, the reduce-to-owner scatters a shaped operand, and the
    updated slices return into the donated block in place.
(e) the layout counter of both cells' configurations.
(f) `grad_compress="int8"` converges on both kinds of axis.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.train_step import FLAT, SpmdTrainer, moment_axis

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
if PERF not in sys.path:
    sys.path.insert(0, PERF)

LR, B1, B2, EPS, WD = 1e-2, 0.9, 0.95, 1e-8, 0.01
F32 = jnp.float32


def _axes(**kw):
    return {"data": 1, "pipe": 1, "sharding": 1, "model": 1, **kw}


def _trainer(axes, cfg, betas=(B1, B2), **kw):
    paddle.seed(5)
    model = LlamaForCausalLM(cfg)
    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, devices=jax.devices()[:n])
    set_global_mesh(mesh)
    return SpmdTrainer(model, mesh, lr=LR, betas=betas, eps=EPS,
                       weight_decay=WD, **kw)


def _data(cfg, bs=4, seq=16):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int64)
    return ids, np.roll(ids, -1, axis=1)


# ---- the plain reference: AdamW on global, param-shaped arrays -------------
@functools.partial(jax.jit, static_argnames=("mdt",))
def plain_adamw(p, g, m, v, step, lr, mdt):
    """One tensor, its own shape, float32 inside: what every layout of
    the trainer has to equal."""
    m = B1 * m.astype(F32) + (1 - B1) * g
    v = B2 * v.astype(F32) + (1 - B2) * g * g
    t = step.astype(F32)
    mhat = m / (1 - B1 ** t)
    vhat = v / (1 - B2 ** t)
    pn = p.astype(F32) * (1 - lr * WD) - lr * mhat / (jnp.sqrt(vhat) + EPS)
    return pn.astype(p.dtype), m.astype(mdt), v.astype(mdt)


def _leaves(canon):
    """[(params leaf, m, v)] of a canonical state, as numpy."""
    out = []
    for kind in ("outer", "stacked"):
        for p, ent in zip(canon["params"][kind], canon["opt"][kind]):
            out.append([np.asarray(p), np.asarray(ent["m"]),
                        np.asarray(ent["v"])])
    return out


def _gradient(probe, state, batch, key):
    """The summed gradient of `state`'s parameters in the trainer's raw
    convention, leaf by leaf: a beta1 = 0 trainer on the same mesh steps
    from the same parameters with zero float32 moments."""
    st = {"params": jax.tree_util.tree_map(jnp.copy, state["params"]),
          "opt": jax.tree_util.tree_map(
              lambda a: jax.device_put(jnp.zeros(a.shape, F32), a.sharding),
              state["opt"]),
          "step": jnp.copy(state["step"])}
    st, _ = probe.step(st, *batch, key=key)
    f = float(probe._batch_rank_factor())
    return [m * f for _, m, _ in _leaves(probe.canonical_state(st))]


def _follow(axes, cfg, steps, exact, **kw):
    """Step the trainer beside the plain reference; compare the canonical
    state after every step."""
    tr = _trainer(axes, cfg, **kw)
    probe = _trainer(axes, cfg, betas=(0.0, B2),
                     **{**kw, "moment_dtype": "float32"})
    batch = _data(cfg)
    state = tr.init_state()
    f = float(tr._batch_rank_factor())
    ref = _leaves(tr.canonical_state(state))        # raw moments: zeros
    lr = jnp.asarray(LR, F32)
    for i in range(steps):
        key = jax.random.key(i)
        grads = _gradient(probe, state, batch, key)
        state, loss = tr.step(state, *batch, key=key)
        assert np.isfinite(float(loss))
        step = jnp.asarray(i + 1, jnp.int32)
        ref = [[np.asarray(x) for x in plain_adamw(
            p, g, m, v, step, lr, mdt=jnp.dtype(m.dtype))]
            for (p, m, v), g in zip(ref, grads)]
        got = _leaves(tr.canonical_state(state))
        for j, ((p, m, v), (gp, gm, gv)) in enumerate(zip(ref, got)):
            # canonical moments are in the global-mean convention
            m = (m.astype(np.float32) / f).astype(m.dtype)
            v = (v.astype(np.float32) / (f * f)).astype(v.dtype)
            for name, want, have in (("p", p, gp), ("m", m, gm),
                                     ("v", v, gv)):
                assert want.shape == have.shape and want.dtype == have.dtype
                if exact:
                    np.testing.assert_array_equal(
                        have, want, err_msg=f"step {i} leaf {j} {name}")
                else:
                    np.testing.assert_allclose(
                        np.asarray(have, np.float32),
                        np.asarray(want, np.float32), rtol=2e-5, atol=1e-7,
                        err_msg=f"step {i} leaf {j} {name}")
    return tr


TWO_LAYERS = dict(num_hidden_layers=2)


@pytest.mark.parametrize("shard,model", [(1, 1), (1, 2), (2, 1), (2, 2)])
def test_update_is_bit_equal_to_plain_adamw(shard, model):
    tr = _follow(_axes(sharding=shard, model=model),
                 LlamaConfig.tiny(**TWO_LAYERS), steps=3, exact=True)
    lay = tr.update_layout()
    assert lay["in_shape_share"] == 1.0 and lay["tensors_flat"] == 0
    assert lay["axis0_share"] == (1.0 if shard > 1 else 0.0)


@pytest.mark.parametrize("model", [1, 2])
def test_update_at_four_shards_to_float32_rounding(model):
    """A reduce-scatter over four ranks may sum in another order."""
    tr = _follow(_axes(sharding=4, model=model),
                 LlamaConfig.tiny(**TWO_LAYERS), steps=2, exact=False)
    lay = tr.update_layout()
    # two layers a stack: axis 0 does not divide by four, a later axis does
    assert lay["tensors_flat"] == 0 and lay["tensors_other_axis"] > 0
    assert lay["tensors_axis0"] > 0             # vocabulary rows, hidden


def test_update_with_pipeline_stages():
    """pipe 2 x sharding 2: a stage's stack holds one layer, so its owned
    slice runs along an axis of the layer's own block. Held to float32
    rounding: where the in-place write of a slice along a LATER axis
    fuses with the update, the CPU backend contracts one multiply-add of
    the moments otherwise (1 ulp; the same gradient sum, two addends)."""
    tr = _follow(_axes(pipe=2, sharding=2), LlamaConfig.tiny(**TWO_LAYERS),
                 steps=2, exact=False, micro_batch_size=1)
    lay = tr.update_layout()
    assert lay["tensors_flat"] == 0 and lay["tensors_other_axis"] > 0


def test_update_in_the_cells_dtypes():
    """bf16 parameters and moments, float32 inside (both cells')."""
    _follow(_axes(sharding=2, model=2), LlamaConfig.tiny(**TWO_LAYERS),
            steps=2, exact=True, param_dtype="bfloat16",
            moment_dtype="bfloat16")


# ---- (b) the rule, and its fallback ----------------------------------------
@pytest.mark.parametrize("shape,S,want", [
    ((18, 2048, 8192), 1, None),        # nothing shards it: the block
    ((), 1, None),
    ((8, 4096, 7168), 2, 0),            # the layers of this stage
    ((46272, 4096), 2, 0),              # vocabulary rows
    ((4096,), 2, 0),
    ((2, 64, 128), 4, 1),               # axis 0 does not divide, axis 1 does
    ((3, 66, 128), 4, 2),
    ((2, 66, 66), 4, FLAT),             # no axis divides
    ((66,), 4, FLAT),
    ((), 2, FLAT),
    ((0, 8), 4, 1),                     # an empty axis owns nothing
])
def test_moment_axis_rule(shape, S, want):
    assert moment_axis(shape, S) == want


# hidden 66 = 3 heads x 22: no multiple of four anywhere but the
# intermediate width (128) and the vocabulary (128)
ODD = dict(num_hidden_layers=2, hidden_size=66, num_attention_heads=3,
           intermediate_size=128, vocab_size=128)


def test_flat_fallback_still_matches_and_is_counted():
    tr = _follow(_axes(sharding=4), LlamaConfig.tiny(**ODD), steps=2,
                 exact=False)
    layouts = tr._moment_layouts()
    by_axis = [lay["axis"] for kind in ("outer", "stacked")
               for lay in layouts[kind]]
    assert FLAT in by_axis                      # [2, 66, 66], [66]
    assert 0 in by_axis                         # the embedding's 128 rows
    assert any(k not in (None, 0, FLAT) for k in by_axis)   # [2, 66, 128]
    for kind in ("outer", "stacked"):
        for lay in layouts[kind]:
            assert lay["axis"] == moment_axis(lay["block"], 4)
            if lay["axis"] == FLAT:
                assert len(lay["local"]) == 1
                assert lay["local"][0] * 4 >= int(np.prod(lay["block"]))
    lay = tr.update_layout()
    assert lay["tensors_flat"] == by_axis.count(FLAT)
    assert 0 < lay["flat_share"] < 1
    assert lay["in_shape_share"] == pytest.approx(1 - lay["flat_share"])
    assert lay["axis0_share"] + lay["other_axis_share"] == pytest.approx(
        lay["in_shape_share"])
    # _build recorded what update_layout() says
    assert profiler.counter_history("trainer")[-1][1] == lay


# ---- the moments as the state holds them ------------------------------------
def _placed_as(x, spec, mesh):
    """x (an array or its abstract shape) is sharded as `spec` says (jit
    drops a mesh axis of size 1 from a result's spec: compare meanings)."""
    from jax.sharding import NamedSharding
    return x.sharding.is_equivalent_to(NamedSharding(mesh, spec), x.ndim)


@pytest.mark.parametrize("axes,kw", [
    (_axes(sharding=2, model=2), {}),
    (_axes(data=2, sharding=2, model=2), {}),
    (_axes(pipe=2, sharding=2), {"micro_batch_size": 1}),
    (_axes(sharding=4), {}),
    (_axes(model=2), {}),
])
def test_moment_specs_tell_the_truth(axes, kw):
    """A moment is GLOBALLY param-shaped, its parameter's spec with
    'sharding' joined to the owned axis; init_state, abstract_state and
    the step agree; no spec names an axis the value does not vary over."""
    tr = _trainer(axes, LlamaConfig.tiny(**TWO_LAYERS), **kw)
    state = tr.init_state()
    abstract = tr.abstract_state()
    layouts = tr._moment_layouts()
    pspecs = tr._param_specs12()
    for kind in ("outer", "stacked"):
        for p, ent, ab, lay, ps in zip(
                state["params"][kind], state["opt"][kind],
                abstract["opt"][kind], layouts[kind], pspecs[kind]):
            for k in ("m", "v"):
                assert ent[k].shape == p.shape == ab[k].shape
                assert _placed_as(ent[k], lay["spec"], tr.mesh)
                assert _placed_as(ab[k], lay["spec"], tr.mesh)
                assert ent[k].addressable_shards[0].data.shape == \
                    lay["local"]
            named = [a for e in lay["spec"] if e is not None
                     for a in (e if isinstance(e, tuple) else (e,))]
            assert "data" not in named
            if lay["axis"] is None:
                assert lay["spec"] == ps
            else:
                assert named.count("sharding") == 1
                e = lay["spec"][lay["axis"]]
                assert (e if isinstance(e, tuple) else (e,))[-1] == \
                    "sharding"
    ids, labels = _data(LlamaConfig.tiny(**TWO_LAYERS))
    after, _ = tr.step(state, ids, labels, key=jax.random.key(0))
    for kind in ("outer", "stacked"):
        for ent, lay in zip(after["opt"][kind], layouts[kind]):
            assert _placed_as(ent["m"], lay["spec"], tr.mesh)


# ---- (c) canonical form, checkpoints, other meshes -------------------------
def _run(tr, st, batch, lo, hi):
    out = []
    for i in range(lo, hi):
        st, loss = tr.step(st, *batch, key=jax.random.key(i))
        out.append(float(loss))
    return st, out


def test_canonical_round_trip_is_the_identity():
    cfg = LlamaConfig.tiny(**ODD)       # flat, axis 0 and another axis
    tr = _trainer(_axes(sharding=4), cfg)
    st, _ = _run(tr, tr.init_state(), _data(cfg), 0, 2)
    canon = tr.canonical_state(st)
    # the contract: global, param-shaped, logical layer order
    for t, p, ent in zip(tr.outer_tensors, canon["params"]["outer"],
                         canon["opt"]["outer"]):
        assert p.shape == ent["m"].shape == ent["v"].shape == \
            tuple(t.shape)
    for t, p, ent in zip(tr.layer_param_tensors,
                         canon["params"]["stacked"],
                         canon["opt"]["stacked"]):
        assert p.shape == ent["m"].shape == (2,) + tuple(t.shape)
    again = tr.canonical_state(tr.state_from_canonical(canon))
    for a, b in zip(jax.tree_util.tree_leaves(canon),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cross_mesh_restore_2x2_to_4x1_to_1(tmp_path):
    cfg = LlamaConfig.tiny(**TWO_LAYERS)
    batch = _data(cfg)
    ref = _trainer(_axes(), cfg)
    _, base = _run(ref, ref.init_state(), batch, 0, 6)

    a = _trainer(_axes(sharding=2, model=2), cfg)
    st, part = _run(a, a.init_state(), batch, 0, 2)
    a.save_checkpoint(st, str(tmp_path / "a"), step=2)

    b = _trainer(_axes(sharding=4), cfg)
    st, index = b.load_checkpoint(str(tmp_path / "a"))
    assert index["step"] == 2
    for ent, lay in zip(st["opt"]["stacked"],
                        b._moment_layouts()["stacked"]):
        assert _placed_as(ent["m"], lay["spec"], b.mesh)
        assert ent["m"].addressable_shards[0].data.shape == lay["local"]
    st, mid = _run(b, st, batch, 2, 4)
    b.save_checkpoint(st, str(tmp_path / "b"), step=4)

    c = _trainer(_axes(), cfg)
    st, index = c.load_checkpoint(str(tmp_path / "b"))
    assert index["step"] == 4
    _, rest = _run(c, st, batch, 4, 6)
    np.testing.assert_allclose(part + mid + rest, base, rtol=2e-5)


# ---- (d) what the step program holds ---------------------------------------
def _cell_config(name):
    from harness import manifest
    cfg = manifest.load_json(f"perf/configs/{name}.json")
    return cfg, manifest.load_plugin("references", cfg["reference"])


def _cell_trainer(name, **sizes):
    cfg, family = _cell_config(name)
    cfg.update(sizes)
    degrees = cfg["training"]["mesh"]
    mesh = build_mesh(degrees, devices=jax.devices()[
        :int(np.prod(list(degrees.values())))])
    set_global_mesh(mesh)
    return SpmdTrainer(family.build_model(cfg, 0), mesh,
                       **cfg["training"]["trainer"])


def _equations(jaxpr, prefix=""):
    """(primitive, whole name stack, equation) of a jaxpr and of every
    jaxpr inside it (an inner one's stacks are relative to its call)."""
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, stack, eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, stack)


WIDTHS = dict(hidden_size=64, intermediate_size=128, vocab_size=128,
              num_attention_heads=4, num_key_value_heads=2,
              max_position_embeddings=64)


@pytest.mark.parametrize("cell,layers", [("internlm2-1_8b-train", 18),
                                         ("internlm2-7b-l8-train", 8)])
def test_step_program_updates_in_shape(cell, layers):
    """Both cells' meshes and depths at tiny widths: the update holds no
    flattened copy and no padding, and ZeRO's reduce-to-owner scatters
    the block in its own shape."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tr = _cell_trainer(cell, **WIDTHS)
    assert tr.n_layers == layers
    mesh, shape = tr.mesh, (2, 32)
    batch = P(tuple(a for a in ("data", "sharding")
                    if mesh.shape[a] > 1) or None)
    ids = jax.ShapeDtypeStruct(shape, jnp.int32,
                               sharding=NamedSharding(mesh, batch))
    rep = NamedSharding(mesh, P())
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    jaxpr = jax.make_jaxpr(tr._build(shape))(
        tr.abstract_state(), ids, ids, key, lr)
    smallest = min(int(np.prod(lay["block"])) for lays in
                   tr._moment_layouts().values() for lay in lays)
    update = [(prim, eqn) for prim, stack, eqn in _equations(jaxpr.jaxpr)
              if "optimizer" in stack.split("/")
              or "grad_sync" in stack.split("/")]
    assert len(update) > 10 * len(tr.layer_param_tensors), \
        "the update's equations were not found"
    prims = {prim for prim, _ in update}
    assert "concatenate" not in prims and "pad" not in prims
    for prim, eqn in update:
        if prim == "reshape":
            out = eqn.outvars[0].aval
            assert not (out.ndim == 1 and out.size >= smallest), eqn
    scatters = [eqn for prim, eqn in update if prim == "reduce_scatter"]
    if tr.S_shard == 1:
        assert not scatters and not prims & {"all_gather", "ppermute"}
    else:
        assert len(scatters) == len(tr.outer_tensors) \
            + len(tr.layer_param_tensors)
        for eqn, lay in zip(scatters, sum(
                tr._moment_layouts().values(), [])):
            operand = eqn.invars[0].aval
            assert operand.shape == lay["block"]
            assert operand.dtype == jnp.float32     # the sum stays float32
            assert eqn.params["scatter_dimension"] == lay["axis"] == 0
        # the updated slices go back into the donated block in place:
        # this rank's and, by collective-permute, the other rank's
        assert "all_gather" not in prims
        n = len(scatters)
        assert sum(prim == "ppermute" for prim, _ in update) == n
        assert sum(prim == "dynamic_update_slice"
                   for prim, _ in update) == 2 * n


# ---- (e) the counter, for the cells as the benchmark builds them -----------
@pytest.mark.parametrize("cell,axis0", [("internlm2-1_8b-train", 0.0),
                                        ("internlm2-7b-l8-train", 1.0)])
def test_layout_counter_of_the_cells(cell, axis0):
    """Full width and depth (a LazyGuard model: nothing is materialized)."""
    tr = _cell_trainer(cell)
    lay = tr.update_layout()
    assert lay["in_shape_share"] == 1.0
    assert lay["flat_share"] == 0.0 and lay["tensors_flat"] == 0
    assert lay["axis0_share"] == axis0 and lay["other_axis_share"] == 0.0
    assert lay["tensors"] == len(tr.outer_tensors) + len(
        tr.layer_param_tensors)


def test_stage3_counts_as_flat():
    """Stage 3 stores flat chunks (the chunks ARE the parameters)."""
    tr = _trainer(_axes(sharding=2), LlamaConfig.tiny(**TWO_LAYERS),
                  sharding_stage=3)
    assert tr.update_layout()["flat_share"] == 1.0


# ---- (f) the int8-compressed variant shares the scaffold -------------------
@pytest.mark.parametrize("axes,sizes", [
    (_axes(data=2, sharding=2), TWO_LAYERS),    # every tensor along axis 0
    (_axes(sharding=4), ODD),                   # axis 0, another axis, flat
])
def test_int8_compressed_update_converges(axes, sizes):
    cfg = LlamaConfig.tiny(**sizes)
    batch = _data(cfg, bs=8)
    finals = {}
    for name, kw in (("exact", {}), ("int8", {"grad_compress": "int8"})):
        tr = _trainer(axes, cfg, **kw)
        st = tr.init_state()
        losses = []
        for _ in range(6):
            st, loss = tr.step(st, *batch, key=jax.random.key(3))
            losses.append(float(loss))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], \
            (name, losses)
        finals[name] = losses[-1]
        if name == "int8":      # the residual mirrors the parameters
            for e, p in zip(jax.tree_util.tree_leaves(st["ef"]),
                            jax.tree_util.tree_leaves(st["params"])):
                assert e.shape == p.shape and e.dtype == jnp.float32
    rel = abs(finals["int8"] - finals["exact"]) / abs(finals["exact"])
    assert rel < 0.05, finals
