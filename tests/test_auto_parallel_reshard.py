"""Auto-parallel Resharder (VERDICT r2 item 6; ref:
auto_parallel/reshard.py:1007): explicit collective chains converting one
sharding to another inside SPMD regions, conflict detection in the
Completer, and the keep-the-larger-operand-in-place cost rule."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed.auto_parallel.reshard import (
    ReshardRecord, plan_conflict, reshard_spec)


def _mesh(n=4, name="x"):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (name,))


def _run_sharded(fn, mesh, in_spec, out_spec, *args):
    return shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                     check_vma=False)(*args)


def test_row_to_col_uses_all_to_all_and_matches():
    """Row-sharded producer feeding a column-sharded consumer: the
    Resharder must move the mesh axis between dims with ONE all_to_all."""
    mesh = _mesh(4)
    a = jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8)
    rec = ReshardRecord()

    def f(x):  # x arrives row-sharded [4, 8]; leave column-sharded [16, 2]
        return reshard_spec(x, ("x", None), (None, "x"), record=rec)

    out = _run_sharded(f, mesh, (P("x", None),), P(None, "x"), a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
    assert [r["op"] for r in rec] == ["all_to_all"], rec


def test_shard_to_replicated_gathers():
    mesh = _mesh(4)
    a = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4)
    rec = ReshardRecord()

    def f(x):
        return reshard_spec(x, ("x", None), (None, None), record=rec)

    out = _run_sharded(f, mesh, (P("x", None),), P(), a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
    assert [r["op"] for r in rec] == ["all_gather"], rec


def test_replicated_to_shard_is_free_slice():
    mesh = _mesh(4)
    a = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4)
    rec = ReshardRecord()

    def f(x):
        return reshard_spec(x, (None, None), ("x", None), record=rec)

    out = _run_sharded(f, mesh, (P(),), P("x", None), a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
    assert [r["op"] for r in rec] == ["slice"], rec


def test_partial_to_sharded_reduce_scatters():
    """Partial sums (e.g. a row-parallel matmul's output before its
    reduction) reshard to a sharded layout with ONE psum_scatter."""
    mesh = _mesh(4)
    a = jnp.ones((8, 4), jnp.float32)
    rec = ReshardRecord()

    def f(x):
        # x is replicated-in, treated as a partial term per rank
        return reshard_spec(x, (None, None), ("x", None),
                            partial_axes=("x",), record=rec)

    out = _run_sharded(f, mesh, (P(),), P("x", None), a)
    np.testing.assert_allclose(np.asarray(out), 4.0 * np.ones((8, 4)))
    assert [r["op"] for r in rec] == ["psum_scatter"], rec


def test_end_to_end_row_producer_col_consumer_matmul():
    """Numeric parity: producer computes row-sharded h = x @ w1; consumer
    needs h column-sharded to do a column-parallel h @ w2. Compare against
    the dense computation."""
    mesh = _mesh(4)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 8), jnp.float32)
    w1 = jnp.asarray(rng.randn(8, 8), jnp.float32)
    w2 = jnp.asarray(rng.randn(8, 12), jnp.float32)

    def f(x_loc, w1, w2):
        h = x_loc @ w1                         # row-sharded [4, 8]
        h = reshard_spec(h, ("x", None), (None, "x"))  # col-sharded [16, 2]
        w2_loc = lax.dynamic_slice_in_dim(
            w2, lax.axis_index("x") * (w2.shape[0] // 4),
            w2.shape[0] // 4, axis=0)
        part = h @ w2_loc                      # partial over 'x'
        return lax.psum(part, "x")

    out = _run_sharded(f, mesh, (P("x", None), P(), P()), P(), x, w1, w2)
    ref = (x @ w1) @ w2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_dim_swap_reshard_matches_and_breaks_cycle():
    """ADVICE r3 medium: src ('x','y') -> dst ('y','x') is a move CYCLE —
    naive per-axis all_to_all clobbers the tracked spec (crash or wrong
    chain). The Resharder must break the cycle (gather one blocker, then
    move, then re-slice) and produce the right global array."""
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("x", "y"))
    a = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
    rec = ReshardRecord()

    def f(x):
        return reshard_spec(x, ("x", "y"), ("y", "x"), record=rec)

    out = shard_map(f, mesh=mesh, in_specs=(P("x", "y"),),
                    out_specs=P("y", "x"), check_vma=False)(a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
    ops = [r["op"] for r in rec]
    assert "all_gather" in ops and "slice" in ops, rec


def test_partial_dst_dim_occupied_then_freed():
    """A single axis move whose destination dim is occupied by an axis
    that itself moves away: drains in dependency order with NO gather.
    src ('x','y',None) -> dst (None,'x','y'): move y 1->2 first (dst dim
    free), then x 0->1."""
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("x", "y"))
    a = jnp.arange(4 * 4 * 4, dtype=jnp.float32).reshape(4, 4, 4)
    rec = ReshardRecord()

    def f(x):
        return reshard_spec(x, ("x", "y", None), (None, "x", "y"), record=rec)

    out = shard_map(f, mesh=mesh, in_specs=(P("x", "y", None),),
                    out_specs=P(None, "x", "y"), check_vma=False)(a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
    assert [r["op"] for r in rec] == ["all_to_all", "all_to_all"], rec


def test_partial_into_already_sharded_dim_merges_spec():
    """A partial axis reduced (psum_scatter) into a dim that is ALREADY
    sharded: the tracked spec must merge — not overwrite — so the
    co-sharding axis still gets moved/resolved afterwards.
    src ('x', None) + partial 'y' -> dst ('y', 'x')."""
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("x", "y"))
    a = jnp.ones((8, 8), jnp.float32)
    rec = ReshardRecord()

    def f(x):
        return reshard_spec(x, ("x", None), ("y", "x"),
                            partial_axes=("y",), record=rec)

    out = shard_map(f, mesh=mesh, in_specs=(P("x", None),),
                    out_specs=P("y", "x"), check_vma=False)(a)
    # each rank contributed ones as a partial term over 'y' (size 2)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones((8, 8)))
    assert rec[0]["op"] == "psum_scatter", rec


def test_tuple_entry_falls_back_to_canonical_chain():
    """A dim sharded by TWO mesh axes at once: partial moves would corrupt
    the nested tiling, so the Resharder takes the canonical gather-then-
    reslice chain and still produces the right global array."""
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("x", "y"))
    a = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    rec = ReshardRecord()

    def f(x):
        return reshard_spec(x, (("x", "y"), None), ("x", "y"), record=rec)

    out = shard_map(f, mesh=mesh, in_specs=(P(("x", "y"), None),),
                    out_specs=P("x", "y"), check_vma=False)(a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
    ops = [r["op"] for r in rec]
    assert ops[:2] == ["all_gather", "all_gather"], rec
    assert ops.count("slice") == 2, rec


def test_completer_records_conflicts():
    from paddle_tpu.distributed.auto_parallel.completion import Completer

    mesh = _mesh(4)

    def f(a, b):
        return a + b

    x = jnp.zeros((8, 8))
    comp = Completer(mesh)
    comp.complete(f, (x, x), {0: ("x", None), 1: (None, "x")})
    assert comp.conflicts, "conflicting elementwise shardings not detected"
    shape, old, new = comp.conflicts[0]
    assert shape == (8, 8) and old != new


def test_plan_conflict_keeps_larger_in_place():
    ms = {"x": 4}
    # a is tiny, b is huge: move a
    assert plan_conflict((8, 8), ("x", None), (4096, 4096), (None, "x"),
                         mesh_shape=ms) == "a"
    assert plan_conflict((4096, 4096), ("x", None), (8, 8), (None, "x"),
                         mesh_shape=ms) == "b"
