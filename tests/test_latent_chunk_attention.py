"""The latent chunk attention kernel (`ops/pallas/chunk_attention.
paged_latent_chunk_attention`, interpret mode) against the XLA key blocks
it replaces (`latent_attention.attend_key_blocks` over `latent.key_blocks`,
the same bf16 rows and queries), at reduced widths: full layers masked by
a selection, window layers by their window, chunks that start mid-page
or end inside, walks that clamp past a block's last query. Rows at or
past t_end are padding, garbage by contract, and are not compared.

The bound is that of bf16 products with float32 sums (the kernel sweep's
2.5e-3 relative, as `paged_chunk_attention` is held to): both sides take
the same bf16 operands and differ in where the running maximum is taken
and in the bf16 rounding of the softmax weights.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (config init)
from paddle_tpu.inference import latent
from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops.pallas import chunk_attention as ca

HEADS, ROW, RANK, P, CHUNK, MP = 8, 256, 128, 16, 32, 24
REL_TOL = 2.5e-3


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6))


def _case(seed, q_start, t_end, window, select, n_pages=48):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(n_pages, P, ROW)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(CHUNK, HEADS, ROW)) * 0.5,
                    jnp.bfloat16)
    # the sequence's pages scattered over the pool; columns past what it
    # holds point at no page
    tab = np.full(MP, n_pages + 5, np.int32)
    held = -(-t_end // P)
    tab[:held] = rng.permutation(n_pages)[:held]
    pos = q_start + jnp.arange(CHUNK, dtype=jnp.int32)
    chosen = None
    if select:
        width = latent.selection_width(MP, P)
        # a scattered subset: each query keeps about a third of its keys
        chosen = jnp.asarray(rng.random((CHUNK, width)) < 0.35)
    return rows, q, jnp.asarray(tab), pos, chosen


def _reference(q, rows, tab, pos, t_end, window, chosen, scale):
    block, lo, hi = latent.key_blocks(rows, tab, pos, jnp.int32(t_end), P,
                                      window, chosen)
    return la.attend_key_blocks(q, block, lo, hi, RANK, scale)


# (q_start, t_end, window, select, plan (tq, pages a step) or None = the
#  shape's own)
CASES = {
    "full_starts_mid_page": (21, 21 + CHUNK, None, True, (8, 2)),
    "full_ends_inside_the_chunk": (16, 16 + 19, None, True, (16, 1)),
    "full_many_pages_walk_clamps": (300, 300 + CHUNK, None, True, (8, 4)),
    "full_scattered_selection_own_plan": (77, 77 + CHUNK, None, True,
                                          None),
    "window_first_key_mid_page": (150, 150 + CHUNK, 37, False, (8, 1)),
    "window_ends_inside_the_chunk": (100, 100 + 9, 37, False, (16, 2)),
    "window_at_the_start_own_plan": (0, CHUNK, 37, False, None),
    "window_one_step_a_block": (64, 64 + CHUNK, 37, False, (8, 4)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_the_key_blocks(name):
    q_start, t_end, window, select, geo = CASES[name]
    rows, q, tab, pos, chosen = _case(len(name), q_start, t_end, window,
                                      select)
    scale = 0.07
    plan = None
    if geo is not None:
        plan = {"tq": geo[0], "pages_per_step": geo[1],
                "vmem_limit_bytes": 16 << 20}
    got = jax.jit(lambda q, rows, tab, ch: ca.paged_latent_chunk_attention(
        q, rows, tab, q_start, t_end, RANK, scale, window=window,
        chosen=ch, plan=plan, interpret=True))(q, rows, tab, chosen)
    want = _reference(q, rows, tab, pos, t_end, window, chosen, scale)
    real = t_end - q_start
    assert got.shape == (CHUNK, HEADS, RANK) and got.dtype == jnp.bfloat16
    err = _rel(got[:real], want[:real])
    assert err < REL_TOL, err
    if geo is not None:
        # the steps the host books lie inside the kernel's grid
        tq, kp = geo
        steps = ca.latent_live_steps(q_start, t_end, CHUNK, P, tq, kp,
                                     window)
        assert 0 < steps <= (CHUNK // tq) * ca.latent_walk_steps(
            P, tq, kp, window, MP)


def test_a_selection_that_drops_a_key_changes_the_result():
    """The mask operand is read: the kernel over a selection and over the
    same selection less one chosen key of one query part where the
    reference parts."""
    q_start, t_end = 40, 40 + CHUNK
    rows, q, tab, pos, chosen = _case(3, q_start, t_end, None, True)
    chosen = chosen & (jnp.arange(chosen.shape[1])[None, :] <= pos[:, None])
    t, key = 5, int(np.nonzero(np.asarray(chosen[5]))[0][-1])
    fewer = chosen.at[t, key].set(False)
    run = jax.jit(lambda ch: ca.paged_latent_chunk_attention(
        q, rows, tab, q_start, t_end, RANK, 1.0, chosen=ch,
        interpret=True))
    a, b = np.asarray(run(chosen), np.float32), np.asarray(run(fewer),
                                                           np.float32)
    assert np.array_equal(np.delete(a, t, 0), np.delete(b, t, 0))
    assert not np.allclose(a[t], b[t])


@pytest.mark.parametrize("geometry,expect", [
    # the cell's two geometries: a full layer of 128 heads x 640 / 512
    # with its selection, a window layer of 64 heads x 1,152 / 1,024
    ((512, 128, 640, 512, 128, None, 18432), (16, 4)),
    ((512, 64, 1152, 1024, 128, 513, None), (16, 6)),
])
def test_the_plan_is_drawn_from_the_shape(geometry, expect):
    chunk, h, row, rank, p, window, width = geometry
    plan = ca.latent_plan(chunk, h, row, rank, p, jnp.bfloat16, window,
                          width)
    assert (plan["tq"], plan["pages_per_step"]) == expect
    assert 16 << 20 <= plan["vmem_limit_bytes"] <= 100 << 20


@pytest.mark.parametrize("shape", [
    (512, 128, 576, 512, 128),      # a row that is no whole lane tile
    (512, 128, 640, 500, 128),      # values that are no whole lane tile
    (512, 4, 128, 16, 8),           # the CPU tests' widths
    (512, 12, 640, 512, 128),       # heads that are no sublane tile
])
def test_a_shape_mosaic_cannot_tile_takes_the_key_blocks(shape):
    chunk, h, row, rank, p = shape
    assert ca.latent_plan(chunk, h, row, rank, p, jnp.bfloat16) is None
    # under interpret every shape is the kernel's
    assert ca.latent_plan(chunk, h, row, rank, p, jnp.bfloat16,
                          interpret=True) is not None
