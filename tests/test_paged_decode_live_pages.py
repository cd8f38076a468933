"""The paged decode attention kernel, one grid step a slot (ISSUE 37).

`paged_attention` no longer walks the page TABLE (slot x column, live or
not): a slot's step loops over its LIVE logical pages [first, last) —
`last` = ceil(seq_len / page), `first` = the page of the oldest key the
window still shows, none for an inactive or empty slot — copied from the
pools left in HBM by the kernel itself along ONE list of all slots' live
pages (`live_walk`), and its products take the pool's type (bf16 pools:
bf16 operands; float32 pools: float32). ONE test over

  lengths  inactive slot | 1 token | p - 1 | exactly p | p + 1 | every
           page full | mixed (an empty active slot, lengths under and
           over the 4,096 window)
  x  keys  per head (32 query heads over 2 KV heads: GQA 16:1) | flat
           192-wide keys with 128-wide values (8 over 2)
  x  window  none | 128 (one page: at most two are live) | 4,096
  x  sinks | none   x   float32 | bfloat16 pools

held to `paged_attention_reference` (float32: 1e-5; bfloat16: 2.5e-3 of
relative error, the bound `paged_chunk_attention` is held to, on inputs
that give logits of order one), to exact zeros for a slot with no live
page, and to the property the table walk could not have: every table
entry outside [first, last) names a page OUTSIDE the pool and every
page outside the live set is NaN, and the output is finite and bit-equal
to the output on clean inputs (a dead page is neither fetched nor
dereferenced; under interpret an out-of-pool id would clamp to the
pool's first or last page, both kept dead here).

What a mutation of the bounds fails (tried on this file, in `live_walk`,
the one place that computes them; of the 21 length x window cases of one
(keys, sinks, dtype)): `last + 1` fails 18 (the page after the last is
dead: its table entry names no page, and a NaN page's masked weights are
0 and 0 x NaN is NaN; not `every_page_full`, which has no column after
the last); `last - 1` and `first + 1` fail all 21 against the reference
(the newest or the oldest visible keys are lost); `first - 1` fails the
6 windowed cases in which a slot's window starts past page 0
(`every_page_full`, `mixed`, `inactive_slot` at both windows) by NaN.

One compiled kernel per (keys, window, sinks, dtype); lengths, activity,
table and pools are run-time arguments, as they are in the engine. The
float32 bit-identity with the megakernel's attention phase and the
verify kernel stays where it was: `tests/test_mk_attn_live_pages.py`,
`tests/test_speculative.py`.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import (paged_attention,
                                                   paged_attention_reference)

B, PG, MP = 5, 128, 40            # 5,120 positions a slot: past 4,096
OUTSIDE = 1 << 20                 # a page id far outside every pool
FULL = MP * PG

LENS = {
    "inactive_slot": ([PG + 3, 5, 2 * PG, 4500, FULL], [0, 1, 0, 1, 0]),
    "one_token": ([1] * B, [1] * B),
    "p_minus_1": ([PG - 1] * B, [1] * B),
    "exactly_p": ([PG] * B, [1] * B),
    "p_plus_1": ([PG + 1] * B, [1] * B),
    "every_page_full": ([FULL] * B, [1] * B),
    "mixed": ([FULL - 5, 0, 3000, 4096 + 77, PG + 1], [1, 1, 1, 0, 1]),
}
# name -> (query heads, kv heads, key width, value width, flat keys)
KEYS = {"per_head": (32, 2, 128, 128, False),
        "flat192": (8, 2, 192, 128, True)}


def _live(n, active, window):
    """[first, last) of a slot, written out from the definition."""
    if not active or n == 0:
        return 0, 0
    first = 0 if window is None else max(n - window, 0) // PG
    return first, -(-n // PG)


@functools.lru_cache(maxsize=None)
def _kernel(keys, window, sinks, dtype, blocks=False):
    flat = KEYS[keys][4]

    @jax.jit
    def run(q, kp, vp, tbl, lens, act, sk):
        # blocks: the transport of pools whose pages Mosaic cannot slice
        # (`_copyable`; never chosen under interpret), forced while the
        # call is traced
        copyable = pa._copyable
        if blocks:
            pa._copyable = lambda pool, interpret: False
        try:
            return paged_attention(q, kp, vp, tbl, lens, interpret=True,
                                   active=act, window=window,
                                   sinks=sk if sinks else None, k_flat=flat)
        finally:
            pa._copyable = copyable
    return run


@functools.lru_cache(maxsize=None)
def _inputs(keys, dtype):
    h, h_kv, d, dv, flat = KEYS[keys]
    rng = np.random.RandomState(37)
    n_pages = B * MP + 2          # pages 0 and n_pages - 1 are never live
    kp = rng.randn(n_pages, PG, h_kv, d).astype(np.float32)
    vp = rng.randn(n_pages, PG, h_kv, dv).astype(np.float32)
    if flat:
        kp = kp.reshape(n_pages, PG, h_kv * d)
    q = rng.randn(B, h, d).astype(np.float32)
    tbl = 1 + rng.permutation(B * MP).reshape(B, MP).astype(np.int32)
    sk = np.linspace(-1.0, 2.0, h).astype(np.float32)
    cast = lambda x: jnp.asarray(x).astype(dtype)      # noqa: E731
    # the query (and with it the output) stays float32 beside bf16
    # pools: what is measured is what the operand rule rounds (q * scale
    # and the softmax weights), not the output's own rounding to bf16
    return jnp.asarray(q), cast(kp), cast(vp), tbl, jnp.asarray(sk)


def _poisoned(kp, vp, tbl, lens, act, window):
    """Dead table entries point outside the pool, dead pages are NaN.
    -> (k pool, v pool, table, each slot's [first, last))."""
    ranges = [_live(n, a, window) for n, a in zip(lens, act)]
    dead_tbl = np.full_like(tbl, OUTSIDE)
    live_page = np.zeros(kp.shape[0], bool)
    for s, (lo, hi) in enumerate(ranges):
        dead_tbl[s, lo:hi] = tbl[s, lo:hi]
        live_page[tbl[s, lo:hi]] = True
    nan = lambda pool: jnp.where(                      # noqa: E731
        jnp.asarray(live_page).reshape((-1,) + (1,) * (pool.ndim - 1)),
        pool, jnp.nan)
    return nan(kp), nan(vp), jnp.asarray(dead_tbl), ranges


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("sinks", [False, True], ids=["nosink", "sinks"])
@pytest.mark.parametrize("window", [None, 128, 4096],
                         ids=["full", "w128", "w4096"])
@pytest.mark.parametrize("keys", list(KEYS))
@pytest.mark.parametrize("name", list(LENS))
def test_decode_walks_live_pages_only(name, keys, window, sinks, dtype):
    lens, act = LENS[name]
    q, kp, vp, tbl, sk = _inputs(keys, dtype)
    run = _kernel(keys, window, sinks, dtype)
    lens_d = jnp.asarray(lens, jnp.int32)
    act_d = jnp.asarray(act, jnp.int32)
    clean = run(q, kp, vp, jnp.asarray(tbl), lens_d, act_d, sk)

    kp_nan, vp_nan, dead_tbl, ranges = _poisoned(kp, vp, tbl, lens, act,
                                                 window)
    got = run(q, kp_nan, vp_nan, dead_tbl, lens_d, act_d, sk)
    got_np = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got_np).all()
    assert (got_np == np.asarray(clean.astype(jnp.float32))).all()

    # a slot with no live page emits exact zeros; the others follow the
    # reference (which takes no `active`, and no empty sequence)
    empty = np.array([lo == hi for lo, hi in ranges])
    assert (got_np[empty] == 0).all()
    if empty.all():
        return
    want = np.asarray(paged_attention_reference(
        q, kp, vp, tbl, np.maximum(lens, 1), window=window,
        sinks=sk if sinks else None,
        k_flat=KEYS[keys][4]).astype(jnp.float32))[~empty]
    if dtype == "float32":
        np.testing.assert_allclose(got_np[~empty], want, rtol=1e-5,
                                   atol=1e-5)
    else:
        err = np.linalg.norm(got_np[~empty] - want) / np.linalg.norm(want)
        assert err < 2.5e-3, err


@pytest.mark.parametrize("window", [None, 128, 4096],
                         ids=["full", "w128", "w4096"])
@pytest.mark.parametrize("keys", list(KEYS))
@pytest.mark.parametrize("name", list(LENS))
def test_pipelined_blocks_give_the_copies_bits(name, keys, window):
    """A pool whose pages Mosaic cannot slice out of HBM (a 64-wide head,
    a lone bf16 KV head) walks the same live pages a grid step a page,
    brought by the index map: the same arithmetic in the same order, so
    the same bits, on the same poisoned inputs (its table is clipped, a
    fetched dead page is never multiplied)."""
    lens, act = LENS[name]
    q, kp, vp, tbl, sk = _inputs(keys, "float32")
    kp, vp, tbl, _ = _poisoned(kp, vp, tbl, lens, act, window)
    args = (q, kp, vp, tbl, jnp.asarray(lens, jnp.int32),
            jnp.asarray(act, jnp.int32), sk)
    got = np.asarray(_kernel(keys, window, True, "float32", True)(*args))
    want = np.asarray(_kernel(keys, window, True, "float32")(*args))
    assert np.isfinite(got).all() and (got == want).all()
