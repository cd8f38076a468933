"""Whole-step decode MEGAKERNEL (TPU Pallas): one kernel invocation runs
a FULL decode step — every transformer layer (int8 weight-only matmuls,
RMS-norm, rope, paged attention), the final norm, and the lm_head tiled
over vocab with an on-kernel running-argmax token select — with the
weights STREAMED through VMEM tile-by-tile.

v1 (PR 6) fused the per-layer math but stopped at the layer boundary:
lm_head, sampling, and the KV page scatter stayed separate XLA ops, and
the kernel was mutually exclusive with both speculation and tensor
parallelism. v2 is the rest of the MPK claim (PAPERS.md — compile the
WHOLE tensor program):

  - ONE 1-D grid walks a statically-built SCHEDULE of tiles:
      [per layer]  Q -> K -> V -> ATTN -> O -> G -> U -> D
      [step tail]  final-norm -> HEAD (lm_head n-tiles over vocab)
    Matmul phases iterate (n-tile outer, k-tile inner); ATTN takes ONE
    step per slot and loops inside it over that slot's LIVE pages only
    (ceil(seq_len / page) of them, none for an inactive slot), fetched
    from the pools left in HBM by the kernel's own double-buffered
    copies — a grid step costs about a microsecond whatever it moves,
    so a page that holds nothing must not cost one (PERF.md 6, PR 29);
    HEAD additionally maintains a RUNNING ARGMAX over the
    emitted logits tiles so the greedy next token leaves the kernel as
    a [b] int32 — the engine's `lax.scan` then drives the invocation
    directly and a decode_block=K block is kernel launches plus only
    the KV page scatter and the tiny carry updates (one compiled
    program, no per-step XLA graph between launches).
  - SPECULATION rides the same schedule: tq > 1 runs the matmul phases
    over [b*tq] feed rows (rows are position-independent) and the ATTN
    phase as the multi-token-q ragged variant — per-slot causal masking
    via the SAME `ragged_causal_mask` the verify kernel uses, with the
    current feed tokens' k/v substituted into their page blocks under
    the engine's write mask (identical bytes to the scatter-then-attend
    unfused path, including the not-yet-written stale rows).
  - TENSOR PARALLELISM composes via per-shard SEGMENTS under shard_map:
    `seg="qkv"` (column-parallel Q/K/V + local-head attention),
    `seg="tail"` (replicated O + norm2 + column-parallel gate/up),
    `seg="down"` (replicated down [+ final norm + the vocab-parallel
    HEAD slice, whose local (max, argmax) pair the engine combines
    gather-free]). The exact-mode gathers run BETWEEN segments — pure
    data movement, so byte-identity with the tp=1 engine survives.
    `pack_decode_layer(..., tp=N)` packs column weights per shard
    (concatenated so a P(None, "mp")-sharded array hands each shard its
    own padded tile grid).

Numerics are kept step-for-step identical to the unfused engine path:
matmul k-tiling shares `quantized_matmul`'s tile bodies (MXU operands
in the type `mm_operand_dtype` picks from the activation and weight
dtypes — bf16 for a bf16 engine, whose f32 row scratches are passed as
the bf16 they hold; f32 otherwise — f32 accumulate, per-channel scale
at emission), norms share
`rms_norm.rms_rows`, single-token attention runs the decode kernel's
per-page online softmax, the tq variant the ragged kernel's (shared
mask helper), and the HEAD running argmax reproduces `jnp.argmax`'s
first-max-wins tie rule tile-by-tile. Interpret mode on CPU is the
parity fallback; see tests/test_megakernel_v2.py.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (NEG_INF, ragged_causal_mask, vmem_limit,
                              wv_diag)
from .quantized_matmul import dot_tile_f32, mm_operand_dtype, scale_emit
from .rms_norm import rms_rows as _rms_rows

# schedule phase ids (ints baked into the scalar-prefetched schedule)
PH_Q, PH_K, PH_V, PH_ATTN, PH_O, PH_G, PH_U, PH_D = range(8)
PH_H = 8          # lm_head tiles (whole-step mode; preceded by an
#                   in-schedule final-norm epilogue at its first step)

# which phases each SEGMENT runs. "full" is the tp=1 whole-layer (or
# whole-stack) walk; the other three split a layer at the exact-mode
# gather boundaries so megakernel + tp>1 compose under shard_map.
SEG_PHASES = {
    "full": (PH_Q, PH_K, PH_V, PH_ATTN, PH_O, PH_G, PH_U, PH_D),
    "qkv": (PH_Q, PH_K, PH_V, PH_ATTN),
    "tail": (PH_O, PH_G, PH_U),
    "down": (PH_D,),
}
# matmul phase -> (weight key, source buffer name)
_MM_SRC = {PH_Q: ("q", "x"), PH_K: ("k", "x"), PH_V: ("v", "x"),
           PH_O: ("o", "attn"), PH_G: ("g", "x"), PH_U: ("u", "x"),
           PH_D: ("d", "act")}

# THE TILE PLAN of the matmul phases: how wide a weight block one grid
# step streams. A grid step costs about a microsecond whatever it moves
# (some twenty index maps, the DMAs issued and waited on), so a layer's
# weights go through in as few, as wide blocks as fit.
#   MM_BK is fixed: it matches quantized_matmul's bk=512, so the NUMBER
#     and ORDER of k-tiles — the f32 accumulation order across them, and
#     with it every output bit — agree with the unfused engine path,
#     whatever type one tile's operands are fed in.
#   bn does not enter that order, so it is chosen (`mm_tile_plan`): the
#     widest multiple of 128 lanes that DIVIDES the packed N (the pack
#     pads an N past MM_N_GRAIN to its multiple and no further, so a
#     wider block never costs a zero column or a second copy of a weight)
#     and keeps one block bk x bn x itemsize within MM_BLOCK_BYTES.
#   MM_BLOCK_BYTES was settled by a sweep of one layer call at the 7B
#     serving geometry on a v5e over 0.25 (the old 512 columns), 1, 2 and
#     4 MiB: docs/probes/mk_layer_probe.py, table in PERF.md 6 (PR 27).
MM_BK = 512
MM_N_GRAIN = 512
MM_BLOCK_BYTES = 2 << 20


def _ktile(dim, want):
    """Tile size for a dimension: the dim itself when it fits, else
    `want` with the caller zero-padding up to a multiple. EXACTLY
    quantized_matmul's `min(bk, k)`-then-pad scheme — a cheaper
    power-of-two-divisor fallback (no padding) would change the NUMBER
    of k-tiles for dims like 7B's ffn 11008 (43x256 vs 22x512) and with
    it the f32 accumulation association, breaking bit-identity with the
    op-chain path. Deterministic from (dim, want) so pack-time and
    call-time agree."""
    return dim if dim <= want else want


def mm_tile_plan(k, n, itemsize, budget=None):
    """The ONE rule for a projection [k, n] of `itemsize`-byte weights:
    -> (bk, bn, k_pad, n_pad). The pack pads to (k_pad, n_pad); the
    call, which sees the padded shape, gets the same (bk, bn) back
    (the rule is idempotent on its own pads). An N within MM_N_GRAIN is
    one block whatever its width (test sizes: no lane multiple needed);
    past it bn = 128 x the largest divisor of n_pad / 128 whose block
    fits the budget (at least one 128-lane tile). `budget` is the
    tests' handle on the constant, not an engine option."""
    budget = MM_BLOCK_BYTES if budget is None else budget
    bk = _ktile(k, MM_BK)
    grain = _ktile(n, MM_N_GRAIN)
    k_pad, n_pad = -(-k // bk) * bk, -(-n // grain) * grain
    if n_pad == grain:
        return bk, n_pad, k_pad, n_pad
    lanes = n_pad // 128
    fit = max(budget // (bk * 128 * itemsize), 1)
    m = max(d for d in range(1, min(lanes, fit) + 1) if lanes % d == 0)
    return bk, 128 * m, k_pad, n_pad


def _pad_axis(a, mult, axis):
    pad = (-a.shape[axis]) % mult
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _vals_scales(w, cdtype):
    """int8 engine snapshots arrive as (int8 [k, n], scales [n]); dense
    weights keep their dtype with unit scales (the kernel's
    `(acc * scale)` is then an exact f32 identity)."""
    if isinstance(w, tuple):
        return w
    return (w.astype(cdtype) if w.dtype != cdtype else w,
            jnp.ones((w.shape[1],), jnp.float32))


def _pack_w(w, cdtype):
    """One projection weight -> (values [k_pad, n_pad], scales [1, n_pad])
    at mm_tile_plan's pads. Zero-padding rows add exact 0.0 to the f32
    accumulator and zero-scale columns emit exact zeros, so padding
    never perturbs real outputs."""
    vals, scales = _vals_scales(w, cdtype)
    _, _, k_pad, n_pad = mm_tile_plan(*vals.shape, vals.dtype.itemsize)
    vals = _pad_axis(_pad_axis(vals, k_pad, 0), n_pad, 1)
    scales = _pad_axis(scales.astype(jnp.float32).reshape(1, -1), n_pad, 1)
    return vals, scales


def _pack_w_sharded(w, cdtype, tp):
    """Column-parallel per-shard pack: slice the OUTPUT channels into tp
    equal shards, pack each shard to its own padded tile grid, and
    concatenate — a P(None, "mp")-sharded placement of the result hands
    shard s exactly its local packed (values, scales). The k-axis pad
    is shard-independent (derived from (k, MM_BK) alone), so every shard
    walks the same k-tile count as the tp=1 pack."""
    if tp == 1:
        return _pack_w(w, cdtype)
    vals, scales = _vals_scales(w, cdtype)
    n = vals.shape[1]
    assert n % tp == 0, (n, tp)
    nl = n // tp
    vparts, sparts = [], []
    for s in range(tp):
        v, sc = _pack_w((vals[:, s * nl:(s + 1) * nl],
                         scales[s * nl:(s + 1) * nl]), cdtype)
        vparts.append(v)
        sparts.append(sc)
    return jnp.concatenate(vparts, 1), jnp.concatenate(sparts, 1)


def pack_decode_layer(wset, cdtype=jnp.float32, tp=1):
    """Repack ONE engine layer snapshot (serving._snapshot_llama entry)
    into the megakernel's streamed layout: per-projection (values,
    scales) padded as mm_tile_plan says, norm weights as [1, H]
    rows. Views/cheap reshapes where no padding is needed — the int8
    pool is NOT duplicated for the common aligned geometries.

    tp > 1 packs the COLUMN-parallel projections (q/k/v/gate/up) per
    shard (see _pack_w_sharded) while o/down stay full — the exact-mode
    row-parallel pair runs REPLICATED on gathered operands, exactly like
    the op-chain tp engine, so byte-identity with tp=1 survives."""
    out = {}
    for name, key in (("q", "wq"), ("k", "wk"), ("v", "wv"),
                      ("g", "wg"), ("u", "wu")):
        vals, scales = _pack_w_sharded(wset[key], cdtype, tp)
        out["w" + name] = vals
        out["s" + name] = scales
    for name, key in (("o", "wo"), ("d", "wd")):
        vals, scales = _pack_w(wset[key], cdtype)
        out["w" + name] = vals
        out["s" + name] = scales
    hp = out["wq"].shape[0]
    out["ln1"] = _pad_axis(wset["ln1"].reshape(1, -1), hp, 1)
    out["ln2"] = _pad_axis(wset["ln2"].reshape(1, -1), hp, 1)
    return out


def pack_lm_head(head, norm_w, cdtype=jnp.float32, tp=1):
    """Pack the final norm + lm_head for the whole-step HEAD phase:
    {"wh": [H_pad, V_pad], "sh": [1, V_pad], "nf": [1, H_pad]}. The
    k-axis pad matches pack_decode_layer's hidden pad (same (dim, MM_BK)
    rule), so the HEAD phase reuses the layer walk's x scratch rows.
    tp > 1 shards the VOCAB columns per shard (the vocab-parallel
    lm_head): each shard streams 1/tp of the head and emits its local
    (max, argmax) pair for the engine's gather-free combine."""
    wh, sh = _pack_w_sharded(head, cdtype, tp)
    return {"wh": wh, "sh": sh,
            "nf": _pad_axis(norm_w.reshape(1, -1), wh.shape[0], 1)}


def stack_packed(layers):
    """[{per-layer packed}] -> one stacked dict ([L, ...] leaves) for the
    multi-layer megakernel. This COPIES the weights once at engine build
    (the price of streaming across layer boundaries from one invocation);
    the per-layer mode reuses the engine's arrays in place."""
    return {k: jnp.stack([lay[k] for lay in layers])
            for k in layers[0]}


def megakernel_supported(nh, nh_kv, hd, hidden, ffn):
    """Geometry gate for the AUTO engine knob on real TPUs: the flat
    [b, heads*hd] activation layout is resliced per head / per segment,
    which Mosaic only lowers cleanly at lane-multiple boundaries.
    Interpret mode (CPU parity/fallback) has no such constraint."""
    return (hd % 128 == 0 and hidden % 128 == 0 and ffn % 128 == 0
            and (nh_kv * hd) % 128 == 0)


def _rope_flat(x, c, s, n_heads, hd, cdtype):
    """Rope over the FLAT [rows, n_heads*hd] layout: per-head unrolled
    half-pair rotation (heads are small and static at decode — the same
    unroll the paged-attention kernels use). x/c/s arrive as f32 holding
    cdtype-representable values (c/s: [rows, hd//2] rope rows at each
    ROW's position); every product and sum is rounded to cdtype where
    _layer_qkv's cdtype arithmetic rounds, so the result is the op
    chain's — and with cdtype == f32 the roundings are no-ops and the
    expression is literally the same. The math stays on 32-bit vectors
    because that is the form Mosaic lowers: the row-addressed scratch is
    f32 (see decode_megakernel)."""
    f32 = jnp.float32

    def rnd(t):
        return t.astype(cdtype).astype(f32)

    hd2 = hd // 2
    outs = []
    for g in range(n_heads):
        x1 = x[:, g * hd:g * hd + hd2]
        x2 = x[:, g * hd + hd2:(g + 1) * hd]
        outs.append(rnd(rnd(x1 * c) - rnd(x2 * s)))
        outs.append(rnd(rnd(x2 * c) + rnd(x1 * s)))
    return jnp.concatenate(outs, axis=1)


def _build_schedule(L, b, counts, phases, head_counts=None):
    """Static tile walk -> four int32 arrays (phase, a0, a1, layer).
    Matmul phases: a0 = k-tile (inner), a1 = n-tile (outer) — k inner
    matches quantized_matmul's grid so each output tile's f32
    accumulation order is identical. ATTN: one entry per slot (a0 =
    slot); which of the slot's pages are live is known only at run
    time, so the pages are the kernel's loop, not the schedule's. The
    HEAD phase (when present) appends after the last layer with
    li = L-1 so every stacked layer-weight BlockSpec stays pinned on
    its final block (no spurious re-DMA)."""
    ph, a0, a1, li = [], [], [], []
    for lyr in range(L):
        for P in phases:
            if P == PH_ATTN:
                for slot in range(b):
                    ph.append(P); a0.append(slot); a1.append(0)
                    li.append(lyr)
            else:
                nk, nn = counts[P]
                for n in range(nn):
                    for k in range(nk):
                        ph.append(P); a0.append(k); a1.append(n)
                        li.append(lyr)
    if head_counts is not None:
        nk, nn = head_counts
        for n in range(nn):
            for k in range(nk):
                ph.append(PH_H); a0.append(k); a1.append(n)
                li.append(L - 1)
    return (np.asarray(ph, np.int32), np.asarray(a0, np.int32),
            np.asarray(a1, np.int32), np.asarray(li, np.int32))


# layer-weight keys that stack [L, ...] in "multi" mode (the head pack
# and the final norm never stack — there is one lm_head per model)
_STACKED_KEYS = frozenset(
    ["w" + k for k in "qkvogud"] + ["s" + k for k in "qkvogud"]
    + ["ln1", "ln2"])


def _mk_kernel(*args, names, seg, stacked, counts, bks, bns, dims,
               eps, p, mp, scale, head, T, head_k=1):
    """One grid step of the schedule walk. `names` maps every ref
    (scalar prefetch, inputs, outputs, scratch — in pallas_call order)
    so the same body serves every segment/variant; python-level
    conditionals on (seg, head, T, stacked) are STATIC — each built
    kernel contains only its own phases."""
    refs = dict(zip(names, args))
    s = pl.program_id(0)
    ph = refs["ph"][s]
    a0 = refs["a0"][s]
    a1 = refs["a1"][s]
    lyr = refs["li"][s]
    R = dims["R"]
    b = dims["b"]
    H = dims["H"]
    nh, nh_kv, hd = dims["nh"], dims["nh_kv"], dims["hd"]
    NQ, NK = nh * hd, nh_kv * hd
    NQp = dims["NQp"]
    rep = nh // nh_kv
    hs = refs["h_scr"]
    xs = refs["x_scr"]
    acc = refs["acc_scr"]
    cdtype = hs.dtype

    def wblk(name):
        r = refs[name]
        return r[0] if (stacked and name in _STACKED_KEYS) else r[...]

    def srow(name):
        r = refs[name]
        return r[0, 0] if (stacked and name in _STACKED_KEYS) else r[0]

    def lnrow(name):
        # a (1, Hp) row either way; broadcasts against [R, Hp]
        r = refs[name]
        return r[0] if (stacked and name in _STACKED_KEYS) else r[...]

    # -- segment entry: load h (and pre-norm where the segment starts
    # -- at the attention block) --------------------------------------
    entry_ph = {"full": PH_Q, "qkv": PH_Q, "tail": PH_O,
                "down": PH_D}[seg]

    @pl.when(jnp.logical_and(ph == entry_ph,
                             jnp.logical_and(a0 == 0, a1 == 0)))
    def _enter():
        if seg in ("full", "qkv"):
            @pl.when(lyr == 0)
            def _():
                hs[...] = refs["h"][...]
            xs[...] = _rms_rows(hs[...], lnrow("ln1"), eps, H)
        else:
            hs[...] = refs["h"][...]

    # -- shared matmul step: acc += x_tile @ w_tile; emit at last k ----
    def seg_write(tgt):
        def emit(out, bn):
            tgt[:, pl.ds(a1 * bn, bn)] = out.astype(tgt.dtype)
        return emit

    def seg_add(tgt):
        def emit(out, bn):
            sl = pl.ds(a1 * bn, bn)
            tgt[:, sl] = tgt[:, sl] + out
        return emit

    def mm_src(src):
        if src == "x":
            return xs
        if src == "attn":
            return refs["attn_in"] if seg == "tail" else refs["attn_scr"]
        return refs["act_in"] if seg == "down" else refs["act_scr"]

    def mm_phase(P, emit):
        wkey, src = _MM_SRC[P]
        nk, nn = counts[P]
        bn = bns[P]
        bk = bks[P]
        x_src = mm_src(src)

        @pl.when(ph == P)
        def _():
            @pl.when(a0 == 0)
            def _():
                acc[...] = jnp.zeros_like(acc)
            # cdtype: attn_scr is an f32 container of cdtype values
            acc[:, :bn] += dot_tile_f32(x_src[:, pl.ds(a0 * bk, bk)],
                                        wblk("w" + wkey), cdtype)

            @pl.when(a0 == nk - 1)
            def _():
                emit(scale_emit(acc[:, :bn], srow("s" + wkey), cdtype),
                     bn)

    emits = {PH_Q: lambda: seg_write(refs["q_scr"]),
             PH_K: lambda: seg_write(refs["k_scr"]),
             PH_V: lambda: seg_write(refs["v_scr"]),
             PH_O: lambda: seg_add(hs),
             PH_G: lambda: seg_write(refs["g_scr"]),
             PH_U: lambda: seg_write(refs["u_scr"]),
             PH_D: lambda: seg_add(hs)}
    for P in SEG_PHASES[seg]:
        if P != PH_ATTN:
            mm_phase(P, emits[P]())

    def _phase_end(P):
        nk, nn = counts[P]
        return jnp.logical_and(ph == P,
                               jnp.logical_and(a0 == nk - 1, a1 == nn - 1))

    # -- phase epilogues ----------------------------------------------
    if PH_Q in counts:
        @pl.when(_phase_end(PH_Q))
        def _rope_q():
            c = refs["cos"][...].astype(jnp.float32)
            sn = refs["sin"][...].astype(jnp.float32)
            refs["q_scr"][:, :NQ] = _rope_flat(refs["q_scr"][:, :NQ],
                                               c, sn, nh, hd, cdtype)

        @pl.when(_phase_end(PH_K))
        def _rope_k():
            c = refs["cos"][...].astype(jnp.float32)
            sn = refs["sin"][...].astype(jnp.float32)
            refs["k_scr"][:, :NK] = _rope_flat(refs["k_scr"][:, :NK],
                                               c, sn, nh_kv, hd, cdtype)
            if stacked:
                refs["kn"][0] = refs["k_scr"][...].astype(cdtype)
            else:
                refs["kn"][...] = refs["k_scr"][...].astype(cdtype)

        @pl.when(_phase_end(PH_V))
        def _emit_v():
            if stacked:
                refs["vn"][0] = refs["v_scr"][...].astype(cdtype)
            else:
                refs["vn"][...] = refs["v_scr"][...].astype(cdtype)

    if PH_O in counts:
        @pl.when(_phase_end(PH_O))
        def _norm2():
            xs[...] = _rms_rows(hs[...], lnrow("ln2"), eps, H)

    if PH_U in counts:
        @pl.when(_phase_end(PH_U))
        def _swiglu():
            g = refs["g_scr"][...]
            refs["act_scr"][...] = jax.nn.silu(
                g.astype(jnp.float32)).astype(cdtype) * refs["u_scr"][...]
            if seg == "tail":           # segment ends here: emit both
                refs["ho"][...] = hs[...]
                refs["act_out"][...] = refs["act_scr"][...]

    if PH_D in counts:
        @pl.when(_phase_end(PH_D))
        def _emit_h():
            refs["ho"][...] = hs[...]

    # -- paged attention phase (a0 = slot; the slot's live pages loop
    # -- inside the step) ---------------------------------------------
    if PH_ATTN in SEG_PHASES[seg]:
        attn_tgt = refs["attn_scr"]
        m_scr, l_scr, aacc = refs["m_scr"], refs["l_scr"], refs["aacc_scr"]
        tblr, lensr, actr = refs["tbl"], refs["lens"], refs["act"]
        wmr = refs["wm"]
        kbuf, vbuf, psem = refs["kbuf"], refs["vbuf"], refs["page_sem"]

        def page_copies(slot, page):
            # logical page `page` of `slot`, pool (HBM) -> buffer
            # page % 2: the SAME descriptors start a copy and wait on it
            pg = tblr[slot, page]
            buf = jax.lax.rem(page, jnp.int32(2))
            src = (lyr, pg) if stacked else (pg,)
            return (pltpu.make_async_copy(refs["kp"].at[src], kbuf.at[buf],
                                          psem.at[0, buf]),
                    pltpu.make_async_copy(refs["vp"].at[src], vbuf.at[buf],
                                          psem.at[1, buf]))

        def start_page(slot, page):
            for cp in page_copies(slot, page):
                cp.start()

        def live_pages(slot):
            # NOTE: every jnp.where operand in this kernel must be an
            # explicitly-typed i32 — interpret mode re-discharges the
            # kernel jaxpr at OUTER-jit lowering time, outside the
            # enable_x64(False) window, and a weak python-int literal
            # re-canonicalizes to i64 there, producing an inconsistent
            # select_n (MLIR verify error).
            seq_len = jnp.where(actr[slot] > 0,
                                lensr[slot] + jnp.int32(T), jnp.int32(0))
            # pages that hold a position < seq_len, walked in ascending
            # order as the unfused kernels do: 0 for an inactive slot
            return seq_len, jnp.minimum(
                (seq_len + jnp.int32(p - 1)) // jnp.int32(p), jnp.int32(mp))

        @pl.when(ph == PH_ATTN)
        def _attn():
            slot = a0
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            aacc[...] = jnp.zeros_like(aacc)

            seq_len, n_live = live_pages(slot)
            base = lensr[slot]

            # a slot's first page is started by the step before it (at
            # its end, below), so the copy flies over the step boundary
            # instead of being waited for; the phase's first slot has no
            # such step
            @pl.when(jnp.logical_and(slot == 0, n_live > 0))
            def _():
                start_page(slot, jnp.int32(0))

            def _page(page, carry):
                @pl.when(page + jnp.int32(1) < n_live)
                def _():
                    start_page(slot, page + jnp.int32(1))
                for cp in page_copies(slot, page):
                    cp.wait()
                buf = jax.lax.rem(page, jnp.int32(2))
                k = kbuf[buf].astype(jnp.float32)
                v = vbuf[buf].astype(jnp.float32)
                page_start = page * jnp.int32(p)
                rows_i = jax.lax.broadcasted_iota(jnp.int32, (p, 1, 1), 0)
                if T == 1:
                    # v1 single-token path: substitute the current
                    # token's k/v into its page block (the unfused path
                    # scatters them BEFORE attending — same block
                    # contents, same online-softmax trajectory)
                    on_page = (base // jnp.int32(p)) == page
                    sub = jnp.logical_and(
                        on_page, rows_i == jax.lax.rem(base, jnp.int32(p)))
                    kc = refs["k_scr"][pl.ds(slot, 1), :][:, :NK].reshape(
                        nh_kv, hd).astype(jnp.float32)
                    vc = refs["v_scr"][pl.ds(slot, 1), :][:, :NK].reshape(
                        nh_kv, hd).astype(jnp.float32)
                    k = jnp.where(sub, kc[None], k)
                    v = jnp.where(sub, vc[None], v)
                    q = refs["q_scr"][pl.ds(slot, 1), :][:, :NQ].reshape(
                        nh, hd).astype(jnp.float32) * jnp.float32(scale)
                    logits = jnp.concatenate([
                        jax.lax.dot_general(
                            q[g * rep:(g + 1) * rep], k[:, g, :],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        for g in range(nh_kv)], axis=0)        # [nh, p]
                    pos = jax.lax.broadcasted_iota(
                        jnp.int32, logits.shape, 1) + page_start
                    logits = jnp.where(pos < seq_len, logits,
                                       jnp.float32(NEG_INF))
                    wrows = rep
                else:
                    # tq > 1 (speculative verify): substitute EVERY
                    # write-gated feed token whose position lands on
                    # this page — rows the gate skips keep the pool's
                    # stale bytes, exactly like the unfused
                    # scatter-then-attend path (write_ok rides in as
                    # the wm prefetch row mask)
                    for j in range(T):
                        pos_j = base + jnp.int32(j)
                        gate = jnp.logical_and(
                            wmr[slot * T + j] > 0,
                            (pos_j // jnp.int32(p)) == page)
                        sub = jnp.logical_and(
                            gate, rows_i == jax.lax.rem(pos_j,
                                                        jnp.int32(p)))
                        kc = refs["k_scr"][
                            pl.ds(slot * T + j, 1), :][:, :NK].reshape(
                            nh_kv, hd).astype(jnp.float32)
                        vc = refs["v_scr"][
                            pl.ds(slot * T + j, 1), :][:, :NK].reshape(
                            nh_kv, hd).astype(jnp.float32)
                        k = jnp.where(sub, kc[None], k)
                        v = jnp.where(sub, vc[None], v)
                    # q rows HEAD-MAJOR [nh*T, hd] (row g*rep*T + j*T
                    # + qi = q head g*rep+j at feed offset qi) — the
                    # ragged kernel's row convention, one contiguous
                    # [rep*T, d] slice per kv head
                    # (one dynamic ROW per load: Mosaic proves no
                    # alignment for a dynamic multi-row window)
                    qs = jnp.concatenate(
                        [refs["q_scr"][pl.ds(slot * T + j, 1), :]
                         for j in range(T)], axis=0)[:, :NQ] \
                        .astype(jnp.float32) * jnp.float32(scale)
                    logits = jnp.concatenate([
                        jax.lax.dot_general(
                            jnp.concatenate(
                                [qs[:, hh * hd:(hh + 1) * hd]
                                 for hh in range(g * rep, (g + 1) * rep)],
                                axis=0),
                            k[:, g, :], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        for g in range(nh_kv)], axis=0)     # [nh*T, p]
                    ok = ragged_causal_mask(logits.shape, T, base,
                                            page_start, seq_len)
                    logits = jnp.where(ok, logits, jnp.float32(NEG_INF))
                    wrows = rep * T
                m_prev = m_scr[:, :1]
                l_prev = l_scr[:, :1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, axis=-1, keepdims=True))
                w = jnp.exp(logits - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[...] = jnp.broadcast_to(
                    alpha * l_prev + jnp.sum(w, axis=-1, keepdims=True),
                    l_scr.shape)
                aacc[...] = alpha * aacc[...] + wv_diag(w, v, hd,
                                                        rep=wrows)
                m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
                return carry

            jax.lax.fori_loop(jnp.int32(0), n_live, _page, jnp.int32(0))

            # every copy of this slot has been waited for: both buffers
            # are free, and the next slot's first page goes into buffer 0
            @pl.when(slot + jnp.int32(1) < jnp.int32(b))
            def _():
                @pl.when(live_pages(slot + jnp.int32(1))[1] > 0)
                def _():
                    start_page(slot + jnp.int32(1), jnp.int32(0))

            # a slot with no live page leaves l = 0, acc = 0: exact zeros
            l_fin = jnp.maximum(l_scr[:, :1], jnp.float32(1e-30))
            res = (aacc[...] / l_fin).astype(cdtype).astype(jnp.float32)
            if T == 1:
                row = res.reshape(1, NQ)                   # [nh, hd]
                if NQp != NQ:         # scratch pads must be exact zeros
                    row = jnp.pad(row, ((0, 0), (0, NQp - NQ)))
                attn_tgt[pl.ds(slot, 1), :] = row
            else:
                res3 = res.reshape(nh, T, hd)
                for qi in range(T):
                    row = res3[:, qi, :].reshape(1, NQ)
                    if NQp != NQ:
                        row = jnp.pad(row, ((0, 0), (0, NQp - NQ)))
                    attn_tgt[pl.ds(slot * T + qi, 1), :] = row
            if seg == "qkv":            # segment ends here: emit it
                @pl.when(slot == b - 1)
                def _():
                    refs["attn_out"][...] = attn_tgt[...].astype(cdtype)

    # -- whole-step tail: final norm + lm_head tiles + running argmax --
    if head:
        nkh, nnh = counts[PH_H]
        bnh = bns[PH_H]
        bkh2 = bks[PH_H]
        Vh = dims["Vh"]
        amax = refs["amax_scr"]
        aidx = refs["aidx_scr"]

        @pl.when(jnp.logical_and(ph == PH_H,
                                 jnp.logical_and(a0 == 0, a1 == 0)))
        def _enter_head():
            xs[...] = _rms_rows(hs[...], refs["nf"][...], eps, H)
            amax[...] = jnp.full_like(amax, NEG_INF)
            aidx[...] = jnp.zeros_like(aidx)

        @pl.when(ph == PH_H)
        def _head():
            @pl.when(a0 == 0)
            def _():
                acc[...] = jnp.zeros_like(acc)
            acc[:, :bnh] += dot_tile_f32(xs[:, pl.ds(a0 * bkh2, bkh2)],
                                         refs["wh"][...], cdtype)

            @pl.when(a0 == nkh - 1)
            def _():
                out = scale_emit(acc[:, :bnh], refs["sh"][0], cdtype)
                if "logits" in refs:
                    # head_k > 1 drops the [R, V] logits OUTPUT from the
                    # pallas_call entirely — the sampled fold's whole
                    # point is that full logits never exist, not even as
                    # an unused buffer (the in-test jaxpr assert)
                    refs["logits"][...] = out
                # running select over the CAST logits (what argmax /
                # lax.top_k see on the unfused path); pad columns (zero
                # scales -> exact 0.0) mask to NEG_INF
                col = jax.lax.broadcasted_iota(
                    jnp.int32, (R, bnh), 1) + a1 * jnp.int32(bnh)
                vals = jnp.where(col < jnp.int32(Vh),
                                 out.astype(jnp.float32),
                                 jnp.float32(NEG_INF))
                if head_k == 1:
                    # running argmax: strictly-greater update +
                    # first-index-within-tile argmax reproduces the
                    # global first-max-wins tie rule tile by tile
                    tmax = jnp.max(vals, axis=1, keepdims=True)
                    targ = jnp.argmax(vals, axis=1).astype(
                        jnp.int32)[:, None] + a1 * jnp.int32(bnh)
                    upd = tmax > amax[:, :1]
                    aidx[...] = jnp.where(
                        upd, jnp.broadcast_to(targ, aidx.shape),
                        aidx[...])
                    amax[...] = jnp.where(
                        upd, jnp.broadcast_to(tmax, amax.shape),
                        amax[...])
                else:
                    # running top-K merge (the sampling fold): merge the
                    # K running entries with this tile's columns under
                    # the total order (value desc, vocab id asc) — K
                    # unrolled select-and-mask steps over the [R, K+bnh]
                    # concat. First-max-wins argmax reproduces the
                    # id-asc tie rule because running entries precede
                    # tile columns in the concat AND carry strictly
                    # smaller vocab ids (tiles arrive in ascending a1),
                    # and columns within a tile are id-ascending — so
                    # position order IS vocab-id order throughout.
                    # Bitwise identical to lax.top_k on the full row:
                    # no arithmetic happens, only selection.
                    Ks = head_k
                    cand_v = jnp.concatenate([amax[:, :Ks], vals], 1)
                    cand_i = jnp.concatenate([aidx[:, :Ks], col], 1)
                    cpos = jax.lax.broadcasted_iota(
                        jnp.int32, cand_v.shape, 1)
                    new_v, new_i = [], []
                    for _ in range(Ks):
                        m = jnp.max(cand_v, axis=1, keepdims=True)
                        a = jnp.argmax(cand_v, axis=1).astype(
                            jnp.int32)[:, None]
                        sel = cpos == a
                        new_v.append(m)
                        new_i.append(jnp.sum(
                            jnp.where(sel, cand_i, jnp.int32(0)),
                            axis=1, keepdims=True))
                        cand_v = jnp.where(sel, jnp.float32(NEG_INF),
                                           cand_v)
                    amax[...] = jnp.concatenate(
                        new_v + [jnp.full((R, 128 - Ks), NEG_INF,
                                          jnp.float32)], 1)
                    aidx[...] = jnp.concatenate(
                        new_i + [jnp.zeros((R, 128 - Ks), jnp.int32)], 1)

                @pl.when(a1 == nnh - 1)
                def _():
                    refs["tok"][...] = aidx[...]
                    refs["maxv"][...] = amax[...]


def _pad_to(a, width):
    """Zero-pad the last axis up to an exact target width."""
    if a.shape[-1] == width:
        return a
    assert a.shape[-1] < width, (a.shape, width)
    pad = [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])]
    return jnp.pad(a, pad)


def decode_megakernel(h, mk, k_pages=None, v_pages=None, page_table=None,
                      lens=None, active=None, cos_sel=None, sin_sel=None,
                      *, nh, nh_kv, hd, eps, scale=None, interpret=False,
                      seg="full", head=None, head_v=None, head_k=None,
                      mlp_v=None, tq=1, wmask=None, attn_in=None,
                      act_in=None):
    """Run decode layer(s) — up to the FULL decode step — as ONE Pallas
    megakernel invocation.

    seg="full" (default): the whole layer walk. h [R, H] hidden rows
      (R = b slots, or b*tq feed rows when tq > 1), mk a
      pack_decode_layer() dict (or stack_packed() for multi-layer),
      pages/table/lens/active as in v1; cos_sel/sin_sel [R, hd//2] rope
      rows at each ROW's position. Returns (h_out, k_new, v_new) — the
      rope'd per-row k/v for the CALLER's page scatter (same pool
      bytes as the unfused engine). With head=pack_lm_head(...) the
      schedule appends the final norm + the lm_head vocab tiles +
      running argmax and ALSO returns (tok [R] i32 greedy argmax,
      maxv [R] f32 its logit, logits [R, head_v]) — the whole-step
      mode. head_v = real (unpadded, local under tp) vocab columns.
      head_k = K > 1 generalizes the running argmax to a running top-K
      merge (the sampling fold): the return becomes (tok [R, K] i32
      vocab ids, maxv [R, K] f32 their logits), BOTH ordered (value
      desc, id-asc ties) bitwise-identically to `lax.top_k` on the full
      row — column 0 is exactly the greedy pair — and the [R, V]
      logits OUTPUT IS DROPPED from the pallas_call: full logits never
      exist, not even as an unused buffer (asserted on the traced
      jaxpr in tests). Requires K <= 128 and K <= head_v.

    tq > 1 (speculative verify): rows are slot-major feed tokens;
      wmask [R] gates which feed tokens' k/v substitute into their page
      blocks (the engine's write_ok, flattened) — ungated rows see the
      pool's stale bytes exactly like the unfused scatter-then-attend
      path, and the ATTN phase applies the ragged kernel's causal mask.

    Tensor-parallel segments (run per shard under shard_map, exact-mode
    gathers BETWEEN invocations):
      seg="qkv":  h + local mk -> (attn [R, nh_l*hd], k_new, v_new)
      seg="tail": h + attn_in (gathered, full heads) -> (h_after_o,
                  act [R, mlp_v] local gate*up)
      seg="down": h + act_in (gathered, full ffn) -> h_out, plus the
                  head outputs when head= rides (vocab-local slice).

    The call is a `jax.jit` of its own, keyed by the shapes and the
    static arguments: the 32 per-layer calls of a decode program are one
    trace of the kernel and one lowering to a Mosaic module, called 32
    times (XLA inlines the calls), not 32 of each — python tracing was
    most of a serving engine's set-up (PERF.md 6, PR 29).
    """
    return _decode_megakernel(
        h, mk, k_pages, v_pages, page_table, lens, active, cos_sel,
        sin_sel, head, wmask, attn_in, act_in, nh=nh, nh_kv=nh_kv, hd=hd,
        eps=eps, scale=scale, interpret=interpret, seg=seg, head_v=head_v,
        head_k=head_k, mlp_v=mlp_v, tq=tq, block_bytes=MM_BLOCK_BYTES)


@functools.partial(jax.jit, static_argnames=(
    "nh", "nh_kv", "hd", "eps", "scale", "interpret", "seg", "head_v",
    "head_k", "mlp_v", "tq", "block_bytes"))
def _decode_megakernel(h, mk, k_pages, v_pages, page_table, lens, active,
                       cos_sel, sin_sel, head, wmask, attn_in, act_in, *,
                       nh, nh_kv, hd, eps, scale, interpret, seg, head_v,
                       head_k, mlp_v, tq, block_bytes):
    # block_bytes: the module's MM_BLOCK_BYTES as the caller saw it (the
    # tests' handle on the constant), static so it keys the trace
    R, H = h.shape
    if seg not in SEG_PHASES:
        raise ValueError(f"unknown megakernel segment {seg!r}")
    has_attn = seg in ("full", "qkv")
    stacked = bool(has_attn and k_pages.ndim == 5)
    L = mk["wq"].shape[0] if stacked else 1
    T = int(tq)
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    cdtype = h.dtype
    NQ, NK = nh * hd, nh_kv * hd

    def shp(key):
        sh = mk[key].shape
        return sh[1:] if (stacked and key in _STACKED_KEYS) else sh

    counts, bks, bns = {}, {}, {}

    def mm_dims(P, w):
        kdim, ndim = w.shape[-2:]
        bks[P], bns[P], k_pad, n_pad = mm_tile_plan(
            kdim, ndim, w.dtype.itemsize, block_bytes)
        assert (k_pad, n_pad) == (kdim, ndim), (
            "weights must come from pack_decode_layer / pack_lm_head",
            (kdim, ndim), (k_pad, n_pad))
        counts[P] = (kdim // bks[P], ndim // bns[P])
        return kdim, ndim

    dims = {"R": R, "H": H, "nh": nh, "nh_kv": nh_kv, "hd": hd}
    if seg in ("full", "qkv"):
        Hp, NQp = mm_dims(PH_Q, mk["wq"])
        _, NKp = mm_dims(PH_K, mk["wk"])
        mm_dims(PH_V, mk["wv"])
        assert R == (R // T) * T
        b = R // T
        pshape = k_pages.shape[1:] if stacked else k_pages.shape
        n_pages, p, h_kv, dd = pshape
        assert dd == hd and h_kv == nh_kv, (k_pages.shape, nh_kv, hd)
        mp = page_table.shape[1]
    else:
        b, mp, p, n_pages = R, 0, 1, 1
        NQp = NKp = None
    if seg == "full":
        assert NQ == H, (nh, hd, H)
        _, Hop = mm_dims(PH_O, mk["wo"])
        _, Fg = mm_dims(PH_G, mk["wg"])
        mm_dims(PH_U, mk["wu"])
        Fp, _ = mm_dims(PH_D, mk["wd"])
        # the pack rules derive every pad from (dim, 512) alone, so the
        # q-output, o-input and o-output pads of the SAME hidden size
        # agree
        assert NQp == Hp == Hop == shp("wd")[1], (NQp, Hp, Hop)
        assert Fg == Fp == shp("wu")[1], (Fg, Fp)
    elif seg == "tail":
        Oin, Hop = mm_dims(PH_O, mk["wo"])
        Hg, Fg = mm_dims(PH_G, mk["wg"])
        mm_dims(PH_U, mk["wu"])
        assert Hg == Hop == mk["ln2"].shape[-1], (Hg, Hop)
        assert Fg == shp("wu")[1], (Fg,)
        Hp, Fp = Hop, Fg
        attn_in = _pad_to(attn_in, Oin)
    elif seg == "down":
        Fp, Hop = mm_dims(PH_D, mk["wd"])
        Hp, Fg = Hop, Fp
        act_in = _pad_to(act_in, Fp)
    else:
        Fg = Fp = 0      # qkv: residual pad (Hp) came from wq's k-axis
    if head is not None:
        if seg not in ("full", "down"):
            raise ValueError(
                f"head= rides the step tail (seg 'full' or 'down'), "
                f"not {seg!r}")
        hk, Vp = head["wh"].shape
        assert hk == Hp, (hk, Hp, "lm_head k-pad must match the hidden "
                          "pad (same (dim, 512) rule)")
        mm_dims(PH_H, head["wh"])
        dims["Vh"] = int(Vp if head_v is None else head_v)
        if head_k is not None and not 1 <= int(head_k) <= min(
                128, dims["Vh"]):
            raise ValueError(
                f"head_k must be in [1, min(128, head_v)] — the top-K "
                f"merge rides the [R, 128] select scratch — got "
                f"{head_k} with head_v={dims['Vh']}")
    dims.update(Hp=Hp, NQp=NQp, b=b)

    ph_arr, a0_arr, a1_arr, li_arr = _build_schedule(
        L, b, counts, SEG_PHASES[seg], counts.get(PH_H))
    n_steps = ph_arr.size
    bn_max = max(bns.values())

    hpad = _pad_to(h, Hp)

    # index maps are traced at jit-lowering time, OUTSIDE the
    # enable_x64(False) window below — under the package's global x64
    # every literal must be pinned to i32 or the block indices promote
    # to i64 and Mosaic/interpret lowering rejects them
    i32 = jnp.int32

    def full_spec(shape):
        return pl.BlockSpec(shape, lambda st, *_: (0,) * len(shape))

    def w_spec(P, key, stk):
        nk, nn = counts[P]
        bk, bn = bks[P], bns[P]

        def idx(st, ph, a0, a1, li, *rest):
            mine = ph[st] == P
            before = ph[st] < P
            k = jnp.where(mine, a0[st],
                          jnp.where(before, i32(0), i32(nk - 1)))
            n = jnp.where(mine, a1[st],
                          jnp.where(before, i32(0), i32(nn - 1)))
            return (li[st], k, n) if stk else (k, n)

        return pl.BlockSpec(((1, bk, bn) if stk else (bk, bn)), idx)

    def s_spec(P, stk):
        nn = counts[P][1]
        bn = bns[P]

        def idx(st, ph, a0, a1, li, *rest):
            mine = ph[st] == P
            before = ph[st] < P
            n = jnp.where(mine, a1[st],
                          jnp.where(before, i32(0), i32(nn - 1)))
            return (li[st], 0, n) if stk else (0, n)

        return pl.BlockSpec(((1, 1, bn) if stk else (1, bn)), idx)

    def ln_spec():
        def idx(st, ph, a0, a1, li, *rest):
            return (li[st], 0, 0) if stacked else (0, 0)

        return pl.BlockSpec(((1, 1, Hp) if stacked else (1, Hp)), idx)

    def out_kv_spec():
        if stacked:
            return pl.BlockSpec((1, R, NKp),
                                lambda st, ph, a0, a1, li, *_:
                                (li[st], 0, 0))
        return pl.BlockSpec((R, NKp), lambda st, *_: (0, 0))

    def logits_spec():
        nnh = counts[PH_H][1]
        bnh = bns[PH_H]

        def idx(st, ph, a0, a1, li, *rest):
            mine = ph[st] == PH_H
            return (0, jnp.where(mine, a1[st], i32(0)))

        return pl.BlockSpec((R, bnh), idx)

    # -- assemble inputs / outputs / scratch per segment ---------------
    names, in_specs, operands = [], [], []

    def add(name, arr, spec):
        names.append(name)
        in_specs.append(spec)
        operands.append(arr)

    # scalar prefetch (names first — kernel unpacks by name)
    pre_names = ["ph", "a0", "a1", "li"]
    pre_ops = [jnp.asarray(ph_arr), jnp.asarray(a0_arr),
               jnp.asarray(a1_arr), jnp.asarray(li_arr)]
    if has_attn:
        table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
        lens_i = lens.astype(jnp.int32)
        act_i = (jnp.ones((b,), jnp.int32) if active is None
                 else active.astype(jnp.int32))
        wm_i = (jnp.ones((R,), jnp.int32) if wmask is None
                else wmask.astype(jnp.int32))
        pre_names += ["tbl", "lens", "act", "wm"]
        pre_ops += [table, lens_i, act_i, wm_i]

    add("h", hpad, full_spec((R, Hp)))
    if has_attn:
        add("cos", cos_sel, full_spec((R, hd // 2)))
        add("sin", sin_sel, full_spec((R, hd // 2)))
        add("ln1", mk["ln1"], ln_spec())
        for P, key in ((PH_Q, "q"), (PH_K, "k"), (PH_V, "v")):
            add("w" + key, mk["w" + key], w_spec(P, key, stacked))
            add("s" + key, mk["s" + key], s_spec(P, stacked))
    if seg == "tail":
        add("attn_in", attn_in, full_spec(attn_in.shape))
    if seg in ("full", "tail"):
        add("ln2", mk["ln2"], ln_spec())
        for P, key in ((PH_O, "o"), (PH_G, "g"), (PH_U, "u")):
            add("w" + key, mk["w" + key], w_spec(P, key, stacked))
            add("s" + key, mk["s" + key], s_spec(P, stacked))
    if seg == "down":
        add("act_in", act_in, full_spec(act_in.shape))
    if seg in ("full", "down"):
        add("wd", mk["wd"], w_spec(PH_D, "d", stacked))
        add("sd", mk["sd"], s_spec(PH_D, stacked))
    if has_attn:
        # the pools stay in HBM: the ATTN step copies a slot's live
        # pages itself (page_copies in _mk_kernel)
        add("kp", k_pages, pl.BlockSpec(memory_space=pl.ANY))
        add("vp", v_pages, pl.BlockSpec(memory_space=pl.ANY))
    if head is not None:
        add("nf", head["nf"], full_spec((1, Hp)))
        add("wh", head["wh"], w_spec(PH_H, "h", False))
        add("sh", head["sh"], s_spec(PH_H, False))

    out_names, out_specs, out_shapes = [], [], []

    def add_out(name, shape, spec, dtype=None):
        out_names.append(name)
        out_specs.append(spec)
        out_shapes.append(jax.ShapeDtypeStruct(shape, dtype or cdtype))

    if seg == "qkv":
        add_out("attn_out", (R, NQp), full_spec((R, NQp)))
    else:
        add_out("ho", (R, Hp), full_spec((R, Hp)))
    if has_attn:
        kv_shape = ((L, R, NKp) if stacked else (R, NKp))
        add_out("kn", kv_shape, out_kv_spec())
        add_out("vn", kv_shape, out_kv_spec())
    if seg == "tail":
        add_out("act_out", (R, Fg), full_spec((R, Fg)))
    if head is not None:
        add_out("tok", (R, 128), full_spec((R, 128)), jnp.int32)
        add_out("maxv", (R, 128), full_spec((R, 128)), jnp.float32)
        if head_k is None or int(head_k) == 1:
            add_out("logits", (R, head["wh"].shape[1]), logits_spec())

    scr_names = ["h_scr", "x_scr", "acc_scr"]
    scratch = [pltpu.VMEM((R, Hp), cdtype), pltpu.VMEM((R, Hp), cdtype),
               pltpu.VMEM((R, bn_max), jnp.float32)]
    if has_attn:
        scr_names += ["q_scr", "k_scr", "v_scr", "m_scr", "l_scr",
                      "aacc_scr"]
        # q/k/v (and the attention rows below) are addressed one
        # dynamic ROW at a time; Mosaic proves no alignment for a
        # dynamic sublane index into a packed (sub-32-bit) buffer, so
        # these hold the cdtype-rounded values in f32 containers
        scratch += [pltpu.VMEM((R, NQp), jnp.float32),
                    pltpu.VMEM((R, NKp), jnp.float32),
                    pltpu.VMEM((R, NKp), jnp.float32),
                    pltpu.VMEM((nh * T, 128), jnp.float32),
                    pltpu.VMEM((nh * T, 128), jnp.float32),
                    pltpu.VMEM((nh * T, hd), jnp.float32)]
    if has_attn:
        scr_names += ["attn_scr", "kbuf", "vbuf"]
        scratch += [pltpu.VMEM((R, NQp), jnp.float32),
                    pltpu.VMEM((2, p, nh_kv, hd), k_pages.dtype),
                    pltpu.VMEM((2, p, nh_kv, hd), v_pages.dtype)]
    if seg in ("full", "tail"):
        scr_names += ["g_scr", "u_scr", "act_scr"]
        scratch += [pltpu.VMEM((R, Fg), cdtype)] * 3
    if head is not None:
        scr_names += ["amax_scr", "aidx_scr"]
        scratch += [pltpu.VMEM((R, 128), jnp.float32),
                    pltpu.VMEM((R, 128), jnp.int32)]

    # DMA semaphores [k | v, buffer] of the page copies: last, so the
    # VMEM accounting below sees arrays only
    sems = [pltpu.SemaphoreType.DMA((2, 2))] if has_attn else []
    kernel = functools.partial(
        _mk_kernel, names=tuple(pre_names + names + out_names
                                + scr_names + ["page_sem"] * len(sems)),
        seg=seg, stacked=stacked, counts=counts, bks=bks, bns=bns,
        dims=dims, eps=float(eps), p=p, mp=mp, scale=float(s),
        head=head is not None, T=T,
        head_k=1 if head_k is None else int(head_k))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pre_names),
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch + sems,
    )
    # seven or eight double-buffered weight streams of up to
    # MM_BLOCK_BYTES, and the widest weight block once more in the type
    # it reaches the MXU in (an int8 block converts to twice its bytes
    # of bf16): far past the 16 MiB default at 7B width. The page
    # blocks are no pipelined operands (the pools stay in HBM, block
    # shape None): their two buffers per pool are scratch, counted once,
    # and the f32 copies of one k and one v page, before and after the
    # feed tokens are substituted, are the four temporaries
    f32 = jnp.float32
    blocks = [(sp.block_shape, op.dtype)
              for sp, op in zip(in_specs, operands)
              if sp.block_shape is not None]
    w_ops = [(sp.block_shape, jnp.dtype(mm_operand_dtype(cdtype, op.dtype)))
             for sp, op, name in zip(in_specs, operands, names)
             if name[0] == "w"]
    limit = vmem_limit(
        blocks=blocks + [(sp.block_shape, sd.dtype)
                         for sp, sd in zip(out_specs, out_shapes)],
        scratch=[(m.shape, m.dtype) for m in scratch],
        temps=([((p, nh_kv, hd), f32)] * 4 if has_attn else [])
        + [((R, bn_max), f32)] * 2
        + [max(w_ops, key=lambda t: math.prod(t[0]) * t[1].itemsize)])
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=limit),
            interpret=interpret,
        )(*pre_ops, *operands)
    res = dict(zip(out_names, outs))
    if seg == "qkv":
        ret = [res["attn_out"][:, :NQ], res["kn"][..., :NK],
               res["vn"][..., :NK]]
    elif seg == "full":
        ret = [res["ho"][:, :H], res["kn"][..., :NK],
               res["vn"][..., :NK]]
    elif seg == "tail":
        act = res["act_out"]
        ret = [res["ho"][:, :H],
               act if mlp_v is None else act[:, :mlp_v]]
    else:
        ret = [res["ho"][:, :H]]
    if head is not None:
        if head_k is not None and int(head_k) > 1:
            K = int(head_k)
            ret += [res["tok"][:, :K], res["maxv"][:, :K]]
        else:
            ret += [res["tok"][:, 0], res["maxv"][:, 0],
                    res["logits"][:, :dims["Vh"]]]
    return tuple(ret) if len(ret) > 1 else ret[0]


def layer_tile_plan(layer, slots, tp=1):
    """What ONE layer's schedule walk is made of, from a packed layer
    (pack_decode_layer's dict; tp = the shards its column-parallel
    projections are concatenated for): per projection [bk, bn] as
    decode_megakernel() will draw them, and the grid steps of the
    layer's matmul phases and of its attention phase: one step a slot,
    which loops over the slot's live pages (`attention_pages`: the walk
    no longer depends on the page table's width). Static facts of an
    engine: health()["mk_tile_plan"]."""
    blocks, steps = {}, 0
    for key in "qkvogud":
        w = layer["w" + key]
        k, n = w.shape
        if key in "qkvgu":
            n //= tp
        bk, bn, _, _ = mm_tile_plan(k, n, w.dtype.itemsize)
        blocks[key] = [bk, bn]
        steps += (k // bk) * (n // bn)
    return {"blocks": blocks, "attention_pages": "live",
            "layer_steps": {"matmul": steps, "attention": slots}}


def megakernel_weight_bytes(mk, n_layers=None, head=None):
    """Weight bytes one decode step streams through this kernel (the
    roofline numerator): every projection's values
    + scales + both norms, per layer — plus the lm_head pack when the
    whole-step mode streams it too."""
    keys = ("wq", "sq", "wk", "sk", "wv", "sv", "wo", "so",
            "wg", "sg", "wu", "su", "wd", "sd", "ln1", "ln2")
    total = sum(int(np.prod(mk[k].shape)) * mk[k].dtype.itemsize
                for k in keys)
    if n_layers is not None:       # per-layer dict counted L times
        total *= n_layers
    if head is not None:
        total += sum(int(np.prod(head[k].shape)) * head[k].dtype.itemsize
                     for k in ("wh", "sh", "nf"))
    return total
