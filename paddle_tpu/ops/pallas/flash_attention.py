"""Pallas flash attention (TPU), forward + fused backward.

Replaces the reference's CUDA fused attention
(ref: paddle/fluid/operators/fused/fused_multi_transformer_op.cu.h:13 —
FasterTransformer-derived masked MHA; fmha_ref.h) with online-softmax
tiled kernels. TPU-first design:

- TRANSPOSE-FREE fast path: when the head dim is a lane multiple
  (d % 128 == 0 — the d=128 LLM geometries), q/k/v are taken as
  [b, s, h*d] VIEWS of the model's native [b, s, h, d] layout (a free
  reshape) and the grid's head dimension indexes lane-blocks of size d
  directly. Head dims that are not lane multiples fall back to the
  transposed [b*h, s, d] layout — the SAME kernels with a single
  lane-covering "head" (Mosaic requires the block's trailing two dims
  to be 8/128-divisible or dim-covering, so a squeezed head dim cannot
  sit in sublane position).
- K/V are streamed from HBM block-by-block via the grid's innermost
  dimension (Pallas double-buffers the DMAs); only [bk, d] tiles are
  ever VMEM-resident, so sequence length is bounded by HBM, not VMEM.
- The [s, s] score matrix is never materialized. Softmax statistics
  (running max + logsumexp) live in VMEM scratch that persists across
  the innermost grid dimension.
- Backward is ONE fused kernel (5 matmuls + 1 exp per block pair where
  separate dQ and dK/dV kernels took 7 + 2). The grid runs K/V blocks
  outer, Q blocks inner: dK/dV accumulate in VMEM scratch across the
  inner dimension, while per-(k-block) dQ partials stream to an
  [nk, ...] float32 HBM buffer — each block written exactly once — and
  are reduced by one XLA sum afterwards (the accumulation pattern of
  public TPU splash attention's fused backward; no read-modify-write
  DMAs). The buffer and the sum shrink with nk: 1.42 ms a call at
  bk = 256, 0.36 at bk = 1024 (shape below).
- Additive masks are supported natively as a blocked operand (bool
  masks are converted to additive form in the wrapper); causal masking
  is computed inline from block indices with whole-block skipping, and
  the key-padding compare is built only where the padded length leaves
  padding (`_block_valid`). Building the mask on the diagonal blocks
  alone was tried (PR 33) and bought nothing: within 1 % at every
  geometry of the sweep below. The mask is not what a step waits for.
- Block geometry is drawn per call from its shape (`flash_geometry`):
  the sequence pads to 256, the blocks grow to 1024 x 1024 where they
  divide the padded length, and `_fit_geometry` shrinks the batch
  slices a step, then bq, then bk under the VMEM budget. What a grid
  step costs grows with its q rows times its k steps (the per-row
  running max / sum / rescale is paid once a k step whatever bk is), so
  wide k blocks win. Measured on one v5e (PR 33,
  docs/probes/flash_train_probe.py, device time of one call at
  (2, 4096, 16, 128) bf16 causal): forward 4.99 ms at 256 x 256 with two
  slices a step (what every call ran before PR 33), 2.57 at 512 x 512,
  1.66 at 512 x 1024, 1.45 at 1024 x 1024 with one slice; backward
  kernel 5.21 / 2.89 / 2.59 / 2.61 ms.
- `nb` batch slices share one grid step where the budget leaves room.
  They pay where the blocks cannot grow: at (64, 256, 16, 128), one
  256 x 256 block a head, eight slices a step ran the forward in 0.75 ms
  and the backward kernel in 1.11 against 1.34 / 1.42 for one slice
  (same probe, `--nb-max`). Where both fit, larger blocks beat more
  slices: at (16, 1024, 16, 128) one slice at 1024 x 1024 took 1.02 ms
  forward against 1.95 for eight at 256 x 256, which is why
  `_fit_geometry` gives up slices before it shrinks a block.
- lse/delta ride in 8-lane (not 128-lane) replicated layouts to bound
  the HBM footprint of the softmax stats at large batch.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
ROW_LANES = 8  # lane replication for per-row stats (lse/delta) in HBM


def _prec(dt):
    """MXU precision by operand dtype: native passes for low precision,
    "highest" for f32 (the package's f32 API-parity contract — DEFAULT
    would silently truncate f32 attention to one bf16 pass on TPU).

    Deliberately NOT overridable by jax.default_matmul_precision: like
    cuDNN fused attention, the kernel's precision contract is a function
    of the input dtype only — callers wanting f32-precision attention on
    bf16 data should cast to f32 (or use the XLA sdpa fallback)."""
    return (jax.lax.Precision.DEFAULT
            if jnp.dtype(dt) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float16))
            else jax.lax.Precision.HIGHEST)


def _dropout_keep(seed_ref, sl, q_start, k_start, bq, bk, dropout_p):
    """Deterministic keep mask from a counter-based integer hash of
    (seed, slice, global row, global col) — recomputing the same tuple in
    the forward and backward kernels regenerates the identical mask,
    so no mask tensor is ever stored. Pure VPU integer ops (xxhash-style
    avalanche), bit-identical across real TPU and interpret mode (the
    pltpu hardware PRNG is stubbed to zeros on the CPU interpreter).
    Applied AFTER the softmax denominator accumulates (dropout scales the
    normalized attention weights, ref fmha semantics), so lse stays the
    pre-dropout logsumexp and the delta = rowsum(dO*O) trick still holds:
    rowsum(da*a) = rowsum(do*o) because the keep mask re-pairs with p."""
    u = jnp.uint32
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0) + u(q_start)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1) + u(k_start)
    h = (seed_ref[0].astype(jnp.uint32) * u(2654435761)
         + sl.astype(jnp.uint32) * u(0x9E3779B9))
    h = h ^ (rows * u(0x85EBCA6B)) ^ (cols * u(0xC2B2AE35))
    h = h ^ (h >> u(15))
    h = h * u(0x2C1B3C6D)
    h = h ^ (h >> u(12))
    h = h * u(0x297A2D39)
    h = h ^ (h >> u(15))
    thresh = min(int(dropout_p * 4294967296.0), 4294967295)
    return h >= u(thresh)


def _slice_id(bb, hh, j, nb, nheads):
    """Unique (batch slice, head) id for the dropout hash stream."""
    return (bb * nb + j) * nheads + hh


def _block_valid(*, bq, bk, nk, s_true, q_start, k_start, causal):
    """Validity mask of a live block, None where the shape alone says every
    cell is valid (no causal mask and no key padding): computed ONCE per
    grid step and shared by all nb slices (the iota/compare VPU work is
    not per-slice)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_start
    valid = None
    if nk * bk > s_true:    # key padding beyond the true sequence
        valid = cols < s_true
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_start
        below = rows >= cols
        valid = below if valid is None else valid & below
    return valid


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, nb, bq, bk, nk, s_true, causal,
                scale, has_mask, mask_batched, nheads, dropout_p=0.0):
    idx = 0
    mask_ref = rest[idx] if has_mask else None
    idx += 1 if has_mask else 0
    seed_ref = rest[idx] if dropout_p > 0.0 else None
    idx += 1 if dropout_p > 0.0 else 0
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[idx:]

    bb = pl.program_id(0)  # hoisted: program_id inside a pl.when body
    #                          is rejected by the interpreter lowering
    hh = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        valid = _block_valid(bq=bq, bk=bk, nk=nk, s_true=s_true,
                             q_start=q_start, k_start=k_start, causal=causal)
        for j in range(nb):
            # MXU matmuls run in the INPUT dtype (bf16 at training shapes)
            # with f32 accumulation; only the softmax math is f32
            q = q_ref[j]
            k = k_ref[j]
            lg = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(q.dtype)) * jnp.float32(scale)
            if mask_ref is not None:
                mj = mask_ref[j] if mask_batched else mask_ref[0]
                lg = lg + mj.astype(jnp.float32)
            if valid is not None:
                lg = jnp.where(valid, lg, jnp.float32(NEG_INF))

            m_prev = m_scr[j][:, :1]
            l_prev = l_scr[j][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(lg, axis=-1, keepdims=True))
            p = jnp.exp(lg - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_p > 0.0:
                keep = _dropout_keep(seed_ref,
                                     _slice_id(bb, hh, j, nb, nheads),
                                     q_start, k_start, bq, bk, dropout_p)
                p = jnp.where(keep,
                              p * jnp.float32(1.0 / (1.0 - dropout_p)), 0.0)
            acc_scr[j] = alpha * acc_scr[j] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(q.dtype))
            m_scr[j] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[j] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    if causal:
        # whole blocks above the diagonal are masked; skip their MXU work
        pl.when(k_start <= q_start + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _emit():
        for j in range(nb):
            m_fin = m_scr[j][:, :1]
            l_fin = l_scr[j][:, :1]
            o_ref[j] = (acc_scr[j] /
                        jnp.maximum(l_fin, jnp.float32(1e-30))
                        ).astype(o_ref.dtype)
            # logsumexp rows; padded/fully-masked rows have l == 0 -> -inf
            lse = m_fin + jnp.log(jnp.maximum(l_fin, jnp.float32(1e-30)))
            lse_ref[j] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _pick_nb(b, mask_group, nb_max=8):
    """Batch slices per grid step: largest power of two <= nb_max dividing
    b, constrained (fallback layout only) so a grouped-mask block never
    spans a mask-group boundary."""
    nb = nb_max
    while nb > 1 and b % nb:
        nb //= 2
    if mask_group is not None and mask_group > 1:
        while nb > 1 and mask_group % nb:
            nb //= 2
    return nb


VMEM_BUDGET = 15 * 1024 * 1024  # of Mosaic's 16 MiB scoped default on v5e


def _step_vmem_bytes(nb, bq, bk, d, isz, has_mask, mask_batched,
                     dropout=False):
    """Worst-kernel (fused backward) per-grid-step VMEM bytes:
    double-buffered operand blocks (q, do, k, v, lse, delta, mask),
    double-buffered outputs (dq partial, dk, dv), f32 dk/dv scratch, and
    the body's temporaries: [bq, bk] float32 tiles (one and a half with
    16-bit operands, five where float32 operands take the multi-pass
    matmul, two more for dropout's hash and keep mask) and the float32
    results of the three [., d] matmuls. VMEM tiles are 128 lanes wide:
    a d = 64 block and the ROW_LANES-wide lse / delta rows take a whole
    tile. An upper bound, 0.6 to 4.5 MiB above the least limit the
    compiler accepts in each of 17 classes of call (PR 33:
    `docs/probes/flash_train_probe.py --aot --classes`, PERF.md 6)."""
    db = 2  # Pallas double-buffers HBM<->VMEM block DMAs
    d = -(-d // 128) * 128
    ins = (2 * nb * bq * d + 2 * nb * bk * d) * isz + 2 * nb * bq * 128 * 4
    if has_mask:
        ins += (nb if mask_batched else 1) * bq * bk * 4
    outs = nb * bq * d * 4 + 2 * nb * bk * d * isz  # dq partial is f32
    scratch = 2 * nb * bk * d * 4
    tiles = (1.5 if isz <= 2 else 5) + 2 * dropout
    temps = int(tiles * bq * bk * 4) + (2 * bk + bq) * d * 4
    return db * (ins + outs) + scratch + temps


def _fit_geometry(b, d, itemsize, has_mask, mask_group, bq, bk, nb_max,
                  dropout=False):
    """Shrink (nb, then bq, then bk) until the worst kernel's per-step
    VMEM fits the budget (ADVICE r2 medium: f32 inputs + d>=128 + a
    batch-varying mask at bq=bk=256/nb=8 exceed ~16MB and fail to
    compile). In that order because the chip's sweep says so (PR 33): a
    step's cost grows with its q rows times its k steps, so a wide bk is
    worth more than a tall bq, and either more than slices a step.
    mask_group: None (no mask) / 1 (per-slice mask) / g > 1 (one mask
    shared by groups of g slices — fallback layout)."""
    batched = mask_group == 1 if has_mask else False
    nb = _pick_nb(b, mask_group if has_mask else None, nb_max)
    while True:
        if _step_vmem_bytes(nb, bq, bk, d, itemsize, has_mask, batched,
                            dropout) <= VMEM_BUDGET:
            return bq, bk, nb
        if nb > 1:
            nb //= 2
        elif bq > 128:
            bq //= 2
        elif bk > 128:
            bk //= 2
        else:
            return bq, bk, nb  # minimal geometry; let Mosaic report


BLOCK_UNIT = 256            # sequences pad to a multiple of this
BLOCK_TARGET = (1024, 1024)  # (bq, bk) drawn where the padded length allows


def _drawn_block(s_pad, target):
    """The largest block <= target that is BLOCK_UNIT times a power of
    two and divides the padded length."""
    blk = BLOCK_UNIT
    while blk * 2 <= target and s_pad % (blk * 2) == 0:
        blk *= 2
    return blk


def _geometry(B, d, itemsize, has_mask, mask_group, s_true, bq, bk, nb_max,
              dropout=False):
    """(bq, bk, nb, s_pad) of a call. Explicit bq / bk are taken as given
    (the sequence pads to the larger); None draws the block from what the
    call shows: the sequence pads to BLOCK_UNIT whatever the target, so a
    larger block never inflates a short sequence (s <= 256 keeps
    256 x 256), and `_fit_geometry` then shrinks nb and the blocks under
    the VMEM budget (operand size, head dim, mask kind)."""
    unit = max(bq or BLOCK_UNIT, bk or BLOCK_UNIT)
    s_pad = -(-s_true // unit) * unit
    bq = min(bq, s_pad) if bq else _drawn_block(s_pad, BLOCK_TARGET[0])
    bk = min(bk, s_pad) if bk else _drawn_block(s_pad, BLOCK_TARGET[1])
    return _fit_geometry(B, d, itemsize, has_mask, mask_group, bq, bk,
                         nb_max, dropout) + (s_pad,)


def _mask_rows(mask_shape, b, h, fast):
    """Leading extent of the mask as the kernels see it: `_prep`
    broadcasts the batch dim to 1 or b and the head dim to 1 or h, and
    the fallback layout folds per-head masks into per-slice rows."""
    mb = mask_shape[0] if mask_shape[0] in (1, b) else b
    mh = mask_shape[1] if mask_shape[1] in (1, h) else h
    return b * h if (not fast and mh > 1) else mb


def flash_geometry(q_shape, dtype, mask_shape=None, bq=None, bk=None,
                   nb_max=8, dropout=False):
    """(bq, bk, nb, s_pad) that `make_flash_attention(bq, bk, nb_max=)`
    runs a [b, s, h, d] call of this dtype with (mask_shape: the additive
    mask's [b|1, h|1, sq, sk], None without one; dropout: through one of
    the build's dropout entries)."""
    b, s, h, d = q_shape
    fast = d % 128 == 0
    B, hk = (b, h) if fast else (b * h, 1)
    mg = None
    if mask_shape is not None:
        mg = _mask_group(_mask_rows(mask_shape, b, h, fast), B, hk)
    return _geometry(B, d, jnp.dtype(dtype).itemsize, mask_shape is not None,
                     mg, s, bq, bk, nb_max, dropout)


def _mask_group(rows, B, h):
    """nb-constraint/VMEM descriptor for a mask of `rows` leading rows:
    1 = per-slice (batched block), g > 1 = one mask shared by groups of
    g slices (fallback layout; nb must divide g), None = shared by
    everything (no nb constraint, single-row block)."""
    if h > 1:  # fast path: head/batch grid dims index the mask directly
        return 1 if rows > 1 else None
    g = B // rows
    return g if g > 1 else 1


def _mask_spec(mask, B, h_grid, nb, bq, bk, bwd, causal=False):
    """BlockSpec for the additive mask.

    Fast path (h_grid > 1): mask stays [b|1, h|1, s, s]; the batch/head
    grid dims index dims 0/1 directly (head squeezed — legal: it is not
    in the block's trailing two dims). Fallback (h_grid == 1): heads are
    folded into B and the mask arrives [Bm, 1, s, s] with Bm in
    {1, b, b*h}; group = B // Bm slices share one mask row (nb is
    constrained to divide the group by _pick_nb). Under causal the
    (i, kb) coordinates of compute-skipped blocks clamp to the diagonal
    so their [bq, bk] mask DMA is elided like the k/v and q-side
    operands. Returns (spec, mask_batched, group)."""
    mb, mh = mask.shape[0], mask.shape[1]

    if causal:
        # literals pinned i32: interpret-mode pallas_call under an OUTER
        # jit re-discharges index maps outside the enable_x64(False)
        # window, where a weak python-int re-canonicalizes to i64 and
        # MLIR verification rejects the mixed floor_divide (the same
        # trap class as the decode-megakernel where-operand pins)
        if bwd:
            def cell(kb, i):  # skipped q blocks clamp up to the diagonal
                return (jnp.maximum(i, (kb * jnp.int32(bk)) // jnp.int32(bq)),
                        kb)
        else:
            def cell(i, kb):  # skipped k blocks clamp back to the diagonal
                return (i, jnp.minimum(kb, (i * jnp.int32(bq)
                                            + jnp.int32(bq - 1))
                                       // jnp.int32(bk)))
    else:
        if bwd:
            def cell(kb, i):
                return (i, kb)
        else:
            def cell(i, kb):
                return (i, kb)

    if h_grid > 1:
        per_head = mh > 1
        batched = mb > 1
        blk = (nb if batched else 1, None, bq, bk)

        if bwd:  # grid (bb, hh, kb, i)
            def imap(bb, hh, kb, i):
                return (bb if batched else 0,
                        hh if per_head else 0) + cell(kb, i)
        else:    # grid (bb, hh, i, kb)
            def imap(bb, hh, i, kb):
                return (bb if batched else 0,
                        hh if per_head else 0) + cell(i, kb)
        return pl.BlockSpec(blk, imap), batched, 1

    group = B // mb
    if group == 1:
        if bwd:
            def imap(bb, hh, kb, i):
                return (bb, 0) + cell(kb, i)
        else:
            def imap(bb, hh, i, kb):
                return (bb, 0) + cell(i, kb)
        return pl.BlockSpec((nb, None, bq, bk), imap), True, 1
    # one mask row shared by the whole block (nb divides group)
    if bwd:
        def imap(bb, hh, kb, i):
            return (bb * nb // group, 0) + cell(kb, i)
    else:
        def imap(bb, hh, i, kb):
            return (bb * nb // group, 0) + cell(i, kb)
    return pl.BlockSpec((1, None, bq, bk), imap), False, group


def _flash_fwd(q, k, v, mask, h, causal, scale, bq, bk, nb, s_true,
               interpret, dropout_p=0.0, seed=None):
    """q,k,v: [B, s, h*d] (seq padded to block multiples) where B carries
    the batch (fast path) or batch*heads with h == 1 (fallback); mask:
    [b|1, h|1, s, s] additive | None; s_true = unpadded sequence length
    (keys beyond it are masked out); (bq, bk, nb) from `_geometry`.
    Returns (out [B, s, h*d], lse [B, h, s, ROW_LANES] — lane-replicated
    logsumexp)."""
    B, s, H = q.shape
    d = H // h
    has_mask = mask is not None
    nq = s // bq
    nk = s // bk

    q_spec = pl.BlockSpec((nb, bq, d), lambda bb, hh, i, kb: (bb, i, hh))
    if causal:
        # blocks above the diagonal are compute-skipped; CLAMP their K/V
        # block index to the diagonal so consecutive skipped iterations
        # see an unchanged index and Pallas elides the DMA entirely —
        # ~half the K/V HBM streaming at causal shapes
        def _kv_map(bb, hh, i, kb):
            # i32-pinned literals: see _mask_spec's causal clamp note
            return (bb, jnp.minimum(kb, (i * jnp.int32(bq)
                                         + jnp.int32(bq - 1))
                                    // jnp.int32(bk)), hh)
        kv_spec = pl.BlockSpec((nb, bk, d), _kv_map)
    else:
        kv_spec = pl.BlockSpec((nb, bk, d),
                               lambda bb, hh, i, kb: (bb, kb, hh))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    mask_batched = False
    if has_mask:
        spec, mask_batched, _ = _mask_spec(mask, B, h, nb, bq, bk, bwd=False, causal=causal)
        in_specs.append(spec)
        args.append(mask)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    kernel = functools.partial(
        _fwd_kernel, nb=nb, bq=bq, bk=bk, nk=nk, s_true=s_true,
        causal=causal, scale=scale, has_mask=has_mask,
        mask_batched=mask_batched, nheads=h, dropout_p=dropout_p)
    # x64 must be off while tracing the kernel/index maps: Mosaic rejects
    # i64 grid indices (the package enables x64 globally for API parity).
    with jax.enable_x64(False):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B // nb, h, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((nb, bq, d),
                             lambda bb, hh, i, kb: (bb, i, hh)),
                pl.BlockSpec((nb, None, bq, ROW_LANES),
                             lambda bb, hh, i, kb: (bb, hh, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, s, H), q.dtype),
                jax.ShapeDtypeStruct((B, h, s, ROW_LANES), jnp.float32),
            ],
            scratch_shapes=[
                # running max / sum only need lane 0; ROW_LANES (8) lanes
                # instead of 128 reclaims ~2MB VMEM toward bigger blocks
                pltpu.VMEM((nb, bq, ROW_LANES), jnp.float32),
                pltpu.VMEM((nb, bq, ROW_LANES), jnp.float32),
                pltpu.VMEM((nb, bq, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="flash_attention_fwd",
        )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# fused backward: one kernel, grid (batch, head, k-blocks, q-blocks)
# ---------------------------------------------------------------------------

def _block_p(q, k, mask_val, lse_col, valid, *, scale):
    # q/k arrive in input dtype (bf16 fast path); accumulate f32 on the MXU
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(q.dtype)) * jnp.float32(scale)
    if mask_val is not None:
        logits = logits + mask_val
    if valid is not None:
        logits = jnp.where(valid, logits, jnp.float32(NEG_INF))
    return jnp.exp(logits - lse_col)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, nb, bq, bk, nq, nk, s_true, causal, scale,
                      has_mask, mask_batched, nheads, dropout_p=0.0):
    """One K/V-block visit computes dV, dK partials (VMEM-accumulated
    across the inner q dimension) AND the dQ partial for this k block
    (streamed to HBM, summed outside): p and dp are computed once where
    the former two-kernel backward computed them twice each."""
    idx = 0
    mask_ref = rest[idx] if has_mask else None
    idx += 1 if has_mask else 0
    seed_ref = rest[idx] if dropout_p > 0.0 else None
    idx += 1 if dropout_p > 0.0 else 0
    dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest[idx:]

    bb = pl.program_id(0)
    hh = pl.program_id(1)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        valid = _block_valid(bq=bq, bk=bk, nk=nk, s_true=s_true,
                             q_start=q_start, k_start=k_start, causal=causal)
        for j in range(nb):
            mj = None
            if mask_ref is not None:
                mj = (mask_ref[j] if mask_batched
                      else mask_ref[0]).astype(jnp.float32)
            q = q_ref[j]
            k = k_ref[j]
            v = v_ref[j]
            do = do_ref[j]
            p = _block_p(q, k, mj, lse_ref[j][:, :1], valid, scale=scale)
            if dropout_p > 0.0:
                # global (row, col) hash — identical to the forward kernel
                keep = _dropout_keep(seed_ref,
                                     _slice_id(bb, hh, j, nb, nheads),
                                     q_start, k_start, bq, bk, dropout_p)
                inv = jnp.float32(1.0 / (1.0 - dropout_p))
                p_v = jnp.where(keep, p * inv, 0.0)
            else:
                p_v = p
            dv_scr[j] += jax.lax.dot_general(
                p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(q.dtype))  # p^T @ do: [bk, d]
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(q.dtype))  # [bq, bk]
            if dropout_p > 0.0:
                dp = jnp.where(keep, dp * inv, 0.0)
            delta = delta_ref[j][:, :1]
            ds = p * (dp - delta) * jnp.float32(scale)  # [bq, bk]
            dk_scr[j] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(q.dtype))  # ds^T @ q: [bk, d]
            dqp_ref[j] = jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_prec(q.dtype)).astype(dqp_ref.dtype)

    if causal:
        skip = k_start > q_start + bq - 1
        pl.when(jnp.logical_not(skip))(_compute)

        @pl.when(skip)
        def _zero_dq():
            # every (k-block, q-block) cell of the partial buffer is
            # flushed; masked-out cells must contribute exact zeros
            dqp_ref[...] = jnp.zeros_like(dqp_ref)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse_l, do, mask, h, causal, scale, bq, bk, nb,
               s_true, interpret, dropout_p=0.0, seed=None):
    """All [B, s, h*d] (seq padded); lse_l [B, h, s, ROW_LANES]; the
    forward's (bq, bk, nb). Returns dq, dk, dv in the same layout."""
    B, s, H = q.shape
    d = H // h
    has_mask = mask is not None
    nq = s // bq
    nk = s // bk

    # delta = rowsum(dO * O) per head — cheap elementwise + reduce, XLA
    # fuses it; the [B, s, h] -> [B, h, s] transpose is d-free (tiny).
    delta = jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)
         ).reshape(B, s, h, d), axis=-1)
    delta_l = jnp.broadcast_to(jnp.swapaxes(delta, 1, 2)[..., None],
                               (B, h, s, ROW_LANES))

    if causal:
        # q-inner mirror of the forward's DMA elision: for k-block kb the
        # compute-skipped q blocks are the PREFIX i < kb*bk//bq — clamp
        # their q/do/lse/delta indices to the diagonal so the repeated
        # index elides the fetch (the dq-partial OUTPUT map stays exact:
        # skipped cells must flush zeros)
        def _qrow(kb, i):
            # i32-pinned literals: see _mask_spec's causal clamp note
            return jnp.maximum(i, (kb * jnp.int32(bk)) // jnp.int32(bq))
        q_spec = pl.BlockSpec(
            (nb, bq, d), lambda bb, hh, kb, i: (bb, _qrow(kb, i), hh))
        row_spec = pl.BlockSpec(
            (nb, None, bq, ROW_LANES),
            lambda bb, hh, kb, i: (bb, hh, _qrow(kb, i), 0))
    else:
        q_spec = pl.BlockSpec((nb, bq, d),
                              lambda bb, hh, kb, i: (bb, i, hh))
        row_spec = pl.BlockSpec((nb, None, bq, ROW_LANES),
                                lambda bb, hh, kb, i: (bb, hh, i, 0))
    kv_spec = pl.BlockSpec((nb, bk, d), lambda bb, hh, kb, i: (bb, kb, hh))

    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    args = [q, k, v, do, lse_l, delta_l]
    mask_batched = False
    if has_mask:
        spec, mask_batched, _ = _mask_spec(mask, B, h, nb, bq, bk, bwd=True, causal=causal)
        in_specs.append(spec)
        args.append(mask)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    with jax.enable_x64(False):
        dq_part, dk, dv = pl.pallas_call(
            functools.partial(_fused_bwd_kernel, nb=nb, bq=bq, bk=bk,
                              nq=nq, nk=nk, s_true=s_true, causal=causal,
                              scale=scale, has_mask=has_mask,
                              mask_batched=mask_batched, nheads=h,
                              dropout_p=dropout_p),
            grid=(B // nb, h, nk, nq),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, nb, bq, d),
                             lambda bb, hh, kb, i: (kb, bb, i, hh)),
                pl.BlockSpec((nb, bk, d),
                             lambda bb, hh, kb, i: (bb, kb, hh)),
                pl.BlockSpec((nb, bk, d),
                             lambda bb, hh, kb, i: (bb, kb, hh)),
            ],
            out_shape=[
                # partials stay f32: each is MXU-accumulated in f32, and
                # rounding to bf16 before the cross-block sum would add
                # ~sqrt(nk) x 2^-8 relative noise to dQ at long sequence
                # (code-review r5); 2x transient HBM for the buffer only
                jax.ShapeDtypeStruct((nk, B, s, H), jnp.float32),
                jax.ShapeDtypeStruct((B, s, H), k.dtype),
                jax.ShapeDtypeStruct((B, s, H), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((nb, bk, d), jnp.float32),
                            pltpu.VMEM((nb, bk, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="flash_attention_bwd",
        )(*args)
    # one streaming reduce over the f32 k-block partials
    if nk == 1:
        dq = dq_part[0].astype(q.dtype)
    else:
        dq = jnp.sum(dq_part, axis=0).astype(q.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# padding / layout / reference helpers
# ---------------------------------------------------------------------------

def _pad_seq(x, blk, axis):
    s = x.shape[axis]
    pad = (-s) % blk
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _xla_ref(q, k, v, causal, scale, mask=None):
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * scale
    if mask is not None:
        logits = logits + mask
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        tri = jnp.tril(jnp.ones((ql, kl), bool), kl - ql)
        logits = jnp.where(tri, logits, NEG_INF)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vT)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def make_flash_attention(bq=None, bk=None, interpret=False, nb_max=8,
                         dropout_p=0.0):
    """Build the custom-vjp flash attention. bq / bk: explicit block sizes
    (the tests'), None = drawn per call from its shape (`flash_geometry`).

    Signature: flash(q, k, v, causal, scale) with [b, s, h, d] inputs,
    and flash_masked(q, k, v, mask, causal, scale) where mask is additive
    [b|1, h|1, sq, sk] (broadcastable). With dropout_p > 0 the build
    ADDITIONALLY exposes flash.dropout(q, k, v, seed, causal, scale) and
    flash.masked_dropout(q, k, v, mask, seed, causal, scale):
    attention-weight dropout runs NATIVELY in the kernels — the keep mask
    is regenerated from (seed, slice, row, col) in the backward kernel,
    never materialized. The plain entries stay deterministic.
    """

    def _geo(x, hk, mask, s_true, dropout):
        """(bq, bk, nb, s_pad) for operands x in the kernels' layout
        [B, s, hk * d] and the prepared mask: the same from the forward's
        unpadded operands and from the backward's residuals."""
        B, _, H = x.shape
        return _geometry(
            B, H // hk, x.dtype.itemsize, mask is not None,
            None if mask is None else _mask_group(mask.shape[0], B, hk),
            s_true, bq, bk, nb_max, dropout)

    def _prep(q, k, v, mask, dropout):
        b, s_true, h, d = q.shape
        # transpose-free fast path: head dim is a lane multiple — take
        # [b, s, h*d] views and index heads as lane-blocks on the grid
        fast = d % 128 == 0
        if fast:
            B, hk = b, h
            qr = q.reshape(b, s_true, h * d)
            kr = k.reshape(b, s_true, h * d)
            vr = v.reshape(b, s_true, h * d)
        else:
            B, hk = b * h, 1
            qr = jnp.swapaxes(q, 1, 2).reshape(B, s_true, d)
            kr = jnp.swapaxes(k, 1, 2).reshape(B, s_true, d)
            vr = jnp.swapaxes(v, 1, 2).reshape(B, s_true, d)
        if mask is not None:
            mb, mh, sq, sk = mask.shape
            # broadcast query/key dims FIRST: a [b,1,1,sk] key-padding mask
            # must apply to every query row, not only row 0 (padding a
            # size-1 query axis would silently unmask rows 1..s-1)
            if sq != s_true or sk != s_true:
                mask = jnp.broadcast_to(mask, (mb, mh, s_true, s_true))
            if mb not in (1, b):
                mask = jnp.broadcast_to(mask, (b,) + mask.shape[1:])
                mb = b
            if mh not in (1, h):
                mask = jnp.broadcast_to(
                    mask, (mask.shape[0], h) + mask.shape[2:])
                mh = h
            if not fast and mh > 1:
                # heads fold into B: per-head masks become per-slice
                mask = jnp.broadcast_to(
                    mask, (b, h) + mask.shape[2:]
                ).reshape(b * h, 1, s_true, s_true)
        bq_, bk_, nb, s_pad = _geo(qr, hk, mask, s_true, dropout)
        qp, kp, vp = (_pad_seq(x, s_pad, 1) for x in (qr, kr, vr))
        mp = mask
        if mask is not None:
            # pad query axis with 0 (rows sliced off); padded keys are
            # excluded by the kernel's s_true column mask
            mp = _pad_seq(_pad_seq(mask, s_pad, 2), s_pad, 3)
        return qp, kp, vp, mp, (b, h, fast), s_true, (bq_, bk_, nb)

    def _unlayout(x, bhf, s_true):
        b, h, fast = bhf
        if fast:
            return x[:, :s_true].reshape(b, s_true, h, -1)
        B, s, d = x.shape
        return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)[:, :s_true]

    def _fwd_impl(q, k, v, mask, causal, scale, seed=None):
        # dropout applies only to the .dropout/.masked_dropout entries
        # (seed provided); the plain entries on the same build stay
        # deterministic
        dp = dropout_p if seed is not None else 0.0
        qp, kp, vp, mp, bhf, s_true, geo = _prep(q, k, v, mask, dp > 0.0)
        o, lse_l = _flash_fwd(qp, kp, vp, mp, bhf[1] if bhf[2] else 1,
                              causal, scale, *geo, s_true, interpret, dp,
                              seed)
        return o, lse_l, qp, kp, vp, mp, bhf, s_true

    def _bwd_impl(res_pack, g, mask, causal, scale, dp=0.0, seed=None):
        qp, kp, vp, o, lse_l, bhf, s_true = res_pack
        b, h, fast = bhf
        hk = h if fast else 1
        geo = _geo(qp, hk, mask, s_true, dp > 0.0)  # the forward's again
        if fast:
            gr = g.reshape(b, s_true, -1)
        else:
            gr = jnp.swapaxes(g, 1, 2).reshape(b * h, s_true, -1)
        gp = _pad_seq(gr, geo[3], 1)
        dq, dk, dv = _flash_bwd(qp, kp, vp, o, lse_l, gp, mask, hk, causal,
                                scale, *geo[:3], s_true, interpret, dp, seed)
        return (_unlayout(dq, bhf, s_true), _unlayout(dk, bhf, s_true),
                _unlayout(dv, bhf, s_true))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
    def flash(q, k, v, causal, scale):
        o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
            q, k, v, None, causal, scale)
        return _unlayout(o, bhf, s_true)

    def flash_fwd(q, k, v, causal, scale):
        o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
            q, k, v, None, causal, scale)
        # Name the kernel-produced residuals so a jax.checkpoint policy
        # (save_only_these_names) can pin them: the backward then reuses
        # o/lse instead of re-running the forward kernel under recompute
        # (train_step recompute_policy="save_attn").
        o = checkpoint_name(o, "sdpa_res")
        lse_l = checkpoint_name(lse_l, "sdpa_res")
        return (_unlayout(o, bhf, s_true),
                (qp, kp, vp, o, lse_l, bhf, s_true))

    def flash_bwd(causal, scale, res, g):
        return _bwd_impl(res, g, None, causal, scale)

    flash.defvjp(flash_fwd, flash_bwd)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
    def flash_masked(q, k, v, mask, causal, scale):
        o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
            q, k, v, mask, causal, scale)
        return _unlayout(o, bhf, s_true)

    def flash_masked_fwd(q, k, v, mask, causal, scale):
        o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
            q, k, v, mask, causal, scale)
        o = checkpoint_name(o, "sdpa_res")
        lse_l = checkpoint_name(lse_l, "sdpa_res")
        return (_unlayout(o, bhf, s_true),
                (qp, kp, vp, mp, o, lse_l, bhf, s_true, mask))

    def flash_masked_bwd(causal, scale, res, g):
        qp, kp, vp, mp, o, lse_l, bhf, s_true, mask = res
        grads = _bwd_impl((qp, kp, vp, o, lse_l, bhf, s_true), g, mp,
                          causal, scale)
        return grads + (jnp.zeros_like(mask),)

    flash_masked.defvjp(flash_masked_fwd, flash_masked_bwd)

    if dropout_p > 0.0:
        import numpy as _np

        @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
        def flash_do(q, k, v, seed, causal, scale):
            o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
                q, k, v, None, causal, scale, seed)
            return _unlayout(o, bhf, s_true)

        def flash_do_fwd(q, k, v, seed, causal, scale):
            o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
                q, k, v, None, causal, scale, seed)
            o = checkpoint_name(o, "sdpa_res")
            lse_l = checkpoint_name(lse_l, "sdpa_res")
            return (_unlayout(o, bhf, s_true),
                    (qp, kp, vp, o, lse_l, bhf, s_true, seed))

        def flash_do_bwd(causal, scale, res, g):
            qp, kp, vp, o, lse_l, bhf, s_true, seed = res
            grads = _bwd_impl((qp, kp, vp, o, lse_l, bhf, s_true), g,
                              None, causal, scale, dropout_p, seed)
            return grads + (_np.zeros((), jax.dtypes.float0),)

        flash_do.defvjp(flash_do_fwd, flash_do_bwd)
        flash.dropout = flash_do

        @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
        def flash_do_masked(q, k, v, mask, seed, causal, scale):
            o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
                q, k, v, mask, causal, scale, seed)
            return _unlayout(o, bhf, s_true)

        def flash_do_masked_fwd(q, k, v, mask, seed, causal, scale):
            o, lse_l, qp, kp, vp, mp, bhf, s_true = _fwd_impl(
                q, k, v, mask, causal, scale, seed)
            o = checkpoint_name(o, "sdpa_res")
            lse_l = checkpoint_name(lse_l, "sdpa_res")
            return (_unlayout(o, bhf, s_true),
                    (qp, kp, vp, mp, o, lse_l, bhf, s_true, mask, seed))

        def flash_do_masked_bwd(causal, scale, res, g):
            qp, kp, vp, mp, o, lse_l, bhf, s_true, mask, seed = res
            grads = _bwd_impl((qp, kp, vp, o, lse_l, bhf, s_true), g, mp,
                              causal, scale, dropout_p, seed)
            return grads + (jnp.zeros_like(mask),
                            _np.zeros((), jax.dtypes.float0))

        flash_do_masked.defvjp(flash_do_masked_fwd, flash_do_masked_bwd)
        flash.masked_dropout = flash_do_masked

    flash.masked = flash_masked
    return flash


_default_flash = None


_dropout_flash_cache = {}


def _norm_mask(m):
    """bool -> additive, and pad leading dims to rank 4."""
    if m.dtype == jnp.bool_:
        m = jnp.where(m, jnp.float32(0.0), jnp.float32(NEG_INF))
    while m.ndim < 4:
        m = m[None]
    return m


def flash_attention_pallas(q, k, v, mask=None, causal=False, scale=None,
                           dropout_p=0.0):
    """sdpa-compatible entry: [b, s, h, d] inputs (paddle layout).
    Attention-weight dropout runs natively in the kernels (the round-2
    XLA fallback is gone); the per-call seed comes from the framework RNG
    stream, so eager steps differ and compiled steps follow the step key."""
    global _default_flash
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if dropout_p and dropout_p > 0.0:
        dp = float(dropout_p)
        fl = _dropout_flash_cache.get(dp)
        if fl is None:
            fl = make_flash_attention(dropout_p=dp)
            _dropout_flash_cache[dp] = fl
        from ...framework import random as frnd
        seed = jax.random.randint(frnd.next_key(), (), 0, 2 ** 31 - 1,
                                  jnp.int32)
        if mask is not None:
            return fl.masked_dropout(q, k, v, _norm_mask(mask), seed,
                                     causal, s)
        return fl.dropout(q, k, v, seed, causal, s)
    if _default_flash is None:
        _default_flash = make_flash_attention()
    if mask is not None:
        return _default_flash.masked(q, k, v, _norm_mask(mask), causal, s)
    return _default_flash(q, k, v, causal, s)
