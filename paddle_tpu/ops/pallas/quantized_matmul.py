"""Pallas int8 weight-only matmul (TPU).

Serving-path GEMM: weights live in HBM as int8 + per-output-channel fp
scales (produced by the PTQ observers in paddle_tpu.quantization), halving
weight bandwidth — the decode bottleneck. The int8 tile is only CONVERTED
in VMEM right before the MXU pass (ref: the reference's int8
fused_multi_transformer variant, fused_multi_transformer_int8_op.cu);
the dequantizing multiply by the scale happens once, on the finished
accumulator:

  out[m, n] = (sum_k x[m, k] * w_int8[k, n]) * scale[n]

The k-loop is the innermost grid dimension with an f32 VMEM accumulator;
the per-channel scale is applied once at emission.

The MXU operand type is decided from the two tile dtypes alone
(`mm_operand_dtype`): bf16 activations against int8 or bf16 weights
multiply as bf16 x bf16 — every operand is exact in bf16, every product
exact in f32, ONE pass through the MXU — and anything else (f32 or f16
activations) multiplies as f32 x f32 at the package's "highest"
precision, which on the chip is a six-pass product. The result and the
accumulator are f32 either way.

The two tile bodies — `dot_tile_f32` (one k-tile MXU step, f32 RESULT)
and `scale_emit` (per-channel dequant at emission) — are module-level so
the decode megakernel (ops/pallas/decode_megakernel) runs the SAME ops in
the same order: its streamed per-layer matmuls are bit-identical to this
standalone kernel because they share these definitions, not because two
copies happen to agree.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def mm_operand_dtype(x_dtype, w_dtype):
    """The type both operands of one k-tile product are fed to the MXU
    in, from what the tile body can observe: bfloat16 when the
    activations are (logically) bf16 and the weight tile is int8 or
    bf16 — both casts are exact, a bf16 x bf16 product is exact in f32,
    and the MXU accumulates in f32, so this is the f32 product of the
    same operands less the five passes that multiplied zeros — and
    float32 for everything else. The ONE rule: `dot_tile_f32` applies
    it, the engine reports it (`health()["mm_operand_dtype"]`)."""
    bf16 = jnp.dtype(jnp.bfloat16)
    if jnp.dtype(x_dtype) == bf16 and jnp.dtype(w_dtype) in (
            bf16, jnp.dtype(jnp.int8)):
        return jnp.bfloat16
    return jnp.float32


def dot_tile_f32(x_tile, w_tile, x_dtype=None):
    """One k-tile partial product, f32 result: x [m, bk] @ w [bk, bn].
    int8 (or any sub-f32) tiles dequantize by the .astype alone — the
    per-channel scale is applied once, at emission (scale_emit).
    x_dtype: the activations' LOGICAL dtype where the tile is a wider
    container of rounded values (the megakernel's f32 row scratches);
    default the tile's own."""
    op = mm_operand_dtype(x_tile.dtype if x_dtype is None else x_dtype,
                          w_tile.dtype)
    # bf16 operands name DEFAULT: the package-wide "highest" (what None
    # resolves to, and what the f32 product keeps) would ask Mosaic for
    # an f32 contraction of operands that are bf16
    return jax.lax.dot_general(
        x_tile.astype(op), w_tile.astype(op), (((1,), (0,)), ((), ())),
        precision=(jax.lax.Precision.DEFAULT if op == jnp.bfloat16
                   else None),
        preferred_element_type=jnp.float32)


def scale_emit(acc, scale_row, out_dtype):
    """Apply the per-output-channel scale to a finished f32 accumulator
    tile and cast to the output dtype. scale_row: [bn] (unit scales make
    this an exact f32 identity for dense weights)."""
    return (acc * scale_row[None, :].astype(jnp.float32)).astype(out_dtype)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += dot_tile_f32(x_ref[...], w_ref[...])

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = scale_emit(acc_scr[...], s_ref[0], o_ref.dtype)


def quantized_matmul(x, w_int8, scales, out_dtype=None, bm=256, bn=256,
                     bk=512, interpret=False):
    """x: [m, k] float; w_int8: [k, n] int8; scales: [n] f32.
    Returns [m, n] in out_dtype (default: x.dtype)."""
    m, k = x.shape
    kk, n = w_int8.shape
    assert kk == k and scales.shape == (n,)
    out_dtype = out_dtype or x.dtype
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k)

    def pad_to(a, mult, axis):
        pad = (-a.shape[axis]) % mult
        if not pad:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        return jnp.pad(a, widths)

    xp = pad_to(pad_to(x, bm, 0), bk, 1)
    wp = pad_to(pad_to(w_int8, bk, 0), bn, 1)
    sp = pad_to(scales.astype(jnp.float32), bn, 0)
    mp, kp = xp.shape
    _, np_ = wp.shape
    nk = kp // bk

    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_qmm_kernel, nk=nk),
            grid=(mp // bm, np_ // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
                pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
                pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(xp, wp, sp.reshape(1, -1))
    return out[:m, :n]


def quantize_weights(w, axis=0):
    """Symmetric per-channel int8 quantization of a [k, n] weight.
    Returns (w_int8 [k, n], scales [n]) with axis=0 reduction (per output
    channel), matching the PTQ observers' convention."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scales = (amax / 127.0).astype(jnp.float32)
    wq = jnp.clip(jnp.round(w / jnp.maximum(scales, 1e-12)), -127, 127)
    return wq.astype(jnp.int8), scales.reshape(-1)
