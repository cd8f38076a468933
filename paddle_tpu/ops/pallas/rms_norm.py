"""Pallas RMSNorm (TPU) with analytic custom VJP.

The LLaMA-family norm; row-tiled VMEM kernel replacing an
XLA op chain (ref analog: phi/kernels/fusion rms_norm / the fused LN
epilogues in fused_multi_transformer_op.cu.h).

Two cast orders live here on purpose:
  - the fused fwd kernel multiplies by the norm weight IN f32 before the
    output cast (training-path rounding);
  - `rms_rows` casts x*rsqrt back to x.dtype BEFORE the weight multiply
    — inference/serving._rms's order, which the decode megakernel must
    reproduce bit-for-bit. Identical for f32; different roundings for
    bf16, so they are NOT interchangeable.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def rms_rows(x, w_row, eps, d_real=None):
    """RMS-norm over [rows, d] in serving cast order — the tile body the
    decode megakernel runs in VMEM (and the reference math of
    inference/serving._rms). d_real: the unpadded feature count when x
    carries exact-zero pad columns — zeros leave the sum unchanged but
    the mean's denominator must stay the real width."""
    d = x.shape[-1] if d_real is None else d_real
    x32 = x.astype(jnp.float32)
    if x.shape[-1] == d:
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    else:
        var = jnp.sum(x32 * x32, axis=-1, keepdims=True) / d
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * w_row.astype(x.dtype)


def _rms_fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + jnp.float32(eps))
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_fwd(x2d, w, eps, rows, interpret):
    n, d = x2d.shape
    br = min(rows, n)
    with jax.enable_x64(False):
        return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(pl.cdiv(n, br),),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=interpret,
    )(x2d, w)


def make_rms_norm(rows=256, interpret=False):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def rms(x, w, eps):
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        o = _rms_fwd(x2, w, eps, rows, interpret)
        return o.reshape(shape)

    def fwd(x, w, eps):
        return rms(x, w, eps), (x, w)

    def bwd(eps, res, g):
        x, w = res
        shape = x.shape
        x2 = x.reshape(-1, shape[-1]).astype(jnp.float32)
        g2 = g.reshape(-1, shape[-1]).astype(jnp.float32)
        w32 = w.astype(jnp.float32)
        var = jnp.mean(x2 * x2, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps)
        xhat = x2 * inv
        gw = jnp.sum(g2 * xhat, axis=0).astype(w.dtype)
        gxhat = g2 * w32
        d = shape[-1]
        gx = inv * (gxhat - xhat * jnp.mean(gxhat * xhat, axis=-1,
                                            keepdims=True))
        return gx.reshape(shape).astype(x.dtype), gw

    rms.defvjp(fwd, bwd)
    return rms


_default_rms = None


def rms_norm_pallas(x, weight, epsilon=1e-6):
    global _default_rms
    if _default_rms is None:
        _default_rms = make_rms_norm()
    return _default_rms(x, weight, epsilon)
