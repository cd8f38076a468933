"""The routed-expert layer as ONE function, told which experts it holds.

`routed_experts` is what expert parallelism asks of a chip: route every
token over ALL experts, compute the part of the result that the experts
held HERE give, leave the rest to the chips that hold them. On one chip
the layer runs without its exchange and nothing stands in for the absent
chips; summed over every chip's `held` range the parts give the whole
layer (tests/test_mimo_v2.py holds the shares to the uncut layer).

Router, float32 throughout, by `score`. "sigmoid" (the DeepSeek-V3 /
`noaux_tc` form): s = sigmoid(x W_g);  choose the top_k largest of s + b
(b a stored correction bias that only steers the choice; a router that
stores none passes None and chooses by s);  w_e = s_e / sum_chosen s. "softmax" (`norm_topk_prob`): p = softmax(x W_g) over all
experts;  choose the top_k largest of p;  w_e = p_e / sum_chosen p; no
bias.
Expert e: SwiGLU, y_e = (silu(x G_e) * (x U_e)) D_e;  y = sum_chosen w_e y_e.

The two products over the experts held are the Pallas grouped matmul
(ops/pallas/grouped_matmul.py): the (token, choice) rows are sorted by
expert, rows of experts held elsewhere go last and belong to no group.
The model's eager forward and the serving engine's step programs both
call this function; the engine calls it inside its `ffn` phase
(profiler.PHASES), and the `router` / `experts` scopes here only split
that phase for a human reading a trace.
"""
import jax
import jax.numpy as jnp

from .pallas.grouped_matmul import grouped_matmul


def route(x, router_w, router_bias, top_k, score="sigmoid"):
    """(expert ids [t, k] int32, weights [t, k] float32) of every token,
    over ALL experts, in float32 at precision "highest"."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        s = jax.nn.softmax(logits, -1)
        _, idx = jax.lax.top_k(s, top_k)
    else:
        assert score == "sigmoid", score
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            s if router_bias is None
            else s + router_bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    return idx.astype(jnp.int32), chosen / jnp.sum(chosen, -1, keepdims=True)


def routed_experts(x, router_w, router_bias, w_gu, w_d, held, top_k,
                   interpret=False, score="sigmoid"):
    """x [t, hidden] -> (y [t, hidden] in x's dtype: the held experts'
    part of the layer's result; rows [held] int32: how many (token,
    choice) rows each held expert received). The router reads x as it
    comes (float32 from an engine with a float32 residual stream); the
    two grouped products run on operands of the WEIGHTS' dtype with
    float32 sums, and the weighted sum over a token's choices is
    float32.

    router_w [hidden, experts], router_bias [experts] (None under a
    "softmax" `score`, or where a "sigmoid" router stores none); w_gu [held, hidden,
    2 x width] with the gate's columns first; w_d [held, width, hidden];
    held = (lo, hi) expert ids, hi - lo == w_gu.shape[0]."""
    t, hidden = x.shape
    lo, hi = held
    n_held = hi - lo
    assert w_gu.shape[0] == n_held == w_d.shape[0], (held, w_gu.shape)
    with jax.named_scope("router"):
        idx, wts = route(x, router_w, router_bias, top_k, score)

    with jax.named_scope("experts"):
        # (token, choice) rows sorted by held expert; rows of experts held
        # elsewhere sort last under the sentinel n_held and join no group
        mine = jnp.logical_and(idx >= lo, idx < hi)
        key = jnp.where(mine, idx - lo, n_held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        rows = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)
        token = jnp.arange(t * top_k, dtype=jnp.int32) // top_k
        xs = jnp.take(x, token[order], axis=0).astype(w_gu.dtype)

        gu = grouped_matmul(xs, w_gu, rows, interpret=interpret)
        width = gu.shape[1] // 2
        act = jax.nn.silu(gu[:, :width].astype(jnp.float32)).astype(gu.dtype) \
            * gu[:, width:]
        ys = grouped_matmul(act, w_d, rows, interpret=interpret)

        # back to (token, choice) order; rows of absent experts are zeros
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * top_k, dtype=order.dtype))
        ys = jnp.take(ys, back, axis=0).reshape(t, top_k, hidden)
        y = jnp.sum(wts[..., None] * ys.astype(jnp.float32), axis=1)
    return y.astype(x.dtype), rows
