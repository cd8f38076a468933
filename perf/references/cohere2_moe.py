"""Cohere2-MoE (`model_type` "cohere2_moe", Command A+), the plain
reference: the language model's block in straightforward jax.numpy,
float32, every matrix multiplication at precision "highest", dense masks,
no kernels, no cache, no batching tricks. The comparison that decides
`correct` runs the system's OWN weights through this and compares logits.

Written from the keys of the public config.json (catalog row
command-a-plus-05-2026, https://huggingface.co/CohereLabs/
command-a-plus-05-2026/blob/main/config.json). For layer l, with ONE norm
(`use_parallel_block`):

  n      = LayerNorm(x)      mean-subtracted, weight only, `layer_norm_eps`
  q,k,v  = n Wq, n Wk, n Wv  `num_attention_heads` query heads over
                             `num_key_value_heads` KV heads of `head_dim`
  "sliding_attention": q, k rotated in INTERLEAVED pairs (2i, 2i + 1)
      (`position_embedding_type` "rope_gptj"), base `rope_theta`, all
      head_dim dims (`rotary_pct` 1); key j visible iff
      i - `sliding_window` < j <= i
  "full_attention": NO rotation; every key j <= i visible
  a      = softmax(q k^T / sqrt(head_dim)) v Wo
  s      = sigmoid(n Wr) over all `num_experts`, float32; the
           `num_experts_per_tok` largest; w_e = s_e / sum_chosen s
           (`norm_topk_prob`)
  routed = sum_chosen w_e (silu(n G_e) * (n U_e)) D_e   width `intermediate_size`
  shared = (1 / `num_shared_experts`) sum_j (silu(n G'_j) * (n U'_j)) D'_j
           (`shared_expert_combination_strategy` "average")
  y      = x + a + routed + shared
  logits = `logit_scale` x LayerNorm(y_last) Emb^T   (`tie_word_embeddings`)

Departures, each stated: (1) the chip's share. The configuration holds
experts [lo, hi) of the published 128 and a slice of the vocabulary; the
router still scores all 128 and chooses 8, and what the absent experts
would have added is LEFT OUT, here as in the program, and that partial
result goes on to the next layer (model-configs guide, section 4).
(2) "average" is read as the mean over the shared experts, added to the
routed sum (the other reading, (routed + shared) / 2, is not taken).
(3) no router correction bias: no key declares one. (4) the vision tower
is not built (its keys are not in the row; its tokens arrive as ids).
(5) `first_k_dense_replace` 0: no leading dense layer; the
`prefix_dense_*` keys are carried and unused. (6) weights are random from
the seed, not the checkpoint's. (7) the ENGINE'S WEIGHTS arrive in its
layouts (`wq` / `wk` of a rotating layer de-interleaved head by head, the
shared experts side by side as one SwiGLU whose down projection carries
the 1 / n, the head a transposed copy of the embedding); `published_layer`
undoes them, exactly (a permutation, a reshape, a power of two), and the
equations above then run on the weights as a checkpoint stores them. The
tied head is read from `emb`; the engine's copy is not used.

At the published widths a layer's float32 copy would be 3 GB beside 12 GB
resident, and [heads, s, s] logits of a 5,600-token sequence 16 GB: one
expert is up-cast at a time (a `lax.map` over experts), sequences go one
at a time, and attention runs a KV head's queries in blocks of
`QUERY_BLOCK` against all the keys. The sums are the same.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"
QUERY_BLOCK = 512


# ------------------------------------------------ the program's model --
def held_experts(cfg):
    return cfg.get("held_experts") or [0, cfg["num_experts"]]


def router_outputs(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def layer_kinds(cfg):
    """The kept layers' `layer_types` entries, from the file's keys."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return tuple(cfg["layer_types"][l] for l in kept)


def model_config(cfg):
    """The program's config object from the configuration file: the
    layers kept (`layers_kept` indexes the published `layer_types`), the
    published expert count for the router, the experts held here."""
    from paddle_tpu.models import Cohere2MoeConfig
    return Cohere2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], layer_types=list(layer_kinds(cfg)),
        sliding_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        rotary_pct=cfg["rotary_pct"], layer_norm_eps=cfg["layer_norm_eps"],
        num_experts=router_outputs(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        logit_scale=cfg["logit_scale"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_parallel_block=cfg["use_parallel_block"],
        use_qk_norm=cfg["use_qk_norm"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_experts=held_experts(cfg))


def build_model(cfg, seed):
    """The configuration as the PROGRAM builds it, parameters deferred
    (LazyGuard) so the engine materializes them from `seed` in the type
    it serves."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Cohere2MoeForCausalLM
    paddle.seed(seed)
    with paddle.LazyGuard():
        return Cohere2MoeForCausalLM(model_config(cfg))


# ------------------------------------------------------ the mathematics --
def f32(w):
    return w.astype(jnp.float32)


def layer_norm(x, w, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_interleaved(x, theta):
    """x [s, heads, d] at positions 0..s-1: the pair (2i, 2i + 1) rotates
    by position x theta^(-2i / d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def attention(q, k, v, window):
    """One sequence. q [s, H, d], k, v [s, KV, d] -> [s, H, d]; a dense
    causal (and window) mask. One KV head at a time, its H / KV query
    heads in blocks of `QUERY_BLOCK` queries against all s keys."""
    s, n_heads, d = q.shape
    n_kv = k.shape[1]
    rep = n_heads // n_kv
    qb = min(QUERY_BLOCK, s)
    n_blocks = -(-s // qb)
    pad = n_blocks * qb - s
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, qb, n_kv, rep, d)
    j = jnp.arange(s)[None, :]

    def kv_head(args):
        q_g, k_g, v_g = args            # [blocks, qb, rep, d], [s, d] x 2

        def q_block(args):
            q_b, b = args               # [qb, rep, d]
            i = (b * qb + jnp.arange(qb))[:, None]
            seen = j <= i
            if window is not None:
                seen = seen & (j > i - window)
            scores = jnp.einsum("qrd,kd->rqk", q_b, k_g) / math.sqrt(d)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(scores, -1),
                              v_g)

        return jax.lax.map(q_block, (q_g, jnp.arange(n_blocks)))

    out = jax.lax.map(kv_head, (jnp.moveaxis(qg, 2, 0),
                                jnp.moveaxis(k, 1, 0),
                                jnp.moveaxis(v, 1, 0)))
    # [KV, blocks, qb, rep, d] -> [s, H, d]
    return jnp.moveaxis(out, 0, 2).reshape(n_blocks * qb, n_heads, d)[:s]


def router(x, w_router, top_k):
    """(expert ids [t, k], weights [t, k]) of every token over ALL
    experts: sigmoid scores, the top k of them, normalised."""
    s = jax.nn.sigmoid(x @ f32(w_router))
    chosen, idx = jax.lax.top_k(s, top_k)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)


def routed(x, w, held, top_k):
    """The held experts' part of the routed layer on x [t, hidden]: every
    held expert on every token, weighted by zero where it was not chosen,
    one expert's float32 copy at a time. Also the experts chosen [t, k]."""
    idx, wts = router(x, w["router"], top_k)
    width = w["w_d"].shape[1]

    def one(y, args):
        e, w_gu, w_d = args
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=1)        # [t]
        return y + w_e[:, None] * swiglu(
            x, w_gu[:, :width], w_gu[:, width:], w_d), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(*held), w["w_gu"], w["w_d"]))
    return y, idx


def shared(x, w):
    """The mean of the shared experts, each computed apart."""
    y, _ = jax.lax.scan(lambda y, gud: (y + swiglu(x, *gud), None),
                        jnp.zeros_like(x), (w["sh_g"], w["sh_u"], w["sh_d"]))
    return y / w["sh_g"].shape[0]


def block(h, w, cfg, kind, choices=False):
    """One decoder layer on one sequence: h [s, hidden] float32, w the
    layer's weights in the PUBLISHED layout (any float type: each matrix
    is up-cast where it is used), kind its `layer_types` entry.
    choices=True also returns the experts chosen [s, top_k]."""
    s = h.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n = layer_norm(h, f32(w["ln1"]), cfg["layer_norm_eps"])
    q = (n @ f32(w["wq"])).reshape(s, nh, d)
    k = (n @ f32(w["wk"])).reshape(s, nkv, d)
    v = (n @ f32(w["wv"])).reshape(s, nkv, d)
    window = None
    if kind == SLIDING:
        q = rope_interleaved(q, cfg["rope_theta"])
        k = rope_interleaved(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    a = attention(q, k, v, window).reshape(s, -1) @ f32(w["wo"])
    y, idx = routed(n, w, held_experts(cfg), cfg["num_experts_per_tok"])
    out = h + a + y + shared(n, w)
    return (out, idx) if choices else out


# ------------------------------------------ the engine's layouts undone --
def interleave(w, n_heads, d):
    """The inverse of the program's `deinterleave`: columns [evens |
    odds] of every head back to (2i, 2i + 1)."""
    order = np.argsort(np.concatenate([np.arange(0, d, 2),
                                       np.arange(1, d, 2)]))
    return w.reshape(w.shape[0], n_heads, d)[:, :, order].reshape(w.shape)


def published_layer(raw, cfg, kind):
    """One layer of the engine's weights (its canonical names and
    layouts) as a checkpoint stores them: wq wk wv wo ln1 router w_gu w_d
    and the shared experts apart, sh_g / sh_u [n, hidden, width], sh_d
    [n, width, hidden]. Exact in any float type."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n_sh, hidden = cfg["num_shared_experts"], cfg["hidden_size"]
    w = {k: raw[k] for k in ("ln1", "wq", "wk", "wv", "wo", "router",
                             "w_gu", "w_d")}
    if kind == SLIDING:
        w["wq"] = interleave(raw["wq"], nh, d)
        w["wk"] = interleave(raw["wk"], nkv, d)
    for name, wide in (("sh_g", raw["ws_g"]), ("sh_u", raw["ws_u"])):
        w[name] = jnp.swapaxes(wide.reshape(hidden, n_sh, -1), 0, 1)
    w["sh_d"] = raw["ws_d"].reshape(n_sh, -1, hidden) * n_sh
    return w


def published_weights(raw, cfg):
    """The whole export of an engine, layer by layer (the tests' form;
    at the published widths `Reference` converts inside each layer's
    call instead, so that no second copy of the weights stays)."""
    return {"emb": raw["emb"], "norm": raw["norm"],
            "layers": [published_layer(w, cfg, kind) for w, kind in
                       zip(raw["layers"], layer_kinds(cfg))]}


def score_rows(x, emb, tokens, scale):
    """For rows x [n, hidden] (after the final norm) and one token id per
    row: (logsumexp over the vocabulary held, the top logit, the logit of
    the given token). `emb` is [vocab, hidden] float32: the tied head."""
    logits = (x @ emb.T) * scale
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jax.nn.logsumexp(logits, -1), jnp.max(logits, -1), picked


def forward(weights, ids, cfg):
    """Logits [b, s, vocab] of ids [b, s] under `weights` in the
    published layout — the whole model as one pure function, for the
    tests."""
    kinds = layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        emb = f32(weights["emb"])

        def one(seq):
            h = emb[seq]
            for w, kind in zip(weights["layers"], kinds):
                h = block(h, w, cfg, kind)
            return (layer_norm(h, f32(weights["norm"]),
                               cfg["layer_norm_eps"]) @ emb.T) \
                * cfg["logit_scale"]
        return jnp.stack([one(seq) for seq in ids])


# ------------------------------------------- at full size, layer by layer --
def _vocab_chunks(vocab, limit=8192):
    n = -(-vocab // limit)
    while vocab % n:
        n += 1
    return [(i * (vocab // n), (i + 1) * (vocab // n)) for i in range(n)]


class Reference:
    """Runs the system's own weights through the mathematics above, one
    layer, one sequence, one expert and one slice of the vocabulary at a
    time, so it fits beside the system on the chip. Each piece is one
    jitted function whose float32 copies live only for that call."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.kinds = layer_kinds(cfg)

        @functools.partial(jax.jit, static_argnums=(2,))
        def layer(h, raw, kind):
            with jax.default_matmul_precision("highest"):
                w = published_layer(raw, cfg, kind)
                return jax.lax.map(lambda hs: block(hs, w, cfg, kind), h)

        @jax.jit
        def final(h, norm):
            return layer_norm(h, f32(norm), cfg["layer_norm_eps"])

        @functools.partial(jax.jit, static_argnums=(3,))
        def scores(x, emb, c0, width, tokens):
            """One slice [c0, c0 + width) of the vocabulary."""
            part = jax.lax.dynamic_slice_in_dim(emb, c0, width, 0)
            with jax.default_matmul_precision("highest"):
                return score_rows(x, f32(part), tokens, cfg["logit_scale"])

        self._layer, self._final, self._scores = layer, final, scores

    def hidden(self, weights, ids):
        """Final-normed hidden states [b, s, hidden] float32 for token ids
        [b, s]; `weights` as the engine exports them."""
        h = f32(weights["emb"][jnp.asarray(ids)])
        for i, kind in enumerate(self.kinds):
            h = self._layer(h, weights["layers"][i], kind)
        return self._final(h, weights["norm"])

    def score(self, weights, x, tokens):
        """score_rows over the vocabulary held, in slices: x [n, hidden],
        tokens [n] -> (logsumexp, top logit, logit of the token), numpy."""
        emb = weights["emb"]
        tokens = np.asarray(tokens, np.int64)
        lse = top = None
        picked = np.zeros(tokens.shape, np.float64)
        for c0, c1 in _vocab_chunks(self.cfg["vocab_size"]):
            inside = (tokens >= c0) & (tokens < c1)
            local = np.where(inside, tokens - c0, 0).astype(np.int32)
            l, t, p = (np.asarray(a, np.float64) for a in self._scores(
                x, emb, jnp.int32(c0), c1 - c0, jnp.asarray(local)))
            picked = np.where(inside, p, picked)
            lse = l if lse is None else np.logaddexp(lse, l)
            top = t if top is None else np.maximum(top, t)
        return lse, top, picked


# ----------------------------------------------- the system's own weights --
def weights_from_engine(engine):
    """The serving engine's weights (public `export_weights()`), in the
    engine's layouts: `Reference` undoes them layer by layer."""
    return engine.export_weights()
