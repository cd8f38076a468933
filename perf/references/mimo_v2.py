"""MiMo-V2 (`model_type` "mimo_v2"), the plain reference: the language
model's block in straightforward jax.numpy, float32, every matrix
multiplication at precision "highest", dense masks, no kernels, no cache,
no batching tricks. The comparison that decides `correct` runs the
system's OWN weights through this and compares logits.

Written from the keys of the public config.json (catalog row MiMo-V2.5,
https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json). For
layer l:  a = h + Attn_l(RMSNorm(h)),  h' = a + FFN_l(RMSNorm(a)),
`layernorm_epsilon`, no biases, untied embedding and head, final RMSNorm.

Attention, by `hybrid_layer_pattern[l]` (0 full, 1 window). Both kinds:
one fused projection whose columns are q | k | v, query and key heads of
width `head_dim`, value heads of width `v_head_dim`, rotary (rotate-half)
on the first int(head_dim x partial_rotary_factor) dimensions of q and k
and the rest passed through, values multiplied by `attention_value_
scale`, logits q.k / sqrt(head_dim), grouped queries (head i reads kv
head i // (heads / kv heads)). Full: `num_key_value_heads`, base
`rope_theta`, causal. Window: `swa_num_key_value_heads`, base
`swa_rope_theta`, keys j with i - `sliding_window` < j <= i. A layer
whose kind has its `add_*_attention_sink_bias` set adds one learned
float s_h per query head to the softmax's DENOMINATOR only:
  out_i = sum_j exp(l_ij - m) v_j / (sum_j exp(l_ij - m) + exp(s_h - m)).

FFN, by `moe_layer_freq[l]` (0 dense SwiGLU of `intermediate_size`, 1
routed): s = sigmoid(x W_g) over all `n_routed_experts` in float32;
choose the `num_experts_per_tok` largest of s + b (b the stored
`noaux_tc` correction bias; `n_group` 1, so no group limit); w_e = s_e /
sum_chosen s (`norm_topk_prob`; `routed_scaling_factor` null = 1);
y = sum_chosen w_e (silu(x G_e) * (x U_e)) D_e. No shared expert.

Departures, each stated: (1) the chip's share. The configuration holds
experts [lo, hi) of the published 256 and a slice of the vocabulary;
the router still scores all 256 and chooses 8, and what the absent
experts would have added is LEFT OUT, here as in the program, and that
partial result goes on to the next layer (model-configs guide, section
4). (2) the three multi-token-prediction layers and the vision and
audio towers are not built (not among the language model's keys).
(3) `attention_chunk_size` is not used: no equation above has it.
(4) no q/k norm: no key declares one. (5) weights are random from the
seed, not the checkpoint's.

Weights are handed over in the engine's canonical layout (`emb`, `norm`,
`head`, `layers[i]` with ln1 wqkv wo [sink] ln2 and wg wu wd or router
router_bias w_gu w_d; matrices [in, out]). One float32 expert layer
(16 x 3 x 4096 x 2048 x 4 B = 1.6 GB) is the most `hidden` holds.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp


# ------------------------------------------------ the program's model --
def model_config(cfg):
    """The program's config object from the configuration file: the
    layers kept (`layers_kept` indexes the published patterns), the
    published expert count for the router, the experts held here."""
    from paddle_tpu.models import MiMoV2Config
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return MiMoV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        swa_num_key_value_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"], swa_rope_theta=cfg["swa_rope_theta"],
        sliding_window=cfg["sliding_window"],
        attention_value_scale=cfg["attention_value_scale"],
        add_full_attention_sink_bias=cfg["add_full_attention_sink_bias"],
        add_swa_attention_sink_bias=cfg["add_swa_attention_sink_bias"],
        hybrid_layer_pattern=[cfg["hybrid_layer_pattern"][l] for l in kept],
        moe_layer_freq=[cfg["moe_layer_freq"][l] for l in kept],
        n_routed_experts=cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        layernorm_epsilon=cfg["layernorm_epsilon"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_experts=held_experts(cfg))


def build_model(cfg, seed):
    """The configuration as the PROGRAM builds it, parameters deferred
    (LazyGuard) so the engine materializes them from `seed` in the type
    it serves."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MiMoV2ForCausalLM
    paddle.seed(seed)
    with paddle.LazyGuard():
        return MiMoV2ForCausalLM(model_config(cfg))


def layer_kinds(cfg):
    """[(window or None, sink, kv heads, rope base, routed)] per layer
    kept, from the file's keys alone."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    out = []
    for l in kept:
        win = bool(cfg["hybrid_layer_pattern"][l])
        out.append((
            cfg["sliding_window"] if win else None,
            cfg["add_swa_attention_sink_bias"] if win
            else cfg["add_full_attention_sink_bias"],
            cfg["swa_num_key_value_heads"] if win
            else cfg["num_key_value_heads"],
            cfg["swa_rope_theta"] if win else cfg["rope_theta"],
            bool(cfg["moe_layer_freq"][l])))
    return out


# ------------------------------------------------------ the mathematics --
def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, rot, theta):
    """x [s, heads, d] at positions 0..s-1: rotate-half over the first
    `rot` dimensions, the rest unchanged."""
    s = x.shape[0]
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(q, k, v, window, sink):
    """One sequence. q [s, H, dk], k [s, KV, dk], v [s, KV, dv]; a dense
    [s, s] mask; sink [H] or None."""
    s, n_heads, dk = q.shape
    rep = n_heads // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dk)
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    m = jnp.max(scores, -1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[:, None, None])
    e = jnp.exp(scores - m)
    den = jnp.sum(e, -1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink[:, None, None] - m)
    return jnp.einsum("hqk,khd->qhd", e / den, v)


def router(x, w, top_k):
    """(expert ids [t, k], weights [t, k]) of every token over ALL
    experts: sigmoid scores, the top k of score + correction bias, the
    chosen scores normalised."""
    s = jax.nn.sigmoid(x @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], top_k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def routed(x, w, held, top_k):
    """The held experts' part of the routed layer on x [t, hidden]: every
    held expert on every token, weighted by zero where it was not
    chosen. Also returns the experts chosen [t, k]."""
    idx, wts = router(x, w, top_k)
    width = w["w_d"].shape[1]
    y = jnp.zeros_like(x)
    for n, e in enumerate(range(*held)):
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=1)    # [t]
        gu = x @ w["w_gu"][n]
        y = y + w_e[:, None] * (
            (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ w["w_d"][n])
    return y, idx


def attention_half(h, w, cfg, kind):
    """a = h + Attn(RMSNorm(h)) on one sequence: h [s, hidden] float32."""
    window, sink, n_kv, theta, _ = kind
    s = h.shape[0]
    nh, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                  cfg["v_head_dim"])
    rot = int(dk * cfg["partial_rotary_factor"])
    x = rms_norm(h, w["ln1"], cfg["layernorm_epsilon"])
    qkv = x @ w["wqkv"]
    q = qkv[:, :nh * dk].reshape(s, nh, dk)
    k = qkv[:, nh * dk:(nh + n_kv) * dk].reshape(s, n_kv, dk)
    v = qkv[:, (nh + n_kv) * dk:].reshape(s, n_kv, dv) \
        * cfg["attention_value_scale"]
    o = attention(rope(q, rot, theta), rope(k, rot, theta), v, window,
                  w["sink"] if sink else None)
    return h + o.reshape(s, -1) @ w["wo"]


def held_experts(cfg):
    return cfg.get("held_experts") or [0, cfg["n_routed_experts"]]


def block(h, w, cfg, kind, choices=False):
    """One decoder layer on one sequence: h [s, hidden] float32, w the
    layer's weights in float32, kind one entry of layer_kinds().
    choices=True (a routed layer) also returns the experts chosen
    [s, top_k]."""
    h = attention_half(h, w, cfg, kind)
    x = rms_norm(h, w["ln2"], cfg["layernorm_epsilon"])
    if kind[4]:
        y, idx = routed(x, w, held_experts(cfg), cfg["num_experts_per_tok"])
        return (h + y, idx) if choices else h + y
    return h + (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def score_rows(x, head, tokens):
    """For rows x [n, hidden] (after the final norm) and one token id per
    row: (logsumexp over the vocabulary held, the top logit, the logit of
    the given token). `head` is [hidden, vocab] float32."""
    logits = x @ head
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jax.nn.logsumexp(logits, -1), jnp.max(logits, -1), picked


def forward(weights, ids, cfg):
    """Logits [b, s, vocab] of ids [b, s] under float32 `weights` — the
    whole model as one pure function, for the tests."""
    kinds = layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        def one(seq):
            h = weights["emb"][seq]
            for w, kind in zip(weights["layers"], kinds):
                h = block(h, w, cfg, kind)
            return rms_norm(h, weights["norm"],
                            cfg["layernorm_epsilon"]) @ weights["head"]
        return jnp.stack([one(seq) for seq in ids])


def loss(weights, ids, labels, cfg):
    """Mean next-token cross-entropy of a batch ids/labels [b, s]."""
    logits = forward(weights, ids, cfg)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, jnp.asarray(labels)[..., None],
                                 -1)[..., 0]
    return jnp.mean(lse - picked)


# ------------------------------------------- at full size, layer by layer --
def _vocab_chunks(vocab, limit=8192):
    n = -(-vocab // limit)
    while vocab % n:
        n += 1
    return [(i * (vocab // n), (i + 1) * (vocab // n)) for i in range(n)]


class Reference:
    """Runs the system's own weights through the mathematics above, one
    layer, one sequence and one slice of the vocabulary at a time, so it
    fits beside the system on the chip. Each piece is one jitted function
    whose float32 copy of the weights lives only for that call."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.kinds = layer_kinds(cfg)

        @functools.partial(jax.jit, static_argnums=(2,))
        def layer(h, raw, kind):
            with jax.default_matmul_precision("highest"):
                w = {k: f32(v) for k, v in raw.items()}
                return jnp.stack([block(hs, w, cfg, kind) for hs in h])

        @jax.jit
        def final(h, norm):
            return rms_norm(h, f32(norm), cfg["layernorm_epsilon"])

        @functools.partial(jax.jit, static_argnums=(3,))
        def scores(x, head, c0, width, tokens):
            """One slice [c0, c0 + width) of the vocabulary."""
            part = jax.lax.dynamic_slice_in_dim(head, c0, width, 1)
            with jax.default_matmul_precision("highest"):
                return score_rows(x, f32(part), tokens)

        self._layer, self._final, self._scores = layer, final, scores

    def hidden(self, weights, ids):
        """Final-normed hidden states [b, s, hidden] float32 for token ids
        [b, s]."""
        h = f32(weights["emb"][jnp.asarray(ids)])
        for i, kind in enumerate(self.kinds):
            h = self._layer(h, weights["layers"][i], kind)
        return self._final(h, weights["norm"])

    def score(self, weights, x, tokens):
        """score_rows over the vocabulary held, in slices: x [n, hidden],
        tokens [n] -> (logsumexp, top logit, logit of the token), numpy."""
        head = weights["head"]
        tokens = np.asarray(tokens, np.int64)
        lse = top = None
        picked = np.zeros(tokens.shape, np.float64)
        for c0, c1 in _vocab_chunks(self.cfg["vocab_size"]):
            inside = (tokens >= c0) & (tokens < c1)
            local = np.where(inside, tokens - c0, 0).astype(np.int32)
            l, t, p = (np.asarray(a, np.float64) for a in self._scores(
                x, head, jnp.int32(c0), c1 - c0, jnp.asarray(local)))
            picked = np.where(inside, p, picked)
            lse = l if lse is None else np.logaddexp(lse, l)
            top = t if top is None else np.maximum(top, t)
        return lse, top, picked

    def loss(self, weights, ids, labels):
        """Mean next-token cross-entropy of ids/labels [b, s]."""
        x = self.hidden(weights, ids)
        x = x.reshape(-1, x.shape[-1])
        lse, _, picked = self.score(weights, x, np.asarray(labels).ravel())
        return float(np.mean(lse - picked))


# ----------------------------------------------- the system's own weights --
def weights_from_engine(engine):
    """The serving engine's weights (public `export_weights()`), already
    in this module's layout."""
    return engine.export_weights()
