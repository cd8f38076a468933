"""dots3-note (`model_type` "dots3_note"), the plain reference: the
language model's block in straightforward jax.numpy, float32, every
matrix multiplication at precision "highest", dense masks, no kernels, no
cache, no absorbed form. The comparison that decides `correct` runs the
system's OWN weights through this and compares logits.

Written from the keys of the public config.json (catalog row dots3-note-
prev, https://huggingface.co/dots-studio/dots3-note-prev/blob/main/
config.json). h a layer's input, x = RMSNorm(h) (`rms_norm_eps`), no
biases, a = h + Attn(x), h' = a + FFN(RMSNorm(a)), untied embedding and
head, final RMSNorm.

Latent attention, both kinds (`layer_types[l]`; a "sliding_attention"
layer reads the `swa_` key of each name below):
  c_q = RMSNorm(x W_dq) * sqrt(hidden / q_lora_rank)
  q_h = c_q W_uq,h = [q_n,h (qk_nope_head_dim) ; q_r,h (qk_rope_head_dim)]
  [c_kv ; k_r] = x W_dkv;  c_kv = RMSNorm(c_kv) * sqrt(hidden / kv_lora_rank)
  q_r,h and k_r rotated (rotate-half, base `rope_theta`); k_r is ONE per
  token for all heads;  k_n,h = c_kv W_uk,h;  v_h = c_kv W_uv,h
  logit(t, s) = (q_n,h(t) . k_n,h(s) + q_r,h(t) . k_r(s))
                / sqrt(qk_nope_head_dim + qk_rope_head_dim)
  o_h = sum_s softmax_s(logit) v_h(s);  g = sigmoid(x W_g) in R^heads,
  o_h <- g_h o_h ("headwise" gate);  Attn = concat(o) W_o.
Full layers: the softmax runs over S_t only, the `index_topk` positions
s <= t with the largest index score (all of them while t < index_topk):
  q^I_j = c_q W^I_q (`index_n_heads` x `index_head_dim`),
  k^I = LayerNorm(x W^I_k) (one per token), both rotated on their leading
  qk_rope_head_dim dimensions, w = x W^I_w,
  I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s)).
The selection is a stable argsort of -I over the visible positions: exact.
Window layers: keys s with t - `sliding_window_size` < s <= t, no indexer.

FFN: layers below `first_k_dense_replace` a dense SwiGLU of
`intermediate_size`. The others: s = sigmoid(x W_r) over all
`n_routed_experts` in float32; the `num_experts_per_tok` largest of s + b
(b the stored `noaux_tc` correction bias, no group limit); w_e = s_e /
sum_chosen s (`norm_topk_prob`, `routed_scaling_factor` 1); y = sum_chosen
w_e (silu(x G_e) * (x U_e)) D_e, plus ONE shared SwiGLU expert of
`moe_intermediate_size` x `n_shared_experts` on every token.

Departures and assumptions, each stated: (1) the chip's share. The
configuration holds experts [lo, hi) of the published 256 and a slice of
the vocabulary; the router still scores all 256 and chooses 8, what the
absent experts would add is LEFT OUT here as in the program, the shared
expert is whole, and that partial result goes on to the next layer.
(2) The multi-token-prediction layer and the vision and audio towers are
not built (not among the language model's keys). (3) ASSUMED, no code of
the family at hand to check against (no network): `apply_mla_qkv_lora_
rescale` is the sqrt(hidden / rank) factor above, in both layer kinds
(the form LongCat-Flash publishes as `mla_scale_q_lora` / `mla_scale_kv_
lora`); the gate reads the normed input x; rotate-half rotary; the window
counts the query's own position; the index key's LayerNorm has weight and
bias, eps 1e-6; the positive constant scales of the index score are
dropped (they change no top-k). (4) `kv_b_proj` arrives split by use as
`w_uk` / `w_uv`: a layout. (5) Weights are random from the seed.

Weights are handed over in the engine's canonical layout (inference/
description.py). Attention is computed one sequence at a time and in
blocks of heads, so 3,088 positions fit beside the system on the chip.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

LN_EPS = 1e-6           # of the index key's LayerNorm (assumed)
HEAD_BLOCK = 16         # heads a block of the attention computes


# ------------------------------------------------ the program's model --
def model_config(cfg):
    """The program's config object from the configuration file."""
    from paddle_tpu.models import Dots3NoteConfig
    keys = ("vocab_size hidden_size intermediate_size moe_intermediate_size "
            "num_hidden_layers layer_types num_attention_heads q_lora_rank "
            "kv_lora_rank qk_nope_head_dim qk_rope_head_dim v_head_dim "
            "rope_theta swa_num_attention_heads swa_q_lora_rank "
            "swa_kv_lora_rank swa_qk_nope_head_dim swa_qk_rope_head_dim "
            "swa_v_head_dim swa_rope_theta sliding_window_size "
            "index_n_heads index_head_dim index_topk "
            "apply_mla_qkv_lora_rescale attention_gate_type "
            "swa_attention_gate_type first_k_dense_replace "
            "n_shared_experts num_experts_per_tok routed_scaling_factor "
            "rms_norm_eps max_position_embeddings").split()
    return Dots3NoteConfig(
        n_routed_experts=cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"]),
        held_experts=held_experts(cfg), layers_kept=cfg.get("layers_kept"),
        **{k: cfg[k] for k in keys})


def build_model(cfg, seed):
    """The configuration as the PROGRAM builds it, parameters deferred
    (LazyGuard) so the engine materializes them from `seed` in the type
    it serves."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Dots3NoteForCausalLM
    paddle.seed(seed)
    with paddle.LazyGuard():
        return Dots3NoteForCausalLM(model_config(cfg))


def held_experts(cfg):
    return cfg.get("held_experts") or [0, cfg["n_routed_experts"]]


def layer_kinds(cfg):
    """One hashable tuple per layer kept, from the file's keys alone:
    (heads, q rank, kv rank, no-position width, rotary width, value
    width, rope base, window or None, index (heads, width, top-k) or
    None, routed)."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    out = []
    for l in kept:
        win = cfg["layer_types"][l] == "sliding_attention"
        g = lambda k: cfg[("swa_" if win else "") + k]  # noqa: E731
        out.append((
            g("num_attention_heads"), g("q_lora_rank"), g("kv_lora_rank"),
            g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim"),
            float(g("rope_theta")),
            cfg["sliding_window_size"] if win else None,
            None if win else (cfg["index_n_heads"], cfg["index_head_dim"],
                              cfg["index_topk"]),
            l >= cfg["first_k_dense_replace"]))
    return out


# ------------------------------------------------------ the mathematics --
def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def rope(x, theta):
    """x [s, ..., d] at positions 0..s-1: rotate-half over all d."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    shape = (s,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(ang), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), jnp.float32).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(x, c_q, w, kind):
    """I(t, s) [s, s] of one sequence."""
    n_i, d_i, _ = kind[8]
    s, rot, theta = x.shape[0], kind[4], kind[6]
    q = (c_q @ w["ix_wq"]).reshape(s, n_i, d_i)
    k = layer_norm(x @ w["ix_wk"], w["ix_kn_w"], w["ix_kn_b"], LN_EPS)
    q = jnp.concatenate([rope(q[..., :rot], theta), q[..., rot:]], -1)
    k = jnp.concatenate([rope(k[..., :rot], theta), k[..., rot:]], -1)
    wt = x @ w["ix_ww"]                                   # [s, n_i]
    out = jnp.zeros((s, s), jnp.float32)
    for j in range(n_i):            # a head at a time: [s, s], never more
        out = out + wt[:, j, None] * jax.nn.relu(q[:, j] @ k.T)
    return jnp.where(out == 0, 0.0, out)    # -0.0 is 0.0: a tie, by position


def selection(scores, seen, top_k):
    """[s, s] bool: for each query the top_k seen positions by score (all
    of them while fewer are seen), by a stable argsort."""
    order = jnp.argsort(jnp.where(seen, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return seen & (rank < top_k)


def attention_half(h, w, cfg, kind, variant=None):
    """a = h + Attn(RMSNorm(h)) on one sequence: h [s, hidden] float32.
    `variant` names ONE deliberate fault, for the tests that must see the
    comparison fail: "no_selection", "no_gate", "no_rescale",
    "window_off_by_one"."""
    nh, q_rank, kv_rank, dn, dr, dv, theta, window, index, _ = kind
    s, hidden, eps = h.shape[0], cfg["hidden_size"], cfg["rms_norm_eps"]
    rescale = cfg["apply_mla_qkv_lora_rescale"] and variant != "no_rescale"
    x = rms_norm(h, w["ln1"], eps)
    c_q = rms_norm(x @ w["wq_a"], w["q_norm"], eps)
    kv = x @ w["wkv_a"]
    c_kv = rms_norm(kv[:, :kv_rank], w["kv_norm"], eps)
    if rescale:
        c_q = c_q * math.sqrt(hidden / q_rank)
        c_kv = c_kv * math.sqrt(hidden / kv_rank)
    k_r = rope(kv[:, kv_rank:], theta)                     # [s, dr]
    q = (c_q @ w["wq_b"]).reshape(s, nh, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], theta)
    k_n = (c_kv @ w["w_uk"]).reshape(s, nh, dn)
    v = (c_kv @ w["w_uv"]).reshape(s, nh, dv)
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    seen = jnp.asarray(j <= i)
    if window is not None:
        if variant == "window_off_by_one":
            window = window - 1
        seen = seen & (j > i - window)
    if index is not None and variant != "no_selection":
        seen = selection(index_scores(x, c_q, w, kind), seen, index[2])
    outs = []
    for h0 in range(0, nh, HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        logits = (jnp.einsum("qhd,khd->hqk", q_n[:, hs], k_n[:, hs])
                  + jnp.einsum("qhd,kd->hqk", q_r[:, hs], k_r)) \
            / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:, hs]))
    o = jnp.concatenate(outs, 1)                            # [s, nh, dv]
    if variant != "no_gate":
        o = o * jax.nn.sigmoid(x @ w["w_gate"])[..., None]
    return h + o.reshape(s, -1) @ w["wo"]


def router(x, w, top_k):
    """(expert ids [t, k], weights [t, k]) of every token over ALL
    experts."""
    s = jax.nn.sigmoid(x @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], top_k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def routed(x, w, held, top_k):
    """The held experts' part of the routed layer on x [t, hidden]: every
    held expert on every token, weighted by zero where it was not
    chosen."""
    idx, wts = router(x, w, top_k)
    width = w["w_d"].shape[1]
    y = jnp.zeros_like(x)
    for n, e in enumerate(range(*held)):
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=1)    # [t]
        gu = x @ w["w_gu"][n]
        y = y + w_e[:, None] * (
            (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ w["w_d"][n])
    return y


def ffn(x, w, cfg, kind, shared=True):
    """FFN(x) of one layer; shared=False leaves the shared expert out
    (the test that adds the shares up counts it once)."""
    if not kind[9]:
        return swiglu(x, w["wg"], w["wu"], w["wd"])
    y = routed(x, w, held_experts(cfg), cfg["num_experts_per_tok"])
    if shared and "ws_g" in w:
        y = y + swiglu(x, w["ws_g"], w["ws_u"], w["ws_d"])
    return y


def block(h, w, cfg, kind, variant=None):
    """One decoder layer on one sequence: h [s, hidden] float32."""
    h = attention_half(h, w, cfg, kind, variant)
    return h + ffn(rms_norm(h, w["ln2"], cfg["rms_norm_eps"]), w, cfg, kind)


def score_rows(x, head, tokens):
    """For rows x [n, hidden] (after the final norm) and one token id per
    row: (logsumexp over the vocabulary held, the top logit, the logit of
    the given token). `head` is [hidden, vocab] float32."""
    logits = x @ head
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jax.nn.logsumexp(logits, -1), jnp.max(logits, -1), picked


def forward(weights, ids, cfg, variant=None):
    """Logits [b, s, vocab] of ids [b, s] under float32 `weights` — the
    whole model as one pure function, for the tests."""
    kinds = layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        def one(seq):
            h = weights["emb"][seq]
            for w, kind in zip(weights["layers"], kinds):
                h = block(h, w, cfg, kind, variant)
            return rms_norm(h, weights["norm"],
                            cfg["rms_norm_eps"]) @ weights["head"]
        return jnp.stack([one(seq) for seq in ids])


# ------------------------------------------- at full size, layer by layer --
def _vocab_chunks(vocab, limit=8192):
    n = -(-vocab // limit)
    while vocab % n:
        n += 1
    return [(i * (vocab // n), (i + 1) * (vocab // n)) for i in range(n)]


class Reference:
    """Runs the system's own weights through the mathematics above, one
    layer's half, one sequence and one slice of the vocabulary at a time,
    so it fits beside the system on the chip. Each piece is one jitted
    function whose float32 copy of the weights lives only for that
    call."""

    def __init__(self, cfg, variant=None, precision="highest"):
        """`variant` (one of attention_half's deliberate faults) and
        `precision` ("bfloat16": every product in one bf16 pass;
        "float8": that, on weights rounded to float8_e4m3fn) are for the
        controls that show the comparison CAN fail at the cell's sizes
        (docs/probes/latent_check_controls.py); the benchmark builds
        Reference(cfg)."""
        self.cfg = cfg
        self.kinds = layer_kinds(cfg)
        passes = "highest" if precision == "highest" else "bfloat16"

        def load(w):
            if precision == "float8":
                w = w.astype(jnp.float8_e4m3fn)
            return w.astype(jnp.float32)
        attn_keys = ("ln1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                     "w_uk", "w_uv", "wo", "w_gate", "ix_wq", "ix_wk",
                     "ix_kn_w", "ix_kn_b", "ix_ww")

        @functools.partial(jax.jit, static_argnums=(2,))
        def attn(h, raw, kind):
            with jax.default_matmul_precision(passes):
                w = {k: load(v) for k, v in raw.items()}
                return attention_half(h, w, cfg, kind, variant)

        @functools.partial(jax.jit, static_argnums=(2,))
        def tail(h, raw, kind):
            with jax.default_matmul_precision(passes):
                w = {k: load(v) for k, v in raw.items()}
                x = rms_norm(h, w["ln2"], cfg["rms_norm_eps"])
                return h + ffn(x, w, cfg, kind)

        @jax.jit
        def final(h, norm):
            return rms_norm(h, load(norm), cfg["rms_norm_eps"])

        @functools.partial(jax.jit, static_argnums=(3,))
        def scores(x, head, c0, width, tokens):
            """One slice [c0, c0 + width) of the vocabulary."""
            part = jax.lax.dynamic_slice_in_dim(head, c0, width, 1)
            with jax.default_matmul_precision(passes):
                return score_rows(x, load(part), tokens)

        def layer(h, raw, kind):
            a = {k: v for k, v in raw.items() if k in attn_keys}
            t = {k: v for k, v in raw.items() if k not in attn_keys}
            return jnp.stack([tail(attn(hs, a, kind), t, kind)
                              for hs in h])

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


# ----------------------------------------------- the system's own weights --
def weights_from_engine(engine):
    """The serving engine's weights (public `export_weights()`), already
    in this module's layout."""
    return engine.export_weights()
