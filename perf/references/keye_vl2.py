"""Keye-VL-2.0 (`model_type` "KeyeVL2"), the plain reference: the language
model's block in straightforward jax.numpy, float32, every matrix
multiplication at precision "highest", dense masks, no kernels, no cache,
no batching. The comparison that decides `correct` runs the system's OWN
weights through this and compares logits.

Written from the keys of the public config.json (catalog row Keye-VL-2.0-
30B-A3B, https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/
config.json). All `num_hidden_layers` layers are alike. h a layer's
input, eps `rms_norm_eps`, no biases, untied embedding and head, final
RMSNorm:  a = h + Attn(RMSNorm(h)),  h' = a + Experts(RMSNorm(a)).

Attention, x = RMSNorm(h):
  q = x W_q as `num_attention_heads` heads x `head_dim`, k = x W_k and
  v = x W_v as `num_key_value_heads` heads x `head_dim`; RMSNorm over
  every q head and every k head (learned weight [head_dim]); rotary,
  rotate-half over all of head_dim on base `rope_theta`, where rotary
  pair i takes its angle from position COMPONENT c(i) by `rope_scaling.
  mrope_section` [16, 24, 24]: temporal for i < 16, height for 16 <= i <
  40, width for i >= 40. A text token's three components are equal: plain
  rotary. Position ids are [3, t]; `None` means text.
  Indexer (`sa_config`): q^I = x W^I_q as `indexer_num_heads` x
  `indexer_head_dim`, k^I = LayerNorm(x W^I_k) (ONE key a token: weight,
  bias, eps 1e-6), w = x W^I_w; rotate-half rotary over all of q^I and
  k^I at the token's temporal position, base `rope_theta`;
    I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s));
  S_t = the `topk` positions s <= t with the largest I(t, s), ties to the
  lower position (a stable argsort of -I), all of them while t + 1 <=
  topk: ONE selection a token and layer, shared by all heads.
  o_h(t) = sum_{s in S_t} softmax_{s in S_t}(q_h(t) . k_g(h)(s) /
  sqrt(head_dim)) v_g(h)(s), g(h) = h // (heads / kv heads);
  Attn = concat_h(o) W_o.
Experts, x = RMSNorm(a): p = softmax(x W_r) over all `num_experts`; the
`num_experts_per_tok` largest; w_e = p_e / sum of the chosen p
(`norm_topk_prob`); y = sum_chosen w_e (silu(x G_e) * (x U_e)) D_e. No
bias, no scaling factor, no shared expert, no dense layer
(`mlp_only_layers` [], `decoder_sparse_step` 1).

Departures and assumptions, each stated (the configuration file's
`assumed` has the reasons): (1) ASSUMED, no code of the family at hand (no
network): the per-head q/k RMSNorm (the Qwen3-MoE convention, whose every
size this language model has; the config has no key for it); the
indexer's query from the NORMED HIDDEN STATE (the config has no query
latent), its rotary over all 64 dims on the layer's base, its key's
LayerNorm, its positive constant scales dropped (they change no top-k):
the DeepSeek sparse-attention form `described_as` names; `sa_config.
q_chunk_size` / `kv_chunk_size` are tile sizes of the source's index
kernel, not a selection by blocks (`topk` counts tokens). (2) The vision
tower is not built; its tokens arrive as ids. (3) `held_experts` [lo,
hi): the experts this chip holds (all 128 in the benchmark's
configuration); what absent ones would add is left out, here as in the
program. (4) Weights are random from the seed.

Weights are handed over in the engine's canonical layout (inference/
description.py). Attention is computed one sequence and one KV group's
heads at a time, the experts one at a time from the weights as stored, so
3,088 positions fit beside the system on the chip.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

LN_EPS = 1e-6           # of the index key's LayerNorm (assumed)
ATTN_KEYS = ("ln1", "wq", "wk", "wv", "wo", "q_hn", "k_hn", "ix_wq",
             "ix_wk", "ix_kn_w", "ix_kn_b", "ix_ww")


# ------------------------------------------------ the program's model --
def held_experts(cfg):
    return cfg.get("held_experts") or [0, cfg["num_experts"]]


def model_config(cfg):
    """The program's config object from the configuration file."""
    from paddle_tpu.models import KeyeVL2Config
    sa = cfg["sa_config"]
    return KeyeVL2Config(
        mrope_section=cfg["rope_scaling"]["mrope_section"],
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        held_experts=held_experts(cfg),
        **{k: cfg[k] for k in (
            "vocab_size hidden_size moe_intermediate_size "
            "num_hidden_layers num_attention_heads num_key_value_heads "
            "head_dim rope_theta num_experts num_experts_per_tok "
            "norm_topk_prob rms_norm_eps max_position_embeddings").split()})


def build_model(cfg, seed):
    """The configuration as the PROGRAM builds it, parameters deferred
    (LazyGuard) so the engine materializes them from `seed` in the type
    it serves."""
    import paddle_tpu as paddle
    from paddle_tpu.models import KeyeVL2ForCausalLM
    paddle.seed(seed)
    with paddle.LazyGuard():
        return KeyeVL2ForCausalLM(model_config(cfg))


# ------------------------------------------------------ the mathematics --
def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def text_positions(s):
    """[3, s]: a text token's three components are its index."""
    return np.broadcast_to(np.arange(s), (3, s))


def rope(x, positions, theta, sections=None):
    """x [s, ..., d], rotate-half over all d. Pair i turns by positions[
    c(i), t] / theta^(2i / d); c(i) walks `sections` (pairs per component:
    temporal, height, width); None: every pair the temporal component."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    comp = np.zeros(d // 2, np.int64) if sections is None \
        else np.repeat(np.arange(len(sections)), sections)
    pos = np.asarray(positions, np.float64)[comp].T         # [s, d / 2]
    shape = (s,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(pos * inv), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(pos * inv), jnp.float32).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(x, w, cfg, positions, low=False):
    """I(t, s) [s, s] of one sequence. low=True (a control): the indexer
    computed on bf16 operands."""
    sa = cfg["sa_config"]
    n_i, d_i = sa["indexer_num_heads"], sa["indexer_head_dim"]
    s, theta = x.shape[0], cfg["rope_theta"]
    # the control rounds every operand of the indexer's products to bf16
    r = (lambda t: f32(t.astype(jnp.bfloat16))) if low else (lambda t: t)
    q = (r(x) @ r(w["ix_wq"])).reshape(s, n_i, d_i)
    k = layer_norm(r(x) @ r(w["ix_wk"]), w["ix_kn_w"], w["ix_kn_b"], LN_EPS)
    q, k = r(rope(q, positions, theta)), r(rope(k, positions, theta))
    wt = r(x) @ r(w["ix_ww"])                                 # [s, n_i]
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


def attention_half(h, w, cfg, positions=None, variant=None):
    """a = h + Attn(RMSNorm(h)) on one sequence: h [s, hidden] float32,
    positions [3, s] or None (text). `variant` names ONE deliberate fault,
    for the controls that must see a comparison fail: "no_selection",
    "bf16_indexer", "no_qk_norm", "float8_kv"."""
    s, eps = h.shape[0], cfg["rms_norm_eps"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    theta = cfg["rope_theta"]
    sections = cfg["rope_scaling"]["mrope_section"]
    if positions is None:
        positions = text_positions(s)
    x = rms_norm(h, w["ln1"], eps)
    q = (x @ w["wq"]).reshape(s, nh, d)
    k = (x @ w["wk"]).reshape(s, nkv, d)
    v = (x @ w["wv"]).reshape(s, nkv, d)
    if variant != "no_qk_norm":
        q, k = rms_norm(q, w["q_hn"], eps), rms_norm(k, w["k_hn"], eps)
    q = rope(q, positions, theta, sections)
    k = rope(k, positions, theta, sections)
    if variant == "float8_kv":
        k, v = (f32(t.astype(jnp.float8_e4m3fn)) for t in (k, v))
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    seen = jnp.asarray(j <= i)
    if variant != "no_selection":
        seen = selection(
            index_scores(x, w, cfg, positions,
                         low=variant == "bf16_indexer"),
            seen, cfg["sa_config"]["topk"])
    rep = nh // nkv
    outs = []
    for g in range(nkv):            # one KV group's heads: [rep, s, s]
        logits = jnp.einsum("qhd,kd->hqk", q[:, g * rep:(g + 1) * rep],
                            k[:, g]) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,kd->qhd", p, v[:, g]))
    return h + jnp.concatenate(outs, 1).reshape(s, -1) @ w["wo"]


def router(x, w_r, top_k, sigmoid=False):
    """(expert ids [t, k], weights [t, k]) of every token over ALL
    experts: softmax, the top k, normalised over the chosen. sigmoid=True
    (a control): the other families' scoring function, no bias."""
    p = jax.nn.sigmoid(x @ w_r) if sigmoid else jax.nn.softmax(x @ w_r, -1)
    chosen, idx = jax.lax.top_k(p, top_k)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def routed(x, w, held, top_k, sigmoid=False):
    """The held experts' part of the routed layer on x [t, hidden]: every
    held expert on every token, weighted by zero where it was not chosen.
    `w_gu` / `w_d` come as stored and are raised to float32 one expert at
    a time (128 float32 experts are 2.4 GB)."""
    idx, wts = router(x, f32(w["router"]), top_k, sigmoid)
    width = w["w_d"].shape[1]

    def add_expert(n, y):
        w_e = jnp.sum(jnp.where(idx == held[0] + n, wts, 0.0), axis=1)
        gu = x @ f32(w["w_gu"][n])
        return y + w_e[:, None] * (
            (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ f32(w["w_d"][n]))

    return jax.lax.fori_loop(0, held[1] - held[0], add_expert,
                             jnp.zeros_like(x))


def expert_half(h, w, cfg, variant=None):
    """h' = a + Experts(RMSNorm(a)) on one sequence."""
    x = rms_norm(h, f32(w["ln2"]), cfg["rms_norm_eps"])
    return h + routed(x, w, held_experts(cfg), cfg["num_experts_per_tok"],
                      sigmoid=variant == "sigmoid_router")


def block(h, w, cfg, positions=None, variant=None):
    """One decoder layer on one sequence: h [s, hidden] float32, w the
    layer's weights (float32 for the attention's)."""
    return expert_half(attention_half(h, w, cfg, positions, variant), w,
                       cfg, variant)


def score_rows(x, head, tokens):
    """For rows x [n, hidden] (after the final norm) and one token id per
    row: (logsumexp over the vocabulary held, the top logit, the logit of
    the given token). `head` is [hidden, vocab] float32."""
    logits = x @ head
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jax.nn.logsumexp(logits, -1), jnp.max(logits, -1), picked


def forward(weights, ids, cfg, positions=None, variant=None):
    """Logits [b, s, vocab] of ids [b, s] under float32 `weights` — the
    whole model as one pure function, for the tests. positions [3, s] (one
    for all sequences) or None (text)."""
    with jax.default_matmul_precision("highest"):
        def one(seq):
            h = weights["emb"][seq]
            for w in weights["layers"]:
                h = block(h, w, cfg, positions, variant)
            return rms_norm(h, weights["norm"],
                            cfg["rms_norm_eps"]) @ weights["head"]
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
    layer's half, one sequence and one slice of the vocabulary at a time,
    so it fits beside the system on the chip. Each piece is one jitted
    function whose float32 copy of the weights lives only for that
    call."""

    def __init__(self, cfg, variant=None, precision="highest"):
        """`variant` (one of the deliberate faults above, or
        "sigmoid_router") and `precision` ("bfloat16": every product in
        one bf16 pass) are for the controls that show what a comparison
        can and cannot see at the cell's sizes (docs/probes/
        latent_check_controls.py); the benchmark builds Reference(cfg)."""
        self.cfg = cfg

        @jax.jit
        def attn(h, raw):
            with jax.default_matmul_precision(precision):
                w = {k: f32(v) for k, v in raw.items()}
                return attention_half(h, w, cfg, None, variant)

        @jax.jit
        def tail(h, raw):
            with jax.default_matmul_precision(precision):
                return expert_half(h, raw, cfg, variant)

        @jax.jit
        def final(h, norm):
            return rms_norm(h, f32(norm), cfg["rms_norm_eps"])

        @functools.partial(jax.jit, static_argnums=(3,))
        def scores(x, head, c0, width, tokens):
            """One slice [c0, c0 + width) of the vocabulary."""
            part = jax.lax.dynamic_slice_in_dim(head, c0, width, 1)
            with jax.default_matmul_precision(precision):
                return score_rows(x, f32(part), tokens)

        def layer(h, raw):
            a = {k: v for k, v in raw.items() if k in ATTN_KEYS}
            t = {k: v for k, v in raw.items() if k not in ATTN_KEYS}
            return jnp.stack([tail(attn(hs, a), t) for hs in h])

        self._layer, self._final, self._scores = layer, final, scores

    def hidden(self, weights, ids):
        """Final-normed hidden states [b, s, hidden] float32 for TEXT
        token ids [b, s]."""
        h = f32(weights["emb"][jnp.asarray(ids)])
        for raw in weights["layers"]:
            h = self._layer(h, raw)
        return self._final(h, weights["norm"])

    def score(self, weights, x, tokens):
        """score_rows over the vocabulary, in slices: x [n, hidden],
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
