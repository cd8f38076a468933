"""InternLM2, the plain reference: the block in straightforward jax.numpy,
float32, every matrix multiplication at precision "highest", no kernels,
no cache, no batching tricks. The comparison that decides `correct` runs
the system's OWN weights through this and compares logits and losses.

Published description: InternLM2 technical report (arXiv:2403.17297) and
the `modeling_internlm2.py` beside the public checkpoints. The block is
  h = h + Wo . Attention(RoPE(Wq x), RoPE(Wk x), Wv x),  x = RMSNorm(h)
  h = h + Wd . (silu(Wg x) * Wu x),                      x = RMSNorm(h)
with grouped-query attention (each group of n_heads / n_kv_heads query
heads shares one key/value head), rotary embedding in the rotate-half
convention over the whole head, causal mask, no biases, untied output
head. Departures, each harmless here: the checkpoint stores q, k and v
fused in one `wqkv` matrix, interleaved by group — a storage layout, the
arithmetic is three projections; `rope_scaling` (dynamic NTK) only acts
past the declared context and no position in the benchmark passes 4096,
so it is not applied.

Weights are handed over in one layout (`emb`, `norm`, `head`, `layers[i]`
with ln1 wq wk wv wo ln2 wg wu wd; matrices [in, out]) in whatever type
the system serves them: a float array, or an (int8, per-column scale)
pair. `as_f32` is the only place that knows.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

_TRAINER_NAMES = {
    "input_layernorm.weight": "ln1", "self_attn.q_proj.weight": "wq",
    "self_attn.k_proj.weight": "wk", "self_attn.v_proj.weight": "wv",
    "self_attn.o_proj.weight": "wo", "post_attention_layernorm.weight": "ln2",
    "mlp.gate_proj.weight": "wg", "mlp.up_proj.weight": "wu",
    "mlp.down_proj.weight": "wd"}


# ------------------------------------------------ the program's model --
def build_model(cfg, seed):
    """The configuration as the PROGRAM builds it: its Llama-equation
    classes at InternLM2's sizes, parameters deferred (LazyGuard) so the
    engine or the trainer materializes them from `seed` in the type it
    serves."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    mcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"])
    paddle.seed(seed)
    with paddle.LazyGuard():
        return LlamaForCausalLM(mcfg)


# ------------------------------------------------------ the mathematics --
def as_f32(w):
    if isinstance(w, (tuple, list)):
        q, scale = w
        return q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [s, heads, d] at positions 0..s-1; rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_block=512):
    """Causal grouped-query attention of one sequence. q [s, H, d],
    k and v [s, KV, d]. Query rows go in blocks only so that the score
    matrix of a 4096-token sequence stays small."""
    s, n_heads, d = q.shape
    rep = n_heads // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for q0 in range(0, s, q_block):
        q1 = min(q0 + q_block, s)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) / math.sqrt(d)
        seen = np.arange(q0, q1)[:, None] >= np.arange(q1)[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, -1), v[:q1]))
    return jnp.concatenate(outs, 0)


def block(h, w, cfg):
    """One decoder layer on one sequence: h [s, hidden] float32, w the
    layer's weights in float32."""
    s = h.shape[0]
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = rms_norm(h, w["ln1"], eps)
    q = rope((x @ w["wq"]).reshape(s, -1, d), theta)
    k = rope((x @ w["wk"]).reshape(s, -1, d), theta)
    v = (x @ w["wv"]).reshape(s, -1, d)
    h = h + attention(q, k, v).reshape(s, -1) @ w["wo"]
    x = rms_norm(h, w["ln2"], eps)
    return h + (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def score_rows(x, head, tokens):
    """For rows x [n, hidden] (after the final norm) and one token id per
    row: (logsumexp over the vocabulary, the top logit, the logit of the
    given token). `head` is [hidden, vocab] float32."""
    logits = x @ head
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jax.nn.logsumexp(logits, -1), jnp.max(logits, -1), picked


def loss(weights, ids, labels, cfg):
    """Mean next-token cross-entropy of a batch ids/labels [b, s] under
    float32 `weights` — the whole model as one pure function, for
    jax.grad at test size."""
    with jax.default_matmul_precision("highest"):
        def one(seq, lab):
            h = weights["emb"][seq]
            for w in weights["layers"]:
                h = block(h, w, cfg)
            x = rms_norm(h, weights["norm"], cfg["rms_norm_eps"])
            lse, _, picked = score_rows(x, weights["head"], lab)
            return lse - picked
        return jnp.mean(jax.vmap(one)(ids, labels))


# ------------------------------------------- at full size, layer by layer --
def _vocab_chunks(vocab, limit=8192):
    n = -(-vocab // limit)
    while vocab % n:
        n += 1
    return [(i * (vocab // n), (i + 1) * (vocab // n)) for i in range(n)]


class Reference:
    """Runs the system's own weights through the mathematics above, one
    layer and one slice of the vocabulary at a time, so it fits beside
    the system on the chip. Each piece is one jitted function whose
    float32 copy of the weights lives only for that call."""

    def __init__(self, cfg):
        self.cfg = cfg

        @jax.jit
        def layer(h, raw):
            with jax.default_matmul_precision("highest"):
                w = {k: as_f32(v) for k, v in raw.items()}
                return jax.vmap(lambda hs: block(hs, w, cfg))(h)

        @jax.jit
        def final(h, norm):
            return rms_norm(h, as_f32(norm), cfg["rms_norm_eps"])

        @functools.partial(jax.jit, static_argnums=(3,))
        def scores(x, head, c0, width, tokens):
            """One slice [c0, c0 + width) of the vocabulary."""
            def cols(a, axis):
                return jax.lax.dynamic_slice_in_dim(a, c0, width, axis)
            part = ((cols(head[0], 1), cols(head[1], 0))
                    if isinstance(head, (tuple, list)) else cols(head, 1))
            with jax.default_matmul_precision("highest"):
                return score_rows(x, as_f32(part), tokens)

        self._layer, self._final, self._scores = layer, final, scores

    def hidden(self, weights, ids):
        """Final-normed hidden states [b, s, hidden] float32 for token ids
        [b, s]. weights["layers"] is indexable and may build each layer
        on demand."""
        h = as_f32(weights["emb"][jnp.asarray(ids)])
        for i in range(self.cfg["num_hidden_layers"]):
            h = self._layer(h, weights["layers"][i])
        return self._final(h, weights["norm"])

    def score(self, weights, x, tokens):
        """score_rows over the whole vocabulary in slices: x [n, hidden],
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


class _TrainerLayers:
    def __init__(self, trainer, stacked):
        self._names = trainer.layer_param_names
        self._where = {li: phys for phys, li in enumerate(trainer.phys_order)}
        self._stacked = stacked

    def __getitem__(self, li):
        phys = self._where[li]
        return {_TRAINER_NAMES[n]: a[phys]
                for n, a in zip(self._names, self._stacked)}


def weights_from_trainer(trainer, state):
    """The trainer's current parameters (public `gather_params`), mapped
    to this module's layout without copying more than a layer at a time."""
    params = trainer.gather_params(state)
    emb, norm, head = params["outer"]
    return {"emb": emb, "norm": norm, "head": head,
            "layers": _TrainerLayers(trainer, params["stacked"])}
