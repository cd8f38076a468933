"""Cohere2-MoE family (`model_type` "cohere2_moe", Command A+): the
language model, built from the keys of its public config.json.

Per layer l, a PARALLEL block under ONE norm (`use_parallel_block`):
  n = LayerNorm(h)       mean-subtracted, a weight and no bias, `layer_norm_eps`
  h' = h + Attn_l(n) + Routed_l(n) + Shared_l(n)
tied embedding and head (`tie_word_embeddings`), logits = `logit_scale` x
LayerNorm(h_last) Emb^T, no biases anywhere.

  - `layer_types[l]`: "sliding_attention" rotates q and k over all
    `head_dim` dims (`rotary_pct` 1) in INTERLEAVED pairs (2i, 2i + 1)
    (`position_embedding_type` "rope_gptj") on base `rope_theta` and sees
    keys j with i - `sliding_window` < j <= i; "full_attention" is
    position-free (no rotation) and causal. Both: `num_attention_heads`
    query heads over `num_key_value_heads` KV heads of `head_dim`, logits
    q.k / sqrt(head_dim).
  - routed: s = sigmoid(n W_r) over all `num_experts` in float32; the
    `num_experts_per_tok` largest s; w_e = s_e / sum_chosen s
    (`norm_topk_prob`; no stored correction bias); SwiGLU experts of
    width `intermediate_size` (ops/moe.py).
  - shared: the MEAN of `num_shared_experts` SwiGLU experts of the same
    width, on every token (`shared_expert_combination_strategy`
    "average"), added to the routed sum.

The expert layer is TOLD which experts it holds (`held_experts` = [lo,
hi)): it routes over all of them and computes its own experts' part, the
chip's share under expert parallelism; the default holds all.

What the engine is handed (`serving_parameters`) are LAYOUTS of these
weights, the mathematics unchanged: `wq` / `wk` of a rotating layer with
each head's columns de-interleaved (the engine rotates half-split; a dot
product does not mind a permutation applied to both sides), the shared
experts as ONE SwiGLU of `num_shared_experts` x the width whose down
projection carries the 1 / n (a sum over experts of down(silu(g) * u) is
a concatenation along the width), and the head as `logit_scale` x Emb^T.

Not here: the vision tower (not among the language model's keys; its
tokens arrive as ids), leading dense layers (`first_k_dense_replace` 0 in
the published config; anything else is refused), q/k norm (`use_qk_norm`
false; true is refused).
"""
import math

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..framework.misc import materialize_lazy
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm
from ..ops import apply
from ..ops.moe import routed_experts

SLIDING, FULL = "sliding_attention", "full_attention"


class Cohere2MoeConfig:
    def __init__(self, vocab_size=262144, hidden_size=4096,
                 intermediate_size=4096, num_hidden_layers=32,
                 num_attention_heads=128, num_key_value_heads=8,
                 head_dim=128, layer_types=None, layer_switch=4,
                 sliding_window=4096, rope_theta=50000.0, rotary_pct=1,
                 layer_norm_eps=1e-5, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=4,
                 norm_topk_prob=True, logit_scale=1.0,
                 tie_word_embeddings=True, use_parallel_block=True,
                 use_qk_norm=False, first_k_dense_replace=0,
                 max_position_embeddings=200000, held_experts=None,
                 dtype="float32"):
        for on, what in (
                (not use_parallel_block, "use_parallel_block false"),
                (not tie_word_embeddings, "tie_word_embeddings false"),
                (not norm_topk_prob, "norm_topk_prob false"),
                (use_qk_norm, "use_qk_norm true"),
                (first_k_dense_replace, "first_k_dense_replace > 0"),
                (rotary_pct != 1, "rotary_pct != 1")):
            if on:
                raise ValueError(
                    f"Cohere2MoeConfig: {what} is not built (the published "
                    "config has a parallel block, tied embeddings, "
                    "normalised top-k weights, no q/k norm, no leading "
                    "dense layer and rotates every dim)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        # `layer_switch` is the period: local layers first, every
        # layer_switch-th one full (`order_of_interleaved_layers`
        # "local_attn_first")
        self.layer_types = list(
            layer_types if layer_types is not None
            else [FULL if (l + 1) % layer_switch == 0 else SLIDING
                  for l in range(num_hidden_layers)])
        if len(self.layer_types) != num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types needs one of {SLIDING!r} / {FULL!r} per "
                f"layer ({num_hidden_layers}): {self.layer_types}")
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.layer_norm_eps = layer_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.logit_scale = logit_scale
        self.max_position_embeddings = max_position_embeddings
        self.held_experts = tuple(held_experts if held_experts is not None
                                  else (0, num_experts))
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(
                f"held_experts {self.held_experts} is no range of the "
                f"{num_experts} routed experts")
        self.dtype = dtype

    def layer_spec(self, l):
        from ..inference.description import (AttentionSpec, FFNSpec,
                                             LayerSpec)
        window = self.layer_types[l] == SLIDING
        attn = AttentionSpec(
            n_heads=self.num_attention_heads,
            n_kv_heads=self.num_key_value_heads,
            qk_dim=self.head_dim, v_dim=self.head_dim,
            rope_dim=self.head_dim if window else 0,
            rope_theta=float(self.rope_theta),
            window=int(self.sliding_window) if window else None)
        ffn = FFNSpec("experts", self.intermediate_size,
                      n_experts=self.num_experts,
                      top_k=self.num_experts_per_tok,
                      held=self.held_experts,
                      shared_width=(self.num_shared_experts
                                    * self.intermediate_size),
                      score="sigmoid")
        return LayerSpec(attn, ffn, parallel=True)

    @staticmethod
    def tiny(**kw):
        """Test size: every mechanism present, nothing wide; two periods
        of [sliding, sliding, sliding, full]."""
        kw.setdefault("vocab_size", 96)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 32)
        kw.setdefault("num_hidden_layers", 8)
        kw.setdefault("num_attention_heads", 8)
        kw.setdefault("num_key_value_heads", 2)
        kw.setdefault("head_dim", 16)
        kw.setdefault("sliding_window", 8)
        kw.setdefault("num_experts", 8)
        kw.setdefault("num_experts_per_tok", 2)
        kw.setdefault("num_shared_experts", 4)
        kw.setdefault("max_position_embeddings", 128)
        return Cohere2MoeConfig(**kw)


def _fan_in(std_of):
    return I.Normal(0.0, 1.0 / math.sqrt(std_of))


# ---------------------------------------------------- the mathematics --
def rope_interleaved(x, theta):
    """x [b, s, heads, d] at positions 0..s-1: the pairs (2i, 2i + 1)
    rotate by position x theta^(-2i / d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sn = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn],
                     -1).reshape(x.shape)


def deinterleave(w, n_heads, d):
    """Columns of a projection [hidden, heads x d]: every head's even
    columns first, then its odd ones. Half-split rotation of the result
    is interleaved rotation of the original, up to the same permutation
    of q and k, which q.k does not see."""
    order = jnp.concatenate([jnp.arange(0, d, 2), jnp.arange(1, d, 2)])
    return w.reshape(w.shape[0], n_heads, d)[:, :, order].reshape(w.shape)


def attention(x, wq, wk, wv, wo, a):
    """One layer's attention on x [b, s, hidden] (already normed), dense
    masks, in x's dtype with a float32 softmax. `a` an AttentionSpec."""
    b, s, _ = x.shape
    q = (x @ wq).reshape(b, s, a.n_heads, a.qk_dim)
    k = (x @ wk).reshape(b, s, a.n_kv_heads, a.qk_dim)
    v = (x @ wv).reshape(b, s, a.n_kv_heads, a.v_dim)
    if a.rope_dim:
        q, k = (rope_interleaved(t, a.rope_theta) for t in (q, k))
    rep = a.n_heads // a.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / math.sqrt(a.qk_dim)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if a.window is not None:
        seen = seen & (j > i - a.window)
    logits = jnp.where(seen[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, -1).astype(x.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, -1) @ wo


def shared_mean(x, gate, up, down):
    """The mean of the shared experts on x [..., hidden]: gate / up [n,
    hidden, width], down [n, width, hidden], each computed apart."""
    ys = [(jax.nn.silu((x @ g).astype(jnp.float32)).astype(x.dtype)
           * (x @ u)) @ d for g, u, d in zip(gate, up, down)]
    return sum(ys) / len(ys)


class _Layout:
    """A layout of stored parameters under one of the engine's canonical
    names: what `.data` gives is made from the sources when the engine
    takes its snapshot (they may be lazy) and kept nowhere."""

    def __init__(self, fn, *params):
        self.fn, self.params = fn, params

    @property
    def data(self):
        return self.fn(*(materialize_lazy(p) for p in self.params))


# ------------------------------------------------------------- layers --
class Cohere2MoeAttention(Layer):
    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        h = config.hidden_size
        nq = spec.n_heads * spec.qk_dim
        nkv = spec.n_kv_heads * spec.qk_dim
        self.q_proj = self.create_parameter(
            [h, nq], default_initializer=_fan_in(h))
        self.k_proj = self.create_parameter(
            [h, nkv], default_initializer=_fan_in(h))
        self.v_proj = self.create_parameter(
            [h, nkv], default_initializer=_fan_in(h))
        self.o_proj = self.create_parameter(
            [nq, h], default_initializer=_fan_in(nq))

    def forward(self, x):
        spec = self.spec
        return apply(lambda xa, wq, wk, wv, wo: attention(
            xa, wq, wk, wv, wo, spec), x, self.q_proj, self.k_proj,
            self.v_proj, self.o_proj, name="cohere2_attention")

    def serving_weights(self):
        a = self.spec
        if not a.rope_dim:              # nothing rotates: as stored
            return dict(wq=self.q_proj, wk=self.k_proj, wv=self.v_proj,
                        wo=self.o_proj)
        return dict(
            wq=_Layout(lambda w: deinterleave(w, a.n_heads, a.qk_dim),
                       self.q_proj),
            wk=_Layout(lambda w: deinterleave(w, a.n_kv_heads, a.qk_dim),
                       self.k_proj),
            wv=self.v_proj, wo=self.o_proj)


class Cohere2MoeFFN(Layer):
    """The routed experts of one layer (the router over ALL experts, the
    weights of the experts held here) and its shared experts."""

    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        h, f = config.hidden_size, spec.width
        n_held = spec.held[1] - spec.held[0]
        n_sh = config.num_shared_experts
        self.router = self.create_parameter(
            [h, spec.n_experts], dtype="float32",
            default_initializer=_fan_in(h))
        self.gate_up_proj = self.create_parameter(
            [n_held, h, 2 * f], default_initializer=_fan_in(h))
        self.down_proj = self.create_parameter(
            [n_held, f, h], default_initializer=_fan_in(f))
        self.shared_gate = self.create_parameter(
            [n_sh, h, f], default_initializer=_fan_in(h))
        self.shared_up = self.create_parameter(
            [n_sh, h, f], default_initializer=_fan_in(h))
        self.shared_down = self.create_parameter(
            [n_sh, f, h], default_initializer=_fan_in(f))

    def forward(self, x):
        spec = self.spec
        interpret = jax.default_backend() == "cpu"

        def run(xa, rw, wgu, wd, sg, su, sd):
            b, s, h = xa.shape
            y, _ = routed_experts(xa.reshape(b * s, h), rw, None, wgu, wd,
                                  spec.held, spec.top_k,
                                  interpret=interpret, score=spec.score)
            return y.reshape(b, s, h) + shared_mean(xa, sg, su, sd)

        # inference only: the grouped product has no backward
        with tape.no_grad():
            return apply(run, x, self.router, self.gate_up_proj,
                         self.down_proj, self.shared_gate, self.shared_up,
                         self.shared_down, name="cohere2_ffn")

    def serving_weights(self):
        def wide(w):        # [n, hidden, width] -> [hidden, n x width]
            return jnp.swapaxes(w, 0, 1).reshape(w.shape[1], -1)

        def tall(w):        # [n, width, hidden] -> [n x width, hidden] / n
            return w.reshape(-1, w.shape[2]) / w.shape[0]

        return dict(router=self.router, w_gu=self.gate_up_proj,
                    w_d=self.down_proj,
                    ws_g=_Layout(wide, self.shared_gate),
                    ws_u=_Layout(wide, self.shared_up),
                    ws_d=_Layout(tall, self.shared_down))


class Cohere2MoeDecoderLayer(Layer):
    def __init__(self, config, l):
        super().__init__()
        spec = config.layer_spec(l)
        self.input_layernorm = LayerNorm(
            config.hidden_size, config.layer_norm_eps, bias_attr=False)
        self.self_attn = Cohere2MoeAttention(config, spec.attn)
        self.mlp = Cohere2MoeFFN(config, spec.ffn)

    def forward(self, h):
        n = self.input_layernorm(h)
        return h + self.self_attn(n) + self.mlp(n)


class Cohere2MoeForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, h], default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([Cohere2MoeDecoderLayer(config, l)
                                 for l in range(config.num_hidden_layers)])
        self.norm = LayerNorm(h, config.layer_norm_eps, bias_attr=False)

    def forward(self, input_ids):
        """Logits [b, s, vocab] of TEXT token ids [b, s]."""
        h = apply(lambda e, ids: jnp.take(e, ids, axis=0),
                  self.embed_tokens, input_ids, name="embedding")
        for layer in self.layers:
            h = layer(h)
        scale = self.config.logit_scale
        return apply(lambda x, e: (x @ e.T) * scale, self.norm(h),
                     self.embed_tokens, name="lm_head")

    # -- the serving engine's seam (inference/description.py) ---------------
    def serving_description(self):
        from ..inference.description import ModelDescription
        cfg = self.config
        return ModelDescription(
            hidden_size=cfg.hidden_size, vocab_size=cfg.vocab_size,
            eps=cfg.layer_norm_eps, norm="layer",
            layers=tuple(cfg.layer_spec(l)
                         for l in range(cfg.num_hidden_layers)))

    def serving_parameters(self):
        scale = self.config.logit_scale
        layers = [dict(ln1=layer.input_layernorm.weight,
                       **layer.self_attn.serving_weights(),
                       **layer.mlp.serving_weights())
                  for layer in self.layers]
        return dict(emb=self.embed_tokens, norm=self.norm.weight,
                    head=_Layout(lambda e: e.T * scale, self.embed_tokens),
                    layers=layers)
