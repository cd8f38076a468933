"""MiMo-V2 family (`model_type` "mimo_v2"): the language model, built from
the keys of its public config.json.

Per layer l: a = h + Attn_l(RMSNorm(h)), h' = a + FFN_l(RMSNorm(a)); no
biases, untied embedding and head, final RMSNorm.

  - `hybrid_layer_pattern[l]`: 0 = full causal attention (`num_key_value_
    heads`, `rope_theta`, sink by `add_full_attention_sink_bias`), 1 =
    sliding window (`swa_num_key_value_heads`, `swa_rope_theta`, keys j
    with i - `sliding_window` < j <= i, sink by `add_swa_attention_sink_
    bias`). Both: one fused QKV projection, query/key width `head_dim`,
    value width `v_head_dim`, rotary (rotate-half) on the first
    int(head_dim x `partial_rotary_factor`) dimensions, values scaled by
    `attention_value_scale` before they are cached, logits q.k /
    sqrt(head_dim), and with a sink s_h one more term exp(s_h) in the
    softmax's denominator.
  - `moe_layer_freq[l]`: 0 = dense SwiGLU of `intermediate_size`, 1 =
    `n_routed_experts` SwiGLU experts of `moe_intermediate_size`, top
    `num_experts_per_tok` by sigmoid score plus the `noaux_tc` correction
    bias, weights normalised over the chosen (ops/moe.py). No shared
    expert, no group limit (`n_group` 1).

The expert layer is TOLD which experts it holds (`held_experts` = [lo,
hi)): it routes over all of them and computes its own experts' part, the
chip's share under expert parallelism; the default holds all.

Not here: the multi-token-prediction layers and the vision and audio
towers (not among the language model's config keys), any q/k norm (no
key declares one), `attention_chunk_size` (no equation above uses it).
"""
import math

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops import apply
from ..ops.moe import routed_experts


class MiMoV2Config:
    def __init__(self, vocab_size=152576, hidden_size=4096,
                 intermediate_size=16384, moe_intermediate_size=2048,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=4, swa_num_key_value_heads=8,
                 head_dim=192, v_head_dim=128, partial_rotary_factor=0.334,
                 rope_theta=1e7, swa_rope_theta=1e4, sliding_window=128,
                 attention_value_scale=0.707,
                 add_full_attention_sink_bias=False,
                 add_swa_attention_sink_bias=True,
                 hybrid_layer_pattern=None, moe_layer_freq=None,
                 n_routed_experts=256, num_experts_per_tok=8,
                 layernorm_epsilon=1e-5, max_position_embeddings=4096,
                 held_experts=None, dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.head_dim = head_dim
        self.v_head_dim = v_head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.swa_rope_theta = swa_rope_theta
        self.sliding_window = sliding_window
        self.attention_value_scale = attention_value_scale
        self.add_full_attention_sink_bias = add_full_attention_sink_bias
        self.add_swa_attention_sink_bias = add_swa_attention_sink_bias
        self.hybrid_layer_pattern = list(
            hybrid_layer_pattern if hybrid_layer_pattern is not None
            else [0] * num_hidden_layers)
        self.moe_layer_freq = list(
            moe_layer_freq if moe_layer_freq is not None
            else [0] * num_hidden_layers)
        if not (len(self.hybrid_layer_pattern) == num_hidden_layers
                == len(self.moe_layer_freq)):
            raise ValueError(
                "hybrid_layer_pattern and moe_layer_freq need one entry "
                f"per layer ({num_hidden_layers})")
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.layernorm_epsilon = layernorm_epsilon
        self.rms_norm_eps = layernorm_epsilon     # the engine's old name
        self.max_position_embeddings = max_position_embeddings
        self.held_experts = tuple(held_experts if held_experts is not None
                                  else (0, n_routed_experts))
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= n_routed_experts:
            raise ValueError(
                f"held_experts {self.held_experts} is no range of the "
                f"{n_routed_experts} routed experts")
        self.dtype = dtype

    @property
    def rope_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    def layer_spec(self, l):
        from ..inference.description import (AttentionSpec, FFNSpec,
                                             LayerSpec)
        window = bool(self.hybrid_layer_pattern[l])
        attn = AttentionSpec(
            n_heads=self.num_attention_heads,
            n_kv_heads=(self.swa_num_key_value_heads if window
                        else self.num_key_value_heads),
            qk_dim=self.head_dim, v_dim=self.v_head_dim,
            rope_dim=self.rope_dim,
            rope_theta=float(self.swa_rope_theta if window
                             else self.rope_theta),
            window=int(self.sliding_window) if window else None,
            sink=bool(self.add_swa_attention_sink_bias if window
                      else self.add_full_attention_sink_bias),
            value_scale=float(self.attention_value_scale))
        if self.moe_layer_freq[l]:
            ffn = FFNSpec("experts", self.moe_intermediate_size,
                          n_experts=self.n_routed_experts,
                          top_k=self.num_experts_per_tok,
                          held=self.held_experts)
        else:
            ffn = FFNSpec("dense", self.intermediate_size)
        return LayerSpec(attn, ffn)

    @staticmethod
    def tiny(**kw):
        """Test size: every mechanism present, nothing wide."""
        kw.setdefault("vocab_size", 96)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("moe_intermediate_size", 32)
        kw.setdefault("num_hidden_layers", 4)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("num_key_value_heads", 1)
        kw.setdefault("swa_num_key_value_heads", 2)
        kw.setdefault("head_dim", 24)
        kw.setdefault("v_head_dim", 16)
        kw.setdefault("sliding_window", 8)
        kw.setdefault("hybrid_layer_pattern", [0, 1, 1, 0])
        kw.setdefault("moe_layer_freq", [0, 1, 1, 1])
        kw.setdefault("n_routed_experts", 8)
        kw.setdefault("num_experts_per_tok", 2)
        kw.setdefault("max_position_embeddings", 128)
        return MiMoV2Config(**kw)


def _fan_in(std_of):
    return I.Normal(0.0, 1.0 / math.sqrt(std_of))


# ---------------------------------------------------- the mathematics --
def rope_tables(n_pos, rope_dim, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                           / rope_dim))
    ang = jnp.arange(n_pos, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def partial_rotary(x, cos, sin):
    """Rotate-half over the first 2 * cos.shape[-1] dims of x [..., s, h,
    d]; the rest pass through. cos/sin [s, r/2]."""
    r = 2 * cos.shape[-1]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def attention(x, wqkv, wo, sink, a):
    """One layer's attention on x [b, s, hidden] (already normed), dense
    masks, in x's dtype with a float32 softmax. `a` an AttentionSpec."""
    b, s, _ = x.shape
    nq, nkv = a.n_heads * a.qk_dim, a.n_kv_heads * a.qk_dim
    qkv = x @ wqkv
    q = qkv[..., :nq].reshape(b, s, a.n_heads, a.qk_dim)
    k = qkv[..., nq:nq + nkv].reshape(b, s, a.n_kv_heads, a.qk_dim)
    v = qkv[..., nq + nkv:].reshape(b, s, a.n_kv_heads, a.v_dim) \
        * a.value_scale
    cos, sin = rope_tables(s, a.rope_dim, a.rope_theta)
    q, k = partial_rotary(q, cos, sin), partial_rotary(k, cos, sin)
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
    m = jnp.max(logits, -1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32)[None, :, None, None]
        m = jnp.maximum(m, sk)
    e = jnp.exp(logits - m)
    den = jnp.sum(e, -1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - m)
    w = (e / den).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, -1)
    return out @ wo


# ------------------------------------------------------------- layers --
class MiMoV2Attention(Layer):
    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        h = config.hidden_size
        out = (spec.n_heads + spec.n_kv_heads) * spec.qk_dim \
            + spec.n_kv_heads * spec.v_dim
        self.qkv_proj = self.create_parameter(
            [h, out], default_initializer=_fan_in(h))
        self.o_proj = self.create_parameter(
            [spec.n_heads * spec.v_dim, h],
            default_initializer=_fan_in(spec.n_heads * spec.v_dim))
        self.sink = self.create_parameter(
            [spec.n_heads], default_initializer=I.Normal(0.0, 1.0)) \
            if spec.sink else None

    def forward(self, x):
        spec = self.spec
        if self.sink is None:
            return apply(lambda xa, wqkv, wo: attention(
                xa, wqkv, wo, None, spec), x, self.qkv_proj, self.o_proj,
                name="mimo_attention")
        return apply(lambda xa, wqkv, wo, sk: attention(
            xa, wqkv, wo, sk, spec), x, self.qkv_proj, self.o_proj,
            self.sink, name="mimo_attention")


class MiMoV2MLP(Layer):
    def __init__(self, config):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        self.gate_proj = self.create_parameter(
            [h, f], default_initializer=_fan_in(h))
        self.up_proj = self.create_parameter(
            [h, f], default_initializer=_fan_in(h))
        self.down_proj = self.create_parameter(
            [f, h], default_initializer=_fan_in(f))

    def forward(self, x):
        return apply(lambda xa, g, u, d: (
            jax.nn.silu((xa @ g).astype(jnp.float32)).astype(xa.dtype)
            * (xa @ u)) @ d, x, self.gate_proj, self.up_proj,
            self.down_proj, name="swiglu")


class MiMoV2Experts(Layer):
    """The routed experts of one layer: the router over ALL experts, the
    weights of the experts held here."""

    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        h, f = config.hidden_size, spec.width
        n_held = spec.held[1] - spec.held[0]
        # the router and its correction bias stay float32 in every engine
        self.router = self.create_parameter(
            [h, spec.n_experts], dtype="float32",
            default_initializer=_fan_in(h))
        self.router_bias = self.create_parameter(
            [spec.n_experts], dtype="float32",
            default_initializer=I.Normal(0.0, 0.1))
        self.gate_up_proj = self.create_parameter(
            [n_held, h, 2 * f], default_initializer=_fan_in(h))
        self.down_proj = self.create_parameter(
            [n_held, f, h], default_initializer=_fan_in(f))

    def forward(self, x):
        spec = self.spec
        interpret = jax.default_backend() == "cpu"

        def run(xa, rw, rb, wgu, wd):
            b, s, h = xa.shape
            y, _ = routed_experts(xa.reshape(b * s, h), rw, rb, wgu, wd,
                                  spec.held, spec.top_k,
                                  interpret=interpret)
            return y.reshape(b, s, h)

        # inference only: the grouped product has no backward yet (experts
        # in SpmdTrainer are PERF.md section 7's), so nothing is taped
        with tape.no_grad():
            return apply(run, x, self.router, self.router_bias,
                         self.gate_up_proj, self.down_proj,
                         name="routed_experts")


class MiMoV2DecoderLayer(Layer):
    def __init__(self, config, l):
        super().__init__()
        spec = config.layer_spec(l)
        eps = config.layernorm_epsilon
        self.input_layernorm = RMSNorm(config.hidden_size, eps)
        self.self_attn = MiMoV2Attention(config, spec.attn)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, eps)
        self.mlp = (MiMoV2Experts(config, spec.ffn)
                    if spec.ffn.kind == "experts" else MiMoV2MLP(config))

    def forward(self, h):
        h = h + self.self_attn(self.input_layernorm(h))
        return h + self.mlp(self.post_attention_layernorm(h))


class MiMoV2ForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, h], default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([MiMoV2DecoderLayer(config, l)
                                 for l in range(config.num_hidden_layers)])
        self.norm = RMSNorm(h, config.layernorm_epsilon)
        self.lm_head = self.create_parameter(
            [h, config.vocab_size], default_initializer=_fan_in(h))

    def forward(self, input_ids):
        """Logits [b, s, vocab] of token ids [b, s]."""
        h = apply(lambda e, ids: jnp.take(e, ids, axis=0),
                  self.embed_tokens, input_ids, name="embedding")
        for layer in self.layers:
            h = layer(h)
        return apply(lambda x, w: x @ w, self.norm(h), self.lm_head,
                     name="lm_head")

    # -- the serving engine's seam (inference/description.py) ---------------
    def serving_description(self):
        from ..inference.description import ModelDescription
        cfg = self.config
        return ModelDescription(
            hidden_size=cfg.hidden_size, vocab_size=cfg.vocab_size,
            eps=cfg.layernorm_epsilon,
            layers=tuple(cfg.layer_spec(l)
                         for l in range(cfg.num_hidden_layers)))

    def serving_parameters(self):
        layers = []
        for layer in self.layers:
            a, f = layer.self_attn, layer.mlp
            w = dict(ln1=layer.input_layernorm.weight,
                     ln2=layer.post_attention_layernorm.weight,
                     wqkv=a.qkv_proj, wo=a.o_proj)
            if a.sink is not None:
                w["sink"] = a.sink
            if isinstance(f, MiMoV2Experts):
                w.update(router=f.router, router_bias=f.router_bias,
                         w_gu=f.gate_up_proj, w_d=f.down_proj)
            else:
                w.update(wg=f.gate_proj, wu=f.up_proj, wd=f.down_proj)
            layers.append(w)
        return dict(emb=self.embed_tokens, norm=self.norm.weight,
                    head=self.lm_head, layers=layers)
