"""Keye-VL-2.0 (`model_type` "KeyeVL2"): the language model, built from
the keys of its public config.json. Every layer is alike:

  a = h + Attn(RMSNorm(h)),  h' = a + Experts(RMSNorm(a)); no biases,
  untied embedding and head, final RMSNorm.

  - Attention: grouped queries (`num_attention_heads` query heads read
    `num_key_value_heads` KV heads of `head_dim`), an RMSNorm over every
    query and key head (learned weight [head_dim]), rotate-half rotary
    over all of `head_dim` on base `rope_theta`, logits q.k /
    sqrt(head_dim). A learned SPARSE selection in every layer
    (`sa_config`; ops/sparse_attention.py has the mathematics): an
    indexer of `indexer_num_heads` heads x `indexer_head_dim` scores the
    cached positions from the layer's normed input (ONE index key a
    token) and a query attends to the `topk` best only, one selection a
    token and layer for all heads.
  - Experts: `num_experts` SwiGLU experts of `moe_intermediate_size`, the
    `num_experts_per_tok` largest of a SOFTMAX over all of them, weights
    normalised over the chosen (`norm_topk_prob`); no shared expert, no
    dense layer (`mlp_only_layers` empty, `decoder_sparse_step` 1).

`rope_scaling.mrope_section` splits the rotary pairs over the three
components (temporal, height, width) of a position; a TEXT token's three
are equal, which is plain rotary, and that is the path built here (the
plain reference, perf/references/keye_vl2.py, takes position ids [3, t]).

The expert layer is TOLD which experts it holds (`held_experts` = [lo,
hi)); the default holds all.

Not here: the vision tower and positions whose components differ. What
the config has no key for and this file assumes is listed in
perf/configs/keye-vl-2.0-30b-a3b-l5-serve.json under `assumed`.
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops import apply
from ..ops import sparse_attention as sa
from ..ops.moe import routed_experts
from .mimo_v2 import rope_tables


class KeyeVL2Config:
    def __init__(self, vocab_size=151936, hidden_size=2048,
                 moe_intermediate_size=768, num_hidden_layers=48,
                 num_attention_heads=32, num_key_value_heads=4,
                 head_dim=128, rope_theta=1e7, mrope_section=(16, 24, 24),
                 indexer_num_heads=16, indexer_head_dim=64, topk=2048,
                 num_experts=128, num_experts_per_tok=8,
                 norm_topk_prob=True, rms_norm_eps=1e-6,
                 max_position_embeddings=4096, held_experts=None,
                 dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.mrope_section = tuple(mrope_section)
        if sum(self.mrope_section) * 2 != head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} must split the "
                f"{head_dim // 2} rotary pairs of a head")
        self.indexer_num_heads = indexer_num_heads
        self.indexer_head_dim = indexer_head_dim
        self.topk = topk
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        if not norm_topk_prob:
            raise ValueError("norm_topk_prob false is not carried by "
                             "ops/moe.route")
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.held_experts = tuple(held_experts if held_experts is not None
                                  else (0, num_experts))
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(
                f"held_experts {self.held_experts} is no range of the "
                f"{num_experts} routed experts")
        self.dtype = dtype

    def layer_spec(self):
        """Every layer's LayerSpec (they are alike)."""
        from ..inference.description import (AttentionSpec, FFNSpec,
                                             IndexerSpec, LayerSpec)
        attn = AttentionSpec(
            n_heads=self.num_attention_heads,
            n_kv_heads=self.num_key_value_heads, qk_dim=self.head_dim,
            v_dim=self.head_dim, rope_dim=self.head_dim,
            rope_theta=float(self.rope_theta), qk_norm=True,
            indexer=IndexerSpec(
                n_heads=self.indexer_num_heads, dim=self.indexer_head_dim,
                rope_dim=self.indexer_head_dim, top_k=self.topk))
        ffn = FFNSpec("experts", self.moe_intermediate_size,
                      n_experts=self.num_experts,
                      top_k=self.num_experts_per_tok,
                      held=self.held_experts, score="softmax")
        return LayerSpec(attn, ffn)

    @staticmethod
    def tiny(**kw):
        """Test size: every mechanism present, nothing wide; the top-k
        far below the tests' contexts."""
        tiny = dict(
            vocab_size=96, hidden_size=64, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mrope_section=(2, 3, 3),
            indexer_num_heads=4, indexer_head_dim=8, topk=8,
            num_experts=8, num_experts_per_tok=2,
            max_position_embeddings=128)
        tiny.update(kw)
        return KeyeVL2Config(**tiny)


def _fan_in(std_of):
    return I.Normal(0.0, 1.0 / math.sqrt(std_of))


@functools.partial(jax.jit, static_argnames=("names", "spec", "eps"))
def _sparse_attention(xa, arrays, names, spec, eps):
    """One compiled program for all layers (they share their spec)."""
    s, ix = xa.shape[1], spec.indexer
    cos, sin = rope_tables(s, spec.rope_dim, spec.rope_theta)
    ix_cos, ix_sin = rope_tables(s, ix.rope_dim, spec.rope_theta)
    return sa.sparse_gqa_attention_dense(
        xa, dict(zip(names, arrays)), spec, eps, cos, sin, ix_cos,
        ix_sin).astype(xa.dtype)


# ------------------------------------------------------------- layers --
class KeyeVL2Attention(Layer):
    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        self.eps = config.rms_norm_eps
        h, d, ix = config.hidden_size, spec.qk_dim, spec.indexer
        mk = self.create_parameter
        self.q_proj = mk([h, spec.n_heads * d],
                         default_initializer=_fan_in(h))
        self.k_proj = mk([h, spec.n_kv_heads * d],
                         default_initializer=_fan_in(h))
        self.v_proj = mk([h, spec.n_kv_heads * spec.v_dim],
                         default_initializer=_fan_in(h))
        self.o_proj = mk([spec.n_heads * spec.v_dim, h],
                         default_initializer=_fan_in(
                             spec.n_heads * spec.v_dim))
        self.q_norm = RMSNorm(d, self.eps)
        self.k_norm = RMSNorm(d, self.eps)
        # the indexer is float32 in every engine, like the router
        f32 = dict(dtype="float32")
        self.ix_wq = mk([h, ix.n_heads * ix.dim],
                        default_initializer=_fan_in(h), **f32)
        self.ix_wk = mk([h, ix.dim], default_initializer=_fan_in(h), **f32)
        self.ix_kn_w = mk([ix.dim], default_initializer=I.Constant(1.0),
                          **f32)
        self.ix_kn_b = mk([ix.dim], default_initializer=I.Constant(0.0),
                          **f32)
        self.ix_ww = mk([h, ix.n_heads], default_initializer=_fan_in(h),
                        **f32)

    def serving_weights(self):
        return dict(wq=self.q_proj, wk=self.k_proj, wv=self.v_proj,
                    wo=self.o_proj, q_hn=self.q_norm.weight,
                    k_hn=self.k_norm.weight, ix_wq=self.ix_wq,
                    ix_wk=self.ix_wk, ix_kn_w=self.ix_kn_w,
                    ix_kn_b=self.ix_kn_b, ix_ww=self.ix_ww)

    def forward(self, x):
        spec, eps = self.spec, self.eps
        names, params = zip(*self.serving_weights().items())
        return apply(lambda xa, *arrays: _sparse_attention(
            xa, arrays, names, spec, eps), x, *params,
            name="sparse_gqa_attention")


class KeyeVL2Experts(Layer):
    """One layer's routed experts: the softmax router over ALL of them,
    the weights of those held here."""

    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        h, f = config.hidden_size, spec.width
        n_held = spec.held[1] - spec.held[0]
        self.router = self.create_parameter(    # float32 in every engine
            [h, spec.n_experts], dtype="float32",
            default_initializer=_fan_in(h))
        self.gate_up_proj = self.create_parameter(
            [n_held, h, 2 * f], default_initializer=_fan_in(h))
        self.down_proj = self.create_parameter(
            [n_held, f, h], default_initializer=_fan_in(f))

    def forward(self, x):
        spec = self.spec
        interpret = jax.default_backend() == "cpu"

        def run(xa, rw, wgu, wd):
            b, s, h = xa.shape
            y, _ = routed_experts(xa.reshape(b * s, h), rw, None, wgu, wd,
                                  spec.held, spec.top_k,
                                  interpret=interpret, score=spec.score)
            return y.reshape(b, s, h)

        # inference only: the grouped product has no backward
        with tape.no_grad():
            return apply(run, x, self.router, self.gate_up_proj,
                         self.down_proj, name="routed_experts")


class KeyeVL2DecoderLayer(Layer):
    def __init__(self, config):
        super().__init__()
        spec = config.layer_spec()
        eps = config.rms_norm_eps
        self.input_layernorm = RMSNorm(config.hidden_size, eps)
        self.self_attn = KeyeVL2Attention(config, spec.attn)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, eps)
        self.mlp = KeyeVL2Experts(config, spec.ffn)

    def forward(self, h):
        h = h + self.self_attn(self.input_layernorm(h))
        return h + self.mlp(self.post_attention_layernorm(h))


class KeyeVL2ForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, h], default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([KeyeVL2DecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(h, config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [h, config.vocab_size], default_initializer=_fan_in(h))

    def forward(self, input_ids):
        """Logits [b, s, vocab] of TEXT token ids [b, s]."""
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
            eps=cfg.rms_norm_eps,
            layers=(cfg.layer_spec(),) * cfg.num_hidden_layers)

    def serving_parameters(self):
        layers = []
        for layer in self.layers:
            f = layer.mlp
            layers.append(dict(
                ln1=layer.input_layernorm.weight,
                ln2=layer.post_attention_layernorm.weight,
                **layer.self_attn.serving_weights(), router=f.router,
                w_gu=f.gate_up_proj, w_d=f.down_proj))
        return dict(emb=self.embed_tokens, norm=self.norm.weight,
                    head=self.lm_head, layers=layers)
