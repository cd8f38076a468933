"""dots3-note family (`model_type` "dots3_note"): the language model,
built from the keys of its public config.json.

Per layer l: a = h + Attn_l(RMSNorm(h)), h' = a + FFN_l(RMSNorm(a)); no
biases, untied embedding and head, final RMSNorm (`rms_norm_eps`).

  - Every layer's attention is LATENT (ops/latent_attention.py has the
    equations): `layer_types[l]` "full_attention" uses `num_attention_
    heads`, `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_
    head_dim`, `v_head_dim`, `rope_theta`, and adds the indexer
    (`index_n_heads`, `index_head_dim`, `index_topk`): its softmax runs
    over the top-k visible positions by index score only;
    "sliding_attention" uses the `swa_*` keys of the same names and sees
    keys j with i - `sliding_window_size` < j <= i, no indexer. Both gate
    their heads (`attention_gate_type` / `swa_attention_gate_type`
    "headwise": o_h <- sigmoid(x W_g)_h o_h before the output projection).
  - `apply_mla_qkv_lora_rescale`: c_q and c_kv are multiplied after their
    norms by sqrt(hidden_size / rank) (ASSUMED: the form LongCat-Flash
    publishes as `mla_scale_q_lora` / `mla_scale_kv_lora`).
  - FFN: layers below `first_k_dense_replace` a dense SwiGLU of
    `intermediate_size`; the others `n_routed_experts` SwiGLU experts of
    `moe_intermediate_size`, top `num_experts_per_tok` by sigmoid score
    plus the `noaux_tc` correction bias, weights normalised over the
    chosen (ops/moe.py), times `routed_scaling_factor`, PLUS
    `n_shared_experts` shared SwiGLU expert(s) of that width on every
    token (one, of width n_shared x moe width).

Assumed besides: the gate reads the layer's normed input; rotate-half
rotary (on the trailing `qk_rope_head_dim` of a query head, on the
leading `qk_rope_head_dim` of the indexer's query and key); the window
counts the query's own position; no group limit on the router; the index
key's LayerNorm has a weight and a bias and eps 1e-6; the positive
constant scales of the index score are dropped (a top-k ignores them).

The expert layer is TOLD which experts it holds (`held_experts` = [lo,
hi)): it routes over all of them and computes its own experts' part plus
the shared expert, the chip's share under expert parallelism.

Not here: the multi-token-prediction layer and the vision and audio
towers (not among the language model's config keys).
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
from ..ops import latent_attention as la
from ..ops.moe import routed_experts
from .mimo_v2 import rope_tables


class Dots3NoteConfig:
    def __init__(self, vocab_size=152064, hidden_size=5120,
                 intermediate_size=13824, moe_intermediate_size=1536,
                 num_hidden_layers=46, layer_types=None,
                 num_attention_heads=128, q_lora_rank=1024,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7,
                 swa_num_attention_heads=64, swa_q_lora_rank=1024,
                 swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
                 swa_qk_rope_head_dim=64, swa_v_head_dim=128,
                 swa_rope_theta=5e4, sliding_window_size=513,
                 index_n_heads=64, index_head_dim=128, index_topk=2048,
                 apply_mla_qkv_lora_rescale=True,
                 attention_gate_type="headwise",
                 swa_attention_gate_type="headwise",
                 first_k_dense_replace=1, n_routed_experts=256,
                 n_shared_experts=1, num_experts_per_tok=8,
                 routed_scaling_factor=1, rms_norm_eps=1e-5,
                 max_position_embeddings=4096, held_experts=None,
                 layers_kept=None, dtype="float32"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        # `layer_types` is the published list; `layers_kept` (default:
        # the first num_hidden_layers) says which published layers these
        # are, so a cut in depth keeps the list whole
        self.layers_kept = list(layers_kept if layers_kept is not None
                                else range(num_hidden_layers))
        self.layer_types = list(
            layer_types if layer_types is not None
            else ["full_attention"] * (max(self.layers_kept) + 1))
        if len(self.layers_kept) != num_hidden_layers \
                or max(self.layers_kept) >= len(self.layer_types):
            raise ValueError(
                f"layers_kept {self.layers_kept} must name "
                f"{num_hidden_layers} of the {len(self.layer_types)} "
                "published layers")
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.swa_num_attention_heads = swa_num_attention_heads
        self.swa_q_lora_rank = swa_q_lora_rank
        self.swa_kv_lora_rank = swa_kv_lora_rank
        self.swa_qk_nope_head_dim = swa_qk_nope_head_dim
        self.swa_qk_rope_head_dim = swa_qk_rope_head_dim
        self.swa_v_head_dim = swa_v_head_dim
        self.swa_rope_theta = swa_rope_theta
        self.sliding_window_size = sliding_window_size
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.apply_mla_qkv_lora_rescale = apply_mla_qkv_lora_rescale
        for gate in (attention_gate_type, swa_attention_gate_type):
            if gate not in (None, "headwise"):
                raise ValueError(f"unknown attention gate {gate!r}")
        self.attention_gate_type = attention_gate_type
        self.swa_attention_gate_type = swa_attention_gate_type
        self.first_k_dense_replace = first_k_dense_replace
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts or 0
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.held_experts = tuple(held_experts if held_experts is not None
                                  else (0, n_routed_experts))
        lo, hi = self.held_experts
        if not 0 <= lo < hi <= n_routed_experts:
            raise ValueError(
                f"held_experts {self.held_experts} is no range of the "
                f"{n_routed_experts} routed experts")
        if float(routed_scaling_factor or 1) != 1.0:
            raise ValueError("routed_scaling_factor other than 1 is not "
                             "carried by ops/moe.routed_experts")
        self.dtype = dtype

    def layer_spec(self, l):
        """LayerSpec of kept layer l (published layer layers_kept[l])."""
        from ..inference.description import (AttentionSpec, FFNSpec,
                                             IndexerSpec, LatentSpec,
                                             LayerSpec)
        pub = self.layers_kept[l]
        window = self.layer_types[pub] == "sliding_attention"
        pre = "swa_" if window else ""
        get = lambda k: getattr(self, pre + k)  # noqa: E731
        q_rank, kv_rank = get("q_lora_rank"), get("kv_lora_rank")
        rescale = self.apply_mla_qkv_lora_rescale
        attn = AttentionSpec(
            n_heads=get("num_attention_heads"), n_kv_heads=1,
            qk_dim=get("qk_nope_head_dim") + get("qk_rope_head_dim"),
            v_dim=get("v_head_dim"), rope_dim=get("qk_rope_head_dim"),
            rope_theta=float(get("rope_theta")),
            window=int(self.sliding_window_size) if window else None,
            latent=LatentSpec(
                q_rank=q_rank, kv_rank=kv_rank,
                q_scale=math.sqrt(self.hidden_size / q_rank)
                if rescale else 1.0,
                kv_scale=math.sqrt(self.hidden_size / kv_rank)
                if rescale else 1.0),
            gate=(self.swa_attention_gate_type if window
                  else self.attention_gate_type) == "headwise",
            indexer=None if window else IndexerSpec(
                n_heads=self.index_n_heads, dim=self.index_head_dim,
                rope_dim=self.qk_rope_head_dim, top_k=self.index_topk))
        if pub < self.first_k_dense_replace:
            ffn = FFNSpec("dense", self.intermediate_size)
        else:
            ffn = FFNSpec("experts", self.moe_intermediate_size,
                          n_experts=self.n_routed_experts,
                          top_k=self.num_experts_per_tok,
                          held=self.held_experts,
                          shared_width=self.n_shared_experts
                          * self.moe_intermediate_size)
        return LayerSpec(attn, ffn)

    @staticmethod
    def tiny(**kw):
        """Test size: every mechanism present, nothing wide; the top-k
        and the window far below the tests' contexts."""
        tiny = dict(
            vocab_size=96, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            layer_types=["full_attention", "full_attention",
                         "sliding_attention", "sliding_attention"],
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            swa_num_attention_heads=2, swa_q_lora_rank=24,
            swa_kv_lora_rank=32, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=8, swa_v_head_dim=16,
            sliding_window_size=13, index_n_heads=4, index_head_dim=16,
            index_topk=12, n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=128)
        tiny.update(kw)
        return Dots3NoteConfig(**tiny)


def _fan_in(std_of):
    return I.Normal(0.0, 1.0 / math.sqrt(std_of))


@functools.partial(jax.jit, static_argnames=("names", "spec", "eps"))
def _latent_attention(xa, arrays, names, spec, eps):
    """One compiled program per layer KIND: layers of equal spec share it
    (traced op by op, the eager forward spent seconds a layer here)."""
    cos, sin = rope_tables(xa.shape[1], spec.rope_dim, spec.rope_theta)
    return la.latent_attention_dense(
        xa, dict(zip(names, arrays)), spec, eps, cos, sin).astype(xa.dtype)


# ------------------------------------------------------------- layers --
class Dots3NoteAttention(Layer):
    def __init__(self, config, spec):
        super().__init__()
        self.spec = spec
        self.eps = config.rms_norm_eps
        h, lat, nh = config.hidden_size, spec.latent, spec.n_heads
        dn = spec.qk_dim - spec.rope_dim
        mk = self.create_parameter
        self.q_a_proj = mk([h, lat.q_rank], default_initializer=_fan_in(h))
        self.q_a_layernorm = RMSNorm(lat.q_rank, self.eps)
        self.q_b_proj = mk([lat.q_rank, nh * spec.qk_dim],
                           default_initializer=_fan_in(
                               lat.q_rank * lat.q_scale ** 2))
        self.kv_a_proj = mk([h, lat.kv_rank + spec.rope_dim],
                            default_initializer=_fan_in(h))
        self.kv_a_layernorm = RMSNorm(lat.kv_rank, self.eps)
        # the published `kv_b_proj` ([latent rank, heads x (no-position +
        # value width)]) held split by use: a layout, one projection
        kv_in = lat.kv_rank * lat.kv_scale ** 2
        self.kv_b_k = mk([lat.kv_rank, nh * dn],
                         default_initializer=_fan_in(kv_in))
        self.kv_b_v = mk([lat.kv_rank, nh * spec.v_dim],
                         default_initializer=_fan_in(kv_in))
        self.o_proj = mk([nh * spec.v_dim, h],
                         default_initializer=_fan_in(nh * spec.v_dim))
        self.gate_proj = mk([h, nh], default_initializer=_fan_in(h)) \
            if spec.gate else None
        ix = spec.indexer
        if ix is not None:
            # float32 in every engine, like the router
            f32 = dict(dtype="float32")
            self.ix_wq = mk([lat.q_rank, ix.n_heads * ix.dim],
                            default_initializer=_fan_in(
                                lat.q_rank * lat.q_scale ** 2), **f32)
            self.ix_wk = mk([h, ix.dim], default_initializer=_fan_in(h),
                            **f32)
            self.ix_kn_w = mk([ix.dim],
                              default_initializer=I.Constant(1.0), **f32)
            self.ix_kn_b = mk([ix.dim],
                              default_initializer=I.Constant(0.0), **f32)
            self.ix_ww = mk([h, ix.n_heads],
                            default_initializer=_fan_in(h), **f32)

    def serving_weights(self):
        w = dict(wq_a=self.q_a_proj, q_norm=self.q_a_layernorm.weight,
                 wq_b=self.q_b_proj, wkv_a=self.kv_a_proj,
                 kv_norm=self.kv_a_layernorm.weight, w_uk=self.kv_b_k,
                 w_uv=self.kv_b_v, wo=self.o_proj)
        if self.gate_proj is not None:
            w["w_gate"] = self.gate_proj
        if self.spec.indexer is not None:
            w.update(ix_wq=self.ix_wq, ix_wk=self.ix_wk,
                     ix_kn_w=self.ix_kn_w, ix_kn_b=self.ix_kn_b,
                     ix_ww=self.ix_ww)
        return w

    def forward(self, x):
        spec, eps = self.spec, self.eps
        names, params = zip(*self.serving_weights().items())

        return apply(lambda xa, *arrays: _latent_attention(
            xa, arrays, names, spec, eps), x, *params,
            name="latent_attention")


class Dots3NoteMLP(Layer):
    def __init__(self, config, width):
        super().__init__()
        h = config.hidden_size
        self.gate_proj = self.create_parameter(
            [h, width], default_initializer=_fan_in(h))
        self.up_proj = self.create_parameter(
            [h, width], default_initializer=_fan_in(h))
        self.down_proj = self.create_parameter(
            [width, h], default_initializer=_fan_in(width))

    def forward(self, x):
        return apply(lambda xa, g, u, d: la.swiglu(xa, g, u, d).astype(
            xa.dtype), x, self.gate_proj, self.up_proj, self.down_proj,
            name="swiglu")


class Dots3NoteExperts(Layer):
    """One layer's routed experts (the router over ALL of them, the
    weights of those held here) and its shared expert."""

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
        self.shared = Dots3NoteMLP(config, spec.shared_width) \
            if spec.shared_width else None

    def forward(self, x):
        spec = self.spec
        interpret = jax.default_backend() == "cpu"

        def run(xa, rw, rb, wgu, wd):
            b, s, h = xa.shape
            y, _ = routed_experts(xa.reshape(b * s, h), rw, rb, wgu, wd,
                                  spec.held, spec.top_k,
                                  interpret=interpret)
            return y.reshape(b, s, h)

        # inference only: the grouped product has no backward
        with tape.no_grad():
            y = apply(run, x, self.router, self.router_bias,
                      self.gate_up_proj, self.down_proj,
                      name="routed_experts")
        return y if self.shared is None else y + self.shared(x)


class Dots3NoteDecoderLayer(Layer):
    def __init__(self, config, l):
        super().__init__()
        spec = config.layer_spec(l)
        eps = config.rms_norm_eps
        self.input_layernorm = RMSNorm(config.hidden_size, eps)
        self.self_attn = Dots3NoteAttention(config, spec.attn)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, eps)
        self.mlp = (Dots3NoteExperts(config, spec.ffn)
                    if spec.ffn.kind == "experts"
                    else Dots3NoteMLP(config, spec.ffn.width))

    def forward(self, h):
        h = h + self.self_attn(self.input_layernorm(h))
        return h + self.mlp(self.post_attention_layernorm(h))


class Dots3NoteForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, h], default_initializer=I.Normal(0.0, 1.0))
        self.layers = LayerList([Dots3NoteDecoderLayer(config, l)
                                 for l in range(config.num_hidden_layers)])
        self.norm = RMSNorm(h, config.rms_norm_eps)
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
            eps=cfg.rms_norm_eps,
            layers=tuple(cfg.layer_spec(l)
                         for l in range(cfg.num_hidden_layers)))

    def serving_parameters(self):
        layers = []
        for layer in self.layers:
            f = layer.mlp
            w = dict(ln1=layer.input_layernorm.weight,
                     ln2=layer.post_attention_layernorm.weight,
                     **layer.self_attn.serving_weights())
            if isinstance(f, Dots3NoteExperts):
                w.update(router=f.router, router_bias=f.router_bias,
                         w_gu=f.gate_up_proj, w_d=f.down_proj)
                if f.shared is not None:
                    w.update(ws_g=f.shared.gate_proj,
                             ws_u=f.shared.up_proj,
                             ws_d=f.shared.down_proj)
            else:
                w.update(wg=f.gate_proj, wu=f.up_proj, wd=f.down_proj)
            layers.append(w)
        return dict(emb=self.embed_tokens, norm=self.norm.weight,
                    head=self.lm_head, layers=layers)
