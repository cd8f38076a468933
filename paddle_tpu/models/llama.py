"""LLaMA family.

The flagship model (BASELINE.md config 4: LLaMA-13B sharding2+recompute).
Built from paddle_tpu layers the way PaddleNLP builds it from the
reference's mpu layers: VocabParallelEmbedding + Column/RowParallelLinear
over the 'model' axis, RMSNorm (Pallas on TPU), rotary attention through
scaled_dot_product_attention (Pallas flash-attention on TPU),
ParallelCrossEntropy vocab-parallel loss.
(ref analog: the fused_multi_transformer production path,
 paddle/fluid/operators/fused/fused_multi_transformer_op.cu.h.)
"""
import math

import numpy as np
import jax.numpy as jnp

from ..nn.layer.layers import Layer
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from ..nn import functional as F
from ..ops import apply
from ..profiler import phase
from ..tensor.tensor import Tensor
from ..tensor import manipulation as M
from ..distributed.fleet.meta_parallel import (
    VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
    ParallelCrossEntropy)


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=2048, rms_norm_eps=1e-6,
                 rope_theta=10000.0, dtype="float32", tie_word_embeddings=False,
                 recompute=False, sequence_parallel=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.dtype = dtype
        self.tie_word_embeddings = tie_word_embeddings
        self.recompute = recompute
        # Megatron-SP (SURVEY §5.7): activations between TP regions live
        # sequence-sharded over 'model'; the linears become the
        # Column/RowSequenceParallelLinear pair
        self.sequence_parallel = sequence_parallel

    @staticmethod
    def llama_7b(**kw):
        return LlamaConfig(hidden_size=4096, intermediate_size=11008,
                           num_hidden_layers=32, num_attention_heads=32, **kw)

    @staticmethod
    def llama_13b(**kw):
        return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                           num_hidden_layers=40, num_attention_heads=40, **kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 4)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return LlamaConfig(**kw)


def _rope_cache(seq_len, head_dim, theta, dtype):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(seq_len)
    freqs = np.outer(t, inv)                        # [s, d/2]
    return (jnp.asarray(np.cos(freqs), dtype),
            jnp.asarray(np.sin(freqs), dtype))


def apply_rotary(x, cos, sin):
    """x: [b, s, h, d] raw jnp; rotate pairs (x1,x2)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :x.shape[1], None, :]
    s = sin[None, :x.shape[1], None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _linear_pair(config):
    """Classic TP pair, or the sequence-parallel pair (input arrives
    sequence-sharded over 'model'; Col all_gathers the sequence, Row
    reduce-scatters it back) when config.sequence_parallel."""
    if getattr(config, "sequence_parallel", False):
        from ..distributed.fleet.utils.sequence_parallel_utils import (
            ColumnSequenceParallelLinear, RowSequenceParallelLinear)
        return ColumnSequenceParallelLinear, RowSequenceParallelLinear
    return ColumnParallelLinear, RowParallelLinear


class LlamaAttention(Layer):
    """Separate q/k/v column-parallel projections: each shards by whole
    heads on the 'model' axis, so the parallel math equals the dense math
    for any mp degree (a fused qkv weight would interleave q/k/v blocks
    across ranks)."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = self.hidden_size // self.num_heads
        # grouped-query attention: k/v project to num_key_value_heads
        # (LLaMA-2-70B geometry); sdpa expands KV head-wise at dispatch
        self.num_kv_heads = config.num_key_value_heads
        kv_out = self.num_kv_heads * self.head_dim
        self.sequence_parallel = getattr(config, "sequence_parallel", False)
        Col, Row = _linear_pair(config)
        kw = dict(has_bias=False, gather_output=False)
        if self.sequence_parallel:
            # ONE shared sequence gather in forward feeds q/k/v: backward
            # emits a single reduce-scatter on the summed cotangents
            kw["gather_input"] = False
        self.q_proj = Col(self.hidden_size, self.hidden_size, **kw)
        self.k_proj = Col(self.hidden_size, kv_out, **kw)
        self.v_proj = Col(self.hidden_size, kv_out, **kw)
        self.o_proj = Row(self.hidden_size, self.hidden_size,
                          has_bias=False, input_is_parallel=True)
        cos, sin = _rope_cache(config.max_position_embeddings, self.head_dim,
                               config.rope_theta, jnp.float32)
        self._cos, self._sin = cos, sin

    def forward(self, hidden_states):
        from ..distributed.mesh import in_spmd_region
        b = hidden_states.shape[0]
        with phase("attn_proj"):
            if self.sequence_parallel:
                from ..distributed.fleet.utils.sequence_parallel_utils import (
                    all_gather_sp)
                hidden_states = all_gather_sp(hidden_states)
            q = self.q_proj(hidden_states)
            k = self.k_proj(hidden_states)
            v = self.v_proj(hidden_states)
            # under Megatron-SP the projections GATHERED the sequence: q/k/v
            # carry the full (sep-local) sequence even though hidden_states
            # arrived sequence-sharded over 'model' — derive s from q
            s = q.shape[1]
            cos, sin = self._cos, self._sin
            hd = self.head_dim
            # context parallelism: activations arrive sequence-sharded over
            # 'sep'; rope positions are GLOBAL (rank offset), attention runs
            # the KV-rotating ring (parallel_layers/ring_attention.py)
            sp = in_spmd_region("sep")

            def rotary(qa, ka, va):
                import jax.lax as lax
                # per-tensor head counts: under GQA k/v carry fewer heads
                qa = qa.reshape(b, s, qa.shape[-1] // hd, hd)
                ka = ka.reshape(b, s, ka.shape[-1] // hd, hd)
                va = va.reshape(b, s, va.shape[-1] // hd, hd)
                if sp:
                    from jax.lax import axis_size as _axis_size
                    n_sep = _axis_size("sep")
                    if s * n_sep > cos.shape[0]:
                        raise ValueError(
                            f"global sequence {s * n_sep} (local {s} x sep "
                            f"{n_sep}) exceeds max_position_embeddings "
                            f"{cos.shape[0]} — dynamic_slice would silently "
                            f"clamp rotary positions")
                    off = lax.axis_index("sep") * s
                    c = lax.dynamic_slice_in_dim(cos, off, s, axis=0)
                    sn = lax.dynamic_slice_in_dim(sin, off, s, axis=0)
                else:
                    c, sn = cos[:s], sin[:s]
                qa = apply_rotary(qa, c.astype(qa.dtype), sn.astype(qa.dtype))
                ka = apply_rotary(ka, c.astype(ka.dtype), sn.astype(ka.dtype))
                return qa, ka, va

            q, k, v = apply(rotary, q, k, v, n_outputs=3, name="rotary_qkv")
        # RingFlashAttention self-dispatches: KV-rotating ring when 'sep'
        # is live, plain sdpa (Pallas flash on TPU) otherwise
        from ..distributed.fleet.meta_parallel.parallel_layers \
            .ring_attention import RingFlashAttention
        with phase("attend"):
            out = RingFlashAttention("sep", causal=True)(q, k, v)
        with phase("attn_proj"):
            out = M.reshape(out, [b, s, -1])
            return self.o_proj(out)


class LlamaMLP(Layer):
    def __init__(self, config):
        super().__init__()
        self.sequence_parallel = getattr(config, "sequence_parallel", False)
        Col, Row = _linear_pair(config)
        kw = dict(has_bias=False, gather_output=False)
        if self.sequence_parallel:
            kw["gather_input"] = False  # shared gather in forward
        self.gate_proj = Col(config.hidden_size, config.intermediate_size,
                             **kw)
        self.up_proj = Col(config.hidden_size, config.intermediate_size,
                           **kw)
        self.down_proj = Row(
            config.intermediate_size, config.hidden_size, has_bias=False,
            input_is_parallel=True)

    def forward(self, x):
        if self.sequence_parallel:
            from ..distributed.fleet.utils.sequence_parallel_utils import (
                all_gather_sp)
            x = all_gather_sp(x)
        g = self.gate_proj(x)
        u = self.up_proj(x)
        act = apply(lambda ga, ua: ua * (ga * (1.0 / (1.0 + jnp.exp(-ga)))),
                    g, u, name="swiglu")
        return self.down_proj(act)


class LlamaDecoderLayer(Layer):
    def __init__(self, config):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        if getattr(config, "sequence_parallel", False):
            # norm weights act on sequence SHARDS: their grads are partial
            # over 'model' and the trainer psums them
            from ..distributed.fleet.utils.sequence_parallel_utils import (
                mark_as_sequence_parallel_parameter)
            mark_as_sequence_parallel_parameter(self.input_layernorm.weight)
            mark_as_sequence_parallel_parameter(
                self.post_attention_layernorm.weight)

    def forward(self, hidden_states):
        # model phases (profiler.PHASES): the attention opens its own
        # around the projections and the kernel
        residual = hidden_states
        with phase("attn_proj"):
            h = self.input_layernorm(hidden_states)
        h = self.self_attn(h)
        with phase("attn_proj"):
            h = residual + h
        with phase("ffn"):
            residual = h
            h2 = self.post_attention_layernorm(h)
            h2 = self.mlp(h2)
            return residual + h2


class LlamaModel(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for i, layer in enumerate(self.layers):
            if self.config.recompute and self.training:
                from ..distributed.fleet.recompute import recompute
                h = recompute(layer, h)
            else:
                h = layer(h)
        return self.norm(h)


class LlamaForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size, has_bias=False,
                                            gather_output=False)
        self.criterion = LlamaPretrainingCriterion(config)

    def forward(self, input_ids, labels=None):
        hidden = self.llama(input_ids)
        logits = self.lm_head(hidden)
        if labels is not None:
            return self.criterion(logits, labels)
        return logits

    # -- the serving engine's seam (inference/description.py) ---------------
    def serving_description(self):
        from ..inference.description import (AttentionSpec, FFNSpec,
                                             LayerSpec, ModelDescription)
        cfg = self.config
        hd = cfg.hidden_size // cfg.num_attention_heads
        layer = LayerSpec(
            AttentionSpec(
                n_heads=cfg.num_attention_heads,
                n_kv_heads=(getattr(cfg, "num_key_value_heads", None)
                            or cfg.num_attention_heads),
                qk_dim=hd, v_dim=hd, rope_dim=hd,
                rope_theta=float(cfg.rope_theta)),
            FFNSpec("dense", cfg.intermediate_size))
        return ModelDescription(
            hidden_size=cfg.hidden_size, vocab_size=cfg.vocab_size,
            eps=cfg.rms_norm_eps,
            layers=(layer,) * cfg.num_hidden_layers)

    def serving_parameters(self):
        layers = []
        for layer in self.llama.layers:
            a, f = layer.self_attn, layer.mlp
            layers.append(dict(
                ln1=layer.input_layernorm.weight,
                ln2=layer.post_attention_layernorm.weight,
                wq=a.q_proj.weight, wk=a.k_proj.weight,
                wv=a.v_proj.weight, wo=a.o_proj.weight,
                wg=f.gate_proj.weight, wu=f.up_proj.weight,
                wd=f.down_proj.weight))
        return dict(emb=self.llama.embed_tokens.weight,
                    norm=self.llama.norm.weight,
                    head=self.lm_head.weight, layers=layers)


class LlamaPretrainingCriterion(Layer):
    """Vocab-parallel CE averaged over tokens (ref analog:
    mp_layers.py:498 ParallelCrossEntropy used by PaddleNLP pretraining)."""

    def __init__(self, config):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels):
        loss = self.ce(logits, labels)
        from ..tensor.math import mean
        return mean(loss)
