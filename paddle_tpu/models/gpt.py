"""GPT family (BASELINE.md config 3: GPT-3 1.3B mp2 x pp2).

Pre-LN GPT built from mpu layers; pipeline-ready via
`gpt_pipeline_layers` which emits the LayerDesc list for PipelineLayer
(ref analog: PaddleNLP GPTForPretrainingPipe over the reference's
meta_parallel pp_layers).
"""
import numpy as np
import jax.numpy as jnp

from ..nn.layer.layers import Layer
from ..nn.layer.container import LayerList
from ..nn.layer.norm import LayerNorm
from ..nn.layer.common import Dropout
from ..nn import functional as F
from ..ops import apply
from ..tensor import manipulation as M
from ..distributed.fleet.meta_parallel import (
    VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
    ParallelCrossEntropy)
from ..distributed.fleet.meta_parallel.parallel_layers.pp_layers import (
    LayerDesc, SharedLayerDesc)


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, hidden_dropout_prob=0.1,
                 attention_probs_dropout_prob=0.1, layer_norm_eps=1e-5,
                 recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.layer_norm_eps = layer_norm_eps
        self.recompute = recompute

    @staticmethod
    def gpt3_1p3b(**kw):
        return GPTConfig(hidden_size=2048, num_hidden_layers=24,
                         num_attention_heads=16, **kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 4)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 64)
        return GPTConfig(**kw)


class GPTEmbeddings(Layer):
    def __init__(self, config):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(config.vocab_size,
                                                      config.hidden_size)
        from ..nn.layer.common import Embedding
        self.position_embeddings = Embedding(config.max_position_embeddings,
                                             config.hidden_size)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids):
        import paddle_tpu as paddle
        from jax import lax
        from ..distributed.mesh import in_spmd_region
        s = input_ids.shape[1]
        pos = paddle.arange(s, dtype="int64")
        if in_spmd_region("sep"):
            # context parallelism: this shard holds a contiguous SLICE of
            # the global sequence — learned positions need the per-rank
            # global offset (same contract as the LLaMA rope offsets)
            from jax.lax import axis_size as _axis_size
            n_sep = _axis_size("sep")
            max_pos = self.position_embeddings.weight.shape[0]
            if s * n_sep > max_pos:
                raise ValueError(
                    f"global sequence {s * n_sep} (local {s} x sep "
                    f"{n_sep}) exceeds max_position_embeddings {max_pos}")
            from ..ops import apply
            pos = apply(lambda p: p + lax.axis_index("sep") * s, pos,
                        name="sep_pos_offset")
        emb = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        return self.dropout(emb)


class GPTAttention(Layer):
    def __init__(self, config):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // self.num_heads
        kw = dict(has_bias=True, gather_output=False)
        self.q_proj = ColumnParallelLinear(config.hidden_size,
                                           config.hidden_size, **kw)
        self.k_proj = ColumnParallelLinear(config.hidden_size,
                                           config.hidden_size, **kw)
        self.v_proj = ColumnParallelLinear(config.hidden_size,
                                           config.hidden_size, **kw)
        self.out_proj = RowParallelLinear(config.hidden_size,
                                          config.hidden_size, has_bias=True,
                                          input_is_parallel=True)
        self.dropout_p = config.attention_probs_dropout_prob

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        hd = self.head_dim
        q0, k0, v0 = self.q_proj(x), self.k_proj(x), self.v_proj(x)

        def split_heads(qa, ka, va):
            nh = qa.shape[-1] // hd
            return (qa.reshape(b, s, nh, hd), ka.reshape(b, s, nh, hd),
                    va.reshape(b, s, nh, hd))

        q, k, v = apply(split_heads, q0, k0, v0, n_outputs=3,
                        name="split_heads")
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.dropout_p if self.training else 0.0)
        out = M.reshape(out, [b, s, -1])
        return self.out_proj(out)


class GPTDecoderLayer(Layer):
    def __init__(self, config):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.fc_in = ColumnParallelLinear(config.hidden_size,
                                          config.intermediate_size,
                                          has_bias=True, gather_output=False)
        self.fc_out = RowParallelLinear(config.intermediate_size,
                                        config.hidden_size, has_bias=True,
                                        input_is_parallel=True)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        h = x + self.attn(self.ln_1(x))
        ff = self.fc_out(F.gelu(self.fc_in(self.ln_2(h)), approximate=True))
        return h + self.dropout(ff)


class GPTModel(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.h = LayerList([GPTDecoderLayer(config)
                            for _ in range(config.num_hidden_layers)])
        self.ln_f = LayerNorm(config.hidden_size, config.layer_norm_eps)

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        for layer in self.h:
            if self.config.recompute and self.training:
                from ..distributed.fleet.recompute import recompute
                x = recompute(layer, x)
            else:
                x = layer(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    def __init__(self, config):
        super().__init__()
        self.gpt = GPTModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size, has_bias=False,
                                            gather_output=False)
        self.ce = ParallelCrossEntropy()

    def forward(self, input_ids, labels=None):
        h = self.gpt(input_ids)
        logits = self.lm_head(h)
        if labels is not None:
            from ..tensor.math import mean
            return mean(self.ce(logits, labels))
        return logits


class _GPTHead(Layer):
    def __init__(self, config):
        super().__init__()
        self.ln_f = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size, has_bias=False,
                                            gather_output=False)

    def forward(self, x):
        return self.lm_head(self.ln_f(x))


def gpt_pipeline_layers(config):
    """LayerDesc list for PipelineLayer (config 3 path)."""
    descs = [LayerDesc(GPTEmbeddings, config)]
    for _ in range(config.num_hidden_layers):
        descs.append(LayerDesc(GPTDecoderLayer, config))
    descs.append(LayerDesc(_GPTHead, config))
    return descs
