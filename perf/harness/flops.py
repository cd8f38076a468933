"""Operations and bytes an algorithm NEEDS, from shapes alone.

For a dense decoder whose block is RMSNorm -> grouped-query attention
with RoPE -> SwiGLU, no biases (InternLM2; the sizes are the keys of the
model's public config.json). Nothing here looks at the program: a
utilisation is these numbers over a measured time and a published peak.
Recomputed operations never count.
"""


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg):
    """Parameters that take part in a matrix multiplication for every
    token: the block's seven projections in every layer, and the output
    head. The input embedding is a row lookup, not a matmul, so it is
    NOT here (counting it, as `6 * n_params` does, overstates the work
    by vocab*hidden: +5 % at 1.3B / vocab 32000, +11 % at 1.8B / vocab
    92544)."""
    h = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs one trained token requires at sequence
    length `seq`: 6 per matmul parameter (2 forward, 4 backward), plus
    causal attention — QK^T and PV are 2 * 2 * seq * hidden forward per
    layer, halved by the causal mask, tripled for the backward pass:
    12 * L * hidden * seq / 2."""
    attn = 12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq // 2
    return 6 * matmul_params(cfg) + attn


def decode_weight_bytes(cfg, bytes_per_weight=1):
    """Bytes of matmul weights one decode step has to stream whatever
    the batch: every layer's projections and the head, once (int8: one
    byte each; the per-channel scales and norm vectors are under 0.1 %
    and left out). The embedding contributes one row per sequence and
    is left out too."""
    return matmul_params(cfg) * bytes_per_weight


def kv_bytes_per_token(cfg, bytes_per_value=2):
    """Bytes of cached keys and values per token of context, over all
    layers (bf16: two bytes a value)."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg) * bytes_per_value
            * cfg["num_hidden_layers"])


def decode_step_bytes(cfg, context_tokens, bytes_per_weight=1,
                      bytes_per_value=2):
    """Least bytes one decode step reads from HBM: the weights once and
    the cached keys and values of `context_tokens` tokens (summed over
    the sequences decoding in that step)."""
    return (decode_weight_bytes(cfg, bytes_per_weight)
            + context_tokens * kv_bytes_per_token(cfg, bytes_per_value))
