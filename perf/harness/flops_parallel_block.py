"""Operations and bytes the parallel block NEEDS, from shapes alone: a
block whose attention (grouped queries; window layers and position-free
full layers), routed experts of which this chip holds some, and shared
experts all read ONE norm of the block's input (the keys of the model's
public config.json, `model_type` "cohere2_moe"; `layers_kept` and
`held_experts` say which layers and experts are here). Nothing looks at
the program: a share is these numbers over a measured time and a
published peak. bf16: two bytes a value; the router is float32. Norm
vectors (under 0.01 %) are left out.
"""
BF16, F32 = 2, 4
SLIDING = "sliding_attention"


def layers(cfg):
    """[windowed] of the layers kept."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return [cfg["layer_types"][l] == SLIDING for l in kept]


def held(cfg):
    lo, hi = cfg.get("held_experts") or [0, cfg["num_experts"]]
    return hi - lo


def router_outputs(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def attention_params(cfg):
    """W_q, W_k, W_v, W_o of one layer."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * (nh + 2 * nkv) * d + nh * d * h


def expert_params(cfg):
    """ONE expert, routed or shared: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg):
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    return cfg["hidden_size"] * router_outputs(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_params(cfg):
    """One layer as this chip holds it."""
    return attention_params(cfg) + shared_params(cfg) + router_params(cfg) \
        + held(cfg) * expert_params(cfg)


def resident_params(cfg, head_copy=True):
    """Every parameter this chip holds: the layers kept, the embedding
    and (the head is tied, the program keeps it transposed) its copy."""
    return len(layers(cfg)) * layer_params(cfg) \
        + (2 if head_copy else 1) * head_params(cfg)


def resident_weight_bytes(cfg):
    """As the program holds them: bf16, the routers float32."""
    f32 = len(layers(cfg)) * router_params(cfg)
    return (resident_params(cfg) - f32) * BF16 + f32 * F32


def kv_bytes_per_token(cfg):
    """Cached keys and values of one token in ONE layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def kv_read_tokens(cfg, contexts):
    """(window layers', full layers') cached tokens one decode step's
    queries must read, summed over layers: a full layer the whole context
    of every sequence, a window layer at most `sliding_window` tokens of
    it. `contexts`: tokens in cache per decoding sequence."""
    win = cfg["sliding_window"]
    kinds = layers(cfg)
    return (sum(kinds) * sum(min(c, win) for c in contexts),
            (len(kinds) - sum(kinds)) * sum(contexts))


def kv_read_bytes(cfg, contexts):
    return sum(kv_read_tokens(cfg, contexts)) * kv_bytes_per_token(cfg)


def expert_mm_bytes(cfg, touched, rows):
    """Bytes the two grouped products of ONE layer's routed experts must
    move: the weights of the `touched` experts once, and for `rows`
    (token, choice) rows the activations in and out of both products."""
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    return touched * expert_params(cfg) * BF16 \
        + rows * (h + 2 * w + w + h) * BF16


def decode_weight_bytes(cfg, touched_per_layer):
    """Weights one decode step must stream: every layer's attention, its
    shared experts, its router (float32) and the TOUCHED held experts,
    and the head. The embedding gives one row per sequence: left out."""
    return head_params(cfg) * BF16 + len(layers(cfg)) * (
        (attention_params(cfg) + shared_params(cfg)) * BF16
        + router_params(cfg) * F32
        + touched_per_layer * expert_params(cfg) * BF16)


def decode_step_bytes(cfg, kv_tokens, touched_per_layer):
    """Least bytes one decode step reads from HBM; `kv_tokens`: cached
    tokens x layers its queries read (`kv_read_tokens`, or the program's
    own count)."""
    return decode_weight_bytes(cfg, touched_per_layer) \
        + kv_tokens * kv_bytes_per_token(cfg)


def chunk_attention_flops(cfg, pairs):
    """Operations a prefill chunk's attention must do for `pairs` (query,
    key) pairs x layers (a causal query sees the keys up to itself, in a
    window layer at most `sliding_window` of them): q.k and p.v, 2 x
    head_dim multiply-adds each, in every query head."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
