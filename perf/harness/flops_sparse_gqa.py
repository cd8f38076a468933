"""Operations and bytes the per-head sparse block NEEDS, from shapes alone:
grouped-query attention whose every layer has an indexer (`sa_config`) and
attends to a top-k selection of its cached K and V, and routed experts
under a softmax router, all alike (the keys of the model's public
config.json; `held_experts` says which experts are here). Nothing looks
at the program: a share is these numbers over a measured time and a
published peak, the SAME count of work whether XLA operations or a Pallas
kernel run a phase. bf16: two bytes a value; the router and the indexer
are float32. Norm vectors (under 0.01 %) are left out.
"""
BF16, F32 = 2, 4


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def held(cfg):
    lo, hi = cfg.get("held_experts") or [0, cfg["num_experts"]]
    return hi - lo


def attention_params(cfg):
    """W_q, W_k, W_v, W_o of one layer."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * (nh + 2 * nkv) * d + nh * d * h


def indexer_params(cfg):
    """W^I_q, W^I_k, W^I_w of one layer (float32 in the program)."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def expert_params(cfg):
    """ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def resident_params(cfg):
    """Every parameter this chip holds (embedding included)."""
    return 2 * head_params(cfg) + n_layers(cfg) * (
        attention_params(cfg) + indexer_params(cfg) + router_params(cfg)
        + held(cfg) * expert_params(cfg))


def resident_weight_bytes(cfg):
    """As the program holds them: bf16, the routers and indexers float32."""
    f32 = n_layers(cfg) * (indexer_params(cfg) + router_params(cfg))
    return (resident_params(cfg) - f32) * BF16 + f32 * F32


def row_bytes(cfg):
    """One token's K and V of every KV head, ONE layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def index_key_bytes(cfg):
    """One token's index key, ONE layer."""
    return cfg["sa_config"]["indexer_head_dim"] * BF16


def token_cache_bytes(cfg):
    """What one token caches over all layers."""
    return n_layers(cfg) * (row_bytes(cfg) + index_key_bytes(cfg))


def cache_read_bytes(cfg, contexts):
    """Bytes of cache one decode step's attention must read, all layers:
    per sequence and layer every index key of the context (to score it)
    and the rows SELECTED, min(context, topk) of them. `contexts`: tokens
    in cache per decoding sequence."""
    top = cfg["sa_config"]["topk"]
    return n_layers(cfg) * sum(
        c * index_key_bytes(cfg) + min(c, top) * row_bytes(cfg)
        for c in contexts)


def decode_weight_bytes(cfg, touched_per_layer):
    """Weights one decode step must stream: every layer's attention, its
    indexer and router (float32), the TOUCHED held experts, and the head.
    The embedding gives one row per sequence: left out."""
    return head_params(cfg) * BF16 + n_layers(cfg) * (
        attention_params(cfg) * BF16
        + (indexer_params(cfg) + router_params(cfg)) * F32
        + touched_per_layer * expert_params(cfg) * BF16)


def decode_step_bytes(cfg, contexts, touched_per_layer):
    """Least bytes one decode step reads from HBM."""
    return decode_weight_bytes(cfg, touched_per_layer) \
        + cache_read_bytes(cfg, contexts)
