"""Operations and bytes the latent sparse block NEEDS, from shapes alone:
latent attention layers (full ones with an indexer and a top-k selection,
window ones without), a head-wise gate, a dense SwiGLU or routed experts
of which this chip holds some plus one shared expert (the keys of the
model's public config.json; `layers_kept` and `held_experts` say which
layers and experts are here). Nothing looks at the program: a share is
these numbers over a measured time and a published peak. bf16: two bytes
a value; the router and the indexer are float32. Norm vectors (under
0.01 %) are left out.
"""
BF16, F32 = 2, 4


def layers(cfg):
    """[(windowed, routed)] of the layers kept."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return [(cfg["layer_types"][l] == "sliding_attention",
             l >= cfg["first_k_dense_replace"]) for l in kept]


def _k(cfg, windowed, key):
    return cfg[("swa_" if windowed else "") + key]


def held(cfg):
    lo, hi = cfg.get("held_experts") or [0, cfg["n_routed_experts"]]
    return hi - lo


def router_outputs(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def row_width(cfg, windowed):
    """Values of one token's cache row in ONE layer: [c_kv ; k_r]."""
    return _k(cfg, windowed, "kv_lora_rank") \
        + _k(cfg, windowed, "qk_rope_head_dim")


def indexer_params(cfg):
    """W^I_q, W^I_k, W^I_w of ONE full layer (float32 in the program)."""
    return cfg["q_lora_rank"] * cfg["index_n_heads"] \
        * cfg["index_head_dim"] \
        + cfg["hidden_size"] * (cfg["index_head_dim"]
                                + cfg["index_n_heads"])


def attention_params(cfg, windowed):
    """The bf16 matrices of one layer's attention: the two down and two
    up projections, the output projection and the head gate."""
    h = cfg["hidden_size"]
    nh = _k(cfg, windowed, "num_attention_heads")
    q_rank = _k(cfg, windowed, "q_lora_rank")
    kv_rank = _k(cfg, windowed, "kv_lora_rank")
    dn = _k(cfg, windowed, "qk_nope_head_dim")
    dr = _k(cfg, windowed, "qk_rope_head_dim")
    dv = _k(cfg, windowed, "v_head_dim")
    return (h * q_rank + q_rank * nh * (dn + dr) + h * (kv_rank + dr)
            + kv_rank * nh * (dn + dv) + nh * dv * h + h * nh)


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return (cfg.get("n_shared_experts") or 0) * expert_params(cfg)


def router_params(cfg):
    return cfg["hidden_size"] * router_outputs(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def resident_params(cfg):
    """Every parameter this chip holds (embedding included)."""
    n = 2 * head_params(cfg)
    for windowed, routed in layers(cfg):
        n += attention_params(cfg, windowed)
        n += 0 if windowed else indexer_params(cfg)
        n += (held(cfg) * expert_params(cfg) + shared_params(cfg)
              + router_params(cfg) if routed else dense_ffn_params(cfg))
    return n


def resident_weight_bytes(cfg):
    """As the program holds them: bf16, the routers and indexers float32."""
    f32 = sum(router_params(cfg) * routed
              + indexer_params(cfg) * (not windowed)
              for windowed, routed in layers(cfg))
    return (resident_params(cfg) - f32) * BF16 + f32 * F32


def cache_read_bytes(cfg, contexts):
    """Bytes of cache one decode step's attention must read, all layers.
    A full layer: every index key of the context (to score it) and the
    rows SELECTED, min(context, index_topk) of them; a window layer at
    most `sliding_window_size` rows. `contexts`: tokens in cache per
    decoding sequence."""
    top, win = cfg["index_topk"], cfg["sliding_window_size"]
    total = 0
    for windowed, _ in layers(cfg):
        row = row_width(cfg, windowed) * BF16
        if windowed:
            total += sum(min(c, win) for c in contexts) * row
        else:
            total += sum(min(c, top) * row
                         + c * cfg["index_head_dim"] * BF16
                         for c in contexts)
    return total


def decode_weight_bytes(cfg, touched_per_layer):
    """Weights one decode step must stream: every layer's attention
    (indexer float32), the dense FFN, the shared expert, the router
    (float32), the TOUCHED held experts of each routed layer, and the
    sliced head. The embedding gives one row per sequence: left out."""
    total = head_params(cfg) * BF16
    for windowed, routed in layers(cfg):
        total += attention_params(cfg, windowed) * BF16
        if not windowed:
            total += indexer_params(cfg) * F32
        if routed:
            total += router_params(cfg) * F32 + shared_params(cfg) * BF16 \
                + touched_per_layer * expert_params(cfg) * BF16
        else:
            total += dense_ffn_params(cfg) * BF16
    return total


def decode_step_bytes(cfg, contexts, touched_per_layer):
    """Least bytes one decode step reads from HBM."""
    return decode_weight_bytes(cfg, touched_per_layer) \
        + cache_read_bytes(cfg, contexts)
