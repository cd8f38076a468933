"""Operations and bytes the hybrid block NEEDS, from shapes alone: window
and full attention layers with key width != value width, a dense SwiGLU
or routed experts of which this chip holds some (the keys of the model's
public config.json; `layers_kept` and `held_experts` say which layers and
experts are here). Nothing looks at the program: a share is these numbers
over a measured time and a published peak. bf16: two bytes a value; the
router is float32.
"""
BF16, F32 = 2, 4


def layers(cfg):
    """[(windowed, routed)] of the layers kept."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return [(bool(cfg["hybrid_layer_pattern"][l]),
             bool(cfg["moe_layer_freq"][l])) for l in kept]


def held(cfg):
    lo, hi = cfg.get("held_experts") or [0, cfg["n_routed_experts"]]
    return hi - lo


def router_outputs(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def kv_heads(cfg, windowed):
    return cfg["swa_num_key_value_heads" if windowed
               else "num_key_value_heads"]


def attention_params(cfg, windowed):
    """The fused q | k | v projection and the output projection."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv, kv = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, windowed)
    return h * (nh * dk + kv * dk + kv * dv) + nh * dv * h


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * router_outputs(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def resident_params(cfg):
    """Every parameter this chip holds (embedding included)."""
    n = 2 * head_params(cfg)
    for windowed, routed in layers(cfg):
        n += attention_params(cfg, windowed)
        n += (held(cfg) * expert_params(cfg) + router_params(cfg)
              if routed else dense_ffn_params(cfg))
    return n


def kv_bytes_per_token(cfg, windowed):
    """Cached keys and values of one token in ONE layer of that kind."""
    return kv_heads(cfg, windowed) * (cfg["head_dim"] + cfg["v_head_dim"]) \
        * BF16


def kv_read_bytes(cfg, contexts):
    """Bytes of cache one decode step's attention must read, all layers:
    a full layer the whole context of every sequence, a window layer at
    most `sliding_window` tokens of it. `contexts`: tokens in cache per
    decoding sequence."""
    win = cfg["sliding_window"]
    total = 0
    for windowed, _ in layers(cfg):
        tokens = sum(min(c, win) if windowed else c for c in contexts)
        total += tokens * kv_bytes_per_token(cfg, windowed)
    return total


def expert_mm_bytes(cfg, touched, rows):
    """Bytes the two grouped products of ONE expert layer must move:
    the weights of the `touched` experts once, and for `rows` (token,
    choice) rows the activations in and out of both products."""
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return touched * expert_params(cfg) * BF16 \
        + rows * (h + 2 * w + w + h) * BF16


def decode_weight_bytes(cfg, touched_per_layer):
    """Weights one decode step must stream: every layer's attention, the
    dense FFN, the router (float32) and the TOUCHED held experts of each
    routed layer, and the sliced head. The embedding gives one row per
    sequence and the norm vectors and sinks are under 0.01 %: left out."""
    total = head_params(cfg) * BF16
    for windowed, routed in layers(cfg):
        total += attention_params(cfg, windowed) * BF16
        if routed:
            total += router_params(cfg) * F32 \
                + touched_per_layer * expert_params(cfg) * BF16
        else:
            total += dense_ffn_params(cfg) * BF16
    return total


def decode_step_bytes(cfg, contexts, touched_per_layer):
    """Least bytes one decode step reads from HBM."""
    return decode_weight_bytes(cfg, touched_per_layer) \
        + kv_read_bytes(cfg, contexts)
