"""`"family": "smallthinker"`: from a configuration file to the
program's own objects, through the path a user takes
(`models/smallthinker.py:SmallThinkerConfig` -> seeded parameters made
on the device at the serving type -> `SmallThinkerServingModel`). Only
this file reads the published keys.
"""


def _window_layers(c):
    """The published layout, as far as this chip's layers go: 1 where a
    layer is a window layer (and rotary), 0 where it is full (and
    NoPE)."""
    layers = int(c["num_hidden_layers"])
    layout = c["sliding_window_layout"][:layers]
    if layout != c["rope_layout"][:layers]:
        raise ValueError("rope_layout and sliding_window_layout differ: "
                         "the program turns exactly the window layers")
    return tuple(i for i, w in enumerate(layout) if w)


def program_config(c):
    from paddle_tpu.models.smallthinker import SmallThinkerConfig
    if not c["moe_primary_router_apply_softmax"] or \
            c["tie_word_embeddings"]:
        raise ValueError("the family is served with softmax routing and "
                         "an untied head")
    return SmallThinkerConfig(
        vocab_size=int(c["vocab_size"]),
        hidden_size=int(c["hidden_size"]),
        num_layers=int(c["num_hidden_layers"]),
        window_layers=_window_layers(c),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        sliding_window=int(c["sliding_window_size"]),
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        moe_intermediate_size=int(c["moe_ffn_hidden_size"]),
        n_routed_experts=int(c["moe_num_primary_experts"]),
        num_experts_per_tok=int(c["moe_num_active_primary_experts"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        n_routed_experts_held=int(c["n_routed_experts_held"]),
        expert_offset=int(c["expert_offset"]),
        max_position=int(c["server"]["max_context"]),
        initializer_range=float(c["assumed"]["initializer_range"]))


def serving_flops(c):
    """Forward operations (x2) one token needs through the layers
    whatever its context: every layer's q, k|v and o projections, its
    router, and the EXPECTED held experts a token (experts per token x
    held / routed); and through the untied head. Beside them, what the
    per-layer readers need of the shapes, under names of the family's
    own: the full layers as `gqa_*` (their walks read the whole
    context), the window layers' count and window, the experts' shape
    (the runner's `head_dim` reads hidden / heads = 91 here)."""
    h = int(c["hidden_size"])
    layers = int(c["num_hidden_layers"])
    window = len(_window_layers(c))
    heads, kv_heads = int(c["num_attention_heads"]), \
        int(c["num_key_value_heads"])
    d = int(c["head_dim"])
    inner = int(c["moe_ffn_hidden_size"])
    routed = int(c["moe_num_primary_experts"])
    held_per_token = (int(c["moe_num_active_primary_experts"])
                      * int(c["n_routed_experts_held"]) / routed)
    layer = (h * heads * d + h * 2 * kv_heads * d + heads * d * h
             + h * routed + held_per_token * 3 * h * inner)
    return {"body_matmul_flops_per_token": int(2 * layers * layer),
            "head_matmul_flops_per_token": 2 * h * int(c["vocab_size"]),
            "gqa_layers": layers - window, "gqa_heads": heads,
            "gqa_kv_heads": kv_heads, "gqa_head_dim": d,
            "window_layers": window,
            "sliding_window": int(c["sliding_window_size"]),
            "window_heads": heads, "window_kv_heads": kv_heads,
            "window_head_dim": d,
            "expert_layers": layers, "expert_hidden": h,
            "expert_inner": inner}


def serving_model(c, seed):
    """Parameters made on the device from the seed, tensor by tensor at
    the serving type (a float32 copy of 5.56 B parameters does not
    fit), behind the program's serving model."""
    import jax.numpy as jnp
    from paddle_tpu.serving import SmallThinkerServingModel

    cfg = program_config(c)
    model = SmallThinkerServingModel.from_seed(
        cfg, seed, getattr(jnp, c["serving_dtype"]))
    return model, cfg
