"""`"family": "brumby"`: from a configuration file to the program's own
objects, through the path a user takes (`models/brumby.py:BrumbyConfig`
-> seeded parameters made on the device at the serving type ->
`BrumbyServingModel`). Only this file reads the published keys.
"""


def program_config(c):
    from paddle_tpu.models.brumby import BrumbyConfig
    if c["attention_bias"] or c["tie_word_embeddings"] or \
            c["use_sliding_window"] or c["hidden_act"] != "silu":
        raise ValueError("the family is served with no attention bias, "
                         "an untied head, no window and SiLU")
    a = c["assumed"]
    return BrumbyConfig(
        vocab_size=int(c["vocab_size"]),
        hidden_size=int(c["hidden_size"]),
        num_layers=int(c["num_hidden_layers"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        intermediate_size=int(c["intermediate_size"]),
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        max_position=int(c["server"]["max_context"]),
        initializer_range=float(a["initializer_range"]),
        gate_half_life_min=float(a["gate_half_life_min"]),
        gate_half_life_max=float(a["gate_half_life_max"]))


def serving_flops(c):
    """Forward operations (x2) one token needs through the layers
    whatever its context: every layer's q, k|v, gate and o projections,
    its SwiGLU MLP, and its retention's products at the expansion's D =
    d (d + 1) / 2 rows (the read of the state and of the normaliser by
    every query head, 2 x heads x D x (d + 1), and the update of the
    state by every KV head, 2 x kv_heads x D x d); and through the
    untied head. Beside them, what the retention readers need of the
    shapes, under names of the family's own."""
    h = int(c["hidden_size"])
    layers = int(c["num_hidden_layers"])
    heads, kv_heads = int(c["num_attention_heads"]), \
        int(c["num_key_value_heads"])
    d = int(c["head_dim"])
    rows = d * (d + 1) // 2
    proj = (h * heads * d + h * 2 * kv_heads * d + h * kv_heads
            + heads * d * h + 3 * h * int(c["intermediate_size"]))
    retention = 2 * heads * rows * (d + 1) + 2 * kv_heads * rows * d
    return {"body_matmul_flops_per_token": layers * (2 * proj + retention),
            "head_matmul_flops_per_token": 2 * h * int(c["vocab_size"]),
            "retention_layers": layers, "retention_heads": heads,
            "retention_kv_heads": kv_heads, "retention_head_dim": d,
            "retention_state_rows": rows, "retention_state_itemsize": 4}


def serving_model(c, seed):
    """Parameters made on the device from the seed, tensor by tensor at
    the serving type (a float32 copy of 4.2 B parameters does not fit),
    behind the program's serving model."""
    import jax.numpy as jnp
    from paddle_tpu.serving import BrumbyServingModel

    cfg = program_config(c)
    model = BrumbyServingModel.from_seed(
        cfg, seed, getattr(jnp, c["serving_dtype"]))
    return model, cfg
