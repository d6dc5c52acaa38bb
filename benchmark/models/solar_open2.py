"""`"family": "solar_open2"`: from a configuration file to the
program's own objects, through the path a user takes
(`models/linear_moe.py:LinearMoEConfig` -> seeded parameters made on
the device at the serving type -> `LinearMoEServingModel`). Only this
file reads the published keys.
"""


def program_config(c):
    from paddle_tpu.models.linear_moe import LinearMoEConfig
    layers = int(c["num_hidden_layers"])
    lin = c["linear_attn_config"]
    a = c["assumed"]
    return LinearMoEConfig(
        vocab_size=int(c["vocab_size"]),
        hidden_size=int(c["hidden_size"]),
        num_layers=layers,
        # the published pattern, as far as this chip's layers go
        gqa_layers=tuple(i for i in c["gqa_layers"] if i < layers),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        short_conv_kernel_size=int(lin["short_conv_kernel_size"]),
        kda_gate_rank=int(a["kda_gate_rank"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        moe_intermediate_size=int(c["moe_intermediate_size"]),
        n_routed_experts=int(c["n_routed_experts"]),
        num_experts_per_tok=int(c["num_experts_per_tok"]),
        n_shared_experts=int(c["n_shared_experts"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        n_routed_experts_held=int(c["n_routed_experts_held"]),
        expert_offset=int(c["expert_offset"]),
        max_position=int(c["server"]["max_context"]),
        initializer_range=float(a["initializer_range"]),
        router_bias_range=float(a["router_bias_range"]))


def serving_flops(c):
    """Forward operations (x2) one token needs through the layers
    whatever its context: a KDA layer's projections (q|k|v, the two
    low-rank gate pairs, beta, o) and its state's four products (decay
    aside: `S'^T k`, the rank-one update, `S^T q`, 2 x 4 x d_k x d_v a
    head, which the chunk form turns into matrix products); a
    grouped-query layer's q, k|v, gate and o (its scores are the
    reader's, by context); every layer's router, shared expert and the
    EXPECTED held experts a token (experts per token x held / routed =
    0.5 here); and through the untied head. Beside them, what the
    per-layer readers need of the shapes, under names of the family's
    own (the runner's `head_dim` reads hidden / heads = 64 here)."""
    h = int(c["hidden_size"])
    layers = int(c["num_hidden_layers"])
    gqa = sum(1 for i in c["gqa_layers"] if i < layers)
    heads, kv_heads = int(c["num_attention_heads"]), \
        int(c["num_key_value_heads"])
    d = int(c["head_dim"])
    lin = c["linear_attn_config"]
    kh, kd = int(lin["num_heads"]), int(lin["head_dim"])
    rank = int(c["assumed"]["kda_gate_rank"])
    moe_i = int(c["moe_intermediate_size"])
    kda_layer = (h * 3 * kh * kd + 2 * (h * rank + rank * kh * kd)
                 + h * kh + kh * kd * h + 4 * kh * kd * kd)
    gqa_layer = 2 * h * heads * d + h * 2 * kv_heads * d + heads * d * h
    expert = 3 * h * moe_i
    held_per_token = (int(c["num_experts_per_tok"])
                      * int(c["n_routed_experts_held"])
                      / int(c["n_routed_experts"]))
    moe = (h * int(c["n_routed_experts"])
           + int(c["n_shared_experts"]) * expert
           + held_per_token * expert)
    body = (layers - gqa) * kda_layer + gqa * gqa_layer + layers * moe
    return {"body_matmul_flops_per_token": int(2 * body),
            "head_matmul_flops_per_token": 2 * h * int(c["vocab_size"]),
            "gqa_layers": gqa, "gqa_heads": heads,
            "gqa_kv_heads": kv_heads, "gqa_head_dim": d,
            "kda_layers": layers - gqa, "kda_heads": kh,
            "kda_key_dim": kd, "kda_value_dim": kd,
            "kda_state_itemsize": 4,
            "expert_layers": layers, "expert_hidden": h,
            "expert_inner": moe_i}


def serving_model(c, seed):
    """Parameters made on the device from the seed, tensor by tensor at
    the serving type (a float32 copy of 5.75 B parameters does not
    fit), behind the program's serving model."""
    import jax.numpy as jnp
    from paddle_tpu.serving import LinearMoEServingModel

    cfg = program_config(c)
    model = LinearMoEServingModel.from_seed(
        cfg, seed, getattr(jnp, c["serving_dtype"]))
    return model, cfg
