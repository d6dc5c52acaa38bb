"""`"family": "joyai_llm_flash"`: from a configuration file to the
program's own objects, through the path a user takes
(`models/latent_moe.py:LatentMoEConfig` -> seeded parameters made on
the device at the serving type -> `LatentMoEServingModel`). Only this
file reads the published keys.
"""


def program_config(c):
    from paddle_tpu.models.latent_moe import LatentMoEConfig
    return LatentMoEConfig(
        vocab_size=int(c["vocab_size"]),
        hidden_size=int(c["hidden_size"]),
        num_layers=int(c["num_hidden_layers"]),
        num_heads=int(c["num_attention_heads"]),
        q_lora_rank=int(c["q_lora_rank"]),
        kv_lora_rank=int(c["kv_lora_rank"]),
        qk_nope_head_dim=int(c["qk_nope_head_dim"]),
        qk_rope_head_dim=int(c["qk_rope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]),
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        intermediate_size=int(c["intermediate_size"]),
        first_k_dense=int(c["first_k_dense_replace"]),
        moe_intermediate_size=int(c["moe_intermediate_size"]),
        n_routed_experts=int(c["n_routed_experts"]),
        num_experts_per_tok=int(c["num_experts_per_tok"]),
        n_shared_experts=int(c["n_shared_experts"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        n_routed_experts_held=int(c["n_routed_experts_held"]),
        expert_offset=int(c["expert_offset"]),
        max_position=int(c["server"]["max_context"]),
        initializer_range=float(c["assumed"]["initializer_range"]),
        router_bias_range=float(c["assumed"]["router_bias_range"]))


def serving_flops(c):
    """Forward matrix-product operations (x2) one token needs: through
    every layer's attention products (q_a, q_b, kv_a, the two halves of
    kv_b, which the absorbed form applies to the query and to the
    output, and o), the dense layers' MLP, each expert layer's router,
    shared expert and the EXPECTED held experts a token (experts per
    token x held / routed = 0.5 here), and through the untied head.
    Beside them, what the new per-layer readers need of the shapes."""
    h, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    q_lora, kv_lora = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    vd, moe_i = int(c["v_head_dim"]), int(c["moe_intermediate_size"])
    layers, dense = int(c["num_hidden_layers"]), \
        int(c["first_k_dense_replace"])
    attn = (h * q_lora + q_lora * heads * (nope + rope)
            + h * (kv_lora + rope) + kv_lora * heads * (nope + vd)
            + heads * vd * h)
    expert = 3 * h * moe_i
    held_per_token = (int(c["num_experts_per_tok"])
                      * int(c["n_routed_experts_held"])
                      / int(c["n_routed_experts"]))
    moe = (h * int(c["n_routed_experts"])
           + int(c["n_shared_experts"]) * expert
           + held_per_token * expert)
    body = (layers * attn + dense * 3 * h * int(c["intermediate_size"])
            + (layers - dense) * moe)
    return {"body_matmul_flops_per_token": int(2 * body),
            "head_matmul_flops_per_token": 2 * h * int(c["vocab_size"]),
            "latent_row_values": kv_lora + rope,
            "latent_value_width": kv_lora,
            "expert_layers": layers - dense,
            "expert_hidden": h, "expert_inner": moe_i}


def serving_model(c, seed):
    """Parameters made on the device from the seed, tensor by tensor at
    the serving type (a float32 copy of 4.78 B parameters does not
    fit), behind the program's serving model."""
    import jax.numpy as jnp
    from paddle_tpu.serving import LatentMoEServingModel

    cfg = program_config(c)
    model = LatentMoEServingModel.from_seed(
        cfg, seed, getattr(jnp, c["serving_dtype"]))
    return model, cfg
