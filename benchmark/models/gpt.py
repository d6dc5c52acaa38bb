"""`"family": "gpt"`: from a configuration file to the program's own
objects, through the path a user takes (`models/gpt.py:GPTConfig` ->
startup program on the chip from the seed -> `GPTServingModel.from_scope`).
"""


def program_config(c):
    from paddle_tpu.models import gpt
    return gpt.GPTConfig(
        vocab_size=int(c["vocab_size"]), hidden_size=int(c["n_embd"]),
        num_layers=int(c["n_layer"]), num_heads=int(c["n_head"]),
        inner_size=int(c["n_inner"]),
        max_position=int(c["n_positions"]), dropout=0.0)


def serving_flops(c):
    """Forward matrix-product operations (x2) one token needs: through
    the layers (q, k, v, o and the two feed-forward products) and
    through the head tied to the token embedding."""
    h, inner = int(c["n_embd"]), int(c["n_inner"])
    return {"body_matmul_flops_per_token":
            int(c["n_layer"]) * 2 * (4 * h * h + 2 * h * inner),
            "head_matmul_flops_per_token": 2 * h * int(c["vocab_size"])}


def serving_model(c, seed):
    """Parameters made on the device by the startup program (one
    compiled call, seeded), then cast to the serving type. The float32
    scope is dropped before the caller allocates the KV pools."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GPTServingModel

    cfg = program_config(c)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = int(seed) & 0x7FFFFFFF
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=16)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
    model = GPTServingModel.from_scope(
        scope, cfg, dtype=getattr(jnp, c["serving_dtype"]))
    for name in list(scope.names()):
        scope.drop(name)
    exe.close()
    return model, cfg
