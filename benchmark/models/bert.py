"""`"family": "bert"`: from a configuration file to the pre-training
program, through the path a user takes (`bert.BertConfig` ->
`build_pretrain_net` -> `AdamOptimizer.minimize` ->
`amp.cast_model_to_bf16`)."""

# keys of the published config.json that BertConfig takes by name
_PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size", "hidden_act",
              "hidden_dropout_prob", "attention_probs_dropout_prob",
              "max_position_embeddings", "type_vocab_size",
              "max_predictions_per_seq")


def program_config(c):
    from paddle_tpu.models import bert
    return bert.BertConfig(**{k: c[k] for k in _PUBLISHED})


def shape_facts(c):
    """The shape the per-layer readers compute kernel work from, under
    the runner's names, whatever the published file calls its keys."""
    heads = int(c["num_attention_heads"])
    return {"num_layers": int(c["num_hidden_layers"]), "num_heads": heads,
            "head_dim": int(c["hidden_size"]) // heads}


def pretrain_programs(c, seq_len, seed):
    """(main, startup, test, loss, forward_matmul_flops_per_row). `test`
    is main cut at the backward marker with dropout off: the program the
    reference is compared with."""
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core import framework
    from paddle_tpu.models import bert
    from benchmark import flops

    cfg = program_config(c)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = int(seed) & 0x7FFFFFFF
    with framework.program_guard(main, startup):
        _feeds, loss, _mlm, _acc = bert.build_pretrain_net(
            cfg, seq_len=seq_len)
        fluid.optimizer.AdamOptimizer(
            float(c["learning_rate"])).minimize(loss)
    amp.cast_model_to_bf16(main)
    test = main.clone(for_test=True)
    fwd = flops.program_forward_matmul_flops(main, 1)
    return main, startup, test, loss, fwd
