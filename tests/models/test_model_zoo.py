"""Model-zoo smoke + convergence tests (SURVEY.md §4 'models' tier).

Mirrors the reference's book tests: build each model's program, run a few
steps, assert the loss moves (full convergence is CI-scale; here we
assert trainability on tiny shapes)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import (mnist, resnet, vgg, word2vec, recommender,
                               lstm_text, transformer, bert, deepfm, gan,
                               detection_demo)


def _train(feed_fn, loss_var, steps=8, lr=0.01, fetch_extra=(),
           opt=None):
    opt = opt or fluid.optimizer.AdamOptimizer(learning_rate=lr)
    opt.minimize(loss_var)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    losses = []
    for i in range(steps):
        out = exe.run(feed=feed_fn(i), fetch_list=[loss_var, *fetch_extra])
        arr = np.asarray(out[0])
        assert arr.size == 1, f"loss fetch must be scalar-sized, got {arr.shape}"
        losses.append(float(arr.reshape(())))
    return losses


def test_mnist_conv_trains():
    np.random.seed(0)
    _img, _lbl, _pred, loss, acc = mnist.build_train_net("conv")
    xs = np.random.randn(8, 1, 28, 28).astype(np.float32)
    ys = np.random.randint(0, 10, (8, 1)).astype(np.int64)

    losses = _train(lambda i: {"img": xs, "label": ys}, loss, steps=10,
                    lr=1e-3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, losses


def test_mnist_mlp_memorizes_batch():
    np.random.seed(1)
    xs = np.random.randn(16, 1, 28, 28).astype(np.float32)
    ys = np.random.randint(0, 10, (16, 1)).astype(np.int64)
    _img, _lbl, _pred, loss, acc = mnist.build_train_net("mlp")
    losses = _train(lambda i: {"img": xs, "label": ys}, loss, steps=40,
                    lr=1e-3)
    assert losses[-1] < losses[0] * 0.5, losses[::8]


def test_resnet18_builds_and_steps():
    np.random.seed(0)
    _ = resnet.build_train_net(depth=18, class_dim=10,
                               image_shape=(3, 32, 32))
    img, label, pred, loss, acc1, acc5 = _

    def feed(i):
        return {"img": np.random.randn(4, 3, 32, 32).astype(np.float32),
                "label": np.random.randint(0, 10, (4, 1)).astype(np.int64)}

    losses = _train(feed, loss, steps=3, lr=1e-3)
    assert np.isfinite(losses).all()


def test_resnet50_graph_builds():
    resnet.resnet(layers.data("img", shape=[3, 64, 64], dtype="float32"),
                  class_dim=100, depth=50)
    n_params = len(fluid.default_main_program().all_parameters())
    # 53 convs + 53 bns (scale+shift) + fc (w+b)
    assert n_params > 150


def test_vgg16_builds_and_steps():
    np.random.seed(0)
    img, label, pred, loss, acc = vgg.build_train_net(
        class_dim=10, image_shape=(3, 32, 32))

    def feed(i):
        return {"img": np.random.randn(4, 3, 32, 32).astype(np.float32),
                "label": np.random.randint(0, 10, (4, 1)).astype(np.int64)}

    losses = _train(feed, loss, steps=3, lr=1e-4)
    assert np.isfinite(losses).all()


def test_word2vec_trains():
    np.random.seed(0)
    dict_size = 100
    words, next_word, pred, loss = word2vec.build_train_net(dict_size)

    def feed(i):
        d = {f"word_{j}": np.random.randint(0, dict_size, (16, 1)).astype(np.int64)
             for j in range(4)}
        d["next_word"] = np.random.randint(0, dict_size, (16, 1)).astype(np.int64)
        return d

    losses = _train(feed, loss, steps=5)
    assert np.isfinite(losses).all()
    # shared embedding table exists exactly once
    names = [p.name for p in fluid.default_main_program().all_parameters()]
    assert names.count("shared_w") == 1


def test_recommender_trains():
    np.random.seed(0)
    feed_vars, infer, loss = recommender.build_train_net(user_vocab=50,
                                                         movie_vocab=40)

    def feed(i):
        b = 8
        return {
            "user_id": np.random.randint(0, 50, (b, 1)).astype(np.int64),
            "gender_id": np.random.randint(0, 2, (b, 1)).astype(np.int64),
            "age_id": np.random.randint(0, 7, (b, 1)).astype(np.int64),
            "job_id": np.random.randint(0, 21, (b, 1)).astype(np.int64),
            "movie_id": np.random.randint(0, 40, (b, 1)).astype(np.int64),
            "category_ids": np.random.randint(0, 19, (b, recommender.MAX_CAT_LEN)).astype(np.int64),
            "category_len": np.random.randint(1, recommender.MAX_CAT_LEN, (b, 1)).astype(np.int64),
            "title_ids": np.random.randint(0, 100, (b, recommender.MAX_TITLE_LEN)).astype(np.int64),
            "title_len": np.random.randint(3, recommender.MAX_TITLE_LEN, (b, 1)).astype(np.int64),
            "score": np.random.uniform(1, 5, (b, 1)).astype(np.float32),
        }

    losses = _train(feed, loss, steps=5)
    assert np.isfinite(losses).all()


def test_lstm_sentiment_trains():
    np.random.seed(0)
    dict_dim, max_len = 200, 24
    data, seq_len, label, pred, loss, acc = lstm_text.build_train_net(
        dict_dim, max_len=max_len)

    def feed(i):
        b = 4
        return {"words": np.random.randint(0, dict_dim, (b, max_len)).astype(np.int64),
                "seq_len": np.random.randint(5, max_len, (b, 1)).astype(np.int64),
                "label": np.random.randint(0, 2, (b, 1)).astype(np.int64)}

    losses = _train(feed, loss, steps=4)
    assert np.isfinite(losses).all()


class _TinyTransformerCfg(transformer.ModelHyperParams):
    src_vocab_size = 64
    trg_vocab_size = 64
    d_model = 32
    d_inner_hid = 64
    n_head = 2
    n_layer = 2
    dropout = 0.0


def test_transformer_trains():
    np.random.seed(0)
    max_len = 12
    feeds, loss, token_num = transformer.build_train_net(
        cfg=_TinyTransformerCfg, max_len=max_len)

    b = 4
    fixed = {
        "src_ids": np.random.randint(2, 64, (b, max_len)).astype(np.int64),
        "src_len": np.full((b, 1), max_len, np.int64),
        "tgt_ids": np.random.randint(2, 64, (b, max_len)).astype(np.int64),
        "tgt_len": np.full((b, 1), max_len, np.int64),
        "lbl_ids": np.random.randint(2, 64, (b, max_len)).astype(np.int64),
    }

    losses = _train(lambda i: fixed, loss, steps=12, lr=1e-3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses


def _bert_feed(cfg, seq_len, b=4, seed=0):
    return bert.make_pretrain_feed(cfg, seq_len, b, seed=seed)


def test_bert_pretrain_memorizes_fixed_batch():
    """Real convergence gate (VERDICT r3 #6): tiny-BERT must OVERFIT a
    fixed pretrain batch to <5% of the initial loss — a 5-step
    loss-went-down check is coin-flip-adjacent. Calibrated: 80 steps
    @1e-3 reaches ~0.2% of initial (20x margin)."""
    np.random.seed(0)
    cfg = bert.bert_tiny()
    seq_len = 32
    feeds, total_loss, mlm_loss, nsp_acc = bert.build_pretrain_net(
        cfg, seq_len=seq_len)
    losses = _train(lambda i: _bert_feed(cfg, seq_len), total_loss,
                    steps=80, lr=1e-3)
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.05 * losses[0], (losses[0], losses[-1])


def test_bert_classifier_builds():
    cfg = bert.bert_tiny()
    feeds, loss, acc, probs = bert.build_classifier_net(cfg, seq_len=16,
                                                        num_labels=3)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    b = 2
    out = exe.run(feed={
        "src_ids": np.random.randint(0, cfg.vocab_size, (b, 16)).astype(np.int64),
        "sent_ids": np.zeros((b, 16), np.int64),
        "input_mask": np.ones((b, 16), np.float32),
        "label": np.random.randint(0, 3, (b, 1)).astype(np.int64),
    }, fetch_list=[loss, probs])
    assert out[1].shape == (b, 3)
    np.testing.assert_allclose(out[1].sum(-1), np.ones(b), rtol=1e-5)


def test_deepfm_trains():
    np.random.seed(0)
    nf, fields = 1000, 13
    ids, vals, label, loss, prob = deepfm.build_train_net(
        num_features=nf, num_fields=fields, embed_dim=8)

    def feed(i):
        b = 16
        return {"feat_ids": np.random.randint(0, nf, (b, fields)).astype(np.int64),
                "feat_vals": np.random.rand(b, fields).astype(np.float32),
                "label": np.random.randint(0, 2, (b, 1)).astype(np.float32)}

    losses = _train(feed, loss, steps=5)
    assert np.isfinite(losses).all()


def test_gan_alternating_steps():
    np.random.seed(0)
    nets = gan.build_gan()
    d_opt = fluid.optimizer.AdamOptimizer(learning_rate=2e-4)
    g_opt = fluid.optimizer.AdamOptimizer(learning_rate=2e-4)
    with fluid.program_guard(nets["d_program"]):
        d_opt.minimize(nets["d_loss"], parameter_list=nets["d_params"])
    with fluid.program_guard(nets["g_program"]):
        g_opt.minimize(nets["g_loss"], parameter_list=nets["g_params"])

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    b = 4
    for i in range(2):
        d_loss, = exe.run(
            nets["d_program"],
            feed={"img": np.random.randn(b, 1, 28, 28).astype(np.float32),
                  "noise": np.random.randn(b, gan.NOISE_DIM).astype(np.float32)},
            fetch_list=[nets["d_loss"]])
        g_loss, = exe.run(
            nets["g_program"],
            feed={"noise": np.random.randn(b, gan.NOISE_DIM).astype(np.float32)},
            fetch_list=[nets["g_loss"]])
    assert np.isfinite(d_loss) and np.isfinite(g_loss)


def test_ssd_builds_and_steps():
    np.random.seed(0)
    out = detection_demo.build_ssd_net(num_classes=4, image_size=64,
                                       max_boxes=4)
    img, gt_box, gt_label, loss = out[:4]

    def feed(i):
        b = 2
        boxes = np.sort(np.random.rand(b, 4, 4).astype(np.float32), axis=-1)
        return {"img": np.random.randn(b, 3, 64, 64).astype(np.float32),
                "gt_box": boxes,
                "gt_label": np.random.randint(1, 4, (b, 4, 1)).astype(np.int64)}

    losses = _train(feed, loss, steps=2, lr=1e-4)
    assert np.isfinite(losses).all()


def test_fit_a_line_converges():
    from paddle_tpu.models import fit_a_line
    np.random.seed(7)
    w_true = np.random.randn(13, 1).astype(np.float32)
    xs = np.random.randn(64, 13).astype(np.float32)
    ys = xs @ w_true + 0.01 * np.random.randn(64, 1).astype(np.float32)
    _x, _y, _pred, loss = fit_a_line.build_train_net()
    losses = _train(lambda i: {"x": xs, "y": ys}, loss, steps=60, lr=0.05,
                    opt=fluid.optimizer.SGDOptimizer(learning_rate=0.05))
    assert losses[-1] < 0.05, losses[-1]


def test_label_semantic_roles_trains_and_decodes():
    from paddle_tpu.models import label_semantic_roles as srl
    rng = np.random.default_rng(9)
    B, T = 4, 8
    feed = {name: rng.integers(
        0, 40, (B, T)).astype(np.int64) for name in srl.FEATURE_NAMES}
    feed["predicate"] %= srl.PRED_DICT_LEN
    feed["mark"] %= srl.MARK_DICT_LEN
    feed["target"] = rng.integers(0, srl.LABEL_DICT_LEN, (B, T)).astype(np.int64)
    feed["length"] = np.array([8, 6, 8, 5], np.int64)

    feats, target, length, cost, decode = srl.build_train_net(B, T,
                                                              hidden_dim=32)
    opt = fluid.optimizer.AdamOptimizer(learning_rate=0.02)
    opt.minimize(cost)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    losses = []
    for _ in range(40):
        out = exe.run(feed=feed, fetch_list=[cost])
        losses.append(float(np.asarray(out[0]).reshape(())))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    dec = np.asarray(exe.run(feed=feed, fetch_list=[decode])[0])
    assert dec.shape == (B, T)
    mask = np.arange(T)[None] < feed["length"][:, None]
    acc = (dec == feed["target"])[mask].mean()
    assert acc > 0.5, acc  # memorizing a tiny batch


def test_faster_rcnn_pipeline_trains():
    rng = np.random.default_rng(11)
    B, S, G = 2, 64, 4
    img = rng.standard_normal((B, 3, S, S)).astype(np.float32)
    base = rng.uniform(4, 30, (B, G, 2)).astype(np.float32)
    gt_box = np.concatenate([base, base + rng.uniform(10, 24, (B, G, 2))
                             .astype(np.float32)], -1)
    gt_label = rng.integers(1, 5, (B, G)).astype(np.int64)
    im_info = np.tile(np.array([S, S, 1.0], np.float32), (B, 1))

    _i, _b, _l, _ii, loss = detection_demo.build_faster_rcnn_train(
        num_classes=5, image_size=S, max_gt=G)
    feed = {"img": img, "gt_box": gt_box, "gt_label": gt_label,
            "im_info": im_info}
    # calibrated: 20 Adam steps on the fixed batch reach ~0.17x initial
    losses = _train(lambda i: feed, loss, steps=20, lr=1e-3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_mnist_convergence_97pct():
    """SURVEY.md §4: MNIST >=97% within an epoch-equivalent. The synthetic
    dataset is learnable by construction; full-dataset accuracy after a
    short training run must clear the reference's book-test bar."""
    import paddle_tpu.dataset as dataset
    import paddle_tpu.reader as reader
    np.random.seed(3)
    _img, _lbl, pred, loss, acc = mnist.build_train_net("conv")
    # eval must NOT touch the training program: the backward marker makes
    # exe.run execute the optimizer too, which would train on test batches
    test_prog = fluid.default_main_program().clone(for_test=True)
    opt = fluid.optimizer.AdamOptimizer(learning_rate=2e-3)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feeder = fluid.DataFeeder(["img", "label"])
    for epoch in range(2):
        for batch in reader.batch(dataset.mnist.train(), 64)():
            exe.run(feed=feeder.feed(batch), fetch_list=[loss])
    accs, ns = [], []
    for batch in reader.batch(dataset.mnist.test(), 64)():
        out = exe.run(test_prog, feed=feeder.feed(batch), fetch_list=[acc])
        accs.append(float(np.asarray(out[0]).reshape(-1)[0]))
        ns.append(len(batch))
    overall = float(np.average(accs, weights=ns))
    assert overall >= 0.97, overall


def test_mobilenet_v1_trains():
    """Depthwise-separable path: v1 must step finitely AND learn a
    small synthetic task (exercises feature_group_count == channels)."""
    from paddle_tpu.models import mobilenet
    np.random.seed(1)
    _ = mobilenet.build_train_net(version=1, class_dim=10,
                                  image_shape=(3, 32, 32),
                                  width_mult=0.25)
    img, label, pred, loss, acc1, acc5 = _
    xs = np.random.randn(16, 3, 32, 32).astype(np.float32)
    ys = np.random.randint(0, 10, (16, 1)).astype(np.int64)
    # calibrated: 40 Adam steps memorize the batch (~0.0002x initial)
    losses = _train(lambda i: {"img": xs, "label": ys}, loss, steps=40,
                    lr=3e-3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])


def test_mobilenet_v2_builds_and_steps():
    from paddle_tpu.models import mobilenet
    np.random.seed(2)
    _ = mobilenet.build_train_net(version=2, class_dim=10,
                                  image_shape=(3, 32, 32),
                                  width_mult=0.35)
    img, label, pred, loss, acc1, acc5 = _

    def feed(i):
        return {"img": np.random.randn(4, 3, 32, 32).astype(np.float32),
                "label": np.random.randint(0, 10, (4, 1)).astype(np.int64)}

    losses = _train(feed, loss, steps=3, lr=1e-3)
    assert np.isfinite(losses).all()


def test_se_resnext_overfits_fixed_batch():
    np.random.seed(5)
    image, label, loss, pred = resnet.build_se_resnext_train_net(
        class_dim=4, image_shape=(3, 16, 16))
    xs = np.random.randn(16, 3, 16, 16).astype(np.float32)
    ys = np.random.randint(0, 4, (16, 1)).astype(np.int64)
    losses = _train(lambda i: {"image": xs, "label": ys}, loss, steps=80,
                    lr=2e-3)
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])
