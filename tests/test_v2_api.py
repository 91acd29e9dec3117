"""v2 API facade end-to-end tests (reference model: the v1_api_demo /
v2 quick-start flows: uci_housing fit-a-line, mnist, imdb sentiment)."""

import io

import numpy as np
import pytest

import paddle_tpu.v2 as paddle


def test_fit_a_line_v2():
    paddle.init(use_gpu=False, trainer_count=1)
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(13))
    y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
    y_predict = paddle.layer.fc(input=x, size=1)
    cost = paddle.layer.mse_cost(input=y_predict, label=y)

    parameters = paddle.parameters.create(cost)
    optimizer = paddle.optimizer.Momentum(momentum=0.9, learning_rate=1e-3)
    trainer = paddle.trainer.SGD(cost=cost, parameters=parameters,
                                 update_equation=optimizer)

    costs = []

    def event_handler(event):
        if isinstance(event, paddle.event.EndIteration):
            costs.append(event.cost)

    reader = paddle.batch(
        paddle.reader.shuffle(paddle.dataset.uci_housing.train(),
                              buf_size=500),
        batch_size=32)
    trainer.train(reader=reader, num_passes=2, event_handler=event_handler)
    assert costs[-1] < 0.5 * costs[0], (costs[0], costs[-1])

    result = trainer.test(reader=paddle.batch(
        paddle.dataset.uci_housing.test(), batch_size=32))
    assert result.cost is not None and np.isfinite(result.cost)


def test_mnist_v2_with_infer():
    paddle.init()
    images = paddle.layer.data(name="pixel",
                               type=paddle.data_type.dense_vector(784))
    label = paddle.layer.data(name="label",
                              type=paddle.data_type.integer_value(10))
    hidden = paddle.layer.fc(input=images, size=64,
                             act=paddle.activation.Relu())
    predict = paddle.layer.fc(input=hidden, size=10,
                              act=paddle.activation.Softmax())
    cost = paddle.layer.classification_cost(input=predict, label=label)

    parameters = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-3))
    reader = paddle.batch(paddle.dataset.mnist.train(), batch_size=64)
    seen = []
    trainer.train(reader=paddle.reader.firstn(reader, 40), num_passes=1,
                  event_handler=lambda e: seen.append(e.cost)
                  if isinstance(e, paddle.event.EndIteration) else None)
    assert seen[-1] < 0.7 * seen[0], (seen[0], seen[-1])

    # inference on the prediction layer using the trained parameters
    test_rows = [r for r, _ in zip(paddle.dataset.mnist.test()(), range(8))]
    probs = paddle.infer(output_layer=predict, parameters=parameters,
                         input=[(r[0],) for r in test_rows])
    assert probs.shape == (8, 10)
    np.testing.assert_allclose(probs.sum(axis=1), np.ones(8), atol=1e-3)


def test_imdb_lstm_sequence_path():
    """Sequence data type -> padded feed -> lstm -> masked pooling."""
    paddle.init()
    words = paddle.layer.data(
        name="words",
        type=paddle.data_type.integer_value_sequence(5149))
    label = paddle.layer.data(name="label",
                              type=paddle.data_type.integer_value(2))
    emb = paddle.layer.embedding(input=words, size=32)
    lstm = paddle.networks.simple_lstm(emb, 32)
    pooled = paddle.layer.pooling(input=lstm,
                                  pooling_type=paddle.pooling.Max())
    predict = paddle.layer.fc(input=pooled, size=2,
                              act=paddle.activation.Softmax())
    cost = paddle.layer.classification_cost(input=predict, label=label)

    parameters = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.Adam(learning_rate=2e-3))
    reader = paddle.batch(paddle.dataset.imdb.train(), batch_size=32)
    seen = []
    trainer.train(reader=paddle.reader.firstn(reader, 30), num_passes=1,
                  event_handler=lambda e: seen.append(e.cost)
                  if isinstance(e, paddle.event.EndIteration) else None)
    assert seen[-1] < 0.9 * seen[0], (seen[0], seen[-1])


def test_parameters_tar_roundtrip():
    paddle.init()
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(4))
    y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
    pred = paddle.layer.fc(input=x, size=1)
    cost = paddle.layer.mse_cost(input=pred, label=y)
    params = paddle.parameters.create(cost)
    name = params.keys()[0]
    w = params.get(name)
    buf = io.BytesIO()
    params.to_tar(buf)
    params.set(name, np.zeros_like(w))
    buf.seek(0)
    params.load_tar(buf)
    np.testing.assert_allclose(params.get(name), w)


def test_reader_decorators():
    r = paddle.reader.firstn(
        paddle.reader.shuffle(paddle.dataset.uci_housing.train(), 100), 10)
    rows = list(r())
    assert len(rows) == 10
    c = paddle.reader.compose(paddle.dataset.uci_housing.train(),
                              paddle.dataset.uci_housing.train())
    row = next(c())
    assert len(row) == 4  # two (x, y) pairs concatenated


def test_new_datasets_schemas():
    """flowers/mq2007/voc2012 record contracts (reference:
    python/paddle/v2/dataset/{flowers,mq2007,voc2012}.py)."""
    from paddle_tpu.v2.dataset import flowers, mq2007, voc2012

    x, y = next(flowers.train()())
    assert x.shape == (3 * 32 * 32,) and x.dtype == np.float32
    assert 0 <= y < flowers.CLASS_NUM

    left, right = next(mq2007.train(format="pairwise")())
    assert left.shape == (46,) and right.shape == (46,)
    xf, rel = next(mq2007.train(format="pointwise")())
    assert xf.shape == (46,) and rel in (0.0, 1.0, 2.0)
    labels, feats = next(mq2007.train(format="listwise")())
    assert len(labels) == len(feats)

    img, mask = next(voc2012.train()())
    assert img.shape[0] == 3 and img.shape[1:] == mask.shape
    vals = set(np.unique(mask).tolist()) - {voc2012.IGNORE_LABEL}
    assert vals <= set(range(voc2012.CLASS_NUM))
    # image and mask agree: pixels of one class share a color
    cls = next(iter(vals - {0}), None)
    if cls is not None:
        ys, xs = np.where(mask == cls)
        colors = img[:, ys, xs]
        assert colors.std(axis=1).max() < 0.2

    # determinism across calls
    x2, y2 = next(flowers.train()())
    np.testing.assert_array_equal(x, x2)


def test_resnet_block_v2_trainer():
    """The BASELINE.json north-star API path: a residual conv network
    training end-to-end from ``paddle.v2.trainer.SGD`` (tiny shapes;
    the full-size throughput row is ``perf/``'s ``resnet50-train-bs256``
    cell).  Covers img_conv/batch_norm/img_pool + the residual add
    through the v2 facade with a synthetic separable image task."""
    import paddle_tpu.v2 as paddle

    paddle.init(use_gpu=False, trainer_count=1)
    img = paddle.layer.data(name="image",
                            type=paddle.data_type.dense_vector(3 * 16 * 16))

    def reshape_img(x):
        from paddle_tpu import layers as L
        from paddle_tpu.v2.layer import LayerOutput

        def build(ctx, v):
            return L.reshape(v, [-1, 3, 16, 16])

        return LayerOutput("img4d", [x], build, size=3 * 16 * 16)

    x4 = reshape_img(img)
    c1 = paddle.layer.img_conv(input=x4, filter_size=3, num_filters=8,
                               padding=1, act=paddle.activation.Linear())
    b1 = paddle.layer.batch_norm(input=c1, act=paddle.activation.Relu())
    c2 = paddle.layer.img_conv(input=b1, filter_size=3, num_filters=8,
                               padding=1, act=paddle.activation.Linear())

    def residual_add(a, b):
        from paddle_tpu import layers as L
        from paddle_tpu.v2.layer import LayerOutput

        def build(ctx, va, vb):
            return L.relu(L.elementwise_add(va, vb))

        return LayerOutput("res_add", [a, b], build, size=None)

    # shortcut projects 3->8 channels with a 1x1 conv
    sc = paddle.layer.img_conv(input=x4, filter_size=1, num_filters=8,
                               act=paddle.activation.Linear())
    res = residual_add(c2, sc)
    pool = paddle.layer.img_pool(input=res, pool_size=16, stride=16,
                                 pool_type=paddle.pooling.Avg())
    pred = paddle.layer.fc(input=pool, size=4,
                           act=paddle.activation.Softmax())
    label = paddle.layer.data(name="label",
                              type=paddle.data_type.integer_value(4))
    cost = paddle.layer.classification_cost(input=pred, label=label)

    parameters = paddle.parameters.create(cost)
    optimizer = paddle.optimizer.Momentum(momentum=0.9, learning_rate=0.3)
    trainer = paddle.trainer.SGD(cost=cost, parameters=parameters,
                                 update_equation=optimizer)
    rng = np.random.RandomState(0)
    protos = rng.randn(4, 3 * 16 * 16).astype(np.float32)

    def reader():
        r = np.random.RandomState(1)
        for _ in range(96):
            y = int(r.randint(0, 4))
            yield (protos[y] + 0.3 * r.randn(3 * 16 * 16).astype(np.float32),
                   y)

    costs = []

    def handler(event):
        if isinstance(event, paddle.event.EndIteration):
            costs.append(event.cost)

    trainer.train(reader=paddle.batch(reader, batch_size=16),
                  num_passes=10, event_handler=handler)
    assert costs[-1] < 0.5 * costs[0], (costs[0], costs[-1])


def test_v2_checkpoint_handler_crash_resume(tmp_path):
    """EndIteration-driven CheckpointHandler: v2 training checkpoints
    params + optimizer state periodically; a fresh trainer restores the
    newest complete step and continues (ISSUE 12 satellite)."""
    import os

    import paddle_tpu.io as io_mod

    paddle.init(use_gpu=False, trainer_count=1)

    def build():
        x = paddle.layer.data(name="x",
                              type=paddle.data_type.dense_vector(4))
        y = paddle.layer.data(name="y",
                              type=paddle.data_type.dense_vector(1))
        pred = paddle.layer.fc(input=x, size=1)
        cost = paddle.layer.mse_cost(input=pred, label=y)
        params = paddle.parameters.create(cost)
        opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=1e-3)
        return paddle.trainer.SGD(cost=cost, parameters=params,
                                  update_equation=opt)

    rng = np.random.RandomState(3)
    rows = [(rng.randn(4).astype(np.float32),
             rng.randn(1).astype(np.float32)) for _ in range(48)]
    reader = paddle.batch(lambda: iter(rows), batch_size=16)

    ck = str(tmp_path / "ck")
    t1 = build()
    t1.train(reader=reader, num_passes=2, checkpoint_dir=ck,
             checkpoint_period=2)
    # 3 batches/pass x 2 passes; period 2 + pass-end saves, retention 3
    assert io_mod.latest_checkpoint_step(ck) == 6
    steps = sorted(int(d[5:]) for d in os.listdir(ck)
                   if d.startswith("step_") and d[5:].isdigit())
    assert len(steps) <= 3  # max_to_keep pruning bounds disk
    pname = t1.topology.main_program.all_parameters()[0].name
    w_end = np.array(t1.parameters.get(pname))

    # "crash": a brand-new trainer restores the newest complete step
    t2 = build()
    assert t2.restore_checkpoint(ck) == 6
    np.testing.assert_allclose(np.array(t2.parameters.get(pname)), w_end)
    # resumed numbering continues rather than overwriting history
    t2.train(reader=reader, num_passes=1, checkpoint_dir=ck,
             checkpoint_period=2)
    assert io_mod.latest_checkpoint_step(ck) == 9
