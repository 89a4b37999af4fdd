"""Gluon core Block/HybridBlock/Parameter behaviors.

Ports the strategy of tests/python/unittest/test_gluon.py (parameter
sharing, deferred init, hybridize-vs-eager numerics, save/load round
trips, hooks, naming) against our TPU-native gluon."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, autograd
from mxnet_tpu.gluon import nn


def test_parameter_basic():
    p = gluon.Parameter("w", shape=(3, 2))
    p.initialize(init=mx.initializer.One())
    np.testing.assert_allclose(p.data().asnumpy(), 1.0)
    assert p.shape == (3, 2)
    p.set_data(nd.zeros((3, 2)))
    np.testing.assert_allclose(p.data().asnumpy(), 0.0)
    assert p.grad() is not None


def test_parameter_deferred_init():
    net = nn.Dense(4)
    net.initialize()
    with pytest.raises(Exception):
        net.weight.data()           # shape unknown until first forward
    net(nd.zeros((2, 5)))
    assert net.weight.shape == (4, 5)


def test_parameter_sharing():
    # sharing matches by full name, so the sharer uses the same prefix
    # (ref: test_gluon.py test_parameter_sharing pattern)
    d1 = nn.Dense(4, in_units=3, prefix="shared_")
    d2 = nn.Dense(4, in_units=3, prefix="shared_",
                  params=d1.collect_params())
    d1.initialize()
    x = nd.array(np.random.RandomState(0).rand(2, 3).astype("float32"))
    np.testing.assert_allclose(d1(x).asnumpy(), d2(x).asnumpy())
    # mutating through one alias is visible through the other
    d1.weight.set_data(nd.zeros((4, 3)))
    np.testing.assert_allclose(d2(x).asnumpy(), d1.bias.data().asnumpy()
                               [None].repeat(2, 0))


def test_block_naming_and_collect():
    net = nn.HybridSequential(prefix="model_")
    with net.name_scope():
        net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    net(nd.zeros((1, 3)))
    names = sorted(net.collect_params().keys())
    assert all(n.startswith("model_") for n in names), names
    sub = net.collect_params(".*weight")
    assert all(n.endswith("weight") for n in sub.keys())


def test_hybridize_matches_eager():
    rs = np.random.RandomState(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="tanh"), nn.BatchNorm(),
            nn.Dense(3))
    net.initialize()
    x = nd.array(rs.rand(4, 6).astype("float32"))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    np.testing.assert_allclose(hybrid, eager, rtol=2e-5, atol=2e-6)
    # gradients agree too
    for mode in (True,):
        xg = nd.array(rs.rand(4, 6).astype("float32"))
        xg.attach_grad()
        with autograd.record():
            y = net(xg).sum()
        y.backward()
        g1 = xg.grad.asnumpy()
        assert np.isfinite(g1).all()


def test_save_load_parameters_roundtrip(tmp_path):
    rs = np.random.RandomState(0)
    net = nn.Sequential()
    net.add(nn.Dense(5, activation="relu"), nn.Dense(2))
    net.initialize()
    x = nd.array(rs.rand(3, 4).astype("float32"))
    ref = net(x).asnumpy()
    f = str(tmp_path / "net.params")
    net.save_parameters(f)
    net2 = nn.Sequential()
    net2.add(nn.Dense(5, activation="relu"), nn.Dense(2))
    net2.load_parameters(f)
    np.testing.assert_allclose(net2(x).asnumpy(), ref, rtol=1e-6)


def test_load_parameters_strictness(tmp_path):
    net = nn.Dense(3, in_units=2)
    net.initialize()
    f = str(tmp_path / "d.params")
    net.save_parameters(f)
    other = nn.Dense(4, in_units=2)
    with pytest.raises(Exception):
        other.load_parameters(f)    # shape mismatch must not pass silently


def test_forward_hooks():
    calls = []
    net = nn.Dense(2, in_units=2)
    net.initialize()
    net.register_forward_pre_hook(lambda blk, ins: calls.append("pre"))
    net.register_forward_hook(lambda blk, ins, out: calls.append("post"))
    net(nd.zeros((1, 2)))
    assert calls == ["pre", "post"]


def test_apply_and_cast():
    net = nn.Sequential()
    net.add(nn.Dense(2, in_units=2))
    net.initialize()
    seen = []
    net.apply(lambda b: seen.append(type(b).__name__))
    assert "Dense" in seen and "Sequential" in seen
    net.cast("float16")
    assert net[0].weight.dtype == np.float16


def test_zero_grad():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    x = nd.ones((1, 3))
    with autograd.record():
        y = net(x).sum()
    y.backward()
    assert np.abs(net.weight.grad().asnumpy()).sum() > 0
    net.zero_grad()
    np.testing.assert_allclose(net.weight.grad().asnumpy(), 0.0)


def test_constant_parameter():
    c = gluon.Constant("c", np.array([1.0, 2.0], "float32"))
    c.initialize()
    np.testing.assert_allclose(c.data().asnumpy(), [1, 2])
    # constants do not receive gradients through Trainer updates
    assert c.grad_req == "null"


def test_sequential_indexing_and_len():
    net = nn.Sequential()
    net.add(nn.Dense(2), nn.Dense(3), nn.Dense(4))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)


def test_summary_runs():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    net.summary(nd.zeros((1, 3)))


def test_symbolblock_from_symbol():
    """SymbolBlock wraps a symbolic graph as a gluon layer
    (ref: test_gluon.py test_symbol_block)."""
    data = mx.sym.var("data")
    out = mx.sym.FullyConnected(data, num_hidden=3, name="fc1")
    out = mx.sym.Activation(out, act_type="relu")
    blk = gluon.SymbolBlock(out, data)
    blk.initialize()
    y = blk(nd.ones((2, 4)))
    assert y.shape == (2, 3)
    assert (y.asnumpy() >= 0).all()


def test_block_repr():
    net = nn.Sequential()
    net.add(nn.Dense(2))
    assert "Dense" in repr(net)


def test_symbolblock_trains():
    """SymbolBlock joins the autograd tape: gradients flow to its params
    through a single-output wrapped graph (regression: single-output
    cotangent structure)."""
    rs = np.random.RandomState(0)
    data = mx.sym.var("data")
    out = mx.sym.FullyConnected(data, num_hidden=1, name="sbt_fc")
    blk = gluon.SymbolBlock(out, data)
    blk.initialize()
    X = rs.rand(16, 3).astype("float32")
    Y = X.sum(1, keepdims=True)
    blk(nd.array(X[:2]))
    tr = gluon.Trainer(blk.collect_params(), "adam",
                       {"learning_rate": 0.1})
    fn = gluon.loss.L2Loss()
    first = last = None
    for _ in range(60):
        with autograd.record():
            L = fn(blk(nd.array(X)), nd.array(Y))
        L.backward()
        tr.step(16)
        v = float(L.mean().asscalar())
        first = v if first is None else first
        last = v
    assert last < first * 0.1, (first, last)


def test_symbolblock_batchnorm_aux_updates():
    """BatchNorm moving stats inside a SymbolBlock update during training
    forwards and feed inference."""
    data = mx.sym.var("data")
    out = mx.sym.BatchNorm(data, name="sbbn", momentum=0.5)
    blk = gluon.SymbolBlock(out, data)
    blk.initialize()
    rs = np.random.RandomState(0)
    x = nd.array((rs.rand(8, 4) * 10 + 5).astype("float32"))
    with autograd.record():
        y = blk(x)
    mm = blk.collect_params()["sbbn_moving_mean"].data().asnumpy()
    assert np.abs(mm).max() > 0.1, mm
    y2 = blk(x)  # inference path with updated stats
    assert np.isfinite(y2.asnumpy()).all()


def test_lean_vjp_when_residuals_would_crowd_the_device(monkeypatch):
    """A hybridized block whose recorded forward would hold more than
    half the device's memory in vjp residuals holds nothing instead: its
    backward is one program that re-runs the forward. Same loss, same
    gradients up to fusion order, moving stats updated once. The CPU
    reports no memory limit and keeps the plain vjp; the limit is faked
    here to drive the decision both ways."""
    from mxnet_tpu.gluon import block as B

    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(4, 6).astype("float32"))
    assert B._device_bytes_limit(x._data) is None      # CPU: no limit
    decisions = []
    real = B._vjp_crowds_device
    monkeypatch.setattr(
        B, "_vjp_crowds_device",
        lambda *a: decisions.append(real(*a)) or decisions[-1])

    def loss_and_grads(limit):
        monkeypatch.setattr(B, "_device_bytes_limit", lambda a: limit)
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=6), nn.BatchNorm(),
                nn.Activation("relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net.hybridize()
        net(x)
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        return float(loss.asscalar()), [
            (p.grad() if p.grad_req != "null" else p.data()).asnumpy()
            for _, p in sorted(net.collect_params().items())]

    roomy = loss_and_grads(1 << 40)
    lean = loss_and_grads(1 << 10)
    assert decisions == [False, True]
    assert roomy[0] == lean[0]
    for a, b in zip(roomy[1], lean[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_loaded_values_land_where_the_parameter_lives(tmp_path):
    """nd.load reads onto the host's first device. set_data on a live
    parameter keeps the parameter's own placement, and load_parameters
    honours ``ctx`` for parameters that have no data yet — on the chip a
    checkpoint load used to move every weight to the CPU while its
    context still said tpu(0)."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    dev1 = jax.devices()[1]
    fname = str(tmp_path / "net.params")

    src = nn.Dense(3, in_units=2)
    src.initialize()
    src.save_parameters(fname)

    live = nn.Dense(3, in_units=2)
    live.initialize(ctx=mx.cpu(1))
    assert live.weight.data()._data.devices() == {dev1}
    live.load_parameters(fname)
    assert live.weight.data()._data.devices() == {dev1}
    np.testing.assert_array_equal(live.weight.data().asnumpy(),
                                  src.weight.data().asnumpy())

    fresh = nn.Dense(3)                      # deferred: no data yet
    fresh.initialize(ctx=mx.cpu(1))
    fresh.load_parameters(fname, ctx=mx.cpu(1))
    assert fresh.weight.data()._data.devices() == {dev1}
