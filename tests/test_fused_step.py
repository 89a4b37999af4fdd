"""Fused train step (gluon/fused_step.py): one donated jitted program
for forward + backward + optimizer update.

Covers the ISSUE 4 acceptance surface: bitwise parity eager-vs-fused
for SGD / SGD(momentum) / Adam over >=3 steps including an lr-schedule
change and a batch_size (rescale divisor) change mid-run with ZERO
retraces, a save_states/load_states round-trip that resumes identically
on both paths, multi-precision masters, every eager-fallback reason
(counted, never a crash), and the fused_step.* counters / train_step
spans in the profiler.

Parity contract: the eager reference is the HYBRIDIZED eager path
(backward = vjp of the same jitted forward). The non-hybridized per-op
tape can differ by ~1 ULP because XLA fuses tiny dots differently per
compilation context.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, profiler
from mxnet_tpu.gluon import fused_step as FS


def _make_net(seed_from=None, hybridize=True, in_units=8):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, in_units=in_units, activation="relu"))
        net.add(gluon.nn.Dense(1, in_units=16))
    net.initialize(mx.init.Uniform(0.1))
    if hybridize:
        net.hybridize()
    if seed_from is not None:
        for (_, p1), (_, p2) in zip(
                sorted(seed_from.collect_params().items()),
                sorted(net.collect_params().items())):
            p2.set_data(p1.data().astype("float32"))
    return net


def _batch(n=4, in_units=8, seed=0):
    rs = np.random.RandomState(seed)
    x = mx.nd.array(rs.rand(n, in_units).astype("float32"))
    y = mx.nd.array(rs.rand(n, 1).astype("float32"))
    return x, y


def _eager_step(net, loss_fn, trainer, x, y, batch_size):
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(batch_size)
    return loss


def _params_bitwise(net_a, net_b):
    return all(
        np.array_equal(pa.data().asnumpy(), pb.data().asnumpy())
        for (_, pa), (_, pb) in zip(
            sorted(net_a.collect_params().items()),
            sorted(net_b.collect_params().items())))


@pytest.mark.parametrize("algo,kwargs", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
    ("adagrad", {"learning_rate": 0.05}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.01,
             "clip_gradient": 0.5}),
], ids=["sgd", "sgd-momentum", "adam", "adagrad", "rmsprop-centered",
        "sgd-wd-clip"])
def test_fused_bitwise_parity_with_replay(algo, kwargs):
    """>=3 parity steps, then an lr change and a batch_size (divisor)
    change mid-run — both must REPLAY the compiled program (operands,
    not constants): fused_step.retraces == 0 and parity stays bitwise."""
    x, y = _batch()
    loss_fn = gluon.loss.L2Loss()
    net_a = _make_net()
    net_b = _make_net(net_a)
    tr_a = gluon.Trainer(net_a.collect_params(), algo, dict(kwargs))
    tr_b = gluon.Trainer(net_b.collect_params(), algo, dict(kwargs))
    step = gluon.train_step(net_b, loss_fn, tr_b)
    FS.reset_stats()

    modes = []
    for _ in range(3):
        la = _eager_step(net_a, loss_fn, tr_a, x, y, 4)
        lb = step(x, y, batch_size=4)
        modes.append(step.last_mode)
        assert np.array_equal(la.asnumpy(), lb.asnumpy())
    assert modes == ["eager-warming", "compile", "fused"]

    # lr-schedule tick: a runtime operand, not a baked constant
    tr_a.set_learning_rate(kwargs["learning_rate"] / 3)
    tr_b.set_learning_rate(kwargs["learning_rate"] / 3)
    _eager_step(net_a, loss_fn, tr_a, x, y, 4)
    step(x, y, batch_size=4)
    assert step.last_mode == "fused"

    # batch_size divisor change (same tensors): rescale is an operand too
    _eager_step(net_a, loss_fn, tr_a, x, y, 8)
    step(x, y, batch_size=8)
    assert step.last_mode == "fused"

    st = FS.stats()
    assert st["retraces"] == 0, st
    assert st["fallbacks"] == 0, st
    assert st["hits"] >= 3, st
    assert _params_bitwise(net_a, net_b)
    # raw grads are adopted back into Parameter.grad() identically
    for (_, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                sorted(net_b.collect_params().items())):
        assert np.array_equal(pa.grad().asnumpy(), pb.grad().asnumpy())


def test_fuse_step_closure_form_matches_block_form():
    x, y = _batch()
    loss_fn = gluon.loss.L2Loss()
    net_a = _make_net()
    net_b = _make_net(net_a)
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    step_a = gluon.train_step(net_a, loss_fn, tr_a)
    step_b = tr_b.fuse_step(lambda xx, yy: loss_fn(net_b(xx), yy))
    for _ in range(3):
        la = step_a(x, y, batch_size=4)
        lb = step_b(x, y, batch_size=4)
        assert np.array_equal(la.asnumpy(), lb.asnumpy())
    assert step_b.last_mode == "fused"
    assert _params_bitwise(net_a, net_b)


def test_save_load_states_roundtrip_resumes_identically(tmp_path):
    """Mid-training checkpoint: both resume paths (eager and fused) must
    continue bitwise-identically — the fused step shares the updater's
    state store and the optimizer's update counts."""
    x, y = _batch()
    loss_fn = gluon.loss.L2Loss()
    pfile = str(tmp_path / "net.params")
    sfile = str(tmp_path / "trainer.states")

    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    step = gluon.train_step(net, loss_fn, tr)
    for _ in range(3):
        step(x, y, batch_size=4)
    assert tr._optimizer.num_update == 3
    net.save_parameters(pfile)
    tr.save_states(sfile)

    def resume(fused):
        net2 = _make_net()
        net2.load_parameters(pfile)
        tr2 = gluon.Trainer(net2.collect_params(), "adam",
                            {"learning_rate": 0.01})
        tr2.load_states(sfile)
        assert tr2._optimizer.num_update == 3
        if fused:
            s2 = gluon.train_step(net2, loss_fn, tr2)
            for _ in range(3):
                s2(x, y, batch_size=4)
        else:
            for _ in range(3):
                _eager_step(net2, loss_fn, tr2, x, y, 4)
        return [p.data().asnumpy()
                for _, p in sorted(net2.collect_params().items())]

    fused_ws = resume(True)
    eager_ws = resume(False)
    for a, b in zip(fused_ws, eager_ws):
        assert np.array_equal(a, b)


def test_multi_precision_parity_fp16_master():
    x, y = _batch()
    x, y = x.astype("float16"), y.astype("float16")
    loss_fn = gluon.loss.L2Loss()
    net_a = _make_net()
    net_b = _make_net(net_a)
    net_a.cast("float16")
    net_b.cast("float16")
    kw = {"learning_rate": 0.1, "momentum": 0.9, "multi_precision": True}
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd", dict(kw))
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd", dict(kw))
    step = gluon.train_step(net_b, loss_fn, tr_b)
    for _ in range(4):
        _eager_step(net_a, loss_fn, tr_a, x, y, 4)
        step(x, y, batch_size=4)
    assert step.last_mode == "fused"
    assert _params_bitwise(net_a, net_b)
    # the fp32 masters (state[0] of each entry) stay bitwise too
    ua, ub = tr_a._updater, tr_b._updater
    for k in ua.states:
        assert np.array_equal(ua.states[k][0].asnumpy(),
                              ub.states[k][0].asnumpy())


def test_deferred_init_first_step_falls_back_then_fuses():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu"))  # no in_units
        net.add(gluon.nn.Dense(1))
    net.initialize(mx.init.Uniform(0.1))
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    x, y = _batch()
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:deferred-init"
    for _ in range(2):
        step(x, y, batch_size=4)
    assert step.last_mode == "compile"
    step(x, y, batch_size=4)
    assert step.last_mode == "fused"


# -- fallback reasons: counted, never a crash --------------------------------

def test_deferred_frozen_param_outside_trainer_falls_back():
    """A deferred-init parameter the TRAINER does not own (frozen layer
    in a fine-tune subset) must fall back, not crash with
    DeferredInitializationError at signature time."""
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu"))  # frozen, deferred
        net.add(gluon.nn.Dense(1, in_units=16))
    net.initialize(mx.init.Uniform(0.1))
    net.hybridize()
    # trainer owns only the second layer's params
    tr = gluon.Trainer(net[1].collect_params(), "sgd",
                       {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    x, y = _batch()
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:deferred-init"
    for _ in range(2):
        step(x, y, batch_size=4)
    assert step.last_mode == "compile"


def test_ignore_stale_grad_skips_stale_params():
    """Reference semantics: ignore_stale_grad=True SKIPS params whose
    grad was not refreshed by backward instead of re-applying the old
    gradient (momentum would keep charging on unused weights)."""
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    x, y = _batch()
    loss_fn = gluon.loss.L2Loss()
    _eager_step(net, loss_fn, tr, x, y, 4)
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()}
    # no new backward: every grad is stale — the step must be a no-op
    tr.step(4, ignore_stale_grad=True)
    for n, p in net.collect_params().items():
        assert np.array_equal(before[n], p.data().asnumpy()), n


def test_fallback_non_hybridized_block_still_trains():
    x, y = _batch()
    loss_fn = gluon.loss.L2Loss()
    net = _make_net(hybridize=False)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, loss_fn, tr)
    FS.reset_stats()
    before = [p.data().asnumpy().copy()
              for _, p in sorted(net.collect_params().items())]
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:non-hybridized"
    assert FS.stats()["fallbacks"] == 1
    after = [p.data().asnumpy()
             for _, p in sorted(net.collect_params().items())]
    assert any(not np.array_equal(a, b) for a, b in zip(before, after))


def test_fallback_kvstore_attached():
    x, y = _batch()
    net = _make_net()
    kv = mx.kv.create("local")
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                       kvstore=kv)
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:kvstore"


def test_fallback_unsupported_optimizer():
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "ftml", {})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:optimizer:FTML"


def test_fallback_disabled_via_toggle():
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    prev = FS.set_fused_step(False)
    try:
        step(x, y, batch_size=4)
        assert step.last_mode == "fallback:disabled"
    finally:
        FS.set_fused_step(prev)


def test_fallback_inside_record_scope():
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    with autograd.record():
        pass
    # a LIVE record scope must not let the fused program swallow the tape
    with autograd.record():
        inner = _batch()[0] * 1.0  # the scope is genuinely recording
        assert autograd.is_recording()
        step(x, y, batch_size=4)
    assert step.last_mode == "fallback:recording-scope"
    del inner


def test_fallback_amp_loss_scaler():
    """amp.init_trainer wraps Trainer._update with overflow-skip logic
    the fused program can't honor — such trainers run eagerly."""
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    tr._amp_loss_scaler = object()  # stand-in for amp.init_trainer
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:amp-loss-scaler"


def test_fallback_grad_req_add():
    x, y = _batch()
    net = _make_net()
    for p in net.collect_params().values():
        p.grad_req = "add"
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    step(x, y, batch_size=4)
    assert step.last_mode == "fallback:grad-req-add"


def test_shape_change_is_a_retrace_not_a_failure():
    """A genuinely new input SHAPE compiles a second program and counts
    one retrace (the shape-churn indicator) — operand changes never do."""
    loss_fn = gluon.loss.L2Loss()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, loss_fn, tr)
    FS.reset_stats()
    x4, y4 = _batch(4)
    x8, y8 = _batch(8)
    for _ in range(2):
        step(x4, y4, batch_size=4)
    for _ in range(2):
        step(x8, y8, batch_size=8)
    assert step.last_mode == "compile"
    assert FS.stats()["retraces"] == 1


def test_trace_failure_falls_back_and_keeps_the_evidence():
    """A step whose trace raises still trains eagerly — and says so:
    the exception stays on the step object, one warning names it, and a
    marker lands in the flight record. On the chip a compiler refusal
    looks exactly like this; it once vanished into a bare ``except``."""
    from mxnet_tpu._debug import flightrec
    loss_fn = gluon.loss.L2Loss()
    net = _make_net()
    ref = _make_net(seed_from=net)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    tr_ref = gluon.Trainer(ref.collect_params(), "sgd",
                           {"learning_rate": 0.1})

    def bad_loss(x, y):
        out = net(x)
        if not autograd.is_recording():  # eager records; the trace does not
            raise ValueError("Mosaic refused the kernel")
        return loss_fn(out, y)

    step = tr.fuse_step(bad_loss)
    assert step.last_trace_error is None
    FS.reset_stats()
    x, y = _batch()
    with pytest.warns(RuntimeWarning,
                      match="ValueError: Mosaic refused") as caught:
        for _ in range(4):
            step(x, y, batch_size=4)
            _eager_step(ref, loss_fn, tr_ref, x, y, 4)
    assert step.last_mode == "fallback:trace-failed"
    assert isinstance(step.last_trace_error, ValueError)
    assert FS.stats()["fallbacks"] == 3       # eager-warming, then 3 failed
    assert len([w for w in caught
                if "trace/compile failed" in str(w.message)]) == 1
    assert any(ev[1] == "fused_step.trace_failed"
               and "Mosaic refused" in ev[6]["error"]
               for ev in flightrec.snapshot())
    assert _params_bitwise(net, ref)          # the fallback still trained


# -- observability -----------------------------------------------------------

def test_counters_surface_in_profiler_metrics():
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    FS.reset_stats()
    for _ in range(3):
        step(x, y, batch_size=4)
    m = profiler.metrics()
    assert m["fused_step"] == FS.stats()
    assert m["fused_step"]["misses"] == 2 and m["fused_step"]["hits"] == 1
    assert "fused_step" in profiler.dumps()


def test_train_step_span_in_gluon_lane(tmp_path):
    import json
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    step(x, y, batch_size=4)  # warm outside the profile
    fn = str(tmp_path / "trace.json")
    profiler.set_config(filename=fn, xprof=False)
    profiler.set_state("run")
    try:
        step(x, y, batch_size=4)
        step(x, y, batch_size=4)
    finally:
        profiler.set_state("stop")
    profiler.dump()
    events = json.load(open(fn))["traceEvents"]
    spans = [e for e in events if e.get("name") == "gluon.train_step"]
    profiler._reset()
    assert spans, "no gluon.train_step span recorded"
    assert all(e["tid"] == profiler.LANES["gluon"] for e in spans)
    assert any(e.get("args", {}).get("mode") == "fused" for e in spans)
    assert all(e.get("args", {}).get("batch_size") == 4 for e in spans)


def test_fused_step_clean_under_lock_detector():
    """Acceptance: fused-step runs under the runtime lock-order detector
    (MXNET_DEBUG_LOCKS) report zero inversions and zero boundary
    violations — the compile happens without any framework lock held."""
    from mxnet_tpu._debug import locktrace
    x, y = _batch()
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
    prev = locktrace.enable()
    locktrace.reset()
    try:
        for _ in range(3):
            step(x, y, batch_size=4)
        assert step.last_mode == "fused"
        r = locktrace.report()
        assert r["inversion_total"] == 0, r
        assert r["boundary_violation_total"] == 0, r
    finally:
        locktrace.reset()
        if not prev:
            locktrace.disable()


def test_env_gate_defaults_on():
    assert os.environ.get("MXNET_GLUON_FUSED_STEP") is None \
        or FS.fused_step_enabled() in (True, False)  # smoke: import-time read
    assert isinstance(FS.fused_step_enabled(), bool)
