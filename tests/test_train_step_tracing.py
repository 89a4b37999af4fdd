"""The measurement inside the train step (ISSUE 26), at tiny sizes on the
CPU: the ``mx.*`` scopes and the kernel names are in the lowered program and
change nothing that is computed; the wrapper keeps what callers do with the
jit; the step and compile counters, the compile ledger fed by jax.monitoring,
the span helper and the device table read what they say they read."""
import collections
import contextlib
import glob
import importlib

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu._debug import devicetable, flightrec
from mxnet_tpu.parallel import create_mesh
from mxnet_tpu.parallel import transformer as T

SCOPES = ("mx.embed", "mx.layer", "mx.attn_proj", "mx.flash", "mx.attn_out",
          "mx.ffn", "mx.head_ce", "mx.optimizer")
KERNELS = ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv")


def _cfg(**kw):
    base = dict(vocab_size=64, dim=16, n_layers=2, n_heads=4, ffn_hidden=32,
                loss_chunks=2)
    base.update(kw)
    return T.TransformerConfig(**base)


def _gspmd(**kw):
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    init_fn, step_fn = T.make_train_step(_cfg(**kw), mesh, learning_rate=0.1)
    toks = jr.randint(jr.PRNGKey(0), (2, 16), 0, 64)
    return mesh, init_fn, step_fn, toks


@pytest.fixture
def clean():
    profiler._reset()
    yield
    profiler._reset()


# -- names in the program ----------------------------------------------------

@pytest.mark.parametrize("mode", ["gspmd", "pipeline"])
def test_every_scope_is_in_the_lowered_step(mode):
    if mode == "gspmd":
        mesh, init_fn, step_fn, toks = _gspmd()
    else:
        mesh = create_mesh(pp=2, dp=2, sp=2)
        init_fn, step_fn = T.make_train_step(
            _cfg(pp=2, n_microbatch=2, loss_chunks=1), mesh)
        toks = jr.randint(jr.PRNGKey(0), (4, 16), 0, 64)
    with mesh.mesh:
        state = jax.eval_shape(init_fn, jr.PRNGKey(1))
        text = step_fn.lower(state, toks, toks).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope


def test_the_flash_kernels_carry_their_names_in_the_tpu_lowering(
        monkeypatch):
    fa = importlib.import_module("mxnet_tpu.pallas_kernels.flash_attention")
    monkeypatch.setattr(fa, "_use_pallas", lambda *a: True)
    q = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *a: fa.flash_attention(
            *a, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))(
                q, k, v)

    module = jax.export.export(jax.jit(grads), platforms=["tpu"])(
        q, q, q).mlir_module()
    for name in KERNELS:
        assert 'kernel_name = "%s"' % name in module, name


def test_scopes_change_nothing_that_is_computed(monkeypatch):
    """The parent's program is this one with every named_scope a no-op:
    loss and new state agree bit for bit."""
    def run():
        mesh, init_fn, step_fn, toks = _gspmd()
        with mesh.mesh:
            state, loss = step_fn(init_fn(jr.PRNGKey(1)), toks, toks)
        return jax.device_get((loss, state))

    named = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = run()
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(a, b)


# -- the wrapper -------------------------------------------------------------

def test_the_wrapped_step_is_bitwise_the_bare_jit_and_still_lowers():
    mesh, init_fn, step_fn, toks = _gspmd()
    assert isinstance(step_fn, jax.stages.Wrapped)     # jax.export takes it
    with mesh.mesh:
        got = step_fn(init_fn(jr.PRNGKey(1)), toks, toks)
        want = step_fn._jitted(init_fn(jr.PRNGKey(1)), toks, toks)
        state = init_fn(jr.PRNGKey(1))
        compiled = step_fn.lower(state, toks, toks).compile()
        again = compiled(state, toks, toks)
    for a, b, c in zip(*(jax.tree_util.tree_leaves(jax.device_get(t))
                         for t in (got, want, again))):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_steps_compiles_and_retraces_after_a_forced_retrace(clean):
    mesh, init_fn, step_fn, toks = _gspmd()
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(1))
        state, _ = step_fn(state, toks, toks)
        state, _ = step_fn(state, toks, toks)
        wide = jnp.concatenate([toks, toks])       # a second batch shape
        state, _ = step_fn(state, wide, wide)
    got = profiler.metrics()["train_step"]
    assert (got["steps"], got["compiles"], got["retraces"]) == (3, 2, 1)
    assert [c for _, c in got["calls"]] == [True, False, True]
    assert all(us > 0 for us, _ in got["calls"])
    # a steady call is far shorter than one that compiles
    assert got["calls"][1][0] < got["calls"][0][0] / 10


def test_the_ledger_books_the_steps_backend_compile_to_the_step(clean):
    mesh, init_fn, step_fn, toks = _gspmd()
    def outside_any_step(x):
        return x * 3 + 1

    with mesh.mesh:
        state = init_fn(jr.PRNGKey(1))
        jax.jit(outside_any_step)(jnp.ones(3))
        step_fn(state, toks, toks)
    ledger = profiler.metrics()["jax_compile"]
    backend = [e for e in ledger["entries"]
               if e["event"] == "backend_compile_duration"]
    step = [e for e in backend if "step_fn" in e["fun_name"]]
    assert len(step) == 1 and step[0]["in_step"] and step[0]["at_step"] == 1
    assert step[0]["seconds"] > 0
    outside = [e for e in backend if "outside_any_step" in e["fun_name"]]
    assert len(outside) == 1 and not outside[0]["in_step"] \
        and outside[0]["at_step"] == 0
    # totals: by phase, and the three phases together; by function, a fold
    # of the entries; a jit traced inside the step's trace is nested and in
    # no total
    phases = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
              "backend_compile_duration")
    assert ledger["compile_s"] == pytest.approx(
        sum(ledger["seconds"][p] for p in phases))
    assert ledger["compile_s"] == pytest.approx(sum(
        e["seconds"] for e in ledger["entries"] if e["event"] in phases))
    by_fun = collections.defaultdict(collections.Counter)
    for e in ledger["entries"]:
        by_fun[e["fun_name"]][e["event"]] += e["seconds"]
    assert by_fun["step_fn"]["jaxpr_trace_duration"] > 0
    assert ledger["nested"] > 0
    assert [e["fun_name"] for e in ledger["entries"]
            if e["event"] == "jaxpr_trace_duration" and e["in_step"]] \
        == ["step_fn"]


# -- the span helper ---------------------------------------------------------

def test_span_records_through_record_op_when_live_and_nothing_when_not(
        clean, monkeypatch, tmp_path):
    profiler.set_config(filename=str(tmp_path / "p.json"), xprof=False)
    profiler.set_state("run")
    try:
        with profiler.span("t.span", lane="user", args={"k": 1}) as sp:
            pass
        with profiler.step_span("t.step", 7) as st:
            st.args = {"mode": "x"}
    finally:
        profiler.set_state("stop")
        profiler.set_config(filename="profile.json", xprof=True)
    agg = profiler.metrics()["aggregate"]
    assert agg["t.span"]["count"] == 1 and agg["t.step"]["count"] == 1
    assert agg["t.span"]["total_us"] == pytest.approx(sp.dur_us)
    assert st.step_num == 7
    events = {e["name"]: e for e in profiler._events}
    assert events["t.span"]["args"] == {"k": 1}
    assert events["t.span"]["tid"] == profiler.LANES["user"]
    assert events["t.step"]["args"] == {"mode": "x"}
    assert events["t.step"]["tid"] == profiler.LANES["train_step"]
    # neither a profile run nor the flight recorder wants spans: nothing
    monkeypatch.setattr(profiler, "_LIVE", False)
    ring = len(flightrec.RING)
    with profiler.span("t.quiet") as quiet:
        pass
    assert quiet.dur_us > 0
    assert "t.quiet" not in profiler.metrics()["aggregate"]
    assert len(flightrec.RING) == ring


def test_a_step_span_lands_on_the_device_traces_host_plane(clean, tmp_path):
    """With xprof on, the program's span is an event of the ``.xplane.pb``
    (the device trace's clock) and dumps() reads the trace back; a CPU
    trace has no device plane, so there is no table to print."""
    mesh, init_fn, step_fn, toks = _gspmd()
    profiler.set_config(filename=str(tmp_path / "p.json"), xprof=True,
                        xprof_dir=str(tmp_path / "xprof_trace"))
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(1))
        state, _ = step_fn(state, toks, toks)
        profiler.set_state("run")
        try:
            state, loss = step_fn(state, toks, toks)
            float(loss)
        finally:
            profiler.set_state("stop")
            profiler.set_config(filename="profile.json", xprof_dir=None)
    assert profiler._state["xprof_last"] == str(tmp_path / "xprof_trace")
    written = glob.glob(str(tmp_path / "xprof_trace" / "plugins" / "profile"
                            / "*" / "*.xplane.pb"))
    trace = devicetable.load_trace(written[0])
    assert [n for n, _, _ in trace["host"]] == ["mx.train_step"]
    assert profiler.device_table() is None
    assert "Device time by scope" not in profiler.dumps()


# -- the device table --------------------------------------------------------

HLO = '''
HloModule jit_step_fn
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step_fn)/jvp(mx.layer)/while/body/closed_call/mx.ffn/mul"}
}
ENTRY %main {
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jvp(mx.layer)/while/body/closed_call/mx.ffn/bsd,df->bsf/dot_general"}
  %fusion.2 = f32[8] fusion(%b), kind=kLoop, metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/mx.ffn/bsd,df->bsf/dot_general"}
  %fusion.3 = f32[8] fusion(%c), kind=kLoop, metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/rematted_computation/mx.ffn/mul"}
  %mx_flash_dq.4 = bf16[8] custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(mx.layer))/while/body/closed_call/checkpoint/mx.flash/transpose(jvp(mx_flash_dq))/pallas_call"}
  %fusion.5 = f32[8] fusion(%e), kind=kLoop, metadata={op_name="jit(step_fn)/mx.optimizer/sub"}
  %copy.6 = f32[8] copy(%f)
  ROOT %while.7 = (f32[8]) while(%g), condition=%c, body=%b, metadata={op_name="jit(step_fn)/jvp(mx.layer)/while"}
}
'''


def _hand_made_trace(with_op_names=False):
    """Two executions of the step, 1000 ns apart; in the window between
    their starts: forward 100, backward 200, recompute 100 (all mx.ffn),
    the dq kernel 300, the optimizer 50, one unscoped copy 50, and the
    while that contains them (a container: its body's ops are events of
    their own). 800 ns busy of 1000."""
    names = devicetable.scope_map(HLO)
    ops = [("%fusion.1 = f32[8] fusion(%a), kind=kLoop", 0, 100),
           ("%fusion.2 = f32[8] fusion(%b), kind=kLoop", 100, 200),
           ("%fusion.3 = f32[8] fusion(%c), kind=kLoop", 300, 100),
           ("%mx_flash_dq.4 = bf16[8] custom-call(%d)", 400, 300),
           ("%fusion.5 = f32[8] fusion(%e), kind=kLoop", 700, 50),
           ("%copy.6 = f32[8] copy(%f)", 750, 50),
           ("%while.7 = (f32[8]) while(%g), condition=%c", 0, 800),
           ("%fusion.1 = f32[8] fusion(%a), kind=kLoop", 1000, 100)]
    return {
        "devices": {"/device:TPU:0": {
            "ops": [(n, float(s), float(d),
                     names.get(devicetable._instruction(n), "")
                     if with_op_names else "") for n, s, d in ops],
            "modules": [("jit_step_fn(1)", 0.0, 800.0),
                        ("jit_step_fn(1)", 1000.0, 800.0),
                        ("jit_norms(2)", 900.0, 10.0)]}},
        "host": [("mx.train_step", 0.0, 400e3), ("mx.train_step", 5e5, 600e3)],
    }


@pytest.mark.parametrize("where", ["hlo_text", "event_stats"])
def test_device_table_on_a_hand_made_trace(where):
    table = devicetable.device_table(
        _hand_made_trace(with_op_names=where == "event_stats"),
        hlo=HLO if where == "hlo_text" else None)
    ns = lambda v: round(v * 1e9)
    assert table["steps"] == 1 and table["module"] == "jit_step_fn(1)"
    assert ns(table["window_s"]) == 1000
    assert ns(table["busy_s"]) == ns(table["op_sum_s"]) == 800
    rows = {s: {p: ns(v) for p, v in r.items()}
            for s, r in table["rows"].items()}
    assert rows == {
        "mx.ffn": {"forward": 100, "backward": 200, "recompute": 100,
                   "total": 400},
        "mx.flash": {"forward": 0, "backward": 300, "recompute": 0,
                     "total": 300},
        "mx.optimizer": {"forward": 50, "backward": 0, "recompute": 0,
                         "total": 50},
        "unscoped": {"forward": 50, "backward": 0, "recompute": 0,
                     "total": 50}}
    # forward 100 of mx.ffn + the unscoped copy; the optimizer apart
    assert {p: ns(v) for p, v in table["phases"].items()} == {
        "forward": 150, "backward": 500, "recompute": 100, "optimizer": 50}
    assert table["kernels"] == {"mx_flash_dq": {"calls": 1,
                                                "s": pytest.approx(300e-9)}}
    assert table["scoped_share"] == pytest.approx(750 / 800)
    assert table["host_spans"]["mx.train_step"] == {
        "count": 2, "total_us": pytest.approx(1000.0),
        "median_us": pytest.approx(600.0)}
    text = devicetable.format_table(table)
    assert "mx.ffn" in text and "50.00%" in text and "mx_flash_dq" in text


def test_device_table_without_a_device_plane_is_none():
    assert devicetable.device_table({"devices": {}, "host": []}) is None
    assert devicetable.format_table(None) == ""
