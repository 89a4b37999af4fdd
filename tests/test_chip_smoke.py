"""chip_smoke.py's control flow and checks, at toy sizes on the CPU.

The script itself refuses to run without a TPU (asserted here); its phases
are functions of their sizes, so the same checks — step modes, counters,
donation read-back, save/load, kernel-vs-reference, loss falling, spread
over devices — run here on the virtual CPU mesh with the kernels in
interpret mode. The chip run is the builder's and the driver's.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY_TRANSFORMER = dict(dim=32, heads=2, ffn=64, vocab=64, seq=128,
                       batch=4, layers=1, loss_chunks=2, dtype="float32",
                       require_mosaic=False)


def tiny_net(classes):
    """Deferred-init Dense -> BatchNorm (aux moving stats) -> Dense."""
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.BatchNorm(), nn.Activation("relu"),
            nn.Dense(classes))
    return net


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable,
                          os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_last_line_is_the_contract_object_and_nothing_more(capsys):
    """The driver refuses a last line with any key beyond ok/device."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke.report(device, {"phases": {"A": {"ok": True}},
                               "total_s": 1.0})
    record, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": device}
    assert list(json.loads(last)["device"]) == ["platform", "kind", "count"]
    assert json.loads(record)["phases"] == {"A": {"ok": True}}


def test_phase_a_fused_step_and_donation_checks():
    rec = chip_smoke.phase_a("cpu", make_net=tiny_net, classes=4, image=4,
                             batch=4, dtype="float32", steps=4)
    assert rec["modes"] == ["eager-warming", "compile", "fused", "fused"]
    assert rec["modes_after_load"] == ["eager-warming", "compile", "fused"]
    assert rec["fused_step"]["fallbacks"] == 0
    assert rec["loss_last"] < rec["loss_first"]


def test_phase_a_fails_on_a_fallback(monkeypatch):
    """A step that falls back (here: a trace that raises) fails the
    phase with the stored exception in the message."""
    from mxnet_tpu.gluon import fused_step as fs

    def boom(self, *a, **k):
        raise RuntimeError("compiler said no")

    monkeypatch.setattr(fs.FusedTrainStep, "_build", boom)
    with pytest.warns(RuntimeWarning, match="compiler said no"):
        with pytest.raises(AssertionError, match="compiler said no"):
            chip_smoke.phase_a("cpu", make_net=tiny_net, classes=4,
                               image=4, batch=4, dtype="float32", steps=3)


def test_phase_b_kernels_against_references():
    rec = chip_smoke.phase_b(
        interpret=True, flash_shape=(2, 1, 128, 64), flash_long_shape=None,
        flash_cell_shapes=(),
        flash_dtype="float32", bn_shapes=(((128, 128), "bfloat16"),),
        qmm_shape=(32, 128, 128), twobit_n=2048)
    assert len(rec["kernels"]) == 4
    assert all("max_rel_err" in k for k in rec["kernels"].values())


def test_phase_b_fails_when_the_kernel_is_wrong(monkeypatch):
    import mxnet_tpu.pallas_kernels as PK
    monkeypatch.setattr(PK, "dequantize_2bit",
                        lambda w, n, **kw: PK.dequantize_2bit_jnp(w, n) + 1)
    with pytest.raises(AssertionError, match="quantize_2bit"):
        chip_smoke.phase_b(interpret=True, flash_shape=None,
                           flash_long_shape=None, flash_cell_shapes=(),
                           bn_shapes=(),
                           qmm_shape=None, twobit_n=2048)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs four virtual devices")
def test_phase_d_four_devices():
    rec = chip_smoke.phase_d(
        "cpu", n=4, make_net=tiny_net, classes=4, image=4, batch=8,
        dtype="float32", dense=(16, 32, 8), transformer=TOY_TRANSFORMER)
    assert rec["ok"] and rec["fused_step"]["fallbacks"] == 0
    assert rec["dp2xtp2_gspmd"]["matched_step_shardings"] is True
    # phase C's own record, here on the dp2 x tp2 mesh; on the CPU
    # attention takes the jnp reference, which the chip run refuses
    # (require_mosaic) and this record shows
    tr = rec["transformer"]
    assert tr["mesh"] == {"dp": 2, "tp": 2}
    assert tr["losses"][-1] < tr["losses"][0]
    assert tr["mosaic_in_hlo"] is False
