"""chipbench/run.py end to end at a tiny size on the CPU: the harness
refuses to report without a chip; with the look for a chip skipped a run
comes out correct, its control (the reference in float8) and each planted
fault come out not correct. Device metrics are never asserted here: a CPU
run has none."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, limits, run  # noqa: E402

TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
SEED = 3000000019       # past 2**31, as the driver's seeds are
DECODER, RESNET = "tiny_decoder-seq128", "tiny_resnet-img64"
NEWKIND = "tiny_newkind-seq128"     # its count is a file of the tiny root


def _run(workload, wrap=None, seconds=0.3):
    import jax
    return run.run_cell(workload, SEED, seconds, False,
                        devices=jax.devices()[:1], wrap=wrap, root=TINY)


def test_without_a_chip_the_benchmark_exits_nonzero_and_prints_no_result(
        capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "baichuan_7b.l5-seq2048", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_an_unknown_workload_is_an_error(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "no_such_cell", "--seed", "1", "--seconds",
                  "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.fixture(scope="module")
def decoder_run():
    return _run(DECODER)


def test_a_tiny_decoder_run_is_correct_and_whole(decoder_run):
    r = decoder_run
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert list(r)[-1] == "compared"        # each number beside its limit
    assert set(r["compared"]) == {"loss1", "loss2", "loss3", "grad_norm_gap",
                                  "grad_norm_gap_med", "delta_norm_gap"}
    assert set(r["not_compared"]) == {"delta_norm_gap_med"}
    assert all(v <= lim for v, lim in r["compared"].values())
    assert set(r["metrics"]) == {"step_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu"   # named for what it is
    json.dumps(r)
    with open(os.path.join(ROOT, "chipbench", "out", "%s.seed%d.steps.json"
                           % (DECODER, SEED))) as f:
        steps = json.load(f)
    # the window IS the sum of the gaps between loss arrivals, and step_ms
    # is the whole window over the steps that finished in it
    assert steps["steps"] == len(steps["gaps_ms"]) == r["attempted"]
    assert sum(steps["gaps_ms"]) == pytest.approx(1e3 * steps["window_s"])
    assert r["metrics"]["step_ms"]["value"] == pytest.approx(
        1e3 * steps["window_s"] / steps["steps"])
    assert steps["window_s"] >= 0.3


def test_the_same_seed_gives_the_same_first_steps(decoder_run):
    again = _run(DECODER, seconds=0.05)
    assert again["compared"] == decoder_run["compared"]


def test_a_kind_whose_count_is_a_file_of_the_tiny_root_is_found(capsys):
    r = _run(NEWKIND, seconds=0.05)
    assert r["correct"] is True and r["attempted"] >= 2
    assert ("%.6g GF a step required (counts/tiny_two_families.py)"
            % (6000 * 4 * 128 / 1e9)) in capsys.readouterr().err


def test_a_configuration_whose_count_has_no_file_fails_before_it_is_built(
        tmp_path):
    import shutil
    root = shutil.copytree(TINY, str(tmp_path / "tiny"))
    path = os.path.join(root, "configs", "tiny_newkind.json")
    with open(path) as f:
        config = json.load(f)
    config["flops"] = "absent_kind"
    with open(path, "w") as f:
        json.dump(config, f)
    with pytest.raises(SystemExit) as e:
        run.run_cell(NEWKIND, SEED, 0.05, False, devices=[], root=root)
    assert os.path.join(root, "counts", "absent_kind.py") in str(e.value)


def _unchanged(cell):
    """A step that returns its state unchanged (the loss is still read)."""
    import jax
    import jax.numpy as jnp
    real = cell.dispatch

    def dispatch(i):
        kept = jax.tree_util.tree_map(jnp.copy, cell.state)
        loss = real(i)
        cell.state = kept
        return loss

    cell.dispatch = dispatch
    return cell


def _half_batch(cell):
    """Half of the batch left out, the mean taken over the rest."""
    cell.batches = [(a[:len(a) // 2], b[:len(b) // 2])
                    for a, b in cell.batches]
    return cell


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_a_broken_timed_path_is_not_correct(fault):
    r = _run(DECODER, wrap=fault, seconds=0.05)
    assert r["correct"] is False
    over = [n for n, (v, lim) in r["compared"].items() if not v <= lim]
    assert "delta_norm_gap" in over or "grad_norm_gap" in over


@pytest.mark.parametrize("workload", [DECODER, RESNET])
def test_the_control_in_float8_is_not_correct(workload):
    import jax
    spec = run.load_cell(workload, TINY)
    got = limits.readings(workload, SEED, ["fp8"], jax.devices()[:1],
                          root=TINY)
    judged = lambda vals: all(v <= spec["limits"][n] for n, v in vals.items()
                              if n not in spec["limits"]["not_compared"])
    assert judged(got["program"])           # the program itself passes ...
    assert not judged(got["fp8"])           # ... its control does not
    assert set(got["fp8"]) == set(got["program"])


def _weights_unchanged(cell):
    """Gluon: every step's new weights are thrown away."""
    real = cell.dispatch

    def dispatch(i):
        kept = {n: p.data()._data.copy() for n, p in cell.params.items()}
        loss = real(i)
        loss.asnumpy()
        for n, p in cell.params.items():
            p.data()._data = kept[n]
        return loss

    cell.dispatch = dispatch
    return cell


def test_a_tiny_resnet_run_through_fuse_step_is_correct():
    r = _run(RESNET, seconds=0.2)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] >= 2 and r["failed"] == 0


def test_a_resnet_step_that_keeps_no_weights_is_not_correct():
    r = _run(RESNET, wrap=_weights_unchanged, seconds=0.05)
    assert r["correct"] is False
    assert r["compared"]["delta_norm_gap_med"][0] == pytest.approx(
        1.0, abs=0.05)


def test_check_numbers_reads_one_for_a_state_left_unchanged():
    ref = {"loss": [1.0], "grad_norm": {"a": 1.0, "b": 2.0},
           "delta_norm": {"a": 0.5, "b": 0.25}}
    prog = dict(ref, delta_norm={"a": 0.0, "b": 0.0})
    vals, _ = check.numbers(prog, ref)
    assert vals["delta_norm_gap"] == pytest.approx(1.0)
