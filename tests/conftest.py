"""Test harness config: force an 8-device virtual CPU mesh so multi-chip
sharding paths (DP/TP/PP/CP) are exercised without TPU hardware. Mirrors the
reference's local-process cluster simulation for dist tests
(ref: ci/docker/runtime_functions.sh:1281 launching tools/launch.py -n 7
--launcher local).

The tests run on the CPU, by explicit choice: JAX resolves backends lazily,
so as long as no computation has executed yet this file pins an 8-device
virtual CPU platform in-process — XLA_FLAGS before the CPU client is created,
and the platform via jax.config (the env var alone is too late once jax is
imported). Nothing here ever touches a chip; chip_smoke.py is the chip run.

NOTE: do NOT os.exec-re-exec pytest from here. pytest's fd-level capture is
already active while conftest imports, so an exec'd child inherits fds
pointing at the dead parent's capture tempfiles and every byte of test output
is silently lost (exit code still propagates, which makes it look like an
empty-but-green run).
"""
import os

_WANT_FLAG = "--xla_force_host_platform_device_count"

if _WANT_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " %s=8" % _WANT_FLAG).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Flight-recorder shards written by suites that exercise crash/OOM/leak
# paths land in a session tmpdir, never the working tree (tests that
# assert on shard paths override per-test with monkeypatch).
if "MXTPU_FLIGHTREC_DIR" not in os.environ:
    import tempfile
    os.environ["MXTPU_FLIGHTREC_DIR"] = tempfile.mkdtemp(
        prefix="mxtpu_flightrec_")

# Goodput run manifests (elastic_train_loop opens a run per call) land
# in a session tmpdir, never the working tree (tests that assert on
# manifest paths override per-test with monkeypatch).
if "MXTPU_RUNS_DIR" not in os.environ:
    import tempfile
    os.environ["MXTPU_RUNS_DIR"] = tempfile.mkdtemp(
        prefix="mxtpu_runs_")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as _np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_rngs():
    import random as _pyrandom
    _pyrandom.seed(0)  # image augmenters draw skip/shuffle/crop from it
    _np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-scale tests (realistic-shape mesh "
        "steps, subprocess clusters, full registry sweeps, JPEG "
        "pipelines); always run by default — `-m 'not slow'` is the "
        "quick lane")
