"""mxlint self-enforcement (tools/mxlint; docs/LINTING.md).

Two halves:

* the tier-1 gate: mxlint over the whole tree must report ZERO
  unwaived findings — the PR 1-2 invariants (single dispatch choke
  point, guarded telemetry, locked shared state, API_BEGIN/API_END on
  the C ABI, monotonic trace clocks) stay true by construction, and
* unit coverage of each rule and of the waiver/baseline machinery on
  synthetic inputs, so a rule regression can't silently turn the gate
  into a no-op.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools import mxlint
from tools.mxlint import core, rules

REPO = core.REPO_ROOT


# -- the gate ----------------------------------------------------------------

def test_tree_is_lint_clean():
    """`python -m tools.mxlint mxnet_tpu src tests` — zero unwaived
    violations. If this fails: fix the finding, or waive it with an
    inline justification (docs/LINTING.md)."""
    findings, n_waived, n_baselined, bad = mxlint.run(
        ["mxnet_tpu", "src", "tests"])
    assert bad == [], "waivers without justification:\n%s" % "\n".join(
        map(repr, bad))
    assert findings == [], "unwaived mxlint findings:\n%s" % "\n".join(
        map(repr, findings))
    # the gate must actually be exercising the rules, not skipping files
    assert n_waived > 0


def test_cli_exits_zero_on_tree():
    r = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "mxnet_tpu", "src",
         "tests"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


def test_baseline_is_empty():
    """The checked-in baseline must stay empty: new findings are fixed
    or waived with a reason, never silently baselined."""
    assert core.load_baseline() == []


# -- rule units on synthetic files -------------------------------------------

def _lint_snippet(tmp_path, relpath, src, rule_codes=None):
    """Run mxlint on one synthetic file planted at a scoped repo-relative
    path under tmp_path."""
    target = tmp_path / relpath
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(textwrap.dedent(src))
    prev = core.REPO_ROOT
    core.REPO_ROOT = str(tmp_path)
    try:
        sel = None
        if rule_codes:
            sel = [r for r in rules.ALL_RULES if r.code in rule_codes]
        return mxlint.run([str(target)], rules=sel, baseline=[])
    finally:
        core.REPO_ROOT = prev


def test_mx001_flags_jnp_and_exempts_asarray(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/ndarray/contrib.py", """\
        import jax.numpy as jnp

        def f(x):
            y = jnp.asarray(x)      # conversion: exempt
            return jnp.tanh(y)      # compute: flagged
        """, {"MX001"})
    assert [f.code for f in findings] == ["MX001"]
    assert "tanh" in findings[0].message


def test_mx002_unguarded_vs_guarded(tmp_path):
    findings, n_waived, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/io/thing.py", """\
        from .. import profiler as _profiler

        def bad():
            _profiler.record_op("x", 1.0)

        def good_inline():
            if _profiler._ACTIVE:
                _profiler.record_op("x", 1.0)

        def good_derived(t0):
            if t0 is not None:
                _profiler.account("bytes", 4)
        """, {"MX002"})
    assert len(findings) == 1
    assert findings[0].line == 4


def test_mx003_mutation_lock_and_definition_waiver(tmp_path):
    findings, n_waived, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/sub/mod.py", """\
        import threading

        _LOCK = threading.Lock()
        _GUARDED = {}
        _NAKED = {}
        _DECLARED = {}  # mxlint: disable=MX003 (import-time only)
        _TLS = threading.local()

        def f(k, v):
            with _LOCK:
                _GUARDED[k] = v
            _NAKED[k] = v
            _DECLARED[k] = v
        """, {"MX003"})
    assert len(findings) == 1
    assert "_NAKED" in findings[0].message
    assert n_waived == 1  # _DECLARED via its definition-line waiver


def test_mx004_buf_outside_ndarray(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/helper.py", """\
        def peek(arr):
            return arr._buf
        """, {"MX004"})
    assert [f.code for f in findings] == ["MX004"]


def test_mx005_jit_call_and_decorator(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/newmod.py", """\
        import jax

        fast = jax.jit(lambda x: x)

        @jax.jit
        def g(x):
            return x
        """, {"MX005"})
    assert [f.code for f in findings] == ["MX005", "MX005"]


def test_mx005_call_form_decorator_reported_once(tmp_path):
    """@jax.jit(...) is both a decorator and a Call node — one site,
    one finding."""
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/decmod.py", """\
        import jax

        @jax.jit(static_argnums=(0,))
        def g(n, x):
            return x
        """, {"MX005"})
    assert len(findings) == 1


def test_mx005_sanctioned_module_is_exempt(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/jit.py", """\
        import jax
        fast = jax.jit(lambda x: x)
        """, {"MX005"})
    assert findings == []


def test_mx005_fused_step_module_is_sanctioned(tmp_path):
    """The fused-train-step program cache (ISSUE 4) is a sanctioned jit
    site: its keys are the signature-keyed compile-on-repeat cache on
    each FusedTrainStep, bounded like the dispatch cache."""
    assert "mxnet_tpu/gluon/fused_step.py" in rules._SANCTIONED_JIT
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/gluon/fused_step.py", """\
        import jax
        prog = jax.jit(lambda x: x)
        """, {"MX005"})
    assert findings == []


def test_mx006_missing_and_present_macros(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "src/c_api_extra.cc", """\
        int MXTGood(void** out) {
          API_BEGIN()
          *out = nullptr;
          API_END()
        }

        int MXTBad(void** out) {
          *out = nullptr;
          return 0;
        }
        """, {"MX006"})
    assert len(findings) == 1
    assert "MXTBad" in findings[0].message


def test_mx007_wall_clock(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/io/meter.py", """\
        import time

        def stamp():
            return time.time()
        """, {"MX007"})
    assert [f.code for f in findings] == ["MX007"]


def test_mx008_bare_except(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/engine.py", """\
        def f():
            try:
                return 1
            except:
                return 2
        """, {"MX008"})
    assert [f.code for f in findings] == ["MX008"]


def test_mx009_flags_swallowed_broad_except(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/io/pipe.py", """\
        def f():
            try:
                return 1
            except Exception:
                return 2
        """, {"MX009"})
    assert [f.code for f in findings] == ["MX009"]


def test_mx009_accepts_reraise_and_accounting(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/kvstore_async.py", """\
        from . import profiler as _profiler

        def f():
            try:
                return 1
            except Exception:
                raise
        def g():
            try:
                return 1
            except BaseException:
                if _profiler._ACTIVE:
                    _profiler.account("kvstore.server_errors", 1)
                return 2
        def narrow():
            try:
                return 1
            except (ConnectionError, OSError):
                return 2  # narrow catches are out of scope
        """, {"MX009"})
    assert findings == []


def test_mx010_flags_unguarded_latency_telemetry(tmp_path):
    """record_latency/record_flow in kvstore_async and the fused step
    must sit behind the inlined active guard (ISSUE 6 satellite)."""
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/gluon/fused_step.py", """\
        from .. import profiler as _profiler

        def bad(dur):
            _profiler.record_latency("fused_step.step", dur)

        def bad_flow(fid):
            _profiler.record_flow("ps.push", fid, "s")
        """, {"MX010"})
    assert [f.code for f in findings] == ["MX010", "MX010"]
    assert "record_latency" in findings[0].message


def test_mx010_accepts_inlined_and_derived_guards(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/kvstore_async.py", """\
        from . import profiler as _profiler

        def good_inline(dur):
            if _profiler._ACTIVE:
                _profiler.record_latency("kvstore.pull_rtt", dur)

        def good_derived(t0):
            if t0 is not None:
                _profiler.record_flow("ps.pull", 7, "f")
        """, {"MX010"})
    assert findings == []


def test_mx010_out_of_scope_module_is_exempt(tmp_path):
    """The rule targets the hot request/step paths; cold modules (e.g.
    a tool) may call the primitives unguarded."""
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/callback.py", """\
        from . import profiler as _profiler

        def f(dur):
            _profiler.record_latency("cb", dur)
        """, {"MX010"})
    assert findings == []


def test_mx011_flags_second_hot_path_branch(tmp_path):
    """Flight-recorder records in hot modules must sit under the ONE
    shared guard — a standalone `if _flightrec.ENABLED:` branch (or no
    guard at all) is a second hot-path cost the flightrec_overhead
    budget does not price. Covers both the helper recorders and the
    raw inlined RING.append form."""
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/ndarray/thing.py", """\
        from .._debug import flightrec as _flightrec

        def bad_own_branch(name):
            if _flightrec.ENABLED:
                _flightrec.RING.append(name)

        def bad_unguarded(name, dur):
            _flightrec.record_span(name, dur)

        def bad_marker(name):
            _flightrec.record_marker(name)
        """, {"MX011"})
    assert [f.code for f in findings] == ["MX011"] * 3
    assert sorted(f.line for f in findings) == [5, 8, 11]


def test_mx011_accepts_shared_and_derived_guards(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/ndarray/thing.py", """\
        from .. import profiler as _profiler
        from .._debug import flightrec as _flightrec

        def good_shared(name, t0):
            if _profiler._HOOKS and _profiler._LIVE:
                _flightrec.RING.append(name)

        def good_derived(name, _prof_t0):
            if _prof_t0 is not None:
                _flightrec.RING.append(name)

        def good_helper(name, dur, t0):
            if t0 is not None:
                _flightrec.record_span(name, dur)
        """, {"MX011"})
    assert findings == []


def test_mx011_out_of_scope_module_is_exempt(tmp_path):
    """Cold modules (the dump path itself, tools) may record freely —
    only the hot dispatch/step modules carry the one-guard contract."""
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/callback.py", """\
        from .._debug import flightrec as _flightrec

        def f(name):
            _flightrec.record_marker(name)
        """, {"MX011"})
    assert findings == []


def test_mx012_flags_contractless_kernel_module(tmp_path):
    """A pallas_kernels module without a reference implementation, an
    interpret= path, or a KERNEL_BENCH registration breaks the kernel
    contract threefold."""
    (tmp_path / "mxnet_tpu" / "pallas_kernels").mkdir(parents=True)
    (tmp_path / "mxnet_tpu" / "pallas_kernels" / "__init__.py") \
        .write_text("KERNEL_BENCH = {'other': 'resnet50'}\n")
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/pallas_kernels/shiny.py", """\
        import jax.numpy as jnp

        def shiny_kernel(x):
            return x * 2
        """, {"MX012"})
    assert [f.code for f in findings] == ["MX012"] * 3
    msgs = " ".join(f.message for f in findings)
    assert "reference" in msgs and "interpret" in msgs \
        and "KERNEL_BENCH" in msgs


def test_mx012_accepts_contract_compliant_module(tmp_path):
    (tmp_path / "mxnet_tpu" / "pallas_kernels").mkdir(parents=True)
    (tmp_path / "mxnet_tpu" / "pallas_kernels" / "__init__.py") \
        .write_text("KERNEL_BENCH = {'shiny': 'fused_kernels'}\n")
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/pallas_kernels/shiny.py", """\
        import jax.numpy as jnp

        def shiny_reference(x):
            return x * 2

        def shiny(x, interpret=False):
            return shiny_reference(x)
        """, {"MX012"})
    assert findings == []


def test_mx012_private_helpers_and_init_are_exempt(tmp_path):
    """_compile_attr.py-style private helpers and the package __init__
    are not kernel modules."""
    for rel in ("mxnet_tpu/pallas_kernels/_helper.py",
                "mxnet_tpu/pallas_kernels/__init__.py"):
        findings, _, _, _ = _lint_snippet(
            tmp_path, rel, "X = 1\n", {"MX012"})
        assert findings == [], rel


def test_mx012_real_tree_kernels_registered():
    """Every shipped kernel module appears in KERNEL_BENCH, and the
    campaign kernels map to the fused_kernels gate."""
    from mxnet_tpu import pallas_kernels as pk
    for mod in ("batchnorm_fused", "optimizer_apply",
                "quantized_matmul"):
        assert pk.KERNEL_BENCH[mod] == "fused_kernels"
    for mod in ("flash_attention", "compression", "conv_fused"):
        assert mod in pk.KERNEL_BENCH


def _plant_catalog(tmp_path, points):
    d = tmp_path / "mxnet_tpu" / "_debug"
    d.mkdir(parents=True, exist_ok=True)
    (d / "faultpoint.py").write_text(
        "POINTS = frozenset((%s,))\n"
        % ", ".join("%r" % p for p in points))


def test_mx013_flags_uncataloged_literal(tmp_path):
    _plant_catalog(tmp_path, ["io.known.point"])
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/io/newthing.py", """\
        from .._debug import faultpoint as _faultpoint

        def f(point):
            _faultpoint.check("io.known.point")    # cataloged: ok
            _faultpoint.check("io.typo.point")     # flagged
            _faultpoint.check(point)               # computed: exempt
        """, {"MX013"})
    assert [f.code for f in findings] == ["MX013"]
    assert "io.typo.point" in findings[0].message
    assert findings[0].line == 5


def test_mx013_import_alias_forms(tmp_path):
    """Both import spellings bind the alias the rule tracks."""
    _plant_catalog(tmp_path, ["a.b"])
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/x.py", """\
        import mxnet_tpu._debug.faultpoint as fp

        def f():
            fp.check("a.b")
            fp.check("a.nope")
        """, {"MX013"})
    assert [f.code for f in findings] == ["MX013"]


def test_mx013_scope_excludes_tests():
    rule = next(r for r in rules.ALL_RULES if r.code == "MX013")
    assert rule.scope("mxnet_tpu/io/shard_service.py")
    assert rule.scope("bench.py")
    assert not rule.scope("tests/test_faultpoints.py")
    assert not rule.scope("docs/DATA.md")


def test_mx013_real_catalog_includes_io_points():
    """The rule reads the REAL catalog: the ISSUE 11 io seams are in
    it, so the clean-tree gate genuinely checks the new check() sites."""
    rule = next(r for r in rules.ALL_RULES if r.code == "MX013")
    catalog = rule._catalog()
    for p in ("io.shard.read", "io.record.corrupt",
              "io.worker.decode", "io.service.fetch",
              "kvstore.send", "checkpoint.save"):
        assert p in catalog, p


def test_mx013_covers_health_points(tmp_path):
    """ISSUE 15: the health chaos seam is cataloged (the real
    healthmon.corruption_operand site lints clean) and a typo'd
    `health.*` literal in an instrumented module is flagged."""
    rule = next(r for r in rules.ALL_RULES if r.code == "MX013")
    assert "health.grad.corrupt" in rule._catalog()
    _plant_catalog(tmp_path, ["health.grad.corrupt"])
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/_debug/newhealth.py", """\
        from . import faultpoint as _faultpoint

        def probe():
            _faultpoint.check("health.grad.corrupt")   # cataloged: ok
            _faultpoint.check("health.grad.corrupted")  # flagged
        """, {"MX013"})
    assert [f.code for f in findings] == ["MX013"]
    assert "health.grad.corrupted" in findings[0].message


def test_mx020_flags_direct_sharding_imports(tmp_path):
    """Every import form that bypasses the compat seam is caught: the
    from-import of the module path, the member pull off ``jax``/
    ``jax.experimental``, and the plain ``import jax.sharding``."""
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/parallel/newplan.py", """\
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from jax.experimental import shard_map as smap
        from jax import sharding
        import jax.sharding

        def f():
            return P, shard_map, smap, sharding
        """, {"MX020"})
    assert [f.code for f in findings] == ["MX020"] * 5
    assert "compat" in findings[0].message


def test_mx020_compat_itself_and_routed_imports_pass(tmp_path):
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/parallel/compat.py", """\
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from jax.experimental.shard_map import shard_map
        """, {"MX020"})
    assert findings == []
    findings, _, _, _ = _lint_snippet(
        tmp_path, "mxnet_tpu/parallel/user.py", """\
        import jax
        from .compat import PartitionSpec as P
        from ..parallel.compat import shard_map

        def f(x):
            return jax.jit(lambda y: y)(x)  # mxlint: disable=MX005 (t)
        """, {"MX020"})
    assert findings == []


def test_mx020_scope_is_the_package_not_tests():
    rule = next(r for r in rules.ALL_RULES if r.code == "MX020")
    assert rule.scope("mxnet_tpu/parallel/mesh.py")
    assert rule.scope("mxnet_tpu/gluon/fused_step.py")
    assert not rule.scope("mxnet_tpu/parallel/compat.py")
    assert not rule.scope("tests/test_gspmd_step.py")
    assert not rule.scope("bench.py")


# -- waiver machinery --------------------------------------------------------

def test_waiver_without_reason_is_flagged(tmp_path):
    findings, _, _, bad = _lint_snippet(
        tmp_path, "mxnet_tpu/w.py", """\
        import jax
        fast = jax.jit(lambda x: x)  # mxlint: disable=MX005
        """, {"MX005"})
    assert findings == []  # the waiver still suppresses
    assert len(bad) == 1
    assert bad[0].code == "MX000"


def test_waiver_on_line_above(tmp_path):
    findings, n_waived, _, bad = _lint_snippet(
        tmp_path, "mxnet_tpu/w2.py", """\
        import jax
        # mxlint: disable=MX005 (bounded: single key)
        fast = jax.jit(lambda x: x)
        """, {"MX005"})
    assert findings == [] and bad == [] and n_waived == 1


def test_file_level_waiver(tmp_path):
    findings, n_waived, _, bad = _lint_snippet(
        tmp_path, "mxnet_tpu/ndarray/extra.py", """\
        # mxlint: disable-file=MX001 (whole-file design exemption for test)
        import jax.numpy as jnp

        def a(x):
            return jnp.tanh(x)

        def b(x):
            return jnp.exp(x)
        """, {"MX001"})
    assert findings == [] and bad == [] and n_waived == 2


# -- MX014: traced-ambient-state capture -------------------------------------

_MINI_REGISTRY = """\
def register(name, **kw):
    def _reg(fn):
        return fn
    return _reg
"""


def _plant(tmp_path, rel, src):
    target = tmp_path / rel
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(textwrap.dedent(src))
    return target


def _lint_tree(tmp_path, rule_codes, roots=("mxnet_tpu",)):
    """Run mxlint over a planted synthetic tree (multi-file: the
    dataflow rules need the whole project model)."""
    prev = core.REPO_ROOT
    core.REPO_ROOT = str(tmp_path)
    try:
        sel = [r for r in rules.ALL_RULES if r.code in rule_codes]
        return mxlint.run([str(tmp_path / r) for r in roots],
                          rules=sel, baseline=[])
    finally:
        core.REPO_ROOT = prev


def test_mx014_flags_unregistered_env_read_in_op_body(tmp_path):
    """The PR 9 `_kernel_env_token` bug class as a fixture: an op body
    (trace entry) reads an env var that is NOT in the signature-token
    registry — the compiled path would silently replay the stale value.
    The registered var and the read in plain host code stay clean."""
    _plant(tmp_path, "mxnet_tpu/ops/registry.py", _MINI_REGISTRY)
    _plant(tmp_path, "mxnet_tpu/ndarray/register.py", """\
        def register_signature_token(name, default=""):
            return name

        register_signature_token("MXTPU_GOOD_TOKEN", "1")
        """)
    _plant(tmp_path, "mxnet_tpu/ops/myops.py", """\
        import os

        from ..ops.registry import register

        @register("shiny_op")
        def shiny_op(x):
            if os.environ.get("MXTPU_SHINY_MODE") == "1":   # flagged
                return x * 2
            if os.environ.get("MXTPU_GOOD_TOKEN") == "1":   # registered
                return x * 3
            return x

        def host_only():
            return os.environ.get("MXTPU_SHINY_MODE")       # not traced
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX014"})
    assert [f.code for f in findings] == ["MX014"]
    assert "MXTPU_SHINY_MODE" in findings[0].message
    assert findings[0].path == "mxnet_tpu/ops/myops.py"
    assert findings[0].line == 7


def test_mx014_follows_the_call_graph(tmp_path):
    """The read sits two calls deep behind the entry — per-line rules
    cannot see it; the project-model reachability does."""
    _plant(tmp_path, "mxnet_tpu/ops/registry.py", _MINI_REGISTRY)
    _plant(tmp_path, "mxnet_tpu/ops/helpers.py", """\
        import os

        def leaf_config():
            return os.environ.get("MXTPU_DEEP_KNOB", "0")

        def middle(x):
            return leaf_config()
        """)
    _plant(tmp_path, "mxnet_tpu/ops/myops.py", """\
        from ..ops.registry import register
        from .helpers import middle

        @register("deep_op")
        def deep_op(x):
            return middle(x)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX014"})
    assert [f.code for f in findings] == ["MX014"]
    assert findings[0].path == "mxnet_tpu/ops/helpers.py"
    assert "MXTPU_DEEP_KNOB" in findings[0].message


def test_mx014_flags_clock_rng_and_env_globals(tmp_path):
    _plant(tmp_path, "mxnet_tpu/ops/registry.py", _MINI_REGISTRY)
    _plant(tmp_path, "mxnet_tpu/ops/myops.py", """\
        import os
        import random
        import time

        from ..ops.registry import register

        _MODE = os.environ.get("MXTPU_AMBIENT_MODE", "fast")

        @register("leaky_op")
        def leaky_op(x):
            t = time.perf_counter()         # clock: flagged
            r = random.random()             # host RNG: flagged
            if _MODE == "fast":             # env-derived global: flagged
                return x + t + r
            return x
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX014"})
    assert len(findings) == 3
    msgs = " ".join(f.message for f in findings)
    assert "clock" in msgs and "RNG" in msgs \
        and "MXTPU_AMBIENT_MODE" in msgs


def test_mx014_cross_module_env_global(tmp_path):
    """A traced op body reading ANOTHER module's env-derived global
    (`cfg.FLAG`) is the same stale-replay hazard as a same-module read
    (review regression: dotted attribute refs must resolve)."""
    _plant(tmp_path, "mxnet_tpu/ops/registry.py", _MINI_REGISTRY)
    _plant(tmp_path, "mxnet_tpu/cfg.py", """\
        import os

        FLAG = os.environ.get("MXTPU_CROSS_FLAG", "0")
        """)
    _plant(tmp_path, "mxnet_tpu/ops/myops.py", """\
        from ..ops.registry import register
        from .. import cfg

        @register("crossy_op")
        def crossy_op(x):
            if cfg.FLAG == "1":
                return x * 2
            return x
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX014"})
    assert [f.code for f in findings] == ["MX014"]
    assert "MXTPU_CROSS_FLAG" in findings[0].message
    assert findings[0].path == "mxnet_tpu/ops/myops.py"


def test_mx014_step_fn_and_waiver(tmp_path):
    """Optimizer step_fns are entries; the waiver idiom applies."""
    findings, n_waived, _, _ = _lint_tree(tmp_path, {"MX014"})
    assert findings == []  # empty tree
    _plant(tmp_path, "mxnet_tpu/optimizer/opt.py", """\
        import os

        class Shiny:
            def step_fn(self, w, g, state, lr, wd, rescale):
                # mxlint: disable=MX014 (test waiver: pretend operand)
                knob = os.environ.get("MXTPU_STEP_KNOB", "0")
                return w - lr * g * float(knob)
        """)
    findings, n_waived, _, _ = _lint_tree(tmp_path, {"MX014"})
    assert findings == [] and n_waived == 1


def test_mx014_real_tree_tokens_registered():
    """The real registry carries the kernel-routing tokens AND the
    bucket-plan cap MX014 found on its first whole-tree run; both
    cache-key builders consume the same tuple."""
    from mxnet_tpu.ndarray import register as r
    names = r.signature_token_names()
    for tok in ("MXTPU_NO_PALLAS", "MXTPU_FUSED_BN",
                "MXTPU_QUANT_MATMUL", "MXTPU_FUSED_APPLY",
                "MXTPU_ELASTIC_BUCKET_MB"):
        assert tok in names, tok
    assert len(r.signature_tokens()) == len(names)


def test_signature_tokens_change_dispatch_key(monkeypatch):
    """Flipping a registered token must change the dispatch partial key
    (the runtime contract MX014 enforces statically)."""
    from mxnet_tpu.ndarray import register as r
    before = r.signature_tokens()
    monkeypatch.setenv("MXTPU_ELASTIC_BUCKET_MB", "17")
    after = r.signature_tokens()
    assert before != after


# -- MX015: env contract sync ------------------------------------------------

_DOCS = """\
# Environment variables

| Variable | Default | Meaning |
|---|---|---|
| `MXTPU_DOCUMENTED` | `1` | a documented knob |
| `MXTPU_PORT_FAMILY` | derived | a documented computed-name family |
"""


def test_mx015_direct_environ_and_undocumented(tmp_path):
    _plant(tmp_path, "docs/ENV_VARS.md", _DOCS)
    _plant(tmp_path, "mxnet_tpu/thing.py", """\
        import os

        from .base import getenv as _getenv

        def bad_direct():
            return os.environ.get("MXTPU_DOCUMENTED")    # choke point

        def bad_direct_getenv():
            return os.getenv("MXTPU_DOCUMENTED")         # choke point

        def bad_undocumented():
            return _getenv("MXTPU_MYSTERY_KNOB", "0")    # not in docs

        def good():
            return _getenv("MXTPU_DOCUMENTED", "1")

        def writes_are_fine(v):
            os.environ["MXTPU_DOCUMENTED"] = v
        """)
    _plant(tmp_path, "mxnet_tpu/base.py",
           "def getenv(name, default=None):\n    return None\n")
    findings, _, _, _ = _lint_tree(tmp_path, {"MX015"})
    assert [f.code for f in findings] == ["MX015"] * 3
    msgs = " ".join(f.message for f in findings)
    assert "choke point" in msgs and "MXTPU_MYSTERY_KNOB" in msgs


def test_mx015_dynamic_family_forms(tmp_path):
    _plant(tmp_path, "docs/ENV_VARS.md", _DOCS)
    _plant(tmp_path, "mxnet_tpu/ports.py", """\
        from .base import getenv_dynamic as _getenv_dynamic

        def good(s):
            name = "MXTPU_PORT_FAMILY_%d" % s
            return _getenv_dynamic(name, 0, family="MXTPU_PORT_FAMILY")

        def bad_no_family(s):
            return _getenv_dynamic("MXTPU_PORT_FAMILY_%d" % s, 0)

        def bad_undoc_family(s):
            return _getenv_dynamic("X_%d" % s, 0, family="MXTPU_NOPE")
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX015"})
    assert [f.code for f in findings] == ["MX015", "MX015"]
    msgs = " ".join(f.message for f in findings)
    assert "family" in msgs and "MXTPU_NOPE" in msgs


def test_mx015_resolves_helper_params_through_callers(tmp_path):
    """The watchdog/flightrec idiom: a helper takes the env NAME as a
    parameter. The rule follows the dataflow one level: literals at
    call sites are doc-checked, computed names are flagged AT THE
    CALLER."""
    _plant(tmp_path, "docs/ENV_VARS.md", _DOCS)
    _plant(tmp_path, "mxnet_tpu/helper.py", """\
        from .base import getenv as _getenv

        def _env_float(name, default):
            return float(_getenv(name, "") or default)

        def good():
            return _env_float("MXTPU_DOCUMENTED", 1.0)

        def bad_literal():
            return _env_float("MXTPU_UNDOC_VIA_HELPER", 0.0)

        def bad_computed(suffix):
            return _env_float("MXTPU_" + suffix, 0.0)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX015"})
    assert len(findings) == 2
    by_line = {f.line: f.message for f in findings}
    assert any("MXTPU_UNDOC_VIA_HELPER" in m for m in by_line.values())
    assert any("cannot resolve" in m or "computed env name" in m
               for m in by_line.values())


def test_mx015_real_tree_docs_cover_the_satellite_vars():
    """The env-doc drift the ISSUE names is fixed: the vars MX015 found
    undocumented on its first run have ENV_VARS.md rows (the seventh,
    MXTPU_FLASH_AUTOTUNE, went with its autotuner in PR 27)."""
    with open(os.path.join(REPO, "docs", "ENV_VARS.md"),
              encoding="utf-8") as f:
        doc = f.read()
    for var in ("MXTPU_PS_SECRET", "MXTPU_PS_BARRIER_TIMEOUT",
                "MXTPU_PS_DONE_TIMEOUT", "MXTPU_ASYNC_PS_PORT",
                "MXTPU_NUM_SERVERS",
                "MXNET_OPTIMIZER_AGGREGATION_SIZE"):
        assert "`%s`" % var in doc, var


def test_mx015_waiver_form(tmp_path):
    _plant(tmp_path, "docs/ENV_VARS.md", _DOCS)
    _plant(tmp_path, "mxnet_tpu/thing.py", """\
        import os

        def sanctioned():
            # mxlint: disable=MX015 (test: exempted direct read)
            return os.environ.get("MXTPU_DOCUMENTED")
        """)
    findings, n_waived, _, bad = _lint_tree(tmp_path, {"MX015"})
    assert findings == [] and bad == [] and n_waived == 1


# -- MX016: use-after-donation -----------------------------------------------

_MINI_OPS = """\
from .registry import register

@register("sgd_mom_update", num_inputs=3, inplace=(2,))
def sgd_mom_update(weight, grad, mom, lr=None):
    return weight, mom
"""


def test_mx016_jit_donate_use_after_donation(tmp_path):
    """The synthetic use-after-donate repro: a local jitted program
    donates its args; reading one afterwards is the TPU crash the CPU
    tier-1 suite cannot see."""
    _plant(tmp_path, "mxnet_tpu/repro.py", """\
        import jax

        def train_step(w, s, step):
            jfn = jax.jit(step, donate_argnums=(0, 1))
            new_w, new_s = jfn(w, s)
            stale = w + 1          # flagged: w was donated
            return new_w, new_s, stale

        def clean_step(w, s, step):
            jfn = jax.jit(step, donate_argnums=(0, 1))
            new_w, new_s = jfn(w, s)
            w = new_w              # rebind clears the binding
            return w + 1
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX016"})
    assert [f.code for f in findings] == ["MX016"]
    assert findings[0].line == 6
    assert "'w'" in findings[0].message


def test_mx016_registry_op_alias_donation(tmp_path):
    """Registry `*_update` ops donate their inplace positions. The
    wrapper re-adopts the state arg itself, so reading `mom` after is
    fine — but a PRE-call alias (`.copy()` shares the buffer, O(1))
    goes stale. `.asnumpy()` BEFORE the call is the sanctioned
    snapshot."""
    _plant(tmp_path, "mxnet_tpu/ops/registry.py", _MINI_REGISTRY)
    _plant(tmp_path, "mxnet_tpu/ops/optimizer_ops.py", _MINI_OPS)
    _plant(tmp_path, "mxnet_tpu/user.py", """\
        from . import nd

        def bad(weight, grad, mom):
            snap = mom.copy()                    # buffer share
            nd.sgd_mom_update(weight, grad, mom, lr=0.1)
            return snap                          # flagged: stale

        def good(weight, grad, mom):
            snap = mom.asnumpy()                 # real host snapshot
            nd.sgd_mom_update(weight, grad, mom, lr=0.1)
            return snap, mom                     # mom was re-adopted
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX016"})
    assert [f.code for f in findings] == ["MX016"]
    assert findings[0].line == 6
    assert "'snap'" in findings[0].message


def test_mx016_adopt_fused_clears(tmp_path):
    _plant(tmp_path, "mxnet_tpu/repro2.py", """\
        import jax

        def step(w, s, f, p):
            jfn = jax.jit(f, donate_argnums=(0,))
            new_w = jfn(w, s)
            p._adopt_fused(w)
            return w        # re-adopted: clean
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX016"})
    assert findings == []


def test_mx016_real_tree_is_clean_and_table_parsed():
    """On the real tree the rule runs against the real inplace table
    (sanity: the fused optimizer state ops are in it)."""
    rule = next(r for r in rules.ALL_RULES if r.code == "MX016")
    table = rule._table()
    assert table.get("sgd_mom_update") == (2,)
    assert table.get("adam_update") == (2, 3)


def test_mx016_tuple_unpack_rebind_and_augassign(tmp_path):
    """`w, s = jfn(w, s)` is the documented-clean rebind idiom (no
    finding); `w += 1` after a donation READS the stale buffer even
    though the AST target is Store ctx (review regressions)."""
    _plant(tmp_path, "mxnet_tpu/repro5.py", """\
        import jax

        def clean_tuple_rebind(w, s, f):
            jfn = jax.jit(f, donate_argnums=(0, 1))
            w, s = jfn(w, s)
            return w + s

        def bad_augassign(w, f):
            jfn = jax.jit(f, donate_argnums=(0,))
            out = jfn(w)
            w += 1
            return out
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX016"})
    assert [f.code for f in findings] == ["MX016"]
    assert findings[0].line == 11 and "'w'" in findings[0].message


def test_mx014_subscript_env_read_and_telemetry_globals(tmp_path):
    """os.environ["X"] subscript reads inside a traced function carry
    the name to MX014. The telemetry-module exemption (ISSUE 13: the
    ledger/detector hooks make the whole dump/metrics subsystem LOOK
    trace-reachable) covers all clauses for telemetry modules — their
    ambient state gates what gets recorded, never a traced value —
    while env-derived globals in COMPUTE modules stay checked (the PR 9
    bug class the rule exists for)."""
    _plant(tmp_path, "mxnet_tpu/ops/registry.py", _MINI_REGISTRY)
    _plant(tmp_path, "mxnet_tpu/_debug/telem.py", """\
        import os
        import time

        _MODE = os.environ.get("MXTPU_TELEM_MODE", "0")

        def helper():
            t = time.perf_counter()   # telemetry clock: exempt
            if _MODE == "1":          # telemetry-owned global: exempt
                return t
            return 0.0
        """)
    _plant(tmp_path, "mxnet_tpu/ops/myops.py", """\
        import os

        from ..ops.registry import register
        from .._debug.telem import helper

        _ROUTE = os.environ.get("MXTPU_COMPUTE_ROUTE", "0")

        @register("sub_op")
        def sub_op(x):
            helper()
            if _ROUTE == "1":         # compute-module global: flagged
                x = x + 1
            return x * int(os.environ["MXTPU_SUBSCRIPT_KNOB"])
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX014"})
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2, findings
    assert any("MXTPU_SUBSCRIPT_KNOB" in m for m in msgs)
    assert any("MXTPU_COMPUTE_ROUTE" in m for m in msgs)
    assert not any("MXTPU_TELEM_MODE" in m for m in msgs)
    assert not any("clock" in m for m in msgs)


def test_mx016_rhs_read_of_own_reassignment(tmp_path):
    """`w = w.copy()` after a donation READS the donated buffer on its
    own RHS — the rebind must not clear the poison before the read is
    seen (review regression)."""
    _plant(tmp_path, "mxnet_tpu/repro4.py", """\
        import jax

        def step(w, f):
            jfn = jax.jit(f, donate_argnums=(0,))
            out = jfn(w)
            w = w.copy()
            return out, w

        def rebind_to_result_is_clean(w, f):
            jfn = jax.jit(f, donate_argnums=(0,))
            w = jfn(w)
            return w + 1
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX016"})
    assert [f.code for f in findings] == ["MX016"]
    assert findings[0].line == 6 and "'w'" in findings[0].message


def test_mx016_waiver_form(tmp_path):
    _plant(tmp_path, "mxnet_tpu/repro3.py", """\
        import jax

        def step(w, s, f):
            jfn = jax.jit(f, donate_argnums=(0,))
            new_w = jfn(w, s)
            # mxlint: disable=MX016 (test: deliberate stale read)
            return w
        """)
    findings, n_waived, _, bad = _lint_tree(tmp_path, {"MX016"})
    assert findings == [] and bad == [] and n_waived == 1


# -- MX017: static lock-order graph ------------------------------------------

_CYCLIC_LOCKS = """\
from .._debug.locktrace import named_lock

_A = named_lock("fix.a")
_B = named_lock("fix.b")

def path_one():
    with _A:
        with _B:
            pass

def path_two():
    with _B:
        with _A:
            pass
"""


def test_mx017_flags_cyclic_two_lock_fixture(tmp_path):
    _plant(tmp_path, "mxnet_tpu/sub/locky.py", _CYCLIC_LOCKS)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX017"})
    assert [f.code for f in findings] == ["MX017"]
    assert "fix.a" in findings[0].message \
        and "fix.b" in findings[0].message


def test_mx017_consistent_order_and_self_attr_locks(tmp_path):
    _plant(tmp_path, "mxnet_tpu/sub/locky.py", """\
        from .._debug.locktrace import named_lock

        _A = named_lock("ok.outer")

        class Thing:
            def __init__(self):
                self._lock = named_lock("ok.inner")

            def work(self):
                with _A:
                    with self._lock:
                        pass

            def also(self):
                with _A:
                    with self._lock:
                        pass
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX017"})
    assert findings == []


def test_mx017_cycle_through_three_modules(tmp_path):
    """The graph is global: each module's nesting is locally consistent
    but the union cycles — only a whole-program pass can see it."""
    _plant(tmp_path, "mxnet_tpu/m1.py",
           "from ._debug.locktrace import named_lock\n"
           "A = named_lock('g.a')\nB = named_lock('g.b')\n"
           "def f():\n    with A:\n        with B:\n            pass\n")
    _plant(tmp_path, "mxnet_tpu/m2.py",
           "from ._debug.locktrace import named_lock\n"
           "B = named_lock('g.b')\nC = named_lock('g.c')\n"
           "def f():\n    with B:\n        with C:\n            pass\n")
    _plant(tmp_path, "mxnet_tpu/m3.py",
           "from ._debug.locktrace import named_lock\n"
           "C = named_lock('g.c')\nA = named_lock('g.a')\n"
           "def f():\n    with C:\n        with A:\n            pass\n")
    findings, _, _, _ = _lint_tree(tmp_path, {"MX017"})
    assert len(findings) == 1
    assert "g.a" in findings[0].message


def test_mx017_real_tree_has_no_lexical_nesting():
    """The framework tree deliberately holds at most one named lock per
    lexical scope (matching the runtime detector's zero inversions) —
    the static graph over the real tree has nodes but no edges."""
    model = core.build_model(["mxnet_tpu"])
    assert model.lock_nodes(lambda p: True)
    assert model.lock_graph(lambda p: True) == {}


def test_mx017_waiver_form(tmp_path):
    """A lock-cycle waiver sits on the finding's anchor site (the
    first edge of the cycle in path/line order)."""
    _plant(tmp_path, "mxnet_tpu/sub/locky.py", """\
        from .._debug.locktrace import named_lock

        _A = named_lock("wf.a")
        _B = named_lock("wf.b")

        def path_one():
            with _A:
                # mxlint: disable=MX017 (test: cycle acknowledged)
                with _B:
                    pass

        def path_two():
            with _B:
                with _A:
                    pass
        """)
    findings, n_waived, _, bad = _lint_tree(tmp_path, {"MX017"})
    assert findings == [] and bad == [] and n_waived == 1


# -- --lock-graph CLI + runtime diff -----------------------------------------

def _run_cli(args, cwd=REPO, repo_root=None):
    env = dict(os.environ)
    if repo_root is not None:
        env["MXLINT_REPO_ROOT"] = str(repo_root)
    else:
        env.pop("MXLINT_REPO_ROOT", None)
    return subprocess.run([sys.executable, "-m", "tools.mxlint"] + args,
                          cwd=cwd, capture_output=True, text=True,
                          env=env, timeout=300)


def test_lock_graph_cli_clean_tree():
    r = _run_cli(["--lock-graph"])
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert "profiler.events" in rep["locks"]
    assert rep["static_cycles"] == []


def test_lock_graph_diff_against_runtime_dump(tmp_path):
    """The PR 3 enforcement pair verifies itself: drive the REAL
    framework locks under the runtime detector (the test_locktrace
    suites' setup), dump locktrace.report(), and diff the static graph
    against it — zero cycles, zero ordering contradictions."""
    from mxnet_tpu import profiler
    from mxnet_tpu._debug import locktrace
    import mxnet_tpu as mx

    prev = locktrace.enable()
    locktrace.reset()
    try:
        profiler.set_config(filename=str(tmp_path / "t.json"))
        profiler.set_state("run")
        (mx.nd.array([1.0, 2.0]) * 2).asnumpy()
        profiler.set_state("stop")
        dump = locktrace.report()
        assert dump["acquisitions"] > 0
    finally:
        locktrace.reset()
        if not prev:
            locktrace.disable()
    dump_path = tmp_path / "locktrace.json"
    dump_path.write_text(json.dumps(dump))
    r = _run_cli(["--lock-graph", "--runtime-dump", str(dump_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep["static_cycles"] == [] and rep["runtime_cycles"] == []
    assert rep["contradictions"] == []


def test_lock_graph_diff_detects_contradiction(tmp_path):
    """A runtime dump ordering two locks OPPOSITE to the static graph
    is a contradiction and a non-zero exit."""
    _plant(tmp_path, "mxnet_tpu/locky.py",
           "from ._debug.locktrace import named_lock\n"
           "A = named_lock('d.a')\nB = named_lock('d.b')\n"
           "def f():\n    with A:\n        with B:\n            pass\n")
    dump_path = tmp_path / "rt.json"
    dump_path.write_text(json.dumps({"order_edges": ["d.b->d.a"]}))
    r = _run_cli(["--lock-graph", "--runtime-dump", str(dump_path),
                  str(tmp_path / "mxnet_tpu")], repo_root=tmp_path)
    assert r.returncode == 1, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep["contradictions"]


def test_lock_graph_diff_static_cycle_is_not_a_contradiction(tmp_path):
    """A cycle that exists entirely WITHIN the static graph is a
    static cycle, never a cross-graph contradiction — even when the
    runtime dump adds unrelated edges that change the union-cycle DFS
    entry point (review regression: cycle identity must be by edge
    membership, not node-list spelling)."""
    _plant(tmp_path, "mxnet_tpu/locky.py",
           "from ._debug.locktrace import named_lock\n"
           "A = named_lock('s.a')\nB = named_lock('s.b')\n"
           "def f():\n    with A:\n        with B:\n            pass\n"
           "def g():\n    with B:\n        with A:\n            pass\n")
    dump_path = tmp_path / "rt.json"
    dump_path.write_text(json.dumps({"order_edges": ["s.0->s.b"]}))
    r = _run_cli(["--lock-graph", "--runtime-dump", str(dump_path),
                  str(tmp_path / "mxnet_tpu")], repo_root=tmp_path)
    assert r.returncode == 1  # the static cycle still fails the run
    rep = json.loads(r.stdout)
    assert rep["static_cycles"] and rep["contradictions"] == []


# -- CLI: --format=github, --jobs --------------------------------------------

def test_github_format_annotations(tmp_path):
    _plant(tmp_path, "mxnet_tpu/w.py",
           "import jax\nfast = jax.jit(lambda x: x)\n")
    r = _run_cli(["--format=github", "--rule", "MX005",
                  str(tmp_path / "mxnet_tpu" / "w.py")],
                 repo_root=tmp_path)
    assert r.returncode == 1
    assert "::error file=" in r.stdout and "MX005" in r.stdout


def test_jobs_parallel_matches_serial():
    """--jobs must not change results — identical findings and waiver
    counts on a real subtree (via the CLI: forking inside the test
    process would drag the loaded jax runtime across fork)."""
    serial = _run_cli(["mxnet_tpu/io"])
    par = _run_cli(["--jobs", "2", "mxnet_tpu/io"])
    assert serial.returncode == par.returncode == 0, \
        serial.stdout + par.stdout + serial.stderr + par.stderr
    assert serial.stdout == par.stdout
    assert serial.stderr == par.stderr  # same waived/baselined summary


def test_baseline_suppresses_and_reports(tmp_path):
    target = tmp_path / "mxnet_tpu" / "b.py"
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text("import jax\nfast = jax.jit(lambda x: x)\n")
    prev = core.REPO_ROOT
    core.REPO_ROOT = str(tmp_path)
    try:
        sel = [r for r in rules.ALL_RULES if r.code == "MX005"]
        baseline = [{"code": "MX005", "path": "mxnet_tpu/b.py",
                     "line": 2}]
        findings, _, n_baselined, _ = mxlint.run(
            [str(target)], rules=sel, baseline=baseline)
        assert findings == [] and n_baselined == 1
    finally:
        core.REPO_ROOT = prev


# -- MX018: unledgered device-buffer creation (ISSUE 13) ---------------------

def test_mx018_flags_unledgered_device_put(tmp_path):
    """A device_put in a hot module whose function never reaches a
    storage.ledger_* choke point is anonymous HBM — flagged."""
    _plant(tmp_path, "mxnet_tpu/io/myfeed.py", """\
        import jax

        def place(batch):
            return jax.device_put(batch)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX018"})
    assert [f.code for f in findings] == ["MX018"]
    assert "device_put" in findings[0].message
    assert findings[0].path.endswith("myfeed.py")


def test_mx018_choke_point_in_function_is_clean(tmp_path):
    _plant(tmp_path, "mxnet_tpu/storage.py", """\
        def ledger_register(buf, tag, site=None):
            pass
        """)
    _plant(tmp_path, "mxnet_tpu/io/myfeed.py", """\
        import jax

        from .. import storage as _storage

        def place(batch):
            placed = jax.device_put(batch)
            _storage.ledger_register(placed, "io")
            return placed
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX018"})
    assert findings == []


def test_mx018_registration_one_call_away_is_clean(tmp_path):
    """The choke point may live in a helper one resolvable call away
    (the _ctx_place idiom)."""
    _plant(tmp_path, "mxnet_tpu/storage.py", """\
        def ledger_register(buf, tag, site=None):
            pass
        """)
    _plant(tmp_path, "mxnet_tpu/ndarray/myfactory.py", """\
        import jax

        from .. import storage as _storage

        def _register_io(buf):
            _storage.ledger_register(buf, "io")

        def place(batch):
            placed = jax.device_put(batch)
            _register_io(placed)
            return placed
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX018"})
    assert findings == []


def test_mx018_jnp_asarray_scoped_to_transport_modules(tmp_path):
    """jnp.asarray is a creator only in the transport/input modules —
    and np.asarray (a HOST array) is never one."""
    _plant(tmp_path, "mxnet_tpu/kvstore_async.py", """\
        import jax.numpy as jnp
        import numpy as np

        def pull_decode(host):
            return jnp.asarray(host)

        def host_only(x):
            return np.asarray(x)
        """)
    _plant(tmp_path, "mxnet_tpu/gluon/parameter.py", """\
        import jax.numpy as jnp

        def outside_asarray_scope(x):
            return jnp.asarray(x)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX018"})
    assert len(findings) == 1, findings
    assert findings[0].path.endswith("kvstore_async.py")
    assert "jnp.asarray" in findings[0].message


def test_mx018_waiver_form(tmp_path):
    _plant(tmp_path, "mxnet_tpu/io/myfeed.py", """\
        import jax

        def place(batch):
            # mxlint: disable=MX018 (transient staging buffer: consumed and dropped before the call returns)
            return jax.device_put(batch)
        """)
    findings, _, waived, _ = _lint_tree(tmp_path, {"MX018"})
    assert findings == []


# -- MX019: metrics() provider doc contract ----------------------------------

def test_mx019_flags_undocumented_provider(tmp_path):
    """A registered metrics() section OBSERVABILITY.md never mentions
    is an API nobody can find — flagged at the registration site."""
    _plant(tmp_path, "docs/OBSERVABILITY.md", """\
        # Observability

        The snapshot carries `metrics()['documented']` (counts stuff).
        """)
    _plant(tmp_path, "mxnet_tpu/mymod.py", """\
        from . import profiler as _profiler

        def stats():
            return {}

        _profiler.register_stats_provider("documented", stats)
        _profiler.register_stats_provider("shiny", stats)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX019"})
    assert [f.code for f in findings] == ["MX019"]
    assert "'shiny'" in findings[0].message
    assert findings[0].path == "mxnet_tpu/mymod.py"


def test_mx019_both_quote_styles_and_registration_in_function(tmp_path):
    """The doc may use either quote style, and registrations inside
    functions (the lazy-init idiom) are checked too."""
    _plant(tmp_path, "docs/OBSERVABILITY.md", """\
        `metrics()["lazy"]` — provider registered at first use.
        """)
    _plant(tmp_path, "mxnet_tpu/mymod.py", """\
        from . import profiler as _profiler

        def _install():
            _profiler.register_stats_provider("lazy", dict)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX019"})
    assert findings == []


def test_mx019_computed_name_flagged(tmp_path):
    """A computed section name defeats the doc contract — the checker
    cannot resolve it, so the call site must pass a literal."""
    _plant(tmp_path, "docs/OBSERVABILITY.md", "everything documented\n")
    _plant(tmp_path, "mxnet_tpu/mymod.py", """\
        from . import profiler as _profiler

        def install(name):
            _profiler.register_stats_provider(name, dict)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX019"})
    assert [f.code for f in findings] == ["MX019"]
    assert "computed" in findings[0].message


def test_mx019_no_doc_file_skips_doc_clause(tmp_path):
    """A tree without docs/OBSERVABILITY.md (a planted fixture, a
    vendored subtree) only enforces the literal-name clause."""
    _plant(tmp_path, "mxnet_tpu/mymod.py", """\
        from . import profiler as _profiler

        _profiler.register_stats_provider("anything", dict)
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX019"})
    assert findings == []


def test_mx019_tree_providers_all_documented():
    """The live contract: every provider registered in the real tree
    has its metrics() section documented (the rule found the `io`
    section undocumented on its first run — this pins the fix)."""
    rule = next(r for r in rules.ALL_RULES if r.code == "MX019")
    docs = rule._documented()
    assert docs is not None
    for name in ("elastic", "faults", "flightrec", "fused_step",
                 "goodput", "io", "kvstore_server", "watchdog"):
        assert name in docs, "metrics()[%r] undocumented" % name


# -- MX021: hardware-constant drift ------------------------------------------

_ASSUMPTIONS_FIXTURE = """\
ASSUMPTIONS = {
    "chip": "tpu_v5e",
    "bf16_peak_tflops": 197.0,
    "peak_tflops": {"bf16": 197.0, "f32": 98.5, "int8": 394.0},
    "hbm_bw_GBps": 819.0,
    "dcn_bw_per_host_GBps": 25.0,
    "chips_per_host": 4,
}
"""


def test_mx021_flags_math_and_table_literals(tmp_path):
    """A rate spelled as a literal in modeled math (a BinOp operand)
    or as a lookup-table dict value forks the hardware model."""
    _plant(tmp_path, "benchmark/comm_model.py", _ASSUMPTIONS_FIXTURE)
    _plant(tmp_path, "mxnet_tpu/_debug/roof.py", """\
        def mfu(flops, dur):
            return flops / (dur * 197.0 * 1e12)

        PEAKS = {"v5e": 98.5}
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX021"})
    assert sorted(f.line for f in findings) == [2, 4]
    assert all(f.code == "MX021" for f in findings)
    assert "ASSUMPTIONS" in findings[0].message


def test_mx021_defaults_thresholds_and_other_floats_clean(tmp_path):
    """Only math-context literals fire: argparse-style defaults,
    comparisons, and non-rate floats in arithmetic all stay clean —
    the 25.0 DCN rate colliding with a --median-pct default must
    never page."""
    _plant(tmp_path, "benchmark/comm_model.py", _ASSUMPTIONS_FIXTURE)
    _plant(tmp_path, "mxnet_tpu/_debug/clean.py", """\
        def f(pct=25.0, bw=819.0):
            if pct == 98.5:
                return None
            g(threshold=197.0)
            return pct * 3.0

        def g(threshold=0.0):
            return threshold
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX021"})
    assert findings == []


def test_mx021_comm_model_itself_and_int_keys_exempt(tmp_path):
    """The one home is exempt, and non-rate keys (chips_per_host) do
    not poison the rate set."""
    _plant(tmp_path, "benchmark/comm_model.py", _ASSUMPTIONS_FIXTURE
           + "\nWIRE = 2 * (4 - 1) / 4 * 819.0\n")
    _plant(tmp_path, "mxnet_tpu/_debug/ok.py", "N = 4 * 2\n")
    findings, _, _, _ = _lint_tree(tmp_path, {"MX021"})
    assert findings == []


def test_mx021_no_comm_model_skips(tmp_path):
    """A tree without benchmark/comm_model.py (installed wheel,
    planted fixture) has no rate table — the rule stays silent."""
    _plant(tmp_path, "mxnet_tpu/_debug/roof.py", "X = 2.0 * 197.0\n")
    findings, _, _, _ = _lint_tree(tmp_path, {"MX021"})
    assert findings == []


def test_mx021_real_tree_rates_parsed_and_clean():
    """The live contract: the real ASSUMPTIONS table parses into the
    expected rate set, and the rule's full real scope (which includes
    bench.py and tools/ — wider than the default lint paths) is clean.
    First run caught bench.py's hardcoded v5e 197.0 — this pins the
    fix."""
    rule = next(r for r in rules.ALL_RULES if r.code == "MX021")
    rates = rule._rates()
    for v in (197.0, 98.5, 394.0, 819.0, 180.0, 25.0):
        assert v in rates, "rate %r missing from parsed table" % v
    findings, _, _, _ = mxlint.run(
        ["bench.py", "benchmark", "tools", "mxnet_tpu"],
        rules=[rule], baseline=[])
    assert findings == [], "\n".join(map(repr, findings))


# -- MX022: jit sites invisible to the compile registry ----------------------

def test_mx022_flags_unregistered_jit(tmp_path):
    """A jax.jit in a hot module that never reaches record_compile is
    an unattributable compile — flagged at the jit site."""
    findings, _, _, _ = _lint_tree(tmp_path, {"MX022"}, roots=(
        _plant(tmp_path, "mxnet_tpu/gluon/block.py", """\
            import jax

            def build(fn):
                return jax.jit(fn)
            """),))
    assert [f.code for f in findings] == ["MX022"]
    assert "record_compile" in findings[0].message
    assert findings[0].path == "mxnet_tpu/gluon/block.py"


def test_mx022_probe_and_caller_registration_clean(tmp_path):
    """Both sanctioned shapes pass: the one-shot _compile_probe nested
    closure, and a direct caller recording on the builder's behalf
    (the fused_step._dispatch -> _build shape)."""
    _plant(tmp_path, "mxnet_tpu/optimizer/optimizer.py", """\
        import jax
        from .. import profiler as _profiler

        def _jitted(fn):
            jf = jax.jit(fn)
            def probe(*a):
                out = jf(*a)
                _profiler.record_compile("optimizer", dur_us=1.0)
                return out
            return probe
        """)
    _plant(tmp_path, "mxnet_tpu/parallel/train.py", """\
        import functools
        import jax
        from .. import profiler as _profiler

        def _build():
            return functools.partial(jax.jit)(lambda x: x)

        def _dispatch():
            f = _build()
            _profiler.record_compile("step", dur_us=1.0)
            return f
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX022"})
    assert findings == [], "\n".join(map(repr, findings))


def test_mx022_scoped_to_hot_modules_and_waivable(tmp_path):
    """Out-of-scope modules never fire; in-scope bench jits carry an
    inline waiver naming who accounts the compile."""
    _plant(tmp_path, "mxnet_tpu/metric.py", """\
        import jax

        def m(fn):
            return jax.jit(fn)
        """)
    _plant(tmp_path, "mxnet_tpu/pallas_kernels/tune.py", """\
        import jax

        def bench(fn):
            @jax.jit  # mxlint: disable=MX022 (micro-bench: the autotuner times this compile itself)
            def many(x):
                return fn(x)
            return many
        """)
    findings, n_waived, _, _ = _lint_tree(tmp_path, {"MX022"})
    assert findings == []
    assert n_waived == 1


def test_mx022_from_jax_import_jit_detected(tmp_path):
    """The `from jax import jit` spelling resolves through imports —
    the rule keys on the resolved target, not the literal text."""
    findings, _, _, _ = _lint_tree(tmp_path, {"MX022"}, roots=(
        _plant(tmp_path, "mxnet_tpu/ndarray/register.py", """\
            from jax import jit as _jit

            def dispatch(fn):
                return _jit(fn)
            """),))
    assert [f.code for f in findings] == ["MX022"]


# -- MX023: zero-badput knob contract (ISSUE 19) -----------------------------

_ZB_DOCS = """\
# Environment variables

| Variable | Default | Meaning |
|---|---|---|
| `MXTPU_CKPT_ASYNC` | `0` | async snapshot-then-persist checkpoints |
| `MXTPU_COMPILE_CACHE_DIR` | unset | persistent AOT compile cache dir |
| `MXTPU_PEER_SNAPSHOT_EVERY` | `1` | peer-snapshot publish cadence |
"""

_ZB_REGISTER = """\
def register_signature_token(name, default=""):
    return name

register_signature_token("MXTPU_CKPT_ASYNC", "0")
"""


def _plant_zb_tree(tmp_path, module_rel, body):
    _plant(tmp_path, "docs/ENV_VARS.md", _ZB_DOCS)
    _plant(tmp_path, "mxnet_tpu/ndarray/register.py", _ZB_REGISTER)
    _plant(tmp_path, "mxnet_tpu/base.py",
           "def getenv(name, default=None):\n    return None\n")
    _plant(tmp_path, module_rel, body)


def test_mx023_doc_and_token_clauses(tmp_path):
    """One read per contract shape in a zero-badput module: documented
    + registered is clean, documented-but-unregistered trips the token
    clause, an unknown knob trips both, a _CADENCE_ONLY knob needs no
    token, and a knob outside the owned prefixes is not this rule's
    business (MX015 already covers its doc half)."""
    _plant_zb_tree(tmp_path, "mxnet_tpu/gluon/compile_cache.py", """\
        from ..base import getenv as _getenv

        def doc_and_registered():
            return _getenv("MXTPU_CKPT_ASYNC", "0")        # clean

        def documented_not_registered():
            return _getenv("MXTPU_COMPILE_CACHE_DIR", "")  # token clause

        def neither():
            return _getenv("MXTPU_PEER_MAGIC", "0")        # both clauses

        def cadence_only():
            return _getenv("MXTPU_PEER_SNAPSHOT_EVERY", "1")  # clean

        def not_owned():
            return _getenv("MXTPU_UNRELATED_KNOB", "0")    # not ours
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX023"})
    assert [f.code for f in findings] == ["MX023"] * 3
    msgs = " ".join(f.message for f in findings)
    assert "MXTPU_COMPILE_CACHE_DIR" in msgs
    assert "MXTPU_PEER_MAGIC" in msgs
    assert "MXTPU_UNRELATED_KNOB" not in msgs
    assert "MXTPU_PEER_SNAPSHOT_EVERY" not in msgs
    # the unknown knob owes both halves: docs row AND token
    magic = [f for f in findings if "MXTPU_PEER_MAGIC" in f.message]
    assert len(magic) == 2


def test_mx023_scoped_to_zero_badput_modules(tmp_path):
    """The same undocumented/unregistered read OUTSIDE the
    checkpoint/cache/peer plane is not flagged by MX023."""
    _plant_zb_tree(tmp_path, "mxnet_tpu/thing.py", """\
        from .base import getenv as _getenv

        def elsewhere():
            return _getenv("MXTPU_PEER_MAGIC", "0")
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX023"})
    assert findings == []


def test_mx023_real_tree_knobs_hold_the_contract():
    """The shipped knobs honor what the rule enforces: ENV_VARS.md rows
    and signature-token registrations for the graph-shaping three, with
    the cadence knob documented but deliberately token-free."""
    from mxnet_tpu.ndarray import register as r
    with open(os.path.join(REPO, "docs", "ENV_VARS.md"),
              encoding="utf-8") as f:
        doc = f.read()
    tokens = r.signature_token_names()
    for var in ("MXTPU_CKPT_ASYNC", "MXTPU_CKPT_DELTA",
                "MXTPU_COMPILE_CACHE_DIR", "MXTPU_PEER_RESTORE"):
        assert "`%s`" % var in doc, var
        assert var in tokens, var
    assert "`MXTPU_PEER_SNAPSHOT_EVERY`" in doc
    assert "MXTPU_PEER_SNAPSHOT_EVERY" not in tokens


# -- MX024: wire-opcode contract (ISSUE 20) ----------------------------------

_OPCODE_DOCS = """\
# Resilience

| Opcode | # | Resend-safe | Fields / notes |
|---|---|---|---|
| `_OP_GOOD` | 1 | yes | documented |
| `_OP_UNDISPATCHED` | 3 | no | documented but no handler arm |
| `_OP_COMPUTED` | 4 | no | documented but value is computed |
"""


def _plant_wire_tree(tmp_path, body, docs=_OPCODE_DOCS):
    _plant(tmp_path, "docs/RESILIENCE.md", docs)
    return _plant(tmp_path, "mxnet_tpu/kvstore_async.py", body)


def test_mx024_literal_dispatch_and_doc_clauses(tmp_path):
    """One opcode per contract shape: literal+dispatched+documented is
    clean; undocumented trips the doc clause; undispatched trips the
    dispatch clause; a computed value trips the literal clause. The
    _OP_NAMES display map is never an opcode."""
    _plant_wire_tree(tmp_path, """\
        _OP_GOOD = 1
        _OP_UNDOC = 2
        _OP_UNDISPATCHED = 3
        _OP_COMPUTED = _OP_GOOD + 100
        _OP_NAMES = {_OP_GOOD: "good"}

        class AsyncPSServer:
            def _handle(self, conn, buf):
                op = buf[0]
                if op == _OP_GOOD:
                    return 1
                elif op == _OP_UNDOC:
                    return 2
                elif op == _OP_COMPUTED:
                    return 4
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX024"})
    assert all(f.code == "MX024" for f in findings)
    msgs = {f.message.split()[2]: [] for f in findings}
    for f in findings:
        msgs[f.message.split()[2]].append(f.message)
    assert "_OP_GOOD" not in msgs
    assert "_OP_NAMES" not in msgs
    assert len(msgs["_OP_UNDOC"]) == 1
    assert "RESILIENCE.md" in msgs["_OP_UNDOC"][0]
    assert len(msgs["_OP_UNDISPATCHED"]) == 1
    assert "_handle" in msgs["_OP_UNDISPATCHED"][0]
    assert len(msgs["_OP_COMPUTED"]) == 1
    assert "literal" in msgs["_OP_COMPUTED"][0]


def test_mx024_dispatch_must_be_in_handle(tmp_path):
    """A comparison in some *other* method does not satisfy the
    dispatch clause — the contract is the server's _handle arm."""
    _plant_wire_tree(tmp_path, """\
        _OP_GOOD = 1

        class AsyncPSServer:
            def _handle(self, conn, buf):
                return None

            def _replay_record(self, buf):
                if buf[0] == _OP_GOOD:
                    return 1
        """)
    findings, _, _, _ = _lint_tree(tmp_path, {"MX024"})
    assert [f.code for f in findings] == ["MX024"]
    assert "_handle" in findings[0].message


def test_mx024_scoped_to_wire_module(tmp_path):
    """_OP_* constants in any other module are not this rule's
    business — the wire protocol lives in kvstore_async.py alone."""
    _plant(tmp_path, "docs/RESILIENCE.md", _OPCODE_DOCS)
    _plant(tmp_path, "mxnet_tpu/other.py", "_OP_ROGUE = object()\n")
    findings, _, _, _ = _lint_tree(tmp_path, {"MX024"})
    assert findings == []


def test_mx024_real_tree_opcode_table_is_complete():
    """The shipped protocol honors the contract: every _OP_* constant
    in kvstore_async.py is an int literal, dispatched in _handle, and
    documented in the RESILIENCE.md opcode table — including the
    ISSUE 20 fence_epoch/preempt_notice pair."""
    import re as _re
    import mxnet_tpu.kvstore_async as kva
    with open(os.path.join(REPO, "docs", "RESILIENCE.md"),
              encoding="utf-8") as f:
        doc_ops = set(_re.findall(r"`(_OP_[A-Z0-9_]+)`", f.read()))
    declared = [n for n in dir(kva)
                if n.startswith("_OP_") and n != "_OP_NAMES"]
    assert "_OP_EPOCH" in declared and "_OP_PREEMPT" in declared
    for name in declared:
        assert isinstance(getattr(kva, name), int), name
        assert name in doc_ops, "%s missing from RESILIENCE.md" % name
