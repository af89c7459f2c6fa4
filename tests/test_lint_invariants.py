"""tools/lint_invariants.py: the Python-side AST lint (ISSUE 10).

Every rule is exercised by a violating fixture AND its allow-escape; the
final test runs the lint over the real package, which must be clean —
the same gate CI runs next to ruff.
"""

import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "det_lint_invariants", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "lint_invariants.py"))
lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lint)


def _lint_src(tmp_path, src: str, rel: str):
    p = tmp_path / "fixture.py"
    p.write_text(src)
    return lint.lint_file(str(p), rel=rel)


PKG = "distributed_embeddings_tpu"


# ------------------------------------------------------ naked-collective
def test_naked_collective_flagged(tmp_path):
    src = (
        "from jax import lax\n"
        "import jax\n"
        "def f(x):\n"
        "    y = lax.all_to_all(x, 'mp', 0, 0)\n"
        "    z = jax.lax.all_gather(y, 'mp')\n"
        "    w = lax.psum_scatter(z, 'mp')\n"
        "    return lax.ppermute(w, 'mp', [(0, 1)])\n"
        "    # lax.psum is fine (accumulation, not an exchange)\n"
    )
    fs = _lint_src(tmp_path, src,
                   rel=os.path.join(PKG, "schedule", "other.py"))
    assert [f.rule for f in fs] == ["naked-collective"] * 4
    assert fs[0].line == 4


def test_naked_collective_allowed_in_wire_and_by_escape(tmp_path):
    src = ("from jax import lax\n"
           "def f(x):\n"
           "    return lax.all_to_all(x, 'mp', 0, 0)\n")
    # the seam module itself is exempt
    assert _lint_src(tmp_path, src,
                     rel=os.path.join(PKG, "ops", "wire.py")) == []
    escaped = ("from jax import lax\n"
               "def f(x):\n"
               "    # lint: allow(naked-collective)\n"
               "    return lax.all_to_all(x, 'mp', 0, 0)\n")
    assert _lint_src(tmp_path, escaped,
                     rel=os.path.join(PKG, "ops", "other.py")) == []
    same_line = ("from jax import lax\n"
                 "def f(x):\n"
                 "    return lax.all_to_all(x, 'mp', 0, 0)"
                 "  # lint: allow(naked-collective)\n")
    assert _lint_src(tmp_path, same_line,
                     rel=os.path.join(PKG, "ops", "other.py")) == []
    # psum / all_reduce-style accumulations are NOT exchange collectives
    psum = ("from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'mp')\n")
    assert _lint_src(tmp_path, psum,
                     rel=os.path.join(PKG, "ops", "other.py")) == []


def test_naked_collective_from_import_and_alias_forms(tmp_path):
    """from-imports and module aliases cannot evade the rule."""
    rel = os.path.join(PKG, "layers", "x.py")
    fi = ("from jax.lax import all_to_all\n"
          "def f(x):\n"
          "    return all_to_all(x, 'mp', 0, 0)\n")
    assert [f.rule for f in _lint_src(tmp_path, fi, rel=rel)] == \
        ["naked-collective"]
    aliased = ("from jax.lax import all_gather as ag\n"
               "def f(x):\n"
               "    return ag(x, 'mp')\n")
    assert [f.rule for f in _lint_src(tmp_path, aliased, rel=rel)] == \
        ["naked-collective"]
    mod_alias = ("import jax.lax as jl\n"
                 "def f(x):\n"
                 "    return jl.psum_scatter(x, 'mp')\n")
    assert [f.rule for f in _lint_src(tmp_path, mod_alias, rel=rel)] == \
        ["naked-collective"]
    from_jax = ("from jax import lax as l2\n"
                "def f(x):\n"
                "    return l2.ppermute(x, 'mp', [(0, 1)])\n")
    assert [f.rule for f in _lint_src(tmp_path, from_jax, rel=rel)] == \
        ["naked-collective"]
    # a NON-collective from jax.lax stays fine
    ok = ("from jax.lax import psum\n"
          "def f(x):\n"
          "    return psum(x, 'mp')\n")
    assert _lint_src(tmp_path, ok, rel=rel) == []
    # the ragged exchange op is an exchange collective too
    ragged = ("from jax import lax\n"
              "def f(x, o, a, b, c, d):\n"
              "    return lax.ragged_all_to_all(x, o, a, b, c, d,"
              " axis_name='mp')\n")
    assert [f.rule for f in _lint_src(tmp_path, ragged, rel=rel)] == \
        ["naked-collective"]


def test_wallclock_from_import_forms(tmp_path):
    rel = os.path.join(PKG, "ops", "x.py")
    fi = ("from time import time\n"
          "def f():\n"
          "    return time()\n")
    assert [f.rule for f in _lint_src(tmp_path, fi, rel=rel)] == \
        ["wallclock-in-jit"]
    dt = ("from datetime import datetime as dt\n"
          "def f():\n"
          "    return dt.now()\n")
    assert [f.rule for f in _lint_src(tmp_path, dt, rel=rel)] == \
        ["wallclock-in-jit"]
    # an unrelated object with a .time() method is NOT a wall clock
    ok = ("def f(profiler):\n"
          "    return profiler.time()\n")
    assert _lint_src(tmp_path, ok, rel=rel) == []


# ----------------------------------------------------- hot-params-access
def test_hot_params_access_flagged(tmp_path):
    src = ("def f(params):\n"
           "    return params['hot'][0]\n")
    fs = _lint_src(tmp_path, src,
                   rel=os.path.join(PKG, "utils", "other.py"))
    assert [f.rule for f in fs] == ["hot-params-access"]


def test_hot_params_access_owners_and_escape(tmp_path):
    src = ("def f(params):\n"
           "    return params['hot']\n")
    for owner in (os.path.join(PKG, "layers", "dist_model_parallel.py"),
                  os.path.join(PKG, "ops", "sparse_update.py")):
        assert _lint_src(tmp_path, src, rel=owner) == []
    escaped = ("def f(params):\n"
               "    return params['hot']  # lint: allow(hot-params-access)\n")
    assert _lint_src(tmp_path, escaped,
                     rel=os.path.join(PKG, "serving", "engine.py")) == []
    # a docstring MENTIONING params['hot'] is not an access
    doc = '"""docs about params["hot"] live here"""\n'
    assert _lint_src(tmp_path, doc,
                     rel=os.path.join(PKG, "utils", "checkpoint.py")) == []


# ------------------------------------------------------ wallclock-in-jit
def test_wallclock_in_jit_flagged(tmp_path):
    src = ("import time, datetime\n"
           "def f():\n"
           "    t = time.time()\n"
           "    d = datetime.datetime.now()\n"
           "    return t, d\n")
    fs = _lint_src(tmp_path, src,
                   rel=os.path.join(PKG, "ops", "fancy_kernel.py"))
    assert [f.rule for f in fs] == ["wallclock-in-jit"] * 2


def test_wallclock_outside_jit_modules_ok(tmp_path):
    src = ("import time\n"
           "def f():\n"
           "    return time.time()\n")
    # store/ and utils/ are host-side: publish timestamps etc. are fine
    for rel in (os.path.join(PKG, "store", "table_store.py"),
                os.path.join(PKG, "utils", "metrics.py"),
                os.path.join("tools", "some_tool.py")):
        assert _lint_src(tmp_path, src, rel=rel) == []
    escaped = ("import time\n"
               "def f():\n"
               "    return time.time()  # lint: allow(wallclock-in-jit)\n")
    assert _lint_src(tmp_path, escaped,
                     rel=os.path.join(PKG, "parallel", "staging.py")) == []


# ------------------------------------------------------------- plumbing
# ---------------------------------------------------- shadow-metric
def test_shadow_metric_flagged_outside_obs(tmp_path):
    src = (
        "from distributed_embeddings_tpu.utils.metrics import "
        "LatencyHistogram\n"
        "from distributed_embeddings_tpu.obs import registry as r\n"
        "from collections import Counter\n"
        "h = LatencyHistogram()\n"
        "c = r.Counter('x', {})\n"
        "g = r.Gauge('y', {})\n"
        "ok = Counter([1, 2])\n"          # collections.Counter untouched
    )
    fs = _lint_src(tmp_path, src,
                   rel=os.path.join(PKG, "serving", "other.py"))
    assert [f.rule for f in fs] == ["shadow-metric"] * 3
    assert [f.line for f in fs] == [4, 5, 6]


def test_shadow_metric_alias_and_deep_import_forms(tmp_path):
    src = (
        "from distributed_embeddings_tpu.obs.registry import "
        "LatencyHistogram as LH\n"
        "import distributed_embeddings_tpu.obs.registry as reg\n"
        "a = LH()\n"
        "b = reg.Gauge('g', {})\n"
    )
    fs = _lint_src(tmp_path, src,
                   rel=os.path.join(PKG, "store", "other.py"))
    assert [f.rule for f in fs] == ["shadow-metric"] * 2


def test_shadow_metric_allowed_in_obs_and_by_escape(tmp_path):
    src = (
        "from distributed_embeddings_tpu.utils.metrics import "
        "LatencyHistogram\n"
        "h = LatencyHistogram()\n"
    )
    # anywhere under obs/ is the sanctioned construction home
    assert _lint_src(tmp_path, src,
                     rel=os.path.join(PKG, "obs", "registry.py")) == []
    assert _lint_src(tmp_path, src,
                     rel=os.path.join(PKG, "obs", "spans.py")) == []
    escaped = (
        "from distributed_embeddings_tpu.utils.metrics import "
        "LatencyHistogram\n"
        "h = LatencyHistogram()  # lint: allow(shadow-metric)\n"
    )
    assert _lint_src(tmp_path, escaped,
                     rel=os.path.join(PKG, "serving", "other.py")) == []
    # registry USE is exactly what the rule steers toward: never flagged
    use = (
        "def f(reg):\n"
        "    reg.histogram('serve/request_seconds').record(0.01)\n"
        "    reg.counter('n').inc()\n"
    )
    assert _lint_src(tmp_path, use,
                     rel=os.path.join(PKG, "serving", "other.py")) == []


# --------------------------------------------------------- upward-import
@pytest.mark.parametrize("src", [
    "from distributed_embeddings_tpu.obs.registry import default_registry\n",
    "from distributed_embeddings_tpu.obs import trace\n",
    "from distributed_embeddings_tpu import obs\n",
    "import distributed_embeddings_tpu.store.table_store\n",
    "from ..schedule import lookahead\n",
    "def f():\n    from ..fleet.admission import AdmissionController\n",
    "from .. import analysis\n",
])
def test_upward_import_flagged(tmp_path, src):
    """A planted import of a package above the hot path is caught in
    every import form, at module level and inside a function; the same
    line outside ops/, parallel/, layers/ is nobody's business."""
    for low in ("ops", "parallel", "layers"):
        fs = _lint_src(tmp_path, src, rel=os.path.join(PKG, low, "x.py"))
        assert [f.rule for f in fs] == ["upward-import"], (low, fs)
    assert _lint_src(tmp_path, src,
                     rel=os.path.join(PKG, "serving", "x.py")) == []


def test_upward_import_tree_clean_but_for_listed_debts(tmp_path,
                                                       monkeypatch):
    """The tree passes with its exceptions listed by name, each listed
    exception still stands for a real import (a stale entry fails here),
    and what the rule allows stays allowed."""
    ok = ("from distributed_embeddings_tpu.obs.stages import staged\n"
          "from distributed_embeddings_tpu.obs import stages\n"
          "from distributed_embeddings_tpu.obs.spans import span\n"
          "from ..utils import profiling\n"
          "from . import wire\n"
          "import jax\n")
    assert _lint_src(tmp_path, ok,
                     rel=os.path.join(PKG, "ops", "x.py")) == []

    def upward():
        return [f for path in lint.default_files()
                for f in lint.lint_file(path) if f.rule == "upward-import"]

    assert upward() == []
    listed = lint.UPWARD_EXCEPTIONS
    monkeypatch.setattr(lint, "UPWARD_EXCEPTIONS", ())
    bare = upward()
    assert len(bare) == len(listed)
    assert sorted(f.path for f in bare) == sorted(
        os.path.join(PKG, path) for path, _, _ in listed)


def test_syntax_error_reported_not_raised(tmp_path):
    fs = _lint_src(tmp_path, "def broken(:\n",
                   rel=os.path.join(PKG, "ops", "x.py"))
    assert [f.rule for f in fs] == ["parse-error"]


def test_multi_rule_escape(tmp_path):
    src = ("from jax import lax\n"
           "import time\n"
           "def f(x, params):\n"
           "    # lint: allow(naked-collective, wallclock-in-jit)\n"
           "    return lax.all_gather(x, 'mp'), time.time()\n")
    assert _lint_src(tmp_path, src,
                     rel=os.path.join(PKG, "layers", "x.py")) == []


def test_finding_str_and_json_shape(tmp_path):
    fs = _lint_src(tmp_path, "import time\nt = time.time()\n",
                   rel=os.path.join(PKG, "ops", "x.py"))
    d = fs[0].to_dict()
    assert set(d) == {"rule", "path", "line", "message"}
    assert "wallclock-in-jit" in str(fs[0])


def test_repo_package_is_clean():
    """The gate itself: the shipped package has zero violations (every
    exchange collective behind ops/wire.py, hot-shard access confined
    to its two owners, no wall clocks in jitted modules)."""
    findings = []
    for path in lint.default_files():
        findings.extend(lint.lint_file(path))
    assert findings == [], [str(f) for f in findings]


def test_cli_exit_codes(tmp_path):
    assert lint.main([]) == 0            # the package is clean
    bad = tmp_path / "bad.py"
    bad.write_text("from jax import lax\ny = lax.all_gather(1, 'mp')\n")
    assert lint.main([str(bad)]) == 1
