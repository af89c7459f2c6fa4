"""measured_default: measured hardware defaults for DET_* knobs.

The dispatch reads the file DET_MEASURED_DEFAULTS_PATH names, if it names
one, as the TPU-backend default. Env always overrides; CPU backends never
consult the file; nothing inside the checkout is read.

Since ISSUE 18 `measured_default` delegates to `tune.resolve.knob_value`
(env > tuned config > measured defaults > fallback) — the tests here
cover the measured-defaults layer and the bench's isolation from it; the
tuned layer is tests/test_tune.py's."""

import json

import jax
import pytest

from distributed_embeddings_tpu.ops import sparse_update
from distributed_embeddings_tpu.tune import resolve as tune_resolve


@pytest.fixture
def defaults_file(tmp_path, monkeypatch):
    path = tmp_path / "measured_defaults.json"
    path.write_text(json.dumps({
        "DET_SCATTER_IMPL": {"value": "tiled", "git_sha": "abc",
                             "measured_at": "2026-07-31T00:00:00Z"},
        "DET_DEDUP_IMPL": "cumsum",          # bare-string form accepted
    }))
    monkeypatch.setenv("DET_MEASURED_DEFAULTS_PATH", str(path))
    monkeypatch.delenv("DET_TUNED_PATH", raising=False)
    monkeypatch.delenv("DET_TUNED_WORKLOAD", raising=False)
    tune_resolve.reset_cache()
    yield path
    tune_resolve.reset_cache()


def test_env_overrides_file(defaults_file, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DET_SCATTER_IMPL", "xla")
    assert sparse_update.measured_default("DET_SCATTER_IMPL", "xla") == "xla"


def test_file_used_on_tpu_backend(defaults_file, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DET_SCATTER_IMPL", raising=False)
    assert sparse_update.measured_default("DET_SCATTER_IMPL",
                                          "xla") == "tiled"
    assert sparse_update.measured_default("DET_DEDUP_IMPL",
                                          "sort") == "cumsum"
    # unknown knob falls back
    assert sparse_update.measured_default("DET_LOOKUP_PATH",
                                          "auto") == "auto"


def test_cpu_backend_ignores_file(defaults_file, monkeypatch):
    monkeypatch.delenv("DET_SCATTER_IMPL", raising=False)
    assert jax.default_backend() == "cpu"
    assert sparse_update.measured_default("DET_SCATTER_IMPL", "xla") == "xla"


def test_missing_file_falls_back(tmp_path, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DET_MEASURED_DEFAULTS_PATH",
                       str(tmp_path / "nope.json"))
    tune_resolve.reset_cache()
    monkeypatch.delenv("DET_SCATTER_IMPL", raising=False)
    assert sparse_update.measured_default("DET_SCATTER_IMPL", "xla") == "xla"
    tune_resolve.reset_cache()


def _load_bench():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "det_bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_nothing_in_the_checkout_is_read(tmp_path, monkeypatch):
    """Without DET_MEASURED_DEFAULTS_PATH there is no measured-defaults
    layer at all: a tools/measured_defaults.json left in the checkout by
    an old bench run (git never held it) must not change what a TPU run
    dispatches to — and bench.py no longer has a writer for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DET_MEASURED_DEFAULTS_PATH", raising=False)
    monkeypatch.delenv("DET_SCATTER_IMPL", raising=False)
    stray = tmp_path / "tools"
    stray.mkdir()
    (stray / "measured_defaults.json").write_text(
        json.dumps({"DET_SCATTER_IMPL": "tiled"}))
    monkeypatch.setattr(tune_resolve, "_ROOT", str(tmp_path))
    tune_resolve.reset_cache()
    assert sparse_update.measured_default("DET_SCATTER_IMPL", "xla") == "xla"
    tune_resolve.reset_cache()
    assert not hasattr(_load_bench(), "_maybe_write_measured_defaults")


def test_bench_isolation_pins_reader(tmp_path, monkeypatch):
    """_isolate_from_measured_defaults points the in-process reader at an
    unparsable path, drops BOTH tuned selectors and resets the resolve
    caches, so the bench's baseline arms can never be contaminated by an
    earlier flip — measured-defaults OR a prior --mode tune record
    (ISSUE 18)."""
    import os
    bench = _load_bench()
    monkeypatch.setenv("DET_MEASURED_DEFAULTS_PATH", "/tmp/whatever.json")
    tuned = tmp_path / "tuned.json"
    tuned.write_text("{}")
    monkeypatch.setenv("DET_TUNED_PATH", str(tuned))
    monkeypatch.setenv("DET_TUNED_WORKLOAD", "dlrm")
    bench._isolate_from_measured_defaults()
    assert os.environ["DET_MEASURED_DEFAULTS_PATH"] == os.devnull
    assert "DET_TUNED_PATH" not in os.environ
    assert "DET_TUNED_WORKLOAD" not in os.environ
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DET_SCATTER_IMPL", raising=False)
    assert sparse_update.measured_default("DET_SCATTER_IMPL", "xla") == "xla"
    tune_resolve.reset_cache()
