"""The knob consumption seam: env > tuned config > measured defaults.

``knob_value(env, fallback)`` is the one resolution order every DET_*
performance knob reads through (``ops.sparse_update.measured_default``
delegates here, as do the wire/storage/training/fleet env-default
helpers):

  1. the env var itself — an operator's explicit word always wins;
  2. the workload's config-of-record ``tools/tuned/<workload>.json``
     written by ``bench.py --mode tune`` — consulted ONLY when
     explicitly selected via ``DET_TUNED_WORKLOAD=<name>`` (resolved
     against the repo's tools/tuned/) or ``DET_TUNED_PATH=<file>``.
     Explicit opt-in keeps CPU test equivalence: no env, no silent
     behavior change because a tuner ran on the same checkout;
  3. a measured-defaults file (the PR-2 seed of this machinery), ONLY
     where ``DET_MEASURED_DEFAULTS_PATH`` names one: consulted on the
     TPU backend, or anywhere under ``DET_MEASURED_DEFAULTS_CONSULT=1``.
     Nothing is looked up inside the checkout: a file that git does not
     hold cannot change what a run dispatches to;
  4. the hand-picked ``fallback``.

Every adoption from layer 2 or 3 lands a flight-recorder instant
(``tune/adopt``) and bumps ``tune/adoptions_total{source=}`` — a
postmortem can always answer "which config was this process actually
running?". A malformed/stale tuned file falls through LOUDLY: one
RuntimeWarning + ``tune/tuned_config_invalid_total``, never a crash,
and entries naming unknown knobs or illegal values are dropped
individually (``tune/tuned_knob_rejected_total``) while the legal rest
still applies.
"""

import json
import os
import threading
import warnings
from typing import Dict, Optional, Tuple

from . import registry as _registry

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lock = threading.Lock()
_TUNED: Optional[Dict[str, str]] = None       # env -> value, or None=unread
_TUNED_INFO: Dict[str, object] = {}           # path/workload/errors diag
_MEASURED: Optional[Dict[str, str]] = None
_ADOPTED: set = set()                         # (env, value, source) emitted
_WARNED: set = set()


def reset_cache() -> None:
    """Drop every per-process cache (tests, bench arm isolation)."""
    global _TUNED, _MEASURED
    with _lock:
        _TUNED = None
        _MEASURED = None
        _TUNED_INFO.clear()
        _ADOPTED.clear()
        _WARNED.clear()


def tuned_source() -> Tuple[Optional[str], Optional[str]]:
    """(path, workload) the tuned layer would consult, or (None, None)
    when neither DET_TUNED_PATH nor DET_TUNED_WORKLOAD is set."""
    path = os.environ.get("DET_TUNED_PATH")
    if path:
        return path, os.environ.get("DET_TUNED_WORKLOAD")
    workload = os.environ.get("DET_TUNED_WORKLOAD")
    if workload:
        return (os.path.join(_ROOT, "tools", "tuned",
                             f"{workload}.json"), workload)
    return None, None


def _warn_once(key: str, msg: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _count(name: str, **labels) -> None:
    try:
        from ..obs.registry import default_registry
        default_registry().counter(name, **labels).inc()
    except Exception:  # noqa: BLE001 - accounting must not break dispatch
        pass


def _load_tuned_locked() -> Dict[str, str]:
    """Read + validate the selected tuned config once per process."""
    path, workload = tuned_source()
    info = {"path": path, "workload": workload, "errors": []}
    if path is None:
        _TUNED_INFO.update(info)
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
        from .search import validate_tuned_record
        errors = validate_tuned_record(doc)
    except Exception as e:  # noqa: BLE001 - absent/corrupt file = loud miss
        doc, errors = None, [f"unreadable: {e}"]
    if doc is None or errors:
        info["errors"] = errors
        _TUNED_INFO.update(info)
        _count("tune/tuned_config_invalid_total")
        _warn_once(f"invalid:{path}",
                   f"tuned config {path} is malformed/stale and was "
                   f"IGNORED (resolution falls through): {errors[:3]}")
        return {}
    if workload and doc.get("workload") != workload:
        # DET_TUNED_WORKLOAD=serve pointed (via DET_TUNED_PATH) at a
        # record tuned for a different workload: refuse, loudly
        info["errors"] = [f"workload mismatch: file is for "
                          f"{doc.get('workload')!r}, requested "
                          f"{workload!r}"]
        _TUNED_INFO.update(info)
        _count("tune/tuned_config_invalid_total")
        _warn_once(f"workload:{path}", f"tuned config {path}: "
                                       f"{info['errors'][0]}")
        return {}
    out: Dict[str, str] = {}
    for env, value in dict(doc.get("winner", {})).items():
        err = _registry.validate_override(env, value)
        if err is not None:
            info["errors"].append(err)
            _count("tune/tuned_knob_rejected_total")
            _warn_once(f"knob:{path}:{env}",
                       f"tuned config {path}: entry rejected — {err}")
            continue
        out[env] = value
    _TUNED_INFO.update(info)
    return out


def _load_measured_locked() -> Dict[str, str]:
    """The file DET_MEASURED_DEFAULTS_PATH names, in its historical shape:
    flat {env: value-or-{value, provenance...}}; unset/absent/invalid =
    {}."""
    path = os.environ.get("DET_MEASURED_DEFAULTS_PATH")
    if not path:
        return {}
    try:
        with open(path) as f:
            raw = json.load(f)
        return {k: (v.get("value") if isinstance(v, dict) else v)
                for k, v in raw.items()}
    except Exception:  # noqa: BLE001 - absent/invalid file = no flips
        return {}


def _emit_adopt(env: str, value: str, source: str) -> None:
    key = (env, value, source)
    if key in _ADOPTED:
        return
    _ADOPTED.add(key)
    _count("tune/adoptions_total", source=source.split(":")[0])
    try:
        from ..obs.trace import default_recorder
        default_recorder().instant("tune/adopt", knob=env, value=value,
                                   source=source)
    except Exception:  # noqa: BLE001 - tracing must not break dispatch
        pass


def tuned_info() -> Dict[str, object]:
    """Diagnostics of the last tuned-config load (path, workload,
    per-entry errors) — empty until something resolved."""
    with _lock:
        return dict(_TUNED_INFO)


def knob_value(env_name: str, fallback: str) -> str:
    """Resolve one knob through the documented precedence (module
    docstring). Signature-compatible with the historical
    ``sparse_update.measured_default(knob, fallback)``."""
    global _TUNED, _MEASURED
    env = os.environ.get(env_name)
    if env is not None:
        return env
    with _lock:
        if _TUNED is None:
            _TUNED = _load_tuned_locked()
        tuned = _TUNED
    if env_name in tuned:
        path = _TUNED_INFO.get("path")
        workload = _TUNED_INFO.get("workload")
        _emit_adopt(env_name, tuned[env_name],
                    f"tuned:{workload or path}")
        return tuned[env_name]
    import jax
    if (jax.default_backend() != "tpu"
            and os.environ.get("DET_MEASURED_DEFAULTS_CONSULT") != "1"):
        # CPU test equivalence must not silently change because a TPU
        # bench wrote measured defaults on the same checkout (PR 2 rule)
        return fallback
    with _lock:
        if _MEASURED is None:
            _MEASURED = _load_measured_locked()
        measured = _MEASURED
    if env_name in measured:
        _emit_adopt(env_name, str(measured[env_name]),
                    "measured_defaults")
        return measured[env_name]
    return fallback
