"""Where JAX's persistent compilation cache lives.

One rule for every entry point of the repo (chip_smoke.py, the
example mains, the tests and tools): a cache directory given from outside
through ``JAX_COMPILATION_CACHE_DIR`` is used as it is — JAX reads that
variable itself, and no other directory is set in code — and without it
the cache is ``<checkout>/.jax_cache``. The path is part of a cache entry's
key, so it is fixed: no /tmp, no pid, no timestamp.

The same call makes the process count what compiling costs it (ISSUE 40):
`jax.monitoring` listeners, registered once, keep these counters of
`obs.default_registry()` current:

  compile/seconds{phase=trace}       tracing a function to a jaxpr
  compile/seconds{phase=lower}       jaxpr to StableHLO module
  compile/seconds{phase=backend}     the backend's compile (a program the
                                     cache did not hold)
  compile/seconds{phase=cache_load}  reading an executable from the cache
  compile/programs                   programs compiled or loaded
  compile/cache_hits                 ... of which the cache held
  compile/cache_misses               entries the cache wrote (a program
                                     under JAX's size or time threshold is
                                     compiled and not written)

Each duration is also an instant ``compile/seconds`` (``phase``,
``seconds``) in `obs.default_recorder()`: the counters say how much, the
ring says when, so a reader can leave out what was compiled after a
moment it names.
"""

import os
import threading

import jax

from distributed_embeddings_tpu.obs.registry import default_registry
from distributed_embeddings_tpu.obs.trace import default_recorder

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           _BACKEND: "backend", _CACHE_LOAD: "cache_load"}
_COUNTS = {"/jax/compilation_cache/cache_hits": "compile/cache_hits",
           "/jax/compilation_cache/cache_misses": "compile/cache_misses"}
_listening = False
# seconds of cache reads inside the backend-compile event that is open on
# this thread: JAX times the read within it, and the phases must add up
_inside = threading.local()


def _on_duration(event, seconds, **kwargs):
    phase = _PHASES.get(event)
    if phase is None:
        return
    if event == _CACHE_LOAD:
        _inside.cache_load = getattr(_inside, "cache_load", 0.0) + seconds
    elif event == _BACKEND:
        default_registry().counter("compile/programs").inc()
        seconds = max(0.0, seconds - getattr(_inside, "cache_load", 0.0))
        _inside.cache_load = 0.0
    default_registry().counter("compile/seconds", phase=phase).inc(seconds)
    default_recorder().instant("compile/seconds", phase=phase,
                               seconds=seconds)


def _on_event(event, **kwargs):
    name = _COUNTS.get(event)
    if name:
        default_registry().counter(name).inc()


def _count_compiles():
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Also starts the process's compile counters (module docstring)."""
    _count_compiles()
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
