"""The measured loops: steps dispatched from Python as
``examples/dlrm/main.py`` dispatches them. State is threaded and donated,
the batches are already on the device and rotated, the loss is fetched every
``sync_every`` steps and after the last one, and a host fetch is the only
synchronisation."""

import glob
import math
import os
import time


class Window:
    """What one loop saw. `attempted` counts dispatched steps, `failed` the
    fetched losses that were not finite plus a call that raised."""

    def __init__(self):
        self.attempted = 0
        self.failed = 0
        self.error = None
        self.losses = []
        self.block_ms = []        # per sync block: block time / steps in it
        self.elapsed_s = 0.0


def _fetch(win, loss):
    value = float(loss)
    win.losses.append(value)
    if not math.isfinite(value):
        win.failed += 1
    return value


def timed(step_fn, state, batches, seconds, sync_every):
    """Dispatch steps for at least `seconds`, ending on a sync. The window
    runs from the first dispatch to the host's fetch of the last loss.
    Returns (Window, state)."""
    win = Window()
    params, opt_state = state
    t_first = t_block = time.perf_counter()
    try:
        while True:
            for _ in range(sync_every):
                numerical, cats, labels = batches[win.attempted % len(batches)]
                params, opt_state, loss = step_fn(params, opt_state,
                                                  numerical, cats, labels)
                win.attempted += 1
            _fetch(win, loss)
            now = time.perf_counter()
            win.block_ms.append(1e3 * (now - t_block) / sync_every)
            t_block = now
            if now - t_first >= seconds:
                break
    except Exception as e:  # noqa: BLE001 - the boundary that reports a failed step
        win.failed += 1
        win.error = f"{type(e).__name__}: {e}"
    win.elapsed_s = time.perf_counter() - t_first
    return win, (params, opt_state)


def traced(step_fn, state, batches, steps, sync_every, trace_dir):
    """`steps` steady steps under the profiler, with this loop's own host
    annotations (`dispatch`, `sync`, `fetch`) on the profiler's clock.
    Returns (Window, state, path of the .xplane.pb)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # one event per Python call otherwise
    options.host_tracer_level = 2
    win = Window()
    params, opt_state = state
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t_first = time.perf_counter()
    try:
        for i in range(steps):
            numerical, cats, labels = batches[i % len(batches)]
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt_state, loss = step_fn(params, opt_state,
                                                  numerical, cats, labels)
            win.attempted += 1
            if (i + 1) % sync_every == 0 or i == steps - 1:
                with jax.profiler.TraceAnnotation("sync"):
                    loss.block_until_ready()
                with jax.profiler.TraceAnnotation("fetch"):
                    _fetch(win, loss)
    except Exception as e:  # noqa: BLE001 - as in timed()
        win.failed += 1
        win.error = f"{type(e).__name__}: {e}"
    finally:
        win.elapsed_s = time.perf_counter() - t_first
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return win, (params, opt_state), (found[-1] if found else None)
