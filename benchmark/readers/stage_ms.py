"""Device ms per step under one of the program's stage scopes
(``params["scope"]``, a name of ``distributed_embeddings_tpu/obs/stages.py``;
``params["pass"]``: ``any``, ``forward`` or ``backward``).

The program traces its step under ``jax.named_scope("det.<stage>")``, and
the scope travels in every operation's own ``op=`` path
(``jit(det_train_step)/det.model/jvp(det.lookup)/det.lookup/jit(_take)/gather:``),
so nothing is matched by clock. An operation's stage is the innermost, which
is the last, ``det.<stage>`` of its path; it is the backward pass's if the
path holds ``transpose(``. What is summed is ``self_ns``, the charge
``xplane.reduce`` already made (a duration less what is nested in it), so
the stages, the operations with a path and no stage, and the operations with
no path (the compiler's own copies) add up to ``step.device_ms``. The mean
over the traced chips.

None where no chip was traced, and where the trace holds no ``det.`` scope
at all: a program from before the scopes, or an executable loaded from a
compile cache that predates them. The latter is said on an output line.
"""

import json
import re

_PATH = re.compile(r" op=(.*?) src=")
_STAGE = re.compile(r"det\.([a-z_]+)")
NO_STAGE, NO_PATH = "path, no stage", "no path"


def stage_of(signature):
    """(stage, is backward) of an operation's signature. The stage is
    `NO_STAGE` for a path under no scope and `NO_PATH` where the operation
    names no path of the jitted program."""
    found = _PATH.search(signature)
    path = found.group(1) if found else ""
    if "/" not in path:
        return NO_PATH, False
    stages = _STAGE.findall(path)
    return (stages[-1] if stages else NO_STAGE), "transpose(" in path


def partition(ctx):
    """{(stage, is backward): ms per step}, the chips' mean, or None where
    the trace holds no scope. Made once per run and kept on `ctx`."""
    if not hasattr(ctx, "stage_partition"):
        ns = {}
        for chip in ctx.chips:
            for op in chip.ops:
                key = stage_of(op.signature)
                ns[key] = ns.get(key, 0.0) + op.self_ns
        if any(stage not in (NO_STAGE, NO_PATH) for stage, _ in ns):
            scale = 1e-6 / len(ctx.chips) / ctx.steps
            ctx.stage_partition = {k: v * scale for k, v in ns.items()}
            _note(ctx)
        else:
            ctx.stage_partition = None
            print("TRACE stage_ms: no operation of this trace lies under a "
                  "det.* scope (a program, or a cached executable, from "
                  "before the stage scopes): no stage metric", flush=True)
    return ctx.stage_partition


def _note(ctx):
    per_stage, backward = {}, {}
    for (stage, is_backward), ms in ctx.stage_partition.items():
        per_stage[stage] = per_stage.get(stage, 0.0) + ms
        if is_backward:
            backward[stage] = backward.get(stage, 0.0) + ms
    ctx.notes.append(
        "stages, ms per step: " + json.dumps(
            {k: round(v, 4) for k, v in sorted(per_stage.items())})
        + "; of which backward: " + json.dumps(
            {k: round(v, 4) for k, v in sorted(backward.items())})
        + f"; sum {sum(per_stage.values()):.4f} = step.device_ms")


def read(ctx, params):
    if not ctx.chips:
        return None
    parts = partition(ctx)
    if parts is None:
        return None
    wanted = {"any": (False, True), "forward": (False,),
              "backward": (True,)}[params["pass"]]
    return sum(parts.get((params["scope"], b), 0.0) for b in wanted)
