"""Per-layer metrics: each is a data file naming a small reader.

``benchmark/layer_metrics/<metric>.json`` holds the reader's name and its
parameters; ``benchmark/readers/<reader>.py`` has ``read(ctx, params)``,
which returns the number, or None where there is nothing to read (the metric
is then left out of the line). Which cells report a metric, its unit, layer
and the end-to-end metric it should move are BENCHMARK.json's.
"""

import dataclasses
from typing import Any, List, Optional

from benchmark.harness import spec


@dataclasses.dataclass
class Context:
    """What a traced run knows when the readers are called."""
    chips: List[Any]               # xplane.Chip per traced chip ([] on a CPU)
    steps: int                     # steps inside the traced window
    built: Any                     # harness.built.Built
    cell: Any                      # harness.spec.Cell
    device_kind: str
    memory_peak_bytes: Optional[int]   # fullest chip, None where not reported
    notes: List[str] = dataclasses.field(default_factory=list)  # for the log


def read_all(ctx: Context) -> dict:
    """{metric: {"value", "unit"}} for the cell's per-layer metrics."""
    out = {}
    for metric in ctx.cell.per_layer:
        params = spec.load_json("benchmark", "layer_metrics",
                                metric["name"] + ".json")
        value = spec.plugin("readers", params["reader"]).read(ctx, params)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out
