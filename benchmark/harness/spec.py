"""Find a cell's files by the names in BENCHMARK.json.

Nothing here lists a configuration, a traffic mix or a metric: a cell is the
``workloads`` entry of that name, its configuration the ``configs`` entry it
names (and the file that entry names), its traffic
``benchmark/traffic/<traffic>.json``, its metrics the ``end_to_end`` and
``per_layer`` entries that do not exclude it through a ``workloads`` list.
Code that belongs to one of them is the module the data file names:
``benchmark/builders/<builder>.py``, ``benchmark/generators/<generator>.py``,
``benchmark/readers/<reader>.py``.
"""

import dataclasses
import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_PLAIN = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


class SpecError(Exception):
    pass


def load_json(*parts):
    path = os.path.join(ROOT, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e.strerror}") from e


def plugin(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``."""
    if not _PLAIN.match(name) or "." in name:
        raise SpecError(f"{kind} name {name!r} is not a plain module name")
    try:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{kind}.{name}":
            raise
        raise SpecError(f"benchmark/{kind}/{name}.py does not exist") from e


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list


def _named(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SpecError(f"BENCHMARK.json has {len(found)} {what} named "
                        f"{name!r}; have {[e['name'] for e in entries]}")
    return found[0]


def load_cell(workload: str) -> Cell:
    bench = load_json("BENCHMARK.json")
    cell = _named(bench["workloads"], workload, "workloads")
    config = load_json(_named(bench["configs"], cell["config"],
                              "configs")["file"])
    if not _PLAIN.match(cell["traffic"]):
        raise SpecError(f"traffic name {cell['traffic']!r} is not plain")
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")

    def mine(entries):
        return [m for m in entries
                if workload in m.get("workloads", [workload])]
    return Cell(workload, int(cell["chips"]), config, traffic,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))
