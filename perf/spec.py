"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

``BENCHMARK.json`` is the index: a cell (``workloads[*].name``) names a
configuration and a traffic mix; the configuration entry names its file;
every metric entry names a reader.  Nothing here, or in ``run.py``, knows
a cell, configuration or metric by name, so a later PR adds entries and
files and edits none:

    perf/configs/<configuration>.json    sizes, pinned knobs, guarantees,
                                         tolerances, and ``driver``
    perf/traffic/<traffic>.json          mode, block size, traced seconds
    perf/drivers/<driver>.py             the adapter for one app
    perf/e2e_metrics/<metric>.py         ``read(run) -> float | None``
    perf/layer_metrics/<metric>.py       ``read(run) -> float | None``
    perf/work_models/<model>.py          ``per_item(work) -> dict``
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def dumps(obj) -> str:
    """One line of JSON; numpy scalars and arrays go as numbers/lists."""
    def plain(x):
        if hasattr(x, "tolist"):
            return x.tolist()
        return str(x)

    return json.dumps(obj, default=plain)


def load_module(path: str):
    """Import one file by path (readers and drivers are plain files, not
    entries of a registry)."""
    name = "perf_file_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(have: {', '.join(sorted(cells))})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(
            os.path.join(root, configs[self.entry["config"]]["file"]))
        self.perf_dir = os.path.join(root, self.bench["paths"][0])
        self.traffic = load_json(os.path.join(
            self.perf_dir, "traffic", self.entry["traffic"] + ".json"))

    def driver_module(self):
        return load_module(os.path.join(
            self.perf_dir, "drivers", self.config["driver"] + ".py"))

    def metrics(self, group: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those without a ``workloads`` list, or with this cell in it."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, group: str, metric: str):
        sub = "e2e_metrics" if group == "end_to_end" else "layer_metrics"
        return load_module(
            os.path.join(self.perf_dir, sub, metric + ".py")).read
