"""Where the benchmark finds its pieces: everything is looked up by name.

``BENCHMARK.json`` at the checkout's root names the cells, configurations
and metrics.  A configuration is the JSON file its entry names; a traffic
mix is ``<bench>/traffic/<name>.json``, and the arrival kind it names is
``<bench>/traffic/<kind>.py``; a metric is a reader in
``<bench>/metrics/<name>.py`` that defines ``read(run)``.  Adding any of
them means adding files and entries, never editing one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Any, Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def load_module(path: pathlib.Path, name: str):
    """The module in the file ``path``, loaded under ``name``."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Layout:
    def __init__(self, root: pathlib.Path = CHECKOUT,
                 bench: pathlib.Path = BENCH):
        self.root = pathlib.Path(root)
        self.bench = pathlib.Path(bench)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def reader(self, metric: str):
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        return load_module(self.bench / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric).read

    def metrics(self, cell: str, trace: bool) -> List[Dict[str, Any]]:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics with ``--trace 0``, its per-layer ones with ``--trace 1``.
        An entry without ``workloads`` belongs to every cell (end-to-end)
        or to every cell that reports the metric it moves (per-layer)."""
        def has(entry):
            return "workloads" not in entry or cell in entry["workloads"]

        e2e = [m for m in self.spec["end_to_end"] if has(m)]
        if not trace:
            return e2e
        reported = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in reported)]
