"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own under the benchmark's directory, found here by the name
the manifest gives it.  A later PR adds a cell, a mix or a metric by adding a
file and an entry; nothing in this module knows any of their names.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    pass


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "perfbench")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.run_seconds = int(self.doc["run_seconds"])

    # -- cells ---------------------------------------------------------------

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.doc["workloads"]]
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (has {known})")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                path = os.path.join(self.root, c["file"])
                doc = _load_json(path)
                doc["_dir"] = os.path.dirname(path)
                return doc
        # a configuration no cell uses yet is found by its name alone
        path = os.path.join(self.bench_dir, "configs", name + ".json")
        if os.path.isfile(path):
            doc = _load_json(path)
            doc["_dir"] = os.path.dirname(path)
            return doc
        raise ManifestError(f"no configuration {name!r} in BENCHMARK.json or at {path}")

    def traffic(self, name: str) -> dict:
        path = os.path.join(self.bench_dir, "traffic", name + ".json")
        if not os.path.isfile(path):
            raise ManifestError(f"no traffic file {path}")
        return _load_json(path)

    # -- metrics -------------------------------------------------------------

    def metrics_for(self, cell: str, group: str) -> list:
        """The metrics of `group` ("end_to_end" or "per_layer") that `cell`
        reports: those that list it, and those that list no cells at all
        (per-layer ones then follow the end-to-end metric they move)."""
        e2e_of_cell = {
            m["name"] for m in self.doc["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]
        }
        out = []
        for m in self.doc[group]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in e2e_of_cell:
                out.append(m)
        return out

    def reader(self, group: str, name: str):
        """The `read(rec)` function of one metric's own file."""
        sub = "end_to_end" if group == "end_to_end" else "metrics"
        return load_function(
            os.path.join(self.bench_dir, sub, name + ".py"), "read"
        )

    def generator(self, kind: str):
        return load_function(
            os.path.join(self.bench_dir, "generators", kind + ".py"), "Generator"
        )


def load_function(path: str, attr: str):
    if not os.path.isfile(path):
        raise ManifestError(f"no file {path}")
    tag = os.path.relpath(path, ROOT).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location("perfbench_file_" + tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        return getattr(mod, attr)
    except AttributeError:
        raise ManifestError(f"{path} defines no {attr!r}") from None
