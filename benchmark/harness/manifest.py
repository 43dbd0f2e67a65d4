"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own and is found by name:

    <root>/configs/<config>.json      sizes, guarantees, dataset module
    <root>/traffic/<traffic>.json     parameters of the general generator
    <root>/metrics/<metric>.json      reader module + its arguments; a
                                      metric split by suffix (`x.sweep`,
                                      `x.point`) without a file of its
                                      own takes `x.json`
    <root>/readers/<reader>.py        read(ctx, **args) -> number | None
    <root>/datasets/<dataset>.py      data, loader, queries, reference

`<root>` is each directory of BENCHMARK.json's `paths`, searched in
order, so a later PR adds a cell, a mix or a metric by adding files and
manifest entries and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os


class Manifest:
    def __init__(self, checkout: str, roots: list = None):
        self.checkout = checkout
        with open(os.path.join(checkout, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.roots = [os.path.join(checkout, p) for p in
                      (roots or self.doc["paths"])]

    def find(self, kind: str, name: str, ext: str = ".json") -> str:
        for root in self.roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"no {kind}/{name}{ext} under {self.roots}")

    def load_json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name)) as f:
            return json.load(f)

    def load_module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric_spec(self, name: str) -> dict:
        """{"reader": ..., "args": {...}} of one metric of
        BENCHMARK.json, which alone states its unit, layer and `moves`."""
        try:
            return self.load_json("metrics", name)
        except FileNotFoundError:
            if "." not in name:
                raise
            return self.load_json("metrics", name.rsplit(".", 1)[0])

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"workload {name!r} is not in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.checkout, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"config {name!r} is not in BENCHMARK.json")

    def metrics_for(self, section: str, workload: str) -> list:
        """Entries of `end_to_end` or `per_layer` that this cell
        reports: those that list it, and those that list no cells and
        whose `moves` (for per-layer) this cell reports."""
        e2e = [m for m in self.doc["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if section == "end_to_end":
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.doc["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]
