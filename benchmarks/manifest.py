"""Read ``BENCHMARK.json`` and find, by name, the files a cell is made of.

Nothing here (or in ``run.py``) names a cell, a configuration or a mix:
a later PR adds entries to the manifest and files beside the existing
ones, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a Python file by path (file names carry ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(
        "benchmarks_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; the manifest "
                             f"has {sorted(cells)}")
        w = cells[name]
        cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
        self.name = name
        self.chips = int(w["chips"])
        self.config_name = w["config"]
        self.traffic_name = w["traffic"]
        self.config_file = os.path.join(root, cfg["file"])
        self.config_module = os.path.splitext(self.config_file)[0] + ".py"
        self.traffic_file = os.path.join(
            HERE, "traffic", w["traffic"] + ".json")
        self.end_to_end: List[dict] = [
            m for m in manifest["end_to_end"] if _applies(m, name)]
        self.per_layer: List[dict] = [
            m for m in manifest["per_layer"] if _applies(m, name)]

    def reader_file(self, metric_name: str) -> str:
        """``layer_metrics/<name>.py``; a name that has no file of its
        own shares the reader of the name before its last dot, so that
        ``x.backlog`` and ``x.paced`` (one quantity, two ``moves``) are
        read by one ``x.py``."""
        own = os.path.join(HERE, "layer_metrics", metric_name + ".py")
        if os.path.isfile(own) or "." not in metric_name:
            return own
        return os.path.join(HERE, "layer_metrics",
                            metric_name.rsplit(".", 1)[0] + ".py")


def with_tiny(d: dict, tiny: bool) -> dict:
    """A config or traffic file may carry a ``tiny`` group: the sizes of
    the CPU rehearsal. They replace the real ones only under ``--tiny``."""
    out = {k: v for k, v in d.items() if k != "tiny"}
    if tiny:
        out.update(d.get("tiny", {}))
    return out


def problems(manifest: dict, root: str = ROOT) -> List[str]:
    """What the contract's static rules would refuse. Used by the tests;
    the driver applies its own copy of the rules."""
    bad: List[str] = []

    def name_ok(s, what):
        if not isinstance(s, str) or not NAME.match(s):
            bad.append(f"{what}: bad name {s!r}")

    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
        name_ok(c["name"], "config")
        for k in c["reduced"]:
            name_ok(k, "reduced")
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config file {c['file']} missing")
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            bad.append(f"config file {c['file']} outside paths")
    seen = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            name_ok(w[k], "workload." + k)
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips {w['chips']}")
        if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"{w['name']}: why too long or multi-line")
        if (w["config"], w["traffic"]) in seen:
            bad.append(f"{w['name']}: duplicate (config, traffic)")
        seen.add((w["config"], w["traffic"]))
        cell = Cell(manifest, w["name"], root)
        for f in (cell.config_module, cell.traffic_file):
            if not os.path.isfile(f):
                bad.append(f"{w['name']}: missing {f}")
        e2e = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in e2e or len(e2e) < 2:
            bad.append(f"{w['name']}: needs setup_s and one more "
                       f"end-to-end metric, has {sorted(e2e)}")
        if not cell.per_layer:
            bad.append(f"{w['name']}: no per-layer metric")
        for m in cell.per_layer:
            if m["moves"] not in e2e:
                bad.append(f"{w['name']}: {m['name']} moves "
                           f"{m['moves']}, which the cell lacks")
            if not os.path.isfile(cell.reader_file(m["name"])):
                bad.append(f"{m['name']}: no reader file")
    cells = {w["name"] for w in manifest["workloads"]}
    names = set()
    for kind, want in (("end_to_end", {"name", "unit", "better", "bound",
                                       "source"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves"})):
        for m in manifest[kind]:
            if set(m) - {"workloads"} != want:
                bad.append(f"{kind} keys {sorted(m)}")
            name_ok(m["name"], kind)
            if m["name"] in names:
                bad.append(f"duplicate metric {m['name']}")
            names.add(m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source {m['source']!r}")
            for c in m.get("workloads", []):
                if c not in cells:
                    bad.append(f"{m['name']}: unknown cell {c}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: end-to-end source {m['source']}")
        if not 0 < m["bound"] <= 0.25:
            bad.append(f"{m['name']}: bound {m['bound']}")
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append(f"run_seconds {manifest['run_seconds']}")
    return bad
