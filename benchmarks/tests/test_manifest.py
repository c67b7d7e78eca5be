"""The manifest against the contract's static rules."""

import glob
import json
import os

import pytest

import manifest as mf
import traffic_plan as tp

TRAFFIC = sorted(glob.glob(os.path.join(mf.HERE, "traffic", "*.json")))


def test_manifest_has_no_problem():
    assert mf.problems(mf.load_manifest()) == []


def test_names_units_and_sizes():
    man = mf.load_manifest()
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) < 65536
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in man[group]:
            assert mf.NAME.match(entry["name"]), entry["name"]
    for m in man["end_to_end"] + man["per_layer"]:
        assert mf.UNIT.match(m["unit"]), m
    for c in man["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        cfg = mf.load_json(os.path.join(mf.ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    layers = {m["layer"] for m in man["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_every_cell_resolves_to_files_and_nothing_in_run_names_one():
    man = mf.load_manifest()
    src = open(os.path.join(mf.HERE, "run.py")).read() + \
        open(os.path.join(mf.HERE, "loadgen.py")).read()
    for w in man["workloads"]:
        cell = mf.Cell(man, w["name"])
        assert os.path.isfile(cell.traffic_file)
        assert os.path.isfile(cell.config_module)
        for word in (w["name"], w["config"], w["traffic"]):
            assert word not in src, f"run.py/loadgen.py name {word!r}"
    json.dumps(man)


@pytest.mark.parametrize("path", TRAFFIC, ids=os.path.basename)
def test_a_backlog_divides_by_its_lanes(path):
    """``per_lane`` mints ``batches // producers`` a lane: the file has
    to say a number that drops nothing, at full size and ``tiny``."""
    raw = mf.load_json(path)
    for tiny in (False, True):
        t = mf.with_tiny(raw, tiny)
        if t["arrivals"] in ("prefilled", "closed"):
            assert t["batches"] % t["producers"] == 0, (path, tiny)
            assert tp.per_lane(t, 40.0) * t["producers"] == t["batches"]
