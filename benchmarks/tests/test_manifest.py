"""The manifest against the contract's static rules."""

import json
import os

import manifest as mf


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
