"""Every cell end to end in its ``--tiny`` CPU form, through the same
``run.py`` the driver calls: generator in its own process, served path,
WAL read back, reference compared, one JSON object on the last line."""

import json
import os
import subprocess
import sys

import manifest as mf

import pytest

CELLS = [w["name"] for w in mf.load_manifest()["workloads"]]


def _run(cell, trace, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(mf.HERE, "run.py"), "--workload",
         cell, "--seed", str(seed), "--seconds", "2", "--trace",
         str(trace), "--tiny"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return (json.loads(p.stdout.strip().splitlines()[-1]),
            p.stdout + p.stderr)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    man = mf.load_manifest()
    c = mf.Cell(man, cell)
    res, out = _run(cell, 0, 2**31 + 17)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["correct"] is True, out[-3000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    # every number compared is printed beside its limit: in the log, as
    # the last lines of stderr, and as the result line's last key
    assert out.count("] check ") >= 6
    assert list(res)[-1] == "checks" and len(res["checks"]) >= 6
    assert all(c["ok"] for c in res["checks"].values())
    last = out.strip().splitlines()[-len(res["checks"]):]
    assert [x.split()[1].rstrip(":") for x in last] == list(res["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_traced(cell):
    c = mf.Cell(mf.load_manifest(), cell)
    res, out = _run(cell, 1, 23)
    assert res["correct"] is True, out[-3000:]
    assert set(res["metrics"]) == {m["name"] for m in c.per_layer}
    assert res["device"]["busy_s"] > 0
    assert res["device"]["window_s"] >= res["device"]["busy_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_no_accelerator_no_result():
    """Without --tiny the run needs a TPU: here it must exit non-zero
    and print no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(mf.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
