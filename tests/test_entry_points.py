"""The process entry point this repo is run through on a chip —
``chip_smoke.py`` — holds the rules a directly attached chip imposes: no
silent CPU, one process per chip, failure reaches the exit status."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, **env_overrides):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # conftest's 8-device mesh: not here
    env["PYTHONPATH"] = REPO
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


def test_chip_smoke_tiny_form_green_on_explicit_cpu(tmp_path):
    cache = str(tmp_path / "cache")     # placed from outside: used as is
    out = _run(["chip_smoke.py", "--tiny"], JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and summary["form"] == "tiny"
    assert summary["compile_cache"]["dir"] == cache
    pr, kn = summary["pagerank"], summary["knn"]
    assert pr["fixpoint_engine"] == "LinearFixpointProgram"
    assert pr["megatick_fallbacks"] == 0 and pr["megatick_windows"] >= 3
    assert pr["windows_pipelined"] >= 1
    assert pr["max_rel_err"] < pr["rel_err_bound"]
    # recovery re-runs the window's ticks one for one: same horizon, and
    # on one backend the very same ranks
    assert pr["recovered_horizon"] == 1 + 3 * pr["window_ticks"]
    assert pr["recovered_vs_served_rel"] == 0.0
    assert kn["pallas_compiled"] is False       # interpreted off-TPU


def test_chip_smoke_default_form_refuses_a_cpu_backend():
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout           # no result line
    assert "FAILED" in out.stderr and "tpu" in out.stderr


def test_chip_smoke_tiny_form_needs_the_cpu_stated():
    # never reached by *finding* no chip: the caller has to say cpu
    out = _run(["chip_smoke.py", "--tiny"], JAX_PLATFORMS=None)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "JAX_PLATFORMS=cpu" in out.stderr
