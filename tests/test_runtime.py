"""utils/runtime: the compile-cache placement helper, and the
forced-sync counter on the CPU oracle path."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = "import jax; print(jax.config.jax_compilation_cache_dir)\n"


def _fresh_python(code: str, cache_env=None):
    """stdout words of ``code`` in a fresh CPU process with
    ``JAX_COMPILATION_CACHE_DIR`` set to ``cache_env`` (None = unset) —
    the helper mutates process-global jax config, so not in this one."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


_PLACE = ("from reflow_tpu.utils.runtime import place_compile_cache\n"
          "print(place_compile_cache())\n" + _PRINT_CACHE_DIR)


def test_compile_cache_left_alone_when_env_places_it(tmp_path):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper sets nothing
    there = str(tmp_path / "placed")
    assert _fresh_python(_PLACE, there) == [there, there]


def test_compile_cache_defaults_to_fixed_in_checkout_path():
    want = os.path.join(REPO, ".jax_cache")
    assert _fresh_python(_PLACE) == [want, want]


def test_importing_the_package_places_no_cache():
    code = ("import reflow_tpu, sys\n"
            "assert 'jax' not in sys.modules\n" + _PRINT_CACHE_DIR)
    assert _fresh_python(code) == ["None"]


def test_scheduler_counts_no_forced_sync_on_cpu_oracle():
    """read_table / sync ticks on the CPU executor are not forced device
    syncs at all: the counter stays 0."""
    import numpy as np

    from reflow_tpu import DirtyScheduler, FlowGraph
    from reflow_tpu.delta import DeltaBatch, Spec

    g = FlowGraph()
    src = g.source("s", Spec((), np.float32, key_space=8))
    red = g.reduce(src, "sum")
    g.sink(red, "out")
    sched = DirtyScheduler(g)
    sched.push(src, DeltaBatch(np.array([1]),
                               np.array([2.0], np.float32)))
    sched.tick()
    sched.read_table(red)
    assert sched.forced_syncs == 0  # cpu executor: no forced syncs
