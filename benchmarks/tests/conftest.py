"""Tests of the benchmark's own files. Not part of tier-1 (``tests/``):
run by hand and in rehearsal,

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They need no chip: the end-to-end ones drive the ``--tiny`` CPU form.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(config: str, mix: str):
    """A configuration and a mix at their ``tiny`` sizes, with the
    configuration's module: ``(cfg, traffic, mod)``."""
    import manifest as mf

    cfg = mf.with_tiny(mf.load_json(os.path.join(
        BENCH, "configs", config + ".json")), True)
    traffic = mf.with_tiny(mf.load_json(os.path.join(
        BENCH, "traffic", mix + ".json")), True)
    mod = mf.load_module(os.path.join(BENCH, "configs", config + ".py"),
                         config)
    return cfg, traffic, mod
