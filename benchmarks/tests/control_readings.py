#!/usr/bin/env python3
"""Readings the limits of ``correct`` were set from, at a cell's own
size: the control (the reference held in bfloat16, put in the program's
place) against the float64 / exact reference, over several seeds.

    python benchmarks/tests/control_readings.py <cell> <seed> [<seed> ...]

No JAX: the reference and its control are NumPy. The sound runs' side of
each limit is what ``run.py`` prints on its ``check`` lines."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import manifest as mf              # noqa: E402
import traffic_plan as tp          # noqa: E402


def main(cell_name: str, seeds) -> None:
    man = mf.load_manifest()
    cell = mf.Cell(man, cell_name)
    cfg = mf.load_json(cell.config_file)
    cfg = mf.with_tiny(cfg, False)
    traffic = mf.with_tiny(mf.load_json(cell.traffic_file), False)
    mod = mf.load_module(cell.config_module, cell.config_name)
    for seed in seeds:
        t0 = time.time()
        stream = mod.Stream(cfg, seed, traffic["producers"])
        stream.load()
        ref = mod.Reference(stream)
        for group in tp.plan_warm(stream, traffic):
            for m in group:
                ref.apply(m.ref)
        n = min(tp.per_lane(traffic, man["run_seconds"]),
                30000 // traffic["producers"])
        for _ in range(n):
            for lane in range(traffic["producers"]):
                ref.apply(stream.next(lane).ref)
        want = ref.expected()
        for c in mod.compare(cfg, ref.expected("bfloat16"), want):
            print(f"control {cell_name} seed {seed}: {c.line()} "
                  f"[{time.time() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
