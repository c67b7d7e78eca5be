"""Offline frontier-decay model of the PageRank churn tick (numpy).

Reproduces the delta-vector loop's per-pass dynamics (tol-gated emission
diff over the 100k-node / 1M-edge graph) on the host, to size the budget
tiers against the REAL frontier: per pass it reports live frontier keys,
frontier edges, which gather tier the device loop would pick, and the
modeled gather/scatter row cost. This is the tool that says whether the
measured per-pass wall is physics (frontier edges / scatter rate) or
waste (tier misfit / dense fallback).

Run: python tools/simulate_decay.py   (pure numpy, ~20s)
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from reflow_tpu.executors.linear_fixpoint import _edge_budget_tiers
    from reflow_tpu.workloads import pagerank

    n_nodes, n_edges, churn, tol = 100_000, 1_000_000, 0.01, 1e-4
    damping = 0.85
    web = pagerank.WebGraph.random(n_nodes, n_edges, seed=7)
    arena_cap = pagerank.churn_arena_capacity(n_edges, churn)
    tiers = _edge_budget_tiers(arena_cap)
    print(f"arena {arena_cap}, tiers {tiers}")

    src, dst = web.src.copy(), web.dst.copy()
    deg = np.zeros(n_nodes, np.int64)
    np.add.at(deg, src, 1)

    def converge(r, emitted, src, dst, deg, trace=False):
        inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
        rows = []
        for it in range(200):
            contrib = np.zeros(n_nodes)
            np.add.at(contrib, dst, r[src] * inv[src])
            agg = (1.0 - damping) + damping * contrib
            changed = np.abs(agg - emitted) > tol
            if not changed.any():
                break
            emitted = np.where(changed, agg, emitted)
            r = emitted
            if trace:
                fkeys = changed & (deg > 0)
                fedges = int(deg[fkeys].sum())
                rows.append((int(changed.sum()), fedges))
        return emitted, rows

    # base convergence (phase-A analog of the initial build)
    emitted = np.zeros(n_nodes)
    emitted, _ = converge(np.ones(n_nodes), emitted, src, dst, deg)

    # one churn tick, matching WebGraph.churn exactly: rewire the DST of
    # 1% of edges (out-degree preserving — src and deg are untouched)
    rng = np.random.default_rng(99)
    ix = rng.choice(n_edges, max(1, int(churn * n_edges)), replace=False)
    dst[ix] = rng.integers(0, n_nodes, len(ix))
    _, rows = converge(emitted, emitted.copy(), src, dst, deg, trace=True)

    gs_rate = 74e6   # scatter/gather rows per second (measured, VPU)
    dense_rows = 3 * arena_cap          # gather + push + scatter full arena
    total_ms = 0.0
    total_edges = 0
    print(f"{'pass':>4} {'fkeys':>8} {'fedges':>9} {'tier':>8} "
          f"{'rows':>9} {'ms':>6}")
    for i, (fk, fe) in enumerate(rows):
        fit = [t for t in tiers if t >= fe]
        tier = min(fit) if fit else 0
        rows_proc = 3 * tier if tier else dense_rows
        ms = rows_proc / gs_rate * 1e3
        total_ms += ms
        total_edges += fe
        print(f"{i:>4} {fk:>8} {fe:>9} {tier or 'dense':>8} "
              f"{rows_proc:>9} {ms:>6.1f}")
    ideal_ms = 3 * total_edges / gs_rate * 1e3
    print(f"passes {len(rows)}, frontier edges {total_edges}")
    print(f"modeled loop {total_ms:.0f} ms; perfect-fit floor "
          f"{ideal_ms:.0f} ms")


if __name__ == "__main__":
    main()
