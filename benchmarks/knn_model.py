"""The k-NN re-index's arithmetic, for its per-layer readers: what a
corpus rescan and one call of the Pallas top-k kernel must compute and
move, the chip's peaks by ``device_kind``, and what the program's own
counters and the device trace say a run did. No JAX outside
``xplane._load``.

Every function that reads a run returns ``None`` on a program that has
no such counter, span or kernel name, as the parent of PR 26 has not: the
reader then leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import pump_spans as ps
import xplane

#: peaks by ``jax.Device.device_kind``. An unknown kind is an error: a
#: share of a peak nobody wrote down is not a number.
PEAKS: Dict[str, Dict[str, float]] = {
    # Cloud TPU v5e, per chip: 197 TFLOP/s bf16, 819 GB/s HBM2e
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}

#: the operation name the program gives its Pallas top-k kernel
#: (``reflow_tpu.kernels.topk.KERNEL_NAME``, copied: the parent has none)
TOPK_KERNEL = "reflow_topk"
#: what stands in for it in the ``--tiny`` CPU rehearsal, where the
#: program selects with ``lax.top_k`` (XLA:CPU's only custom call in
#: this graph) and no kernel runs
TOPK_ON_CPU = "custom-call"

_ITEM = {"int8": 1, "bfloat16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise ValueError(f"no peaks on file for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}")
    return PEAKS[device_kind]


def rescan_flops(cfg: dict) -> float:
    """One rescan scores every query against every corpus slot: a
    multiply and an add a term."""
    return 2.0 * cfg["queries"] * cfg["doc_slots"] * cfg["dim"]


def rescan_bytes(cfg: dict) -> float:
    """What one rescan must read from HBM once: the corpus table, its
    live mask (a byte a slot) and the queries. The score blocks between
    the matmul and the kernel are traffic a better program could keep on
    the chip, so they are not counted."""
    return float(cfg["doc_slots"] * cfg["dim"] * _ITEM[cfg["doc_dtype"]]
                 + cfg["doc_slots"]
                 + cfg["queries"] * cfg["dim"] * _ITEM[cfg["query_dtype"]])


def rescan_floor_s(cfg: dict, device_kind: str) -> float:
    """The roofline of one rescan: the longer of its arithmetic at the
    MXU's bf16 peak and its bytes at the HBM peak."""
    p = peaks(device_kind)
    return max(rescan_flops(cfg) / p["bf16_flops_per_s"],
               rescan_bytes(cfg) / p["hbm_bytes_per_s"])


def topk_call_bytes(cfg: dict) -> float:
    """What one kernel call of the rescan must touch. Since PR 27 the
    kernel reads the ``[queries, k]`` carry (float32 values, int32 ids)
    and the ``[queries, scan_chunk]`` float32 score chunk as they are,
    and writes ``[queries, k]`` values and ids. Counted here: the
    carry's values and the chunk in (``4 * queries * (k + scan_chunk)``)
    and values and ids out. Left out: the carry's ids in, ``4 * queries
    * k`` = 16 KB of 8.44 MB at the cell's sizes, so a share of the
    roofline taken from this reads 0.2 % low, never high. The count
    stays as PR 26 set it: a reading that moves on an unchanged program
    would be worse than one that is 0.2 % low."""
    q, k = cfg["queries"], cfg["k"]
    width = k + min(cfg["scan_chunk"], cfg["doc_slots"])
    return 4.0 * q * width + 8.0 * q * k


def device_kind(run) -> str:
    """The kind the roofline is taken against: the device's own, or in
    the CPU rehearsal (whose numbers are counts, never speeds) the one
    kind on file, so that the arithmetic runs."""
    import jax

    if run.trace is not None and run.trace["on_cpu"]:
        return next(iter(PEAKS))
    return jax.devices()[0].device_kind


# -- the program's counters, from its window_device spans ----------------


def _counted(run):
    """``(done, {counter: value summed over the nodes})`` of every
    ``window_device`` span that carries counters, in order."""
    out = []
    for s in run.spans:
        c = s["args"].get("counters") if s["name"] == "window_device" \
            else None
        if c:
            rows = list(c.values())
            out.append((s["t1"], [sum(r[i] for r in rows)
                                  for i in range(len(rows[0]))]))
    return sorted(out)


def counters_between(run, t0: float, t1: float) -> Optional[dict]:
    """By how much the KnnIndex counters (rescans, incremental ticks,
    rows folded) moved between the last window the device finished by
    ``t0`` and the last it finished by ``t1``, and those two times."""
    seen = _counted(run)
    lo = [x for x in seen if x[0] <= t0]
    hi = [x for x in seen if x[0] <= t1]
    if not lo or not hi or hi[-1][0] <= lo[-1][0]:
        return None
    (ta, a), (tb, b) = lo[-1], hi[-1]
    d = [y - x for x, y in zip(a, b)]
    return {"rescans": d[0], "incremental": d[1], "rows_folded": d[2],
            "t0": ta, "t1": tb}


_ONCE: Dict[tuple, object] = {}


def _once(fn):
    """Two readers share each quantity: computed (and said) once a run."""
    def cached(run):
        key = (fn.__name__, id(run))
        if key not in _ONCE:
            _ONCE[key] = fn(run)
        return _ONCE[key]
    return cached


@_once
def rescan_ms(run) -> Optional[float]:
    """Device busy time per rescanning tick over the traced stretch:
    the trace's busy share of its span, over the rescans a second the
    program's counters show between the first and the last window the
    device finished in the same stretch (the last 40 % of the window)."""
    if run.trace is None:
        return None
    moved = counters_between(
        run, run.t_open + 0.6 * (run.t_close - run.t_open), run.t_close)
    if moved is None or moved["rescans"] <= 0:
        return None
    per_s = moved["rescans"] / (moved["t1"] - moved["t0"])
    busy = run.trace["busy_s"] / run.trace["window_s"]
    ps.say(f"knn: {moved['rescans']} rescans, {moved['incremental']} "
           f"incremental ticks, {moved['rows_folded']} rows folded in the "
           f"traced {moved['t1'] - moved['t0']:.3f} s ({per_s:.3f} "
           f"rescans/s); device busy {100 * busy:.3f} %")
    return 1e3 * busy / per_s


# -- the kernel, from the device trace -------------------------------------

@_once
def kernel_time(run) -> Optional[Tuple[float, int]]:
    """``(seconds, calls)`` of the top-k kernel in the run's own trace,
    by its operation name, averaged over the devices; read once a run."""
    if run.trace is None:
        return None
    want = TOPK_ON_CPU if run.trace["on_cpu"] else TOPK_KERNEL
    path = ps.own_trace_path()
    got = None
    if path is not None:
        secs, calls, planes = 0.0, 0, 0
        for plane in xplane._load(path).planes:
            on_dev = plane.name.startswith("/device:")
            if on_dev == run.trace["on_cpu"]:
                continue
            lines = [ln for ln in plane.lines
                     if (ln.name == "XLA Ops" if on_dev
                         else ln.name.startswith("tf_XLA"))]
            for line in lines:
                hits = [e.duration_ns for e in line.events
                        if want in e.name and e.duration_ns > 0]
                secs += 1e-9 * sum(hits)
                calls += len(hits)
            planes += bool(on_dev and lines)   # a plane without operations
            #                                    is no device
        planes = max(planes, 1)
        if calls:
            got = (secs / planes, calls // planes)
            ps.say(f"knn: kernel {want!r}: {got[1]} calls, "
                   f"{got[0]:.6f} s, {1e6 * got[0] / got[1]:.3f} us a call")
    return got
