"""Reductions that several per-layer metrics share.

Each returns None where the run gave it nothing to read, and the harness
then leaves the metric out of the result; none returns 0 in place of a
share it could not measure.
"""

from __future__ import annotations

from perfbench import roofline


def idle_pct(r) -> float | None:
    """Idle share of the cell's devices over the traced window, averaged
    over the devices."""
    return r.summary.idle_pct() if r.summary.busy_s else None


def kernel_roofline_pct(r, module: str, calls: str, count) -> float | None:
    """Roofline share of jitted module ``module``: the bytes ``count``
    gives for each recorded call in ``counters[calls]``, at the chip's
    HBM peak, over the module's device time in the trace."""
    recorded = r.counters.get(calls) or []
    seconds = r.module_s(module)
    if not recorded or seconds <= 0:
        return None
    return roofline.roofline_pct(
        seconds=seconds, hbm_bytes=sum(count(*c) for c in recorded),
        chip=r.peaks)


def kernel_route_pct(r) -> float | None:
    """Share of the decode plane's routing decisions that took a kernel:
    decoded column chunks, predicates and compacted columns."""
    routes = []
    for rep in r.counters.get("reports", []):
        routes += rep.get("columns", {}).values()
        if "predicate" in rep:
            routes.append(rep["predicate"])
        routes += rep.get("compact", {}).values()
    if not routes:
        return None
    return 100.0 * sum(x == "kernel" for x in routes) / len(routes)


def per(total: float, base: float, scale: float = 1.0) -> float | None:
    """``total`` per ``scale`` units of ``base``; None where there is no
    base."""
    if not base:
        return None
    return total / (base / scale)
