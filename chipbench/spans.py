"""Nesting of the program's ``repro.obs`` spans, for the span readers.

A reader gets the window's complete events (``ctx["obs"]``: dicts with
``name``, ``ts_us``, ``dur_us`` and ``tid``).  One span holds another when
both ran on the same thread and the first one's interval contains the
second's.  A span whose name ends in ``.wait`` holds only a device-to-host
copy: there the host waited on the device.
"""

from __future__ import annotations

import bisect

EPS_US = 1e-3  # timestamps are whole ns, printed as float microseconds


def named(spans, name: str) -> list:
    return [e for e in spans if e["name"] == name]


def is_wait(e) -> bool:
    return e["name"].endswith(".wait")


def waits(spans) -> list:
    return [e for e in spans if is_wait(e)]


def total_us(spans) -> float:
    return float(sum(e["dur_us"] for e in spans))


def inside(outer, inner) -> list:
    """For each span of ``outer``, the spans of ``inner`` it holds."""
    by_tid: dict = {}
    for e in sorted(inner, key=lambda e: e["ts_us"]):
        by_tid.setdefault(e["tid"], []).append(e)
    starts = {t: [e["ts_us"] for e in es] for t, es in by_tid.items()}
    out = []
    for o in outer:
        es = by_tid.get(o["tid"], [])
        lo, hi = o["ts_us"], o["ts_us"] + o["dur_us"]
        k = bisect.bisect_left(starts.get(o["tid"], []), lo - EPS_US)
        held = []
        for e in es[k:]:
            if e["ts_us"] > hi + EPS_US:
                break
            if e["ts_us"] + e["dur_us"] <= hi + EPS_US:
                held.append(e)
        out.append(held)
    return out


def host_us(spans, name: str):
    """Total time of the spans called ``name`` less the waits they hold, or
    None when the window has no such span or the program marks no waits."""
    outer, w = named(spans, name), waits(spans)
    if not outer or not w:
        return None
    return total_us(outer) - sum(total_us(h) for h in inside(outer, w))


def per_epoch_ms(us, ctx):
    """Microseconds of the window per decided epoch, in ms; None when there
    is nothing to divide."""
    epochs = ctx["layer"]["epochs"]
    if us is None or not epochs:
        return None
    return us * 1e-3 / epochs
