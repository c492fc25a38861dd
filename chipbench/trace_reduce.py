"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` a ``jax.profiler`` trace wrote, read with
``jax.profiler.ProfileData`` (nothing else).  The traced window is the span
of the benchmark's host annotation ``chipbench.traced``.  Output:

* ``busy_s`` — the union of the intervals in which an operation ran on a
  device (line ``XLA Ops`` of each ``/device:TPU:*`` plane), clipped to the
  window, averaged over the devices that ran anything;
* ``window_s`` — the window's length;
* ``op_s`` — device seconds per operation name (summed durations; the name
  is the HLO instruction's, so a Pallas kernel shows under the name of the
  function that calls ``pallas_call``);
* ``idle_gaps`` — the device's idle time inside the window, attributed to
  what the host was doing: each gap goes to the innermost host span that
  covers its midpoint (the benchmark's annotations and, where given, the
  program's ``obs`` spans put on the trace's clock), ``"host:other"`` when
  none does.
"""

from __future__ import annotations

import bisect
import heapq
import pathlib

import numpy as np

WINDOW = "chipbench.traced"
ANCHOR = "chipbench.anchor"
OPS_LINE = "XLA Ops"


def load(trace_dir):
    """The ProfileData of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def host_events(pd, prefix: str = "chipbench.") -> list:
    """``(name, start_ns, end_ns)`` of host events whose name starts with
    ``prefix``, in start order."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda e: e[1])


def op_name(event_name: str) -> str:
    """An op's name without its HLO text and instance number:
    ``"%linkload_pallas_fleet.1 = (f32[...]..."`` -> ``linkload_pallas_fleet``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def device_ops(pd) -> dict:
    """Device plane name -> ``(op name, start_ns, end_ns)`` of its ops."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [(op_name(ev.name), ev.start_ns, ev.end_ns)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        if ops:
            out[plane.name] = ops
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals, clipped to ``[lo, hi)``."""
    iv = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                if b > lo and a < hi)
    merged = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def timeline(spans) -> tuple:
    """``(starts, labels)``: the host's time cut into segments, each labelled
    with the innermost (latest-starting) span that covers it, or
    ``"host:other"``.  Look a time up with :func:`label`."""
    bounds = sorted({x for _, a, b in spans for x in (a, b)})
    by_start = sorted(spans, key=lambda s: s[1])
    active, starts, labels, i = [], [], [], 0
    for t in bounds:
        while i < len(by_start) and by_start[i][1] <= t:
            name, a, b = by_start[i]
            heapq.heappush(active, (-a, b, name))
            i += 1
        while active and active[0][1] <= t:
            heapq.heappop(active)
        # spans that ended below the top leave when they reach it
        starts.append(t)
        labels.append(active[0][2] if active else "host:other")
    return starts, labels


def label(tl: tuple, t: float) -> str:
    starts, labels = tl
    k = bisect.bisect_right(starts, t) - 1
    return labels[k] if k >= 0 else "host:other"


def reduce(pd, host_spans=(), top: int = 10) -> dict:
    """The numbers listed in the module docstring.  ``host_spans`` are
    extra ``(name, start_ns, end_ns)`` host spans on the trace's clock."""
    marks = host_events(pd)
    win = [(a, b) for n, a, b in marks if n == WINDOW]
    if not win:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = win[0]
    devs = device_ops(pd)
    busy, op_s, gaps = [], {}, {}
    tl = timeline([m for m in marks if m[0] != WINDOW] + list(host_spans))
    for ops in devs.values():
        merged = union([(a, b) for _, a, b in ops], lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in ops:
            if b > lo and a < hi:
                op_s[name] = op_s.get(name, 0.0) + (min(b, hi) - max(a, lo))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                k = label(tl, 0.5 * (a + b))
                gaps[k] = gaps.get(k, 0.0) + (b - a) / len(devs)
    n = max(len(devs), 1)
    by = lambda d: sorted(([k, v * 1e-9] for k, v in d.items()),  # noqa: E731
                          key=lambda kv: -kv[1])[:top]
    return {"busy_s": float(np.sum(busy)) * 1e-9 / n,
            "window_s": (hi - lo) * 1e-9,
            "devices": len(devs),
            "op_s": {k: v * 1e-9 / n for k, v in op_s.items()},
            "device_ops": by({k: v / n for k, v in op_s.items()}),
            "idle_gaps": by(gaps)}


def clock_offset(pd, anchor_perf_ns: int) -> float:
    """Trace time minus ``time.perf_counter_ns()``, from the benchmark's
    ``chipbench.anchor`` annotation entered at ``anchor_perf_ns``."""
    a = [s for n, s, _ in host_events(pd) if n == ANCHOR]
    if not a:
        raise ValueError(f"the trace has no {ANCHOR!r} annotation")
    return a[0] - anchor_perf_ns
