"""One run of one cell: set-up, the measured window, metrics and the check.

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration (its ``file``) and traffic mix (``chipbench/traffic/<mix>.json``)
and lists its metrics; the configuration's ``entry`` names the driver
(``chipbench/drivers/<entry>.py``), and each per-layer metric has a reader
``chipbench/metrics/<metric>.py``.  A driver module has a ``Cell`` with
``setup()``, ``trace_unit()``, ``window(seconds)``, ``layer_context()``,
``finish()`` and ``answers(served)``; a reader has ``read(ctx)``, which
returns a number or ``None`` when the run gave it nothing to read.

A ``--trace 1`` run profiles one short unit of the cell's work between
set-up and the window (``trace_unit``: 8 routing epochs),
then runs the window unprofiled with the program's ``obs`` spans on: the
device numbers come from the profiled unit, the span and counter metrics
from the window.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"
TRACE_DIR = ROOT / ".chipbench" / "trace"
CACHE_DIR = ROOT / ".jax_cache"


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration, mix
    and the metrics it reports."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    mine = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {"workload": wl,
            "config": json.loads((ROOT / conf["file"]).read_text()),
            "mix": json.loads((HERE / "traffic" / f"{wl['traffic']}.json")
                              .read_text()),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program cached however short its compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _traced(c) -> tuple:
    """Run the driver's trace unit under the profiler (no Python tracer):
    the clock anchor, then the window annotation around the unit.  Returns
    ``(units, anchor perf_counter_ns)``."""
    import jax

    from chipbench import trace_reduce

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        anchor = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
            pass
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            units = c.trace_unit()
    finally:
        jax.profiler.stop_trace()
    return units, anchor


def _reduce_trace(anchor_ns: int, spans: list) -> dict:
    from chipbench import trace_reduce

    pd = trace_reduce.load(TRACE_DIR)
    off = trace_reduce.clock_offset(pd, anchor_ns)
    host = [(e["name"], e["ts_us"] * 1e3 + off,
             (e["ts_us"] + e["dur_us"]) * 1e3 + off) for e in spans]
    return trace_reduce.reduce(pd, host_spans=host)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        cell: dict | None = None, log=None) -> dict:
    """Set up, measure and check one run; returns the result's fields."""
    from repro import obs

    from chipbench import check
    from chipbench.compile_clock import CompileClock

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = cell or load_cell(name)
    cfg = cell["config"]
    clock = CompileClock()
    driver = load_module(HERE / "drivers" / f"{cfg['entry']}.py")
    c = driver.Cell(cfg, cell["mix"], seed)
    c.setup()
    setup_s = time.perf_counter() - t_start
    compiles0 = clock.snapshot()
    log(f"chipbench: {name} seed {seed}: set-up {setup_s:.3f} s, "
        f"{compiles0[1]} compiles, {compiles0[2]} cache hits")

    if trace:  # a short profiled unit with the program's spans, then the
        obs.enable(capacity=1 << 20)  # window with spans only
        obs.clear()
        units, anchor_ns = _traced(c)
        unit_spans = [e for e in obs.events() if e["ph"] == "X"]
        obs.clear()
    win = c.window(seconds)
    spans = [e for e in obs.events() if e["ph"] == "X"] if trace else []
    obs.disable()
    compiles = clock.snapshot()[1] - compiles0[1]
    device = device_info()
    log(f"chipbench: window {win['wall_s']:.3f} s, {win['attempted']} "
        f"attempted, {compiles} compiles in the window, epochs at the "
        f"iteration cap {win['capped']}"
        + (f", topology epochs {win['topology_s']} s" if "topology_s" in win
           else ""))

    result_metrics, breakdown = {}, None
    if not trace:
        for m in cell["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else \
                win["end_to_end"][m["name"]]
            result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        tr = _reduce_trace(anchor_ns, unit_spans)
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
        ctx = {"obs": spans, "layer": c.layer_context(), "trace": tr,
               "traced_units": units, "device_kind": device["kind"]}
        for m in cell["per_layer"]:
            v = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    served = c.finish()
    t0 = time.perf_counter()
    values = check.readings(c.answers(served), cfg, seed)
    correct, rows = check.judge(values, cfg["limits"])
    log(f"chipbench: check {time.perf_counter() - t0:.3f} s, correct {correct}")
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": result_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window"] = {"wall_s": win["wall_s"], "compiles": compiles,
                     "units": win["days"], "capped": win["capped"]}
    out["reported"] = {k: v for k, v in values.items()
                       if k not in cfg["limits"]}
    out["checks"] = rows
    return out
