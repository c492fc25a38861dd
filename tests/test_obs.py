"""repro.obs: tracing must be free when disabled and invisible when enabled.

Covers the observability hard requirements: enabling tracing leaves every
controller numeric bit-identical on both engines, the disabled fast path
costs well under 2% of a controller run, the JSONL / Chrome ``trace_event``
exports round-trip, ``SolverStats`` / ``stage_times`` ride on
``ControllerResult`` with the shared phase-key schema, and the report CLI
aggregates self/cumulative time correctly.
"""

import dataclasses
import json
import time

import numpy as np
import pytest

from repro import obs
from repro.core import ControllerConfig, SolverConfig, Strategy, run_controller
from repro.core.fleet import FLEET_SPECS, make_fabric, make_trace
from repro.obs.report import format_table, main as report_main, summarize

CC = ControllerConfig(routing_interval_hours=12.0, topology_interval_days=3.0,
                      aggregation_days=3.0, k_critical=4)
SC = SolverConfig(stage1_method="scaled")
P999 = ("p999_mlu", "p999_alu", "p999_olr", "p999_stretch")
PHASE_KEYS = {"plan", "anchor", "solve", "score", "transition",
              "failures"}


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled and a clean buffer."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


@pytest.fixture(scope="module")
def tiny_fabric():
    return make_fabric(FLEET_SPECS[0])


@pytest.fixture(scope="module")
def tiny_trace(tiny_fabric):
    # short + coarse: enough epochs to exercise every phase, small enough
    # that the traced/untraced double runs stay cheap
    return make_trace(FLEET_SPECS[0], tiny_fabric, days=5.0,
                      interval_minutes=240.0)


def _run(fabric, trace, **over):
    return run_controller(fabric, trace, Strategy(nonuniform=False,
                                                  hedging=True),
                          dataclasses.replace(CC, **over), SC)


def _serve(fabric, trace, engine, solver_backend):
    """The served path: warm PDHG, Pallas scoring with burst loss."""
    from repro.burst import LossConfig
    from repro.serve import ServeConfig, StreamingController, TMStream

    cc = dataclasses.replace(CC, solver_backend=solver_backend,
                             backend="pallas", loss=LossConfig(seed=1))
    return StreamingController(
        fabric, TMStream.from_trace(trace),
        Strategy(nonuniform=False, hedging=True), cc, SC,
        serve=ServeConfig(auto_strategy=False)).run().result


# ---- tracing on/off parity (bit-identical results) --------------------------

@pytest.mark.parametrize("engine,backend", [("sequential", "scipy"),
                                            ("batched", "pdhg"),
                                            ("serve", "pdhg")])
def test_tracing_parity_bit_identical(tiny_fabric, tiny_trace, engine,
                                      backend):
    run = _serve if engine == "serve" else _run
    off = run(tiny_fabric, tiny_trace, engine=engine, solver_backend=backend)
    assert obs.events() == [], "a run with tracing off must record nothing"
    obs.enable()
    on = run(tiny_fabric, tiny_trace, engine=engine, solver_backend=backend)
    assert obs.events(), "enabled run must have recorded spans"
    obs.disable()
    for k in P999:
        assert on.summary[k] == off.summary[k], k
    np.testing.assert_array_equal(on.metrics.mlu, off.metrics.mlu)
    np.testing.assert_array_equal(on.metrics.alu, off.metrics.alu)
    np.testing.assert_array_equal(on.metrics.olr, off.metrics.olr)
    np.testing.assert_array_equal(on.metrics.stretch, off.metrics.stretch)
    if off.metrics.loss is not None:
        np.testing.assert_array_equal(on.metrics.loss, off.metrics.loss)
    assert on.n_routing_updates == off.n_routing_updates
    assert on.n_topology_updates == off.n_topology_updates
    # phase accounting exists in both modes with the same keys
    assert set(on.stage_times) == set(off.stage_times)


# ---- stage_times / SolverStats schema ---------------------------------------

def test_stage_times_schema_across_engines(tiny_fabric, tiny_trace):
    seq = _run(tiny_fabric, tiny_trace, engine="sequential",
               solver_backend="scipy")
    bat = _run(tiny_fabric, tiny_trace, engine="batched",
               solver_backend="pdhg")
    for res in (seq, bat):
        assert res.stage_times, "stage_times must be populated, not a stub"
        assert set(res.stage_times) <= PHASE_KEYS
        assert {"plan", "solve", "score"} <= set(res.stage_times)
        assert all(v >= 0.0 for v in res.stage_times.values())
    # scipy path has no PDHG telemetry; pdhg path must attach it
    assert seq.solver_stats is None
    st = bat.solver_stats
    assert st is not None and st.backend == "pdhg"
    assert st.max_iters == CC.pdhg_max_iters and st.tol == CC.pdhg_tol
    s1 = st.stages["stage1"]
    assert s1.n == bat.n_routing_updates  # one stage-1 solve per epoch
    assert all(1 <= i <= st.max_iters for i in s1.iters)
    assert all(np.isfinite(g) for g in s1.gaps)
    assert 0.0 <= st.frac_capped() <= 1.0
    d = st.to_dict(per_epoch=True)
    assert len(d["stages"]["stage1"]["iters"]) == s1.n
    assert set(d) == {"backend", "max_iters", "tol", "anchor_seconds",
                      "n_fallbacks", "frac_capped", "stages"}
    # summaries are JSON-serializable as stamped into bench artifacts
    json.dumps(d)


# ---- disabled-path overhead --------------------------------------------------

def test_disabled_overhead_under_two_percent(tiny_fabric, tiny_trace):
    t0 = time.perf_counter()
    _run(tiny_fabric, tiny_trace, engine="sequential", solver_backend="scipy")
    wall = time.perf_counter() - t0
    # count the spans+events one run emits
    obs.enable()
    obs.clear()
    _run(tiny_fabric, tiny_trace, engine="sequential", solver_backend="scipy")
    n_events = len(obs.events())
    obs.disable()
    # cost of the disabled fast path, measured directly
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs.span("x", a=1):
            pass
    per_span = (time.perf_counter() - t0) / reps
    assert per_span * n_events < 0.02 * wall, (
        f"disabled tracing would cost {per_span * n_events:.4f}s of a "
        f"{wall:.2f}s run ({n_events} events at {per_span * 1e9:.0f}ns)")


def test_disabled_span_is_singleton_noop():
    assert obs.span("a") is obs.span("b", k=1)  # no allocation when disabled
    with obs.span("a"):
        with obs.span("b") as sp:
            sp.set(late=1)
    obs.event("decision", x=1)
    obs.counter("c", 2.0)
    assert obs.events() == []


# ---- one clock with the profiler, compiles as spans --------------------------

def _trace_reduce():
    """``chipbench/trace_reduce.py``, the benchmark's own reduction."""
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parents[1] / "chipbench"
            / "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spans_land_on_the_profiler_clock(tmp_path):
    import jax

    tr = _trace_reduce()
    obs.enable()
    obs.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        anchor = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            pass
        with obs.span("probe.span"):
            time.sleep(0.002)
        phases = obs.PhaseTimes()
        with phases("solve", "probe.timed"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    recs = {e["name"]: e for e in obs.events()}
    pd = tr.load(tmp_path)
    host = {n: (a, b) for n, a, b in tr.host_events(pd, prefix="probe.")}
    assert set(host) == {"probe.span", "probe.timed"}
    off = tr.clock_offset(pd, anchor)
    for name, (a, b) in host.items():
        r = recs[name]
        assert abs(r["ts_us"] * 1e3 + off - a) < 200e3, name
        assert abs((r["ts_us"] + r["dur_us"]) * 1e3 + off - b) < 200e3, name


def test_compile_is_a_span_only_when_tracing():
    import jax

    f = jax.jit(lambda x: x * 3.0 + 1.0)
    obs.enable()  # hooks the compile listener ...
    obs.disable()  # ... which then records nothing
    f(np.ones(7, np.float32))
    assert obs.events() == []
    obs.enable()
    with obs.span("outer"):
        f(np.ones(9, np.float32))  # a new shape: one compile
        f(np.ones(9, np.float32))  # cached in memory: none
    comp = [e for e in obs.events() if e["name"] == "jax.compile"]
    assert len(comp) == 1
    (c,) = comp
    assert c["ph"] == "X" and c["dur_us"] > 0 and "cached" in c["args"]
    outer = next(e for e in obs.events() if e["name"] == "outer")
    assert outer["ts_us"] <= c["ts_us"]
    assert c["ts_us"] + c["dur_us"] <= outer["ts_us"] + outer["dur_us"]


# ---- export round-trips ------------------------------------------------------

def _synthetic_buffer():
    obs.enable()
    obs.clear()
    with obs.span("outer", fabric="F1"):
        with obs.span("inner"):
            time.sleep(0.002)
        obs.event("decision", applied=True)
    obs.counter("queue", 3.0)


def test_jsonl_round_trip(tmp_path):
    _synthetic_buffer()
    recs = obs.events()
    path = tmp_path / "t.jsonl"
    obs.export_jsonl(path)
    back = obs.read_jsonl(path)
    assert back == json.loads(json.dumps(recs))  # byte-stable schema
    phs = [r["ph"] for r in back]
    assert phs.count("X") == 2 and "i" in phs and "C" in phs
    inner, outer = (next(r for r in back if r["name"] == n)
                    for n in ("inner", "outer"))
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["dur_us"] >= inner["dur_us"] >= 2000.0
    assert outer["args"] == {"fabric": "F1"}


def test_chrome_trace_schema(tmp_path):
    _synthetic_buffer()
    path = tmp_path / "t.chrome.json"
    doc = obs.export_chrome_trace(path)
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(doc))
    assert loaded["displayTimeUnit"] == "ms"
    evs = loaded["traceEvents"]
    assert len(evs) == 4
    for ev in evs:
        assert {"ph", "name", "cat", "pid", "tid", "ts"} <= set(ev)
        assert ev["cat"] == "repro"
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    counter = next(ev for ev in evs if ev["ph"] == "C")
    assert counter["args"] == {"value": 3.0}
    # converting a saved JSONL trace must agree with the live buffer
    jl = tmp_path / "t.jsonl"
    obs.export_jsonl(jl)
    assert obs.chrome_trace_events(obs.read_jsonl(jl)) == evs


def test_ring_buffer_caps_at_capacity():
    obs.enable(capacity=8)
    for i in range(20):
        with obs.span(f"s{i}"):
            pass
    recs = obs.events()
    assert len(recs) == 8
    assert recs[-1]["name"] == "s19"  # keeps the newest events
    obs.enable(capacity=65536)  # restore the default for later tests


# ---- report CLI --------------------------------------------------------------

def test_report_summarize_self_time():
    # outer [0, 100ms] contains inner [10, 40ms]: self(outer) = 70ms
    recs = [
        {"ph": "X", "name": "outer", "ts_us": 0.0, "dur_us": 100000.0,
         "tid": 1, "depth": 0},
        {"ph": "X", "name": "inner", "ts_us": 10000.0, "dur_us": 30000.0,
         "tid": 1, "depth": 1},
        {"ph": "i", "name": "ev", "ts_us": 5.0, "dur_us": 0.0, "tid": 1,
         "depth": 1},
    ]
    rows = {r["name"]: r for r in summarize(recs)}
    assert rows["outer"]["total_ms"] == pytest.approx(100.0)
    assert rows["outer"]["self_ms"] == pytest.approx(70.0)
    assert rows["inner"]["self_ms"] == pytest.approx(30.0)
    assert rows["outer"]["p50_ms"] == pytest.approx(100.0)
    table = format_table(summarize(recs))
    assert "outer" in table and "inner" in table


def test_report_cli_end_to_end(tmp_path, capsys):
    _synthetic_buffer()
    jl = tmp_path / "t.jsonl"
    obs.export_jsonl(jl)
    obs.disable()
    chrome = tmp_path / "t.chrome.json"
    assert report_main([str(jl), "--chrome", str(chrome)]) == 0
    out = capsys.readouterr().out
    assert "outer" in out and "self_ms" in out
    assert json.loads(chrome.read_text())["traceEvents"]
    assert report_main([str(jl), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["n_events"] == 4 and payload["rows"]
