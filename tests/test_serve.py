"""Streaming controller (repro.serve): replay parity, warm-start
correctness, rolling-window incrementality, and latency telemetry.

The load-bearing contract is **replay parity**: streaming over a recorded
trace must reproduce the offline batch engine's decisions and metrics —
exactly on the scipy backend (identical LP pipelines, identical seeds),
within solver tolerance on PDHG.  The warm start is only allowed to change
how *fast* PDHG converges, never what it converges to.
"""

import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core.controller import ControllerConfig, run_controller
from repro.core.engine import _pad_tms, _solve_routing_scipy, routing_solver_for
from repro.core.solver import SolverConfig, Strategy
from repro.serve import (RollingWindow, ServeConfig, StreamingController,
                        TMStream)
from repro.transition import TransitionConfig

CC = ControllerConfig(routing_interval_hours=12.0, topology_interval_days=3.0,
                      aggregation_days=3.0, k_critical=4)
SC = SolverConfig(stage1_method="scaled")


def _stream_run(fabric, trace, strat, cc, sc=SC, warm=True, slo=None):
    ctrl = StreamingController(
        fabric, TMStream.from_trace(trace), strat, cc, sc,
        serve=ServeConfig(warm_start=warm, auto_strategy=False,
                          latency_slo_s=slo))
    return ctrl.run()


# ---- rolling window ---------------------------------------------------------


def test_rolling_window_matches_trace_slices(rng):
    demand = rng.random((40, 12))
    win = RollingWindow(capacity=7, n_commodities=12)
    for t in range(demand.shape[0]):
        win.push(demand[t])
        lo = max(0, t + 1 - 7)
        expect = demand[lo : t + 1]
        np.testing.assert_array_equal(win.view(), expect)
        np.testing.assert_allclose(win.mean(), expect.mean(axis=0),
                                   rtol=0, atol=1e-9)
    assert win.full and len(win) == 7


def test_rolling_window_sum_stays_exact_over_many_wraps(rng):
    # thousands of pushes with adversarial magnitudes: the incrementally
    # maintained sum must track an exact recompute (periodic refresh bounds
    # float cancellation drift)
    win = RollingWindow(capacity=13, n_commodities=5)
    rows = rng.random((5000, 5)) * np.logspace(-3, 6, 5)
    for row in rows:
        win.push(row)
    np.testing.assert_allclose(win.mean(), win.view().mean(axis=0),
                               rtol=0, atol=1e-9)


def test_rolling_window_rejects_bad_shapes():
    win = RollingWindow(capacity=3, n_commodities=4)
    with pytest.raises(ValueError):
        win.push(np.zeros(5))
    with pytest.raises(ValueError):
        RollingWindow(capacity=0, n_commodities=4)


# ---- warm-start correctness -------------------------------------------------


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_warm_start_converges_to_cold_objective(small_fabric, small_trace,
                                                precision):
    """Warm-started PDHG must reach the same certified objective as a cold
    start (the exit is gated by the duality-gap certificate either way), and
    both must agree with the scipy LP ground truth."""
    fabric, trace = small_fabric, small_trace
    from repro.core import clustering
    from repro.core.graph import uniform_topology
    from repro.core.rounding import realize

    solver = routing_solver_for(fabric, CC.k_critical, CC.pdhg_max_iters,
                                CC.pdhg_tol, precision)
    caps = fabric.capacities(realize(fabric, uniform_topology(fabric))[0])
    tol = CC.pdhg_tol if precision == "f32" else 2 * CC.pdhg_tol
    state = None
    for epoch, start in enumerate(range(36, 36 + 12, 6)):
        tms = _pad_tms(clustering.critical_tms(
            trace.demand[start - 36 : start], k=CC.k_critical, seed=epoch),
            CC.k_critical)
        warm_out, state = solver.solve_routing_warm(
            tms, caps, hedging=True, delta=0.05, anchor_state=state)
        cold_out, _ = solver.solve_routing_warm(
            tms, caps, hedging=True, delta=0.05, anchor_state=None)
        _, u_ref, _ = _solve_routing_scipy(fabric, tms, SC, caps, 0.05)
        for out in (warm_out, cold_out):
            assert np.isfinite(out["u_star"])
            assert out["u_star"] == pytest.approx(u_ref, rel=tol)
        assert warm_out["u_star"] == pytest.approx(cold_out["u_star"],
                                                   rel=tol)
        # the warm state must carry every stage's iterates once hedged
        assert state.f2 is not None and state.y3 is not None


def test_warm_start_only_changes_iterations(small_fabric, small_trace):
    """End-to-end: warm vs cold streaming runs agree on the metrics to
    solver tolerance while the warm run spends no more stage-1 iterations."""
    cc = dataclasses.replace(CC, solver_backend="pdhg")
    strat = Strategy(nonuniform=False, hedging=True)
    warm = _stream_run(small_fabric, small_trace, strat, cc, warm=True)
    cold = _stream_run(small_fabric, small_trace, strat, cc, warm=False)
    assert warm.result.summary["p999_mlu"] == pytest.approx(
        cold.result.summary["p999_mlu"], rel=5 * cc.pdhg_tol)
    w = np.asarray(warm.result.solver_stats.stages["stage1"].iters)
    c = np.asarray(cold.result.solver_stats.stages["stage1"].iters)
    assert w.size == c.size and w.size > 0
    assert np.median(w) <= np.median(c)
    savings = obs.warm_start_savings(warm.result.solver_stats,
                                     cold.result.solver_stats)
    assert savings["stage1"]["iters_ratio"] <= 1.0
    assert savings["overall"]["cold_median_iters"] > 0


# ---- replay parity ----------------------------------------------------------


def test_streaming_replay_parity_scipy(small_fabric, small_trace):
    """scipy backend: streaming is bit-for-bit the offline batch engine."""
    strat = Strategy(nonuniform=True, hedging=True)
    off = run_controller(small_fabric, small_trace, strat, CC, SC)
    res = _stream_run(small_fabric, small_trace, strat, CC)
    on = res.result
    assert on.n_routing_updates == off.n_routing_updates
    assert on.n_topology_updates == off.n_topology_updates
    assert on.n_skipped_topology == off.n_skipped_topology
    np.testing.assert_array_equal(on.final_topology, off.final_topology)
    np.testing.assert_allclose(on.metrics.mlu, off.metrics.mlu, atol=1e-12)
    np.testing.assert_allclose(on.metrics.alu, off.metrics.alu, atol=1e-12)
    np.testing.assert_allclose(on.metrics.stretch, off.metrics.stretch,
                               atol=1e-12)
    assert on.transit_fraction == pytest.approx(off.transit_fraction,
                                                abs=1e-12)
    assert len(res.decisions) == off.n_routing_updates


@pytest.mark.slow
def test_streaming_replay_parity_with_transitions(small_fabric, small_trace):
    """The §4.6 gate and drain-staged scoring survive the move online: with
    transitions enabled, streaming still reproduces the offline engine."""
    cc = dataclasses.replace(
        CC, transition=TransitionConfig(n_panels=4, stage_intervals=1))
    strat = Strategy(nonuniform=True, hedging=True)
    off = run_controller(small_fabric, small_trace, strat, cc, SC)
    res = _stream_run(small_fabric, small_trace, strat, cc)
    on = res.result
    assert on.n_topology_updates == off.n_topology_updates
    assert on.n_skipped_topology == off.n_skipped_topology
    assert len(on.transition_log) == len(off.transition_log)
    for a, b in zip(on.transition_log, off.transition_log):
        assert a["applied"] == b["applied"]
    np.testing.assert_allclose(on.metrics.mlu, off.metrics.mlu, atol=1e-12)


def test_streaming_replay_parity_pdhg(small_fabric, small_trace):
    """PDHG backend: same decisions, summaries within solver tolerance."""
    cc = dataclasses.replace(CC, solver_backend="pdhg")
    strat = Strategy(nonuniform=False, hedging=True)
    off = run_controller(small_fabric, small_trace, strat, cc, SC)
    res = _stream_run(small_fabric, small_trace, strat, cc)
    on = res.result
    assert on.n_routing_updates == off.n_routing_updates
    assert on.metrics.mlu.size == off.metrics.mlu.size
    for key in ("p999_mlu", "p999_alu"):
        assert on.summary[key] == pytest.approx(off.summary[key],
                                                rel=5 * cc.pdhg_tol)


# ---- latency / telemetry ----------------------------------------------------


def test_serve_latency_and_metrics(small_fabric, small_trace):
    strat = Strategy(nonuniform=False, hedging=True)
    obs.metrics.enable()
    try:
        res = _stream_run(small_fabric, small_trace, strat, CC, slo=10.0)
        snap = obs.metrics.snapshot()
    finally:
        obs.metrics.disable()
    assert res.latencies_s.shape == (len(res.decisions),)
    assert np.all(res.latencies_s > 0)
    q = res.latency_quantiles()
    assert 0 < q["p50_s"] <= q["p99_s"] <= q["max_s"]
    assert res.intervals_per_s > 0
    assert res.n_intervals == small_trace.n_intervals
    hists = [h for h in snap["histograms"]
             if h["name"] == "serve.time_to_new_weights_s"]
    assert hists and hists[0]["count"] == len(res.decisions)
    assert any(c["name"] == "serve.decisions" for c in snap["counters"])
    gauges = [g for g in snap["gauges"]
              if g["name"] == "serve.latency_slo_burn"]
    assert gauges and gauges[0]["value"] == 0.0  # 10s SLO never burned


# ---- the served epoch as a span tree -----------------------------------------


@pytest.fixture(scope="module")
def traced_serve(small_fabric, small_trace):
    """A nonuniform+hedging run on PDHG with Pallas scoring and burst loss,
    with tracing on: its decisions and its trace events."""
    from repro.burst import LossConfig

    cc = dataclasses.replace(CC, solver_backend="pdhg", backend="pallas",
                             loss=LossConfig(seed=3))
    obs.enable()
    obs.clear()
    try:
        res = _stream_run(small_fabric, small_trace,
                          Strategy(nonuniform=True, hedging=True), cc)
        spans = obs.events()
    finally:
        obs.disable()
        obs.clear()
    return res, spans


def _held(outer, spans, name):
    """The spans called ``name`` that ``outer`` holds (same thread, inside
    its interval)."""
    end = outer["ts_us"] + outer["dur_us"] + 1e-3
    return [e for e in spans if e["name"] == name
            and e["tid"] == outer["tid"] and e is not outer
            and e["ts_us"] >= outer["ts_us"] - 1e-3
            and e["ts_us"] + e["dur_us"] <= end]


def _named(spans, name):
    return [e for e in spans if e["name"] == name]


def test_serve_epoch_holds_the_layer_spans(traced_serve):
    res, spans = traced_serve
    epochs = _named(spans, "serve.epoch")
    assert len(epochs) == len(res.decisions) > 2
    assert [e["args"]["epoch"] for e in epochs] == [
        d.epoch for d in res.decisions]
    assert [e["args"]["start"] for e in epochs] == [
        d.start for d in res.decisions]
    for i, ep in enumerate(epochs):
        # the first epoch has no finished block to score
        layers = ("serve.plan", "serve.solve") + (("serve.score",) if i
                                                  else ())
        held = {n: _held(ep, spans, n) for n in layers}
        assert all(len(v) == 1 for v in held.values()), (i, held)
        covered = sum(v[0]["dur_us"] for v in held.values())
        assert covered >= 0.95 * ep["dur_us"], (i, covered, ep["dur_us"])
    names = {e["name"] for e in spans}
    for gone in ("serve.interval", "serve.replan", "serve.decision"):
        assert gone not in names


def test_serve_topology_epochs_hold_the_topology_span(traced_serve):
    res, spans = traced_serve
    epochs = _named(spans, "serve.epoch")
    topo = [d.topology_solved for d in res.decisions]
    assert any(topo) and not all(topo)
    for ep, solved in zip(epochs, topo):
        (plan,) = _held(ep, spans, "serve.plan")
        held = _held(plan, spans, "serve.plan.topology")
        assert len(held) == int(solved)
        assert len(_held(plan, spans, "serve.plan.critical_tms")) == 1
        assert len(_held(plan, spans, "serve.plan.delta")) == 1
        if solved:
            assert held[0]["args"]["highs_s"] > 0


def test_serve_solve_and_score_mark_their_waits(traced_serve):
    _, spans = traced_serve
    for solve in _named(spans, "serve.solve"):
        waits = _held(solve, spans, "jaxlp.wait")
        assert waits and {w["args"]["stage"] for w in waits} == {1, 2, 3}
        for name in ("serve.solve.prepare", "jaxlp.warm_inputs",
                     "jaxlp.warm_stage1", "serve.solve.fallback",
                     "serve.solve.weights"):
            assert len(_held(solve, spans, name)) == 1, name
    for score in _named(spans, "serve.score"):
        for name in ("score.linkload", "score.bursts", "score.queueloss"):
            assert len(_held(score, spans, name)) == 1, name
        assert len(_held(score, spans, "score.wait")) == 2
    # a wait holds a copy and nothing else: no span inside it
    for w in (e for e in spans if e["name"].endswith(".wait")):
        assert not [e for e in spans if e is not w and e["tid"] == w["tid"]
                    and e["ts_us"] >= w["ts_us"]
                    and e["ts_us"] + e["dur_us"] <= w["ts_us"] + w["dur_us"]
                    and e["name"] != "jax.compile"]


def test_decision_latency_is_the_epoch_span(traced_serve):
    res, spans = traced_serve
    epochs = _named(spans, "serve.epoch")
    lat = np.asarray([e["dur_us"] * 1e-6 for e in epochs])
    np.testing.assert_allclose(res.latencies_s, lat, atol=1e-3)
    np.testing.assert_allclose([d.latency_s for d in res.decisions], lat,
                               atol=1e-3)


def test_serve_rejects_offline_only_configs(small_fabric, small_trace):
    from repro.failures.config import FailureConfig

    stream = TMStream.from_trace(small_trace)
    with pytest.raises(ValueError, match="offline-only"):
        StreamingController(
            small_fabric, stream, Strategy(False, True),
            dataclasses.replace(CC, failures=FailureConfig()), SC,
            serve=ServeConfig(auto_strategy=False))
    with pytest.raises(ValueError, match="strategy"):
        StreamingController(small_fabric, stream, None, CC, SC,
                            serve=ServeConfig(auto_strategy=False))


def test_auto_strategy_picks_at_warmup_end(small_fabric, small_trace):
    """With no explicit strategy, the predictor runs on the warm-up window
    (predict_from_window) and the chosen strategy drives the whole run."""
    ctrl = StreamingController(small_fabric, TMStream.from_trace(small_trace),
                               None, CC, SC, serve=ServeConfig())
    res = ctrl.run()
    assert res.result.strategy is not None
    assert res.result.n_routing_updates == len(res.decisions) > 0


def test_predict_from_window_matches_trace_semantics(small_fabric,
                                                     small_trace):
    from repro.core.predictor import predict_from_window

    agg = int(round(CC.aggregation_days * small_trace.intervals_per_day()))
    window = small_trace.demand[:agg]
    pred = predict_from_window(small_fabric, window,
                               small_trace.interval_minutes, CC, SC)
    assert pred.strategy.name in pred.per_strategy
    assert len(pred.per_strategy) == 4
    with pytest.raises(ValueError, match="too short"):
        predict_from_window(small_fabric, window[:2],
                            small_trace.interval_minutes, CC, SC)
