"""Reconfiguration-transition subsystem (repro.transition): diff/schedule/
score units, the §4.6 decision rule, and controller integration."""

import dataclasses
import itertools

import numpy as np
import pytest

from repro.burst import BurstParams, LossConfig
from repro.core import (ControllerConfig, SolverConfig, Strategy,
                        TransitionConfig, run_controller, should_reconfigure)
from repro.core.fleet import FLEET_SPECS, make_fabric
from repro.core.graph import Fabric, trunk_index, uniform_topology
from repro.core.rounding import realize
from repro.transition import (diff_topologies, evaluate_transition, proxy_mlu,
                              residual_trunks, schedule_drains,
                              score_stage_batch, stage_metrics, stage_spans,
                              stage_trunks_for_order)

CC = ControllerConfig(routing_interval_hours=12.0, topology_interval_days=3.0,
                      aggregation_days=3.0, k_critical=4)
SC = SolverConfig(stage1_method="scaled")
TC = TransitionConfig(n_panels=4, stage_intervals=1)
LOSS = LossConfig(burst=BurstParams(rate=0.05, shape=1.6, scale=2.5, clip=8.0),
                  n_sub=4, buffer_ms=25.0, seed=3)


@pytest.fixture(scope="module")
def topologies(small_fabric):
    """Two distinct realized integer topologies of the small fabric."""
    n_uni = realize(small_fabric, uniform_topology(small_fabric))[0]
    rng = np.random.default_rng(5)
    v = small_fabric.n_pods
    skew = np.zeros_like(n_uni, dtype=np.float64)
    trunks = trunk_index(v)
    hot = rng.permutation(v)[:2]
    # shift capacity toward one hot pod pair, away from elsewhere
    for e, (i, j) in enumerate(trunks):
        if i in hot and j in hot:
            skew[e] = 4.0
    n_skew = realize(small_fabric, np.maximum(n_uni + skew - 0.5, 1.0))[0]
    assert (n_skew != n_uni).any()
    return n_uni, n_skew


# ---------------------------------------------------------------- diff -----

def test_diff_identical_topologies_has_no_moves(small_fabric, topologies):
    n_uni, _ = topologies
    d = diff_topologies(small_fabric.n_pods, n_uni, n_uni, 4)
    assert d.total_moves == 0
    assert d.panels_with_moves.size == 0


def test_diff_measures_fiber_moves(small_fabric, topologies):
    """Outside Thm. 4's exact regime the two panel decompositions may place a
    pod's ports differently; the deviation must be measured, and must be zero
    when nothing changes (identical decompositions)."""
    n_uni, n_skew = topologies
    same = diff_topologies(small_fabric.n_pods, n_uni, n_uni, 4)
    assert same.total_fiber_moves == 0
    d = diff_topologies(small_fabric.n_pods, n_uni, n_skew, 4)
    assert d.total_fiber_moves >= 0  # reported, not assumed away
    assert d.fiber_moves_per_panel.shape == (4,)


def test_diff_counts_partition_topology(small_fabric, topologies):
    n_uni, n_skew = topologies
    d = diff_topologies(small_fabric.n_pods, n_uni, n_skew, 4)
    np.testing.assert_array_equal(d.old_counts.sum(axis=0), n_uni)
    np.testing.assert_array_equal(d.new_counts.sum(axis=0), n_skew)
    assert d.total_moves > 0
    # a panel's moves bound the larger side of its multiset difference
    for p in range(4):
        removed = np.maximum(d.old_counts[p] - d.new_counts[p], 0).sum()
        added = np.maximum(d.new_counts[p] - d.old_counts[p], 0).sum()
        assert d.moves_per_panel[p] == max(removed, added)


# ------------------------------------------------------------ schedule -----

def test_residual_trunks_track_drain_progress(small_fabric, topologies):
    n_uni, n_skew = topologies
    d = diff_topologies(small_fabric.n_pods, n_uni, n_skew, 4)
    p0, p1 = 0, 1
    # nothing drained yet: all other panels carry old links
    r0 = residual_trunks(d, [], p0)
    np.testing.assert_array_equal(r0, n_uni - d.old_counts[p0])
    # p0 drained (now new), p1 down
    r1 = residual_trunks(d, [p0], p1)
    expect = n_uni - d.old_counts[p0] - d.old_counts[p1] + d.new_counts[p0]
    np.testing.assert_array_equal(r1, expect)


def test_schedule_exact_is_optimal_and_beats_naive(small_fabric, topologies,
                                                   small_trace):
    n_uni, n_skew = topologies
    d = diff_topologies(small_fabric.n_pods, n_uni, n_skew, 4)
    tms = small_trace.demand[:6]
    order, cost, naive_cost = schedule_drains(small_fabric, tms, d)
    assert set(order) == set(int(p) for p in d.panels_with_moves)
    assert cost <= naive_cost + 1e-12
    # exact subset DP == brute force over all permutations
    def worst(perm):
        return max(
            proxy_mlu(small_fabric, tms,
                      small_fabric.capacities(residual_trunks(d, perm[:s], p)))
            for s, p in enumerate(perm))
    brute = min(worst(p) for p in itertools.permutations(order))
    assert cost == pytest.approx(brute, rel=1e-12)
    # greedy path agrees with DP on feasibility (not optimality)
    g_order, g_cost, _ = schedule_drains(small_fabric, tms, d, max_exact=0)
    assert set(g_order) == set(order)
    assert g_cost >= cost - 1e-12


def test_proxy_mlu_stranded_is_inf(small_fabric):
    caps = np.zeros(small_fabric.n_directed)
    assert proxy_mlu(small_fabric, np.ones((2, small_fabric.n_directed)),
                     caps) == float("inf")


def test_stage_spans_clip_to_block():
    assert stage_spans(3, 2, 10) == [(0, 0, 2), (1, 2, 4), (2, 4, 6)]
    assert stage_spans(3, 2, 3) == [(0, 0, 2), (1, 2, 3)]
    assert stage_spans(2, 5, 4) == [(0, 0, 4)]


# ------------------------------------------------------------ decision -----

def test_should_reconfigure_rule():
    assert should_reconfigure(benefit=1.0, disruption=0.5)
    assert not should_reconfigure(benefit=0.4, disruption=0.5)
    assert not should_reconfigure(benefit=0.0, disruption=0.0)
    assert should_reconfigure(benefit=0.1, disruption=0.0)
    # hysteresis raises the bar
    assert should_reconfigure(benefit=0.6, disruption=0.5, hysteresis=0.0)
    assert not should_reconfigure(benefit=0.6, disruption=0.5, hysteresis=0.5)
    assert not should_reconfigure(benefit=-1.0, disruption=0.0)


# --------------------------------------------------------------- score -----

@pytest.fixture(scope="module")
def evaluated(small_fabric, small_trace, topologies):
    n_uni, n_skew = topologies
    tms = small_trace.demand[:4]
    return evaluate_transition(small_fabric, tms, n_uni, n_skew, TC, CC, SC,
                               horizon_intervals=24)


def test_evaluate_transition_shapes_and_predictions(small_fabric, evaluated):
    ev = evaluated
    assert ev is not None
    s = ev.n_stages
    assert s == len(ev.order) > 0
    assert ev.stage_caps.shape == (s, small_fabric.n_directed)
    assert ev.stage_w.shape[0] == s
    assert np.isfinite(ev.stage_u).all()
    assert ev.worst_stage_u >= max(ev.u_old, ev.u_new) - 1e-9  # less capacity
    assert ev.disruption >= 0.0
    expected_benefit = (ev.u_old - ev.u_new) * (24 - ev.transition_intervals)
    assert ev.benefit == pytest.approx(expected_benefit)


def test_evaluate_transition_none_when_identical(small_fabric, small_trace,
                                                 topologies):
    n_uni, _ = topologies
    tms = small_trace.demand[:4]
    assert evaluate_transition(small_fabric, tms, n_uni, n_uni, TC, CC, SC,
                               horizon_intervals=24) is None


@pytest.mark.parametrize("backend", ["scipy", "pdhg"])
@pytest.mark.slow
def test_score_stage_batch_stranded_stage_is_infinite(backend):
    """A drain stage that strands a commodity must score u = inf on BOTH
    backends — scipy's LP turns infeasible, while the PDHG operators treat
    dead links as unconstrained and would happily report a finite u."""
    fab = Fabric.homogeneous("Tiny", 4, 6)
    tms = np.ones((2, fab.n_directed))
    caps = np.stack([fab.capacities(np.full(fab.n_trunks, 2.0)),
                     np.zeros(fab.n_directed)])
    cc = dataclasses.replace(CC, solver_backend=backend, k_critical=2,
                             pdhg_max_iters=200)
    f, u, _ = score_stage_batch(fab, tms, caps, 0.0, False, SC, cc)
    assert np.isfinite(u[0])
    assert u[1] == float("inf")
    assert f.shape[0] == 2


def test_stage_metrics_batched_one_shot(small_trace, evaluated):
    ev = evaluated
    demand = small_trace.demand[:5]
    per_stage = stage_metrics(demand, ev, backend="numpy")
    assert len(per_stage) == ev.n_stages
    for m in per_stage:
        assert m.mlu.shape == (5,)
        assert np.isfinite(m.mlu).all()
    # draining a panel with more load at stake must not lower MLU below the
    # steady-state solve on full capacity
    assert max(m.mlu.max() for m in per_stage) >= 0.0


# ---------------------------------------------------- controller paths -----

def _run(fabric, trace, strategy, **over):
    return run_controller(fabric, trace, strategy,
                          dataclasses.replace(CC, **over), SC)


@pytest.mark.parametrize("engine", ["sequential", "batched"])
def test_transition_requires_realized_topologies(small_fabric, small_trace,
                                                 engine):
    with pytest.raises(ValueError, match="realize_topology"):
        _run(small_fabric, small_trace, Strategy(True, False), engine=engine,
             transition=TC, realize_topology=False)


def test_transition_unset_is_legacy(small_fabric, small_trace):
    res = _run(small_fabric, small_trace, Strategy(True, False))
    assert res.n_skipped_topology == 0
    assert res.transition_log == ()


@pytest.mark.parametrize("engine", ["sequential", "batched"])
@pytest.mark.slow
def test_transition_scores_all_intervals_once(small_fabric, small_trace, engine):
    """Staged scoring must neither drop nor double-count intervals."""
    res = _run(small_fabric, small_trace, Strategy(True, True), engine=engine,
               transition=dataclasses.replace(TC, decide=False))
    warm = int(3 * small_trace.intervals_per_day())
    assert res.metrics.mlu.shape[0] == small_trace.n_intervals - warm
    assert res.n_skipped_topology == 0
    assert len(res.transition_log) == res.n_topology_updates - 1  # first is free
    assert all(e["applied"] for e in res.transition_log)
    assert any(e["worst_stage_u"] > max(e["u_old"], e["u_new"])
               for e in res.transition_log)


@pytest.mark.slow
def test_transition_engines_agree(small_fabric, small_trace):
    tc = dataclasses.replace(TC, decide=False, stage_intervals=2)
    seq = _run(small_fabric, small_trace, Strategy(True, True),
               engine="sequential", transition=tc, loss=LOSS)
    bat = _run(small_fabric, small_trace, Strategy(True, True),
               engine="batched", transition=tc, loss=LOSS)
    assert seq.n_topology_updates == bat.n_topology_updates
    assert seq.n_skipped_topology == bat.n_skipped_topology
    np.testing.assert_allclose(bat.metrics.mlu, seq.metrics.mlu, rtol=1e-3)
    np.testing.assert_array_equal(bat.metrics.loss, seq.metrics.loss)
    np.testing.assert_array_equal(bat.final_topology, seq.final_topology)
    assert len(seq.transition_log) == len(bat.transition_log)
    for a, b in zip(seq.transition_log, bat.transition_log):
        assert a["order"] == b["order"]
        assert a["applied"] == b["applied"]


@pytest.mark.slow
def test_high_hysteresis_skips_reconfigurations(small_fabric, small_trace):
    tc = dataclasses.replace(TC, hysteresis=50.0)
    res = _run(small_fabric, small_trace, Strategy(True, True), transition=tc)
    base = _run(small_fabric, small_trace, Strategy(True, True))
    assert res.n_skipped_topology >= 1
    assert (res.n_topology_updates + res.n_skipped_topology
            == base.n_topology_updates)
    skipped = [e for e in res.transition_log if not e["applied"]]
    assert skipped and all(
        not should_reconfigure(e["benefit"], e["disruption"], 50.0)
        for e in skipped)


@pytest.mark.slow
def test_instantaneous_keeps_decision_without_staging(small_fabric, small_trace):
    tc = dataclasses.replace(TC, decide=False, instantaneous=True)
    res = _run(small_fabric, small_trace, Strategy(True, True), transition=tc)
    base = _run(small_fabric, small_trace, Strategy(True, True))
    # decision rule ran (log populated) but scoring is the legacy model
    assert len(res.transition_log) >= 1
    np.testing.assert_allclose(res.metrics.mlu, base.metrics.mlu, rtol=1e-9)
