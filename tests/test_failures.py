"""Failure-contingency subsystem (repro.failures): scenario sampling, mask
composition, vmapped contingency evaluation vs the per-scenario loop, the
None-default bit-identity contract, failure-aware decisions, the PDHG
non-finite fallback, zero-capacity scoring semantics, and the fleet solver's
``valid``-mask pod-removal property."""

import dataclasses

import numpy as np
import pytest

from repro.burst import BurstParams, LossConfig
from repro.core import (ControllerConfig, FailureConfig, SolverConfig,
                        STRATEGIES, pick_best, run_controller, run_fleet,
                        should_reconfigure)
from repro.core.engine import pdhg_finite_fallback, routing_solver_for
from repro.core.fleet import FLEET_SPECS, commodity_slots, scatter_pad
from repro.core.fleet_engine import _bucket_fabric
from repro.core.graph import uniform_topology
from repro.core.rounding import realize
from repro.core.simulator import route_metrics, route_metrics_batched
from repro.failures import (contingency_metrics, directed_masks,
                            fixed_mlu_under_masks, pick_best_contingency,
                            sample_masks, sample_scenarios, scenario_seed)

CC = ControllerConfig(routing_interval_hours=24.0, topology_interval_days=3.0,
                      aggregation_days=2.0, k_critical=3)
SC = SolverConfig(stage1_method="scaled")
FC = FailureConfig(n_scenarios=8, p_link=0.1, seed=0)
LOSS = LossConfig(burst=BurstParams(rate=0.05, shape=1.6, scale=2.5, clip=8.0),
                  n_sub=4, buffer_ms=25.0, seed=3)


# ------------------------------------------------------------- sampling -----

def test_scenario_sampling_is_deterministic(small_fabric):
    a = sample_scenarios(small_fabric, FC)
    b = sample_scenarios(small_fabric, FC)
    np.testing.assert_array_equal(a.trunk_keep, b.trunk_keep)
    np.testing.assert_array_equal(a.pod_keep, b.pod_keep)
    np.testing.assert_array_equal(a.n_failed_links, b.n_failed_links)


def test_scenario_seed_depends_on_fabric_and_component():
    assert scenario_seed("F1", 0, "link") != scenario_seed("F2", 0, "link")
    assert scenario_seed("F1", 0, "link") != scenario_seed("F1", 0, "panel")
    assert scenario_seed("F1", 0, "link") != scenario_seed("F1", 1, "link")


def test_link_draws_paired_across_config_changes(small_fabric):
    """Turning other failure modes on must not shift the link-failure draws
    (separate per-component streams keep strategy comparisons paired)."""
    base = sample_scenarios(small_fabric, FC)
    both = sample_scenarios(
        small_fabric, dataclasses.replace(FC, p_panel=0.5, p_pod=0.3))
    n_ref = np.maximum(base.n_ref_links, 1)
    # recover the link-only retention: panel faults multiply on top
    failed_base = np.rint((1 - base.trunk_keep) * n_ref)
    assert (both.trunk_keep <= base.trunk_keep + 1e-12).all()
    np.testing.assert_array_equal(base.n_failed_links,
                                  np.rint(failed_base.sum(axis=1)))


def test_masks_shape_and_range(small_fabric):
    scen, masks = sample_masks(small_fabric, FC)
    e_d = small_fabric.n_pods * (small_fabric.n_pods - 1)
    assert masks.shape == (FC.n_scenarios, e_d)
    assert (masks >= 0).all() and (masks <= 1).all()
    np.testing.assert_allclose(masks, directed_masks(small_fabric, scen))


def test_pod_failure_kills_incident_edges(small_fabric):
    fc = FailureConfig(n_scenarios=16, p_link=0.0, p_pod=1.0,
                       pod_degrade=0.0, seed=1)
    scen, masks = sample_masks(small_fabric, fc)
    d = small_fabric.directed
    dead_pods = scen.pod_keep <= 0.0
    for k in range(16):
        touched = dead_pods[k, d[:, 0]] | dead_pods[k, d[:, 1]]
        assert (masks[k, touched] == 0.0).all()


# ------------------------------------- fused vs per-scenario loop parity -----

@pytest.mark.parametrize("backend,k,with_loss", [("numpy", 64, True),
                                                 ("pallas", 8, True)])
def test_contingency_matches_per_scenario_loop(small_fabric, small_trace,
                                               backend, k, with_loss):
    """K scenarios as one extra leading batch axis == the K-iteration Python
    loop over ``route_metrics_batched`` (≤1e-5; the acceptance criterion)."""
    caps = np.asarray(small_fabric.capacities(
        realize(small_fabric, uniform_topology(small_fabric))[0]), float)
    t = small_trace.demand.shape[0] // 4
    blocks = [small_trace.demand[:t], small_trace.demand[t:2 * t]]
    from repro.core.paths import build_paths, routing_weight_matrices
    paths = build_paths(small_fabric.n_pods)
    w = routing_weight_matrices(
        paths, np.full((2, paths.n_paths),
                       1.0 / (small_fabric.n_pods - 1)))
    caps_b = np.stack([caps, caps * 0.9])
    scen, masks = sample_masks(
        small_fabric, dataclasses.replace(FC, n_scenarios=k, p_link=0.15))
    loss_cfg = LOSS if with_loss else None
    seeds = [11, 12]
    fused = contingency_metrics(
        blocks, w, caps_b, masks, 0.8, backend=backend, loss_cfg=loss_cfg,
        loss_seeds=seeds, interval_seconds=3600.0)
    assert len(fused) == k
    for ki in range(k):
        loop = route_metrics_batched(
            blocks, w, caps_b * masks[ki][None, :], 0.8, backend=backend,
            loss_cfg=loss_cfg, loss_seeds=seeds, interval_seconds=3600.0)
        np.testing.assert_allclose(fused[ki].mlu, loop.mlu, atol=1e-5)
        np.testing.assert_allclose(fused[ki].alu, loop.alu, atol=1e-5)
        np.testing.assert_allclose(fused[ki].olr, loop.olr, atol=1e-5)
        if with_loss:
            np.testing.assert_allclose(fused[ki].loss, loop.loss, atol=1e-5)


# ----------------------------------------------- engine identity / parity ----

@pytest.mark.parametrize("engine", ["sequential", "batched"])
def test_failures_none_is_bit_identical(small_fabric, small_trace, engine):
    cc0 = dataclasses.replace(CC, engine=engine)
    cc1 = dataclasses.replace(CC, engine=engine, failures=FC)
    r0 = run_controller(small_fabric, small_trace, STRATEGIES[3], cc0, SC)
    r1 = run_controller(small_fabric, small_trace, STRATEGIES[3], cc1, SC)
    np.testing.assert_array_equal(r0.metrics.mlu, r1.metrics.mlu)
    np.testing.assert_array_equal(r0.metrics.alu, r1.metrics.alu)
    assert r0.summary == {k: v for k, v in r1.summary.items()
                          if not k.startswith("cont_")}
    assert r0.contingency is None
    assert r1.contingency is not None
    assert r1.contingency.n_scenarios == FC.n_scenarios
    assert len(r1.contingency.n_failed_links) == FC.n_scenarios


def test_sequential_and_batched_contingency_agree(small_fabric, small_trace):
    """Both engines feed the same scoring blocks to the evaluator, so their
    cont_* summaries are identical on the bit-exact scipy path."""
    cc = dataclasses.replace(CC, failures=FC, loss=LOSS)
    rs = run_controller(small_fabric, small_trace, STRATEGIES[3],
                        dataclasses.replace(cc, engine="sequential"), SC)
    rb = run_controller(small_fabric, small_trace, STRATEGIES[3], cc, SC)
    for key in rs.summary:
        if key.startswith("cont_"):
            assert rs.summary[key] == pytest.approx(rb.summary[key],
                                                    abs=1e-12), key


@pytest.mark.slow
def test_fleet_contingency_matches_batched_engine(small_fabric, small_trace):
    cc = dataclasses.replace(CC, solver_backend="pdhg", failures=FC)
    res_f = run_fleet([(small_fabric, small_trace, STRATEGIES[3], cc, SC)])[0]
    res_b = run_controller(small_fabric, small_trace, STRATEGIES[3], cc, SC)
    assert res_f.contingency is not None
    for key in ("cont_worst_p999_mlu", "cont_mean_p999_mlu"):
        assert res_f.summary[key] == pytest.approx(res_b.summary[key],
                                                   rel=1e-3)


def test_resolve_mode_reduces_worst_contingency_mlu(small_fabric, small_trace):
    """Re-solved routing can only help the what-if MLU vs frozen splits."""
    fixed = run_controller(
        small_fabric, small_trace, STRATEGIES[0],
        dataclasses.replace(CC, failures=dataclasses.replace(
            FC, n_scenarios=4, p_link=0.3)), SC)
    resolved = run_controller(
        small_fabric, small_trace, STRATEGIES[0],
        dataclasses.replace(CC, failures=dataclasses.replace(
            FC, n_scenarios=4, p_link=0.3, resolve=True)), SC)
    assert resolved.contingency.resolve
    assert (resolved.summary["cont_worst_p999_mlu"]
            <= fixed.summary["cont_worst_p999_mlu"] + 1e-6)


# -------------------------------------------------------- policy / gate -----

PER = {
    "a": {"p999_mlu": 1.00, "p999_alu": 0.5, "cont_worst_p999_mlu": 3.0},
    "b": {"p999_mlu": 1.04, "p999_alu": 0.4, "cont_worst_p999_mlu": 1.2},
}


def test_pick_best_contingency_weight_zero_matches_legacy():
    assert pick_best(PER, 0.05, "mlu") == \
        pick_best_contingency(PER, 0.05, "mlu", 0.0)


def test_pick_best_contingency_weight_one_prefers_robust():
    # expected-case picks "b" already (within cushion, lower ALU); shrink the
    # cushion so the legacy rule picks "a" and only worst-case flips it
    assert pick_best(PER, 0.01, "mlu") == "a"
    assert pick_best_contingency(PER, 0.01, "mlu", 1.0) == "b"
    assert pick_best(PER, 0.01, "mlu", contingency_weight=1.0) == "b"


def test_pick_best_contingency_missing_keys_raises():
    with pytest.raises(ValueError, match="cont_worst_p999_mlu"):
        pick_best_contingency({"a": {"p999_mlu": 1.0, "p999_alu": 0.1}},
                              0.05, "mlu", 0.5)


def test_should_reconfigure_blend():
    # legacy arithmetic untouched without a weight
    assert should_reconfigure(1.0, 0.5)
    assert not should_reconfigure(0.4, 0.5)
    # a robust-looking move in expectation, catastrophic under failures
    assert should_reconfigure(1.0, 0.5, contingency_weight=0.0,
                              benefit_worst=-5.0, disruption_worst=9.0)
    assert not should_reconfigure(1.0, 0.5, contingency_weight=0.9,
                                  benefit_worst=-5.0, disruption_worst=9.0)
    with pytest.raises(ValueError):
        should_reconfigure(1.0, 0.5, contingency_weight=0.5)


def test_fixed_mlu_under_masks_identity(rng):
    """All-ones masks reproduce the plain fixed-routing MLU."""
    v = 4
    from repro.core.paths import build_paths, routing_weight_matrices
    paths = build_paths(v)
    f = np.full((2, paths.n_paths), 1.0 / (v - 1))
    w = routing_weight_matrices(paths, f)
    tms = rng.random((3, v * (v - 1)))
    caps = 1.0 + rng.random((2, v * (v - 1)))
    u = fixed_mlu_under_masks(tms, w, caps, np.ones((1, v * (v - 1))))
    for b in range(2):
        m = route_metrics(tms, w[b], caps[b], backend="numpy")
        assert u[0, b] == pytest.approx(float(m.mlu.max()), rel=1e-12)


def test_failure_aware_gate_changes_decisions(small_fabric, small_trace):
    """contingency_weight=1 with catastrophic scenarios vetoes transitions
    the expected-case gate would apply."""
    from repro.transition import TransitionConfig

    tc = TransitionConfig(n_panels=4, stage_intervals=1)
    cc_exp = dataclasses.replace(CC, transition=tc, failures=FC)
    cc_rob = dataclasses.replace(
        CC, transition=tc,
        failures=dataclasses.replace(FC, contingency_weight=1.0, p_link=0.6,
                                     n_scenarios=16))
    r_exp = run_controller(small_fabric, small_trace, STRATEGIES[2], cc_exp,
                           SC)
    r_rob = run_controller(small_fabric, small_trace, STRATEGIES[2], cc_rob,
                           SC)
    # same candidate transitions were evaluated; the robust gate can only
    # veto more of them
    assert len(r_rob.transition_log) == len(r_exp.transition_log)
    assert r_rob.n_skipped_topology >= r_exp.n_skipped_topology


# ----------------------------------------------- PDHG non-finite fallback ----

def test_pdhg_finite_fallback_replaces_bad_elements(small_fabric,
                                                    small_trace):
    v = small_fabric.n_pods
    caps = np.asarray(small_fabric.capacities(
        realize(small_fabric, uniform_topology(small_fabric))[0]), float)
    window = small_trace.demand[:8]
    from repro.core import critical_tms
    tms = critical_tms(window, k=3, seed=0)
    from repro.core.paths import build_paths
    p = build_paths(v).n_paths
    f_b = np.full((3, p), 1.0 / (v - 1))
    u_b = np.ones(3)
    f_b[1, 0] = np.nan  # poisoned element
    u_b[2] = np.inf
    f_fix, u_fix, n_fb = pdhg_finite_fallback(
        small_fabric, [tms] * 3, np.stack([caps] * 3), np.zeros(3), SC,
        f_b, u_b)
    assert n_fb == 2
    assert np.isfinite(f_fix).all() and np.isfinite(u_fix[:2]).all()
    # untouched element passes through bit-identically
    np.testing.assert_array_equal(f_fix[0], f_b[0])
    assert u_fix[0] == 1.0
    # the two re-solved elements agree (identical inputs)
    np.testing.assert_allclose(f_fix[1], f_fix[2], atol=1e-9)


def test_pdhg_finite_fallback_counts_into_solver_stats(monkeypatch,
                                                       small_fabric,
                                                       small_trace):
    import repro.core.jaxlp as jaxlp

    orig = jaxlp.JaxRoutingSolver.solve_routing_batch

    def poisoned(self, tms, capacities, **kw):
        out = dict(orig(self, tms, capacities, **kw))
        f = np.array(out["f"], float, copy=True)
        f[0, 0] = np.nan
        out["f"] = f
        return out

    monkeypatch.setattr(jaxlp.JaxRoutingSolver, "solve_routing_batch",
                        poisoned)
    cc = dataclasses.replace(CC, solver_backend="pdhg")
    res = run_controller(small_fabric, small_trace, STRATEGIES[0], cc, SC)
    assert res.solver_stats.n_fallbacks >= 1
    assert res.solver_stats.to_dict()["n_fallbacks"] >= 1
    assert np.isfinite(res.metrics.mlu).all()


# ------------------------------------------- zero-capacity scoring guard -----

@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_all_dead_capacities_score_zero(backend, rng):
    v = 4
    from repro.core.paths import build_paths, routing_weight_matrices
    paths = build_paths(v)
    w = routing_weight_matrices(
        paths, np.full((1, paths.n_paths), 1.0 / (v - 1)))[0]
    demand = rng.random((5, v * (v - 1)))
    m = route_metrics(demand, w, np.zeros(v * (v - 1)), backend=backend)
    assert np.isfinite(m.mlu).all()
    np.testing.assert_array_equal(m.mlu, np.zeros(5))
    np.testing.assert_array_equal(m.alu, np.zeros(5))
    np.testing.assert_array_equal(m.olr, np.zeros(5))


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_dead_link_excluded_from_mlu_but_drops_its_demand(backend, rng):
    """A fully-failed link carries no utilization (excluded from MLU/ALU/OLR)
    while demand still aimed at it is dropped by the loss model."""
    v = 4
    e_d = v * (v - 1)
    from repro.core.paths import build_paths, routing_weight_matrices
    paths = build_paths(v)
    w = routing_weight_matrices(
        paths, np.full((1, paths.n_paths), 1.0 / (v - 1)))[0]
    demand = np.full((4, e_d), 0.2)
    caps = np.ones(e_d)
    dead = 3
    caps_dead = caps.copy()
    caps_dead[dead] = 0.0
    m_live = route_metrics(demand, w, caps, backend=backend, loss_cfg=LOSS,
                           interval_seconds=3600.0)
    m_dead = route_metrics(demand, w, caps_dead, backend=backend,
                           loss_cfg=LOSS, interval_seconds=3600.0)
    assert np.isfinite(m_dead.mlu).all()
    # live links are below 1.0 utilization; killing one link cannot raise MLU
    # above the live maximum plus the dead link's exclusion
    assert (m_dead.loss >= m_live.loss - 1e-12).all()
    assert m_dead.loss.mean() > m_live.loss.mean()


# ----------------------------------------------- valid-mask pod removal ------

def test_fleet_valid_mask_equals_pod_removal():
    """Masking pods out via ``valid`` ≡ solving the smaller fabric (≤1e-5),
    and capacities on masked-out edges cannot leak into the solve."""
    v, vp, m = 5, 8, 3
    nat = _bucket_fabric(v)
    pad = _bucket_fabric(vp)
    rng = np.random.default_rng(7)
    tms = rng.random((m, v * (v - 1)))
    caps = 1.0 + rng.random(v * (v - 1))
    slots = commodity_slots(v, vp)
    cp = vp * (vp - 1)
    tms_p = scatter_pad(tms[None], slots, cp, axis=2)
    caps_p = scatter_pad(caps[None], slots, cp, axis=1)
    solver_p = routing_solver_for(pad, m, 8000, 1e-5)
    valid = solver_p.valid_for_pods(v)[None]

    def fleet_solve(caps_row):
        return solver_p.solve_routing_fleet(
            tms_p, caps_row, valid, np.asarray([0]), np.asarray([0]),
            hedging=False, deltas=np.zeros(1), skip_stage3=True)

    out_masked = fleet_solve(caps_p)
    # garbage capacity on masked-out edges must be exactly invisible
    caps_leak = caps_p.copy()
    leak = np.ones(cp, bool)
    leak[slots] = False
    caps_leak[0, leak] = 7.5
    out_leak = fleet_solve(caps_leak)
    assert float(out_leak["u_star"][0]) == pytest.approx(
        float(out_masked["u_star"][0]), abs=1e-10)
    np.testing.assert_allclose(out_leak["f"][0], out_masked["f"][0],
                               atol=1e-10)

    solver_n = routing_solver_for(nat, m, 8000, 1e-5)
    out_nat = solver_n.solve_routing_fleet(
        tms[None], caps[None], solver_n.valid_for_pods(v)[None],
        np.asarray([0]), np.asarray([0]), hedging=False, deltas=np.zeros(1),
        skip_stage3=True)
    assert float(out_masked["u_star"][0]) == pytest.approx(
        float(out_nat["u_star"][0]), abs=1e-5)


def test_resolve_weights_route_nothing_over_dead_links(small_fabric, rng):
    """The what-if's PDHG re-solve leaves every path over a failed link out
    of the routing (a dead link is not free capacity)."""
    from repro.failures.evaluate import resolve_weights

    cap = small_fabric.capacities(
        realize(small_fabric, uniform_topology(small_fabric))[0])
    tms = rng.uniform(0.0, 0.3, (1, 3, cap.size)) * cap.mean()
    masks = np.ones((2, cap.size))
    dead = rng.choice(cap.size, size=4, replace=False)
    masks[1, dead] = 0.0
    cc = dataclasses.replace(CC, solver_backend="pdhg", pdhg_tol=1e-2,
                             pdhg_max_iters=3000)
    w, _ = resolve_weights(small_fabric, tms, cap[None], masks,
                           np.zeros(1), cc, SC)
    assert np.abs(w[1, 0][:, dead]).max() < 1e-5
    assert np.abs(w[0, 0][:, dead]).max() > 1e-3  # live, they carry load
