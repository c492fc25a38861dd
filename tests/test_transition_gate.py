"""The §4.6 gate served online, against its plain reference.

``StreamingController`` with ``ControllerConfig.transition`` set runs on a
small mixed-generation fabric (radix 64/32, 100G/40G) over a seeded trace
whose hot pod pair moves: two topology changes are installed and drained
panel by panel, a later one is vetoed.  Every gate evaluation
(``Decision.gate``) is held to :mod:`chipbench.reference_gate` (float64
numpy and HiGHS, independent of the program): panel moves, drain order,
drained capacities, u* within the PDHG tolerance of HiGHS, the decision, and
the staged epoch's per-interval MLU and loss.  On a harder trace the batch
can stop at its iteration cap short of the tolerance, and must say so.  The
gate's PDHG batch runs at one fixed size whatever its stage count.
"""

import dataclasses

import numpy as np
import pytest

from chipbench import gen, reference, reference_gate
from repro.burst import BurstParams, LossConfig
from repro.core import ControllerConfig, SolverConfig, Strategy
from repro.core.graph import Fabric
from repro.core.paths import build_paths, routing_weight_matrices
from repro.core.simulator import route_metrics_batched
from repro.serve import StreamingController, TMStream
from repro.transition import TransitionConfig, score_stage_batch

FAB = {"name": "H6", "n_pods": 6, "skew_sigma": 1.1, "burst_rate": 2e-3,
       "burst_shape": 2.0, "burst_scale": 2.0, "noise": 0.15,
       "target_uniform_mlu": 0.45, "radix": [64, 32, 64, 32, 64, 64],
       "speed": [100.0, 40.0, 100.0, 100.0, 40.0, 100.0]}
LOSS = {"burst": {"rate": 0.05, "shape": 1.6, "scale": 2.5, "clip": 8.0},
        "n_sub": 4, "buffer_ms": 25.0}
TOL = 1e-2
TC = TransitionConfig(n_panels=4, stage_intervals=1)
CC = ControllerConfig(
    routing_interval_hours=3.0, topology_interval_days=1.0,
    aggregation_days=1.0, k_critical=4, pdhg_tol=TOL, pdhg_max_iters=3000,
    solver_backend="pdhg",
    loss=LossConfig(burst=BurstParams(**LOSS["burst"]), n_sub=LOSS["n_sub"],
                    buffer_ms=LOSS["buffer_ms"], seed=3),
    transition=TC)
# u* of a valid routing at a duality gap of TOL, with float32 rounding room
U_REL = 1.0 / (1.0 - TOL) - 1.0 + 1e-4


def _fabric():
    return Fabric(name=FAB["name"], radix=np.asarray(FAB["radix"]),
                  speed=np.asarray(FAB["speed"]))


def _trace(hot=((2, 5), (0, 3)), factor=12.0, seed=4):
    """Hourly TMs for 5 days: a calm day, then the first ``hot`` pod pair
    talks ``factor`` times more for two days, then the second does."""
    demand = gen.make_trace(FAB, 5.0, 60.0, seed)
    pairs = gen.directed_edges(FAB["n_pods"]).tolist()
    for day, (i, j) in enumerate((hot[0], hot[0], hot[1], hot[1]), 1):
        cols = [c for c, p in enumerate(pairs) if sorted(p) == [i, j]]
        demand[day * 24:(day + 1) * 24, cols] *= factor
    return demand


@pytest.fixture(scope="module")
def served():
    return _serve(_trace())


def _serve(demand):
    """Every decision, with what the controller planned it from and what it
    installed."""
    ctrl = StreamingController(
        _fabric(), TMStream(name="H6", intervals=iter(()),
                            interval_minutes=60.0, n_pods=6),
        Strategy(nonuniform=True, hedging=True), CC, SolverConfig())
    out = []
    for t in range(demand.shape[0]):
        n_before = ctrl._n_realized
        d = ctrl.ingest(demand[t])
        if d is not None:
            out.append({"d": d, "tms": ctrl._tms_prev, "n_old": n_before,
                        "n": ctrl._n_realized, "w": ctrl._w,
                        "cap": ctrl._cap})
    return {"demand": demand, "agg": ctrl.agg, "step": ctrl.route_step,
            "horizon": ctrl.topo_step, "decided": out,
            "mlu": ctrl._metrics.mlu, "loss": ctrl._metrics.loss}


@pytest.fixture(scope="module")
def gated(served):
    """The gate evaluations, each with the reference's numbers for the
    program's panel decomposition and drain order."""
    out = []
    for r in served["decided"]:
        ev = r["d"].gate
        if ev is None:
            continue
        ref = reference_gate.evaluate(
            r["tms"], FAB, ev.diff.old_counts, ev.diff.new_counts, ev.order,
            ev.stage_intervals, served["horizon"], TC.hysteresis)
        out.append((r, ev, ref))
    return out


def test_gate_sees_an_applied_and_a_vetoed_change(served, gated):
    assert [r["d"].topology_applied for r, _, _ in gated] == [True, True,
                                                              False]
    topo = [r["d"] for r in served["decided"] if r["d"].topology_solved]
    assert len(topo) == 4 and topo[0].gate is None  # the first installs free
    for _, ev, _ in gated:  # every stage-1 solve certified its gap
        assert ev.pdhg_stats["stage1"]["gap"].max() <= TOL


def test_gate_panel_moves_match_reference(gated):
    for r, ev, _ in gated:
        np.testing.assert_array_equal(
            ev.diff.moves_per_panel,
            reference_gate.panel_moves(ev.diff.old_counts, ev.diff.new_counts))
        np.testing.assert_array_equal(
            ev.diff.fiber_moves_per_panel,
            reference_gate.fiber_moves(ev.diff.old_counts, ev.diff.new_counts))
        cand = ev.diff.new_counts.sum(axis=0)
        assert reference_gate.assignment_violations(
            6, r["n_old"], ev.diff.old_counts) == 0
        assert reference_gate.assignment_violations(
            6, cand, ev.diff.new_counts) == 0
        if r["d"].topology_applied:
            np.testing.assert_array_equal(cand, np.rint(r["n"]))


def test_gate_drain_order_and_capacities_match_reference(gated):
    for r, ev, ref in gated:
        order, worst = reference_gate.best_order(
            r["tms"], FAB, ev.diff.old_counts, ev.diff.new_counts)
        assert len(ev.order) == len(order)
        assert ev.proxy_worst == pytest.approx(worst, rel=1e-9)
        np.testing.assert_array_equal(ev.stage_trunks, ref["stage_trunks"])
        np.testing.assert_allclose(ev.stage_caps, ref["stage_caps"],
                                   rtol=1e-12)
        assert (ev.stage_caps <= 0).any()  # a drain takes trunks down


def test_gate_u_star_within_pdhg_tolerance_of_highs(gated):
    for _, ev, ref in gated:
        ours = np.concatenate([[ev.u_old, ev.u_new], ev.stage_u])
        theirs = np.concatenate([[ref["u_old"], ref["u_new"]],
                                 ref["stage_u"]])
        np.testing.assert_array_less(np.abs(ours / theirs - 1.0), U_REL)


def test_gate_weights_are_routings(gated):
    """Every solve of the batch installs whole splits on live paths (the
    stages' last iterates are projected back onto the path simplex)."""
    for _, ev, _ in gated:
        for w, cap in zip(np.concatenate([ev.steady_w, ev.stage_w]),
                          np.concatenate([ev.steady_caps, ev.stage_caps])):
            assert reference.splits(w, 6)[1] < 1e-5
            assert np.abs(w[:, cap <= 0]).max(initial=0.0) < 1e-5


def test_gate_decision_matches_reference(gated):
    for r, ev, ref in gated:
        applied = r["d"].topology_applied
        assert applied == reference_gate.decide(ev.benefit, ev.disruption,
                                                TC.hysteresis)
        assert applied == ref["apply"]
        # both cases clear the rule by a wide margin, so PDHG's tolerance
        # cannot flip them
        assert abs(ref["benefit"] - ref["disruption"]) > 0.1 * max(
            abs(ref["benefit"]), ref["disruption"])
    assert gated[0][1].pdhg_iters > 0


def test_staged_epoch_scores_match_reference(served, gated):
    r, ev, _ = gated[0]
    start, step = r["d"].start, served["step"]
    lo = start - served["agg"]
    mlu, loss = reference_gate.score_staged(
        served["demand"][start:start + step], ev.n_stages,
        ev.stage_intervals, ev.stage_w, ev.stage_caps, r["w"], r["cap"],
        LOSS, CC.loss.seed + start, 3600.0)
    np.testing.assert_allclose(served["mlu"][lo:lo + step], mlu, rtol=1e-9)
    np.testing.assert_allclose(served["loss"][lo:lo + step], loss,
                               rtol=1e-9, atol=1e-12)
    assert loss.max() > 0  # the drains drop traffic the check can see
    # the drained stages' MLU is what their own capacities give
    spans, _ = reference_gate.stage_spans(ev.n_stages, 1, step)
    for k, a, b in spans:
        np.testing.assert_allclose(
            mlu[a:b], reference.mlu(served["demand"][start + a:start + b],
                                    ev.stage_w[k], ev.stage_caps[k]))


def test_transition_unset_gives_no_gate():
    demand = _trace()[:60]
    ctrl = StreamingController(
        _fabric(), TMStream(name="H6", intervals=iter(()),
                            interval_minutes=60.0, n_pods=6),
        Strategy(nonuniform=True, hedging=False),
        dataclasses.replace(CC, transition=None, solver_backend="scipy"),
        SolverConfig())
    ds = [d for d in map(ctrl.ingest, demand) if d is not None]
    assert ds and all(d.gate is None for d in ds)


def test_gate_flags_every_solve_it_did_not_certify():
    """On a hard change (a 100x hot pair overloading the fabric) some batch
    elements stop at the iteration cap short of the tolerance; each such
    element reports its uncertified gap, and every u* is still the MLU of a
    valid routing (never below the LP optimum)."""
    hard = _serve(_trace(hot=((0, 5), (2, 3)), factor=100.0, seed=3))
    for r in hard["decided"]:
        ev = r["d"].gate
        if ev is None:
            continue
        gaps = ev.pdhg_stats["stage1"]["gap"]
        ours = np.concatenate([[ev.u_old, ev.u_new], ev.stage_u])
        caps = np.concatenate([ev.steady_caps, ev.stage_caps])
        for u, cap, gap in zip(ours, caps, gaps):
            u_lp = reference.stage1_u(r["tms"], cap, 6)
            assert u >= u_lp * (1.0 - 1e-4)
            if u / u_lp - 1.0 > U_REL:
                assert gap > TOL


# ---- fixed batch shape ---------------------------------------------------------


def _caps_batch(b: int) -> np.ndarray:
    """``b`` capacity vectors of the fabric: a realized topology, then copies
    with one more trunk taken down each (drain-stage-like, dead links)."""
    fab = _fabric()
    base = gen.edge_capacities(FAB, gen.uniform_trunks(FAB).round())
    out = [base]
    te = gen.trunk_of_edge(fab.n_pods)
    for k in range(1, b):
        cap = out[-1].copy()
        cap[te == k * 3 % len(gen.trunks(6))] = 0.0
        out.append(cap)
    return np.stack(out)


def _tms() -> np.ndarray:
    return _trace()[24:28]


class _Compiles:
    def __init__(self):
        import jax

        self.n = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def test_gate_batch_compiles_once_for_every_stage_count():
    """Batches of 1 + 2 to 4 + 2 real elements (old, new and 1..4 drain
    stages) all run the programs compiled for the first."""
    tms = _tms()
    fab = _fabric()
    for b in (1, 3):  # all links live, then some dead
        score_stage_batch(fab, tms, _caps_batch(b), 1.0, True, SolverConfig(),
                          CC, pad_to=TC.n_panels + 2)
    clock = _Compiles()
    try:
        for b in (3, 4, 5, 6):
            f, u, stats = score_stage_batch(
                fab, tms, _caps_batch(b), 1.0, True, SolverConfig(), CC,
                pad_to=TC.n_panels + 2)
            assert f.shape[0] == u.shape[0] == b
            assert all(stats[s]["iters"].shape == (b,)
                       for s in ("stage1", "stage2", "stage3"))
    finally:
        clock.close()
    assert clock.n == 0


def test_staged_scoring_compiles_once_for_every_span_count():
    demand = _trace()[24:27]
    caps = _caps_batch(3)
    w = np.stack([np.eye(30)] * 3)  # direct paths only
    loss = CC.loss
    route_metrics_batched([demand[:1]], w[:1], caps[:1], backend="pallas",
                          loss_cfg=loss, loss_seeds=[0],
                          interval_seconds=3600.0, pad_to=3)
    clock = _Compiles()
    try:
        for n in (1, 2, 3):
            m = route_metrics_batched(
                [demand[k:k + 1] for k in range(n)], w[:n], caps[:n],
                backend="pallas", loss_cfg=loss, loss_seeds=list(range(n)),
                interval_seconds=3600.0, pad_to=3)
            ref = route_metrics_batched(
                [demand[k:k + 1] for k in range(n)], w[:n], caps[:n],
                backend="numpy", loss_cfg=loss, loss_seeds=list(range(n)),
                interval_seconds=3600.0)
            assert m.mlu.shape == (n,)
            np.testing.assert_allclose(m.mlu, ref.mlu, rtol=1e-5)
    finally:
        clock.close()
    assert clock.n == 0


def test_padded_batch_matches_unpadded():
    tms = _tms()
    caps = _caps_batch(3)
    fab = _fabric()
    f_p, u_p, st_p = score_stage_batch(fab, tms, caps, 1.0, True,
                                       SolverConfig(), CC, pad_to=6)
    f_u, u_u, st_u = score_stage_batch(fab, tms, caps, 1.0, True,
                                       SolverConfig(), CC)
    np.testing.assert_allclose(u_p, u_u, rtol=TOL)
    np.testing.assert_allclose(f_p, f_u, atol=0.05)
    assert st_p["stage1"]["iters"].shape == st_u["stage1"]["iters"].shape


def test_gate_batch_routes_nothing_over_dead_links():
    """A drained stage's solve leaves every path over a dead link empty and
    reaches the LP optimum of the live links (the PDHG operators alone would
    count a dead link as free capacity)."""
    tms = _tms()
    caps = _caps_batch(4)
    f, u, _ = score_stage_batch(_fabric(), tms, caps, 1.0, True,
                                SolverConfig(), CC, pad_to=6)
    w = routing_weight_matrices(build_paths(6), f)
    for i in range(caps.shape[0]):
        assert np.abs(w[i][:, caps[i] <= 0]).max(initial=0.0) < 1e-4
        u_lp = reference.stage1_u(tms, caps[i], 6)
        assert abs(u[i] / u_lp - 1.0) < U_REL


# ---- the benchmark driver's check of the gate ----------------------------------


def _gated_cell():
    """``chipbench/drivers/serve_gated.py`` on the 6-pod fabric and its
    seeded trace, with ``f1-serve``'s limits."""
    import json
    import pathlib

    from chipbench import harness

    root = pathlib.Path(harness.__file__).resolve().parent
    limits = json.loads((root / "configs" / "f1-serve.json").read_text())[
        "limits"]
    cfg = {"fabrics": [FAB], "interval_minutes": 60.0,
           "routing_interval_hours": 3.0, "topology_interval_days": 1.0,
           "window_days": 1.0, "k_critical": 4, "pdhg_tol": TOL,
           "pdhg_max_iters": 3000, "solver_backend": "pdhg",
           "solver_precision": "f32", "backend": "numpy",
           "overload_threshold": 0.8, "stage_slack": 0.005,
           "transition": dataclasses.asdict(TC), "loss": LOSS,
           "trace_days": 4, "limits": limits}
    driver = harness.load_module(root / "drivers" / "serve_gated.py")
    cell = driver.Cell(cfg, {"strategy": {"nonuniform": True,
                                          "hedging": True}}, 3)
    cell.feed.demand = _trace()
    return cell, cfg


def _run_gated_cell():
    """Set-up, then two one-day windows: the installed and the vetoed
    change of the trace."""
    cell, cfg = _gated_cell()
    cell.setup()
    for _ in range(2):
        cell.window(0.0)
    gate = cell.layer_context()["gate"]
    return cell.answers(cell.finish()), cfg, gate


def _topo_viol(ans) -> int:
    """The check's ``topo_viol`` (every topology decision is judged)."""
    return sum(reference.topology_violations(d.fab, d.n_trunk, d.cap)
               for d in ans.decided if d.n_trunk is not None)


def test_gated_driver_answers_pass_the_check():
    """A sound gate, one change installed and one vetoed: every topology
    decision and gate solve meets ``f1-serve``'s limits on what the program
    guarantees whatever the traffic (the ``w_*`` limits were set on F1's
    calm traffic, not on this trace's 12x hot pair)."""
    from chipbench import check

    ans, cfg, gate = _run_gated_cell()
    assert gate["applied"] >= 1 and gate["vetoed"] >= 1
    rows = [check.decision_readings(d, cfg) for d in ans.decided
            if d.topology]
    values = check.readings(ans, cfg, 3, rows=rows)
    for k in ("crit_viol", "topo_viol", "u_star_rel", "w_split_err",
              "mlu_rel", "loss_rel"):
        assert values[k] <= cfg["limits"][k], (k, values[k])


def _flip_decision(monkeypatch):
    from repro.core import controller

    gate0 = controller._transition_gate

    def flipped(*a, **k):
        apply, staged, ev, s = gate0(*a, **k)
        if ev is None:
            return apply, staged, ev, s
        return (not apply), (None if apply else ev), ev, s
    monkeypatch.setattr(controller, "_transition_gate", flipped)


def _worst_order(monkeypatch):
    import itertools

    from repro.transition import score

    def worst(fabric, tms, diff, max_exact=8):
        def cost(order):
            return max(reference_gate.proxy_mlu(
                tms, gen.edge_capacities(FAB, t), 6)
                for t in reference_gate.stage_trunks(
                    diff.old_counts, diff.new_counts, order))
        panels = tuple(int(p) for p in diff.panels_with_moves)
        order = max(itertools.permutations(panels), key=cost)
        assert cost(order) > reference_gate.best_order(
            tms, FAB, diff.old_counts, diff.new_counts)[1]
        return order, cost(order), cost(panels)
    monkeypatch.setattr(score, "schedule_drains", worst)


def _panel_skipped(monkeypatch):
    from repro.transition import score

    diff0 = score.diff_topologies

    def skipped(*a, **k):
        d = diff0(*a, **k)
        moves = d.moves_per_panel.copy()
        moves[d.panels_with_moves[0]] = 0
        return dataclasses.replace(d, moves_per_panel=moves)
    monkeypatch.setattr(score, "diff_topologies", skipped)


@pytest.mark.parametrize("plant", [_flip_decision, _worst_order,
                                   _panel_skipped],
                         ids=["decision_flipped", "order_worst",
                              "panel_skipped"])
def test_gated_driver_check_catches_a_faulty_gate(monkeypatch, plant):
    """A gate that decides against the reference, drains in a worse order
    than the best, or drains too few panels reads ``topo_viol`` above its
    limit of 0."""
    plant(monkeypatch)
    ans, _, gate = _run_gated_cell()
    assert gate["evaluations"] >= 2
    assert _topo_viol(ans) > 0
