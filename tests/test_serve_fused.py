"""The served PDHG solve as one device program.

``JaxRoutingSolver.solve_routing_warm`` runs stages 1 → [2] → [3] of one
epoch in a single jitted program and fetches its outputs once.  It must give
what the stage-by-stage composition of the ``_solve_*_batch[_warm]``
programs gives from the same inputs and the same carried iterates: the same
iteration count in every stage, the same ``f``, ``u*`` and ``r*``, and the
same iterates to carry on.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.graph import Fabric, uniform_topology
from repro.core.jaxlp import JaxRoutingSolver, RoutingWarmState

V, M = 6, 4
DELTA = 0.05
FIELDS = ("f1", "y1", "f2", "y2", "z2", "y3")


def _composed(s, tms, caps, hedging, delta, skip_stage3, st):
    """Stages 1 → [2] → [3] as separate B = 1 stage programs, each stage
    warm where ``st`` carries its iterates (the served solve's contract)."""
    d3 = s._dense_tms(tms)[None]
    ic = s._dense_inv_cap(caps)[None]
    vb = s._tile_valid(1)
    if st is None:
        f3, u, it1, y1, _ = s._solve_mlu_batch(d3, ic, vb)
    else:
        f3, u, it1, y1, _ = s._solve_mlu_batch_warm(d3, ic, vb, st.f1, st.y1)
    new = RoutingWarmState(f1=f3, y1=y1)
    u_budget = jnp.asarray(u) * 1.005 + 1e-9
    iters = {"stage1": int(it1[0])}
    r_star = None
    run2 = hedging and delta > 0
    if run2:
        dl = jnp.asarray([delta], jnp.float32)
        if st is None or st.f2 is None:
            f3, r, _, y2, z2, it2, _ = s._solve_risk_batch(
                d3, ic, vb, u_budget, dl)
        else:
            f3, r, _, y2, z2, it2, _ = s._solve_risk_batch_warm(
                d3, ic, vb, u_budget, dl, st.f2, st.y2, st.z2)
        new.f2, new.y2, new.z2 = f3, y2, z2
        r_star = float(r[0])
        iters["stage2"] = int(it2[0])
    if not skip_stage3:
        r_in = jnp.asarray([r_star * 1.005 + 1e-12 if run2 else 1e9],
                           jnp.float32)
        dl_in = jnp.asarray([delta if run2 else 0.0], jnp.float32)
        if st is None or st.y3 is None:
            f3, y3, it3, _ = s._solve_stretch_batch(
                d3, ic, vb, u_budget, r_in, dl_in, f3)
        else:
            f3, y3, it3, _ = s._solve_stretch_batch_warm(
                d3, ic, vb, u_budget, r_in, dl_in, f3, st.y3)
        new.y3 = y3
        iters["stage3"] = int(it3[0])
    return (s._flat_f(np.asarray(f3))[0], float(u[0]), r_star, iters), new


@pytest.fixture(scope="module")
def instance():
    fab = Fabric(name="v6", radix=np.full(V, 16), speed=np.full(V, 100.0))
    rng = np.random.default_rng(11)
    tms = [rng.random((M, V * (V - 1))) * 200.0 for _ in range(2)]
    return fab, fab.capacities(uniform_topology(fab)), tms


@pytest.fixture(scope="module")
def solvers(instance):
    fab = instance[0]
    return {p: JaxRoutingSolver(fab, M, tol=1e-2, precision=p)
            for p in ("f32", "bf16")}


def _anchor(s, instance, kind):
    """The carried state: none, every stage's, or stage 1's alone."""
    _, caps, tms = instance
    if kind == "cold":
        return None
    full = kind == "warm"
    return s.solve_routing_warm(tms[0], caps, hedging=full, delta=DELTA,
                                skip_stage3=not full)[1]


@pytest.mark.parametrize("skip_stage3", [False, True],
                         ids=["stages123", "skip_stage3"])
@pytest.mark.parametrize("delta", [DELTA, 0.0], ids=["hedged", "delta0"])
@pytest.mark.parametrize("anchor", ["cold", "warm", "partial"])
@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_served_solve_matches_stage_composition(solvers, instance, precision,
                                                anchor, delta, skip_stage3):
    s = solvers[precision]
    _, caps, tms = instance
    st = _anchor(s, instance, anchor)
    out, state = s.solve_routing_warm(tms[1], caps, hedging=True, delta=delta,
                                      skip_stage3=skip_stage3,
                                      anchor_state=st)
    (f, u, r, iters), ref_state = _composed(s, tms[1], caps, True, delta,
                                            skip_stage3, st)
    assert {k: int(v["iters"][0]) for k, v in out["stats"].items()
            if k.startswith("stage")} == iters
    assert out["u_star"] == pytest.approx(u, rel=1e-5)
    if delta > 0:
        assert out["r_star"] == pytest.approx(r, rel=1e-5)
        assert out["stats"]["stage2"]["active"].tolist() == [True]
    else:
        assert out["r_star"] is None and r is None
    np.testing.assert_allclose(out["f"], f, rtol=1e-5, atol=1e-5)
    for name in FIELDS:  # the iterates carried to the next epoch
        got, want = getattr(state, name), getattr(ref_state, name)
        assert (got is None) == (want is None), name
        if want is not None:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("skip_stage3", [False, True],
                         ids=["stages123", "skip_stage3"])
@pytest.mark.parametrize("delta", [DELTA, 0.0], ids=["hedged", "delta0"])
def test_served_solve_is_one_dispatch_and_one_wait(solvers, instance, delta,
                                                   skip_stage3):
    s = solvers["f32"]
    _, caps, tms = instance
    st = _anchor(s, instance, "warm")
    obs.enable()
    obs.clear()
    try:
        for anchor_state in (None, st):
            s.solve_routing_warm(tms[1], caps, hedging=True, delta=delta,
                                 skip_stage3=skip_stage3,
                                 anchor_state=anchor_state)
        spans = [e for e in obs.events() if e["name"].startswith("jaxlp.")]
    finally:
        obs.disable()
        obs.clear()
    last = 3 if not skip_stage3 else 2 if delta > 0 else 1
    per_call = ["jaxlp.warm_inputs", "jaxlp.warm_solve", "jaxlp.wait"]
    assert [e["name"] for e in spans] == per_call * 2
    assert [e["args"]["stage"] for e in spans
            if e["name"] == "jaxlp.wait"] == [last, last]
