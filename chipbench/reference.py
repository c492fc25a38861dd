"""Plain reference of the controller's answers, independent of the program.

Nothing here imports ``repro``.  Each function judges one kind of answer by
what it says, in float64 numpy and scipy's HiGHS:

* :func:`crit_violations` — critical TMs against the window they summarise;
* :func:`stage1_u` — the routing LP's optimum ``u*`` (min MLU over the
  critical TMs, 1- and 2-hop paths, fixed capacities);
* :func:`stage2_rho`, :func:`stage3_stretch` — the optima of the hedging
  and stretch stages that follow it, within the budgets stage 1 leaves;
* :func:`splits`, :func:`risk`, :func:`stretch` — what an installed weight
  matrix routes, and its value under those two objectives;
* :func:`mlu` — the MLU a weight matrix gives a set of TMs;
* :func:`topology_violations` — an installed topology against the fabric;
* :func:`score_block` — per-interval MLU and burst loss of one routing epoch's
  intervals under the weights installed for it (fluid queue over the
  sub-interval expansion of :func:`chipbench.gen.expand`).

:func:`score_block_control` is the control: the same scoring in float32 with
its matmuls at ``Precision.HIGH`` (three bf16 passes, written out on the
host so that every platform computes the same), the step below the
configuration's float32 at HIGHEST.
"""

from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog

from chipbench import gen

LIVE = 1e-9  # a link with capacity at or below this is dead


def crit_violations(window: np.ndarray, crit: np.ndarray) -> int:
    """Count of window TMs under no critical TM, plus critical TMs that are
    not the element-wise maximum of the window TMs under them.  Zero exactly
    when the critical TMs are the maxima of a cover of the window."""
    window = np.asarray(window, np.float64)
    crit = np.asarray(crit, np.float64)
    under = (window[:, None, :] <= crit[None, :, :]).all(axis=2)  # (T, k)
    bad = int((~under.any(axis=1)).sum())
    for j in range(crit.shape[0]):
        rows = window[under[:, j]]
        if not rows.size or not np.array_equal(rows.max(axis=0), crit[j]):
            bad += 1
    return bad


@functools.lru_cache(maxsize=8)
def _paths(n_pods: int):
    """Path -> (commodity, edge, edge): direct paths (second edge -1) and
    every 2-hop transit path."""
    pairs = gen.directed_edges(n_pods)
    edge_of = {(i, j): e for e, (i, j) in enumerate(pairs.tolist())}
    comm, e1, e2 = [], [], []
    for c, (i, j) in enumerate(pairs.tolist()):
        comm.append(c), e1.append(edge_of[(i, j)]), e2.append(-1)
        for k in range(n_pods):
            if k not in (i, j):
                comm.append(c), e1.append(edge_of[(i, k)])
                e2.append(edge_of[(k, j)])
    return np.asarray(comm), np.asarray(e1), np.asarray(e2)


def _load_rows(crit: np.ndarray, n_pods: int):
    """``(load, comm, hop_p, hop_e)``: the ``(m E, P)`` load matrix of the
    critical TMs over every path, and each path hop as (path, edge)."""
    comm, e1, e2 = _paths(n_pods)
    n_p, n_e, m = comm.size, n_pods * (n_pods - 1), crit.shape[0]
    hop_p = np.concatenate([np.arange(n_p), np.flatnonzero(e2 >= 0)])
    hop_e = np.concatenate([e1, e2[e2 >= 0]])
    rows = (np.arange(m)[:, None] * n_e + hop_e[None, :]).ravel()
    cols = np.tile(hop_p, m)
    vals = crit[:, comm[hop_p]].ravel()
    load = sp.csr_matrix((vals, (rows, cols)), shape=(m * n_e, n_p))
    return load, comm, hop_p, hop_e


def _routing_lp(crit, cap, n_pods, u_budget=None, rho_budget=None,
                objective="u"):
    """One routing LP over the path splits ``f`` (summing to 1 per
    commodity) and, for ``objective`` ``"u"`` or ``"rho"``, one scalar:

    * ``"u"``: min u  s.t.  load_{t,e}(f) <= u cap_e;
    * ``"rho"``: min rho  s.t.  load <= u_budget cap,  f_p <= rho cap_e for
      every hop e of p (the hedging risk with the burst size divided out);
    * ``"stretch"``: min sum_t sum_p f_p d_t[c(p)] len(p)  s.t.
      load <= u_budget cap,  f_p <= rho_budget cap_e (no risk rows where
      ``rho_budget`` is None).

    Returns the optimum, NaN (which fails the check) when HiGHS finds none
    in 120 s."""
    crit = np.asarray(crit, np.float64)
    cap = np.asarray(cap, np.float64)
    load, comm, hop_p, hop_e = _load_rows(crit, n_pods)
    n_p, m = comm.size, crit.shape[0]
    extra = objective != "stretch"
    blocks, b_ub = [], []
    if objective == "u":
        blocks.append(sp.hstack([load, sp.csr_matrix(-np.tile(cap, m)[:, None])]))
        b_ub.append(np.zeros(load.shape[0]))
    else:
        blocks.append(sp.hstack([load, sp.csr_matrix((load.shape[0], 1))])
                      if extra else load)
        b_ub.append(u_budget * np.tile(cap, m))
    if objective == "rho" or rho_budget is not None:
        r = np.arange(hop_p.size)
        risk = sp.csr_matrix((np.ones(r.size), (r, hop_p)),
                             shape=(r.size, n_p))
        if objective == "rho":
            blocks.append(sp.hstack([risk, sp.csr_matrix(-cap[hop_e][:, None])]))
            b_ub.append(np.zeros(r.size))
        else:
            blocks.append(risk)
            b_ub.append(rho_budget * cap[hop_e])
    flow = sp.csr_matrix((np.ones(n_p), (comm, np.arange(n_p))),
                         shape=(comm.max() + 1, n_p))
    if extra:
        c = np.zeros(n_p + 1)
        c[-1] = 1.0
        flow = sp.hstack([flow, sp.csr_matrix((flow.shape[0], 1))])
    else:
        c = crit.sum(axis=0)[comm] * _path_len(n_pods)
    res = linprog(c, A_ub=sp.vstack(blocks, format="csr"),
                  b_ub=np.concatenate(b_ub), A_eq=flow.tocsr(),
                  b_eq=np.ones(flow.shape[0]), bounds=(0, None),
                  method="highs", options={"time_limit": 120.0})
    if res.status != 0:
        return float("nan")
    return float(res.x[-1]) if extra else float(res.fun)


def _path_len(n_pods: int) -> np.ndarray:
    _, _, e2 = _paths(n_pods)
    return np.where(e2 >= 0, 2.0, 1.0)


def stage1_u(crit: np.ndarray, cap: np.ndarray, n_pods: int) -> float:
    """min u  s.t.  sum_{p through e} f_p d_t[c(p)] <= u cap_e  for every
    critical TM t and directed edge e;  sum_{p of c} f_p = 1;  f, u >= 0.
    NaN (which fails the check) when HiGHS finds no optimum in 120 s."""
    return _routing_lp(crit, cap, n_pods)


def stage2_rho(crit, cap, n_pods: int, u_budget: float) -> float:
    """Least hedging risk ``max_{p, e in p} f_p / cap_e`` of a routing whose
    MLU over the critical TMs stays within ``u_budget``."""
    return _routing_lp(crit, cap, n_pods, u_budget, objective="rho")


def stage3_stretch(crit, cap, n_pods: int, u_budget: float,
                   rho_budget: float | None) -> float:
    """Least carried volume ``sum_t sum_p f_p d_t[c(p)] len(p)`` of a routing
    within ``u_budget`` and, unless None, ``rho_budget``."""
    return _routing_lp(crit, cap, n_pods, u_budget, rho_budget, "stretch")


def splits(w: np.ndarray, n_pods: int) -> tuple:
    """``(f, err)``: the path splits that weight matrix ``w`` installs (each
    edge of a commodity's paths lies on exactly one of them), and the worst
    departure of ``w`` from a routing: a commodity's splits not summing to
    1, a negative split, a transit path's two hops carrying different
    shares, or weight on an edge that is on none of the commodity's
    paths."""
    w = np.asarray(w, np.float64)
    comm, e1, e2 = _paths(n_pods)
    f = w[comm, e1]
    two = e2 >= 0
    on = np.zeros(w.shape, bool)
    on[comm, e1] = True
    on[comm[two], e2[two]] = True
    total = np.bincount(comm, weights=f, minlength=w.shape[0])
    err = max(float(np.abs(total - 1.0).max()),
              float(np.maximum(-f, 0.0).max()),
              float(np.abs(w[comm[two], e2[two]] - f[two]).max()),
              float(np.abs(w[~on]).max()) if (~on).any() else 0.0)
    return f, err


def risk(f: np.ndarray, cap: np.ndarray, n_pods: int) -> float:
    """Hedging risk of splits ``f``: ``max_{p, e in p} f_p / cap_e`` over
    live links (the burst size divided out)."""
    cap = np.asarray(cap, np.float64)
    comm, e1, e2 = _paths(n_pods)
    p = np.concatenate([np.arange(comm.size), np.flatnonzero(e2 >= 0)])
    e = np.concatenate([e1, e2[e2 >= 0]])
    live = cap[e] > LIVE
    return float((np.asarray(f, np.float64)[p][live] / cap[e][live]).max())


def stretch(crit: np.ndarray, w: np.ndarray) -> float:
    """Carried volume ``sum_t sum_c d_t[c] sum_e w[c, e]`` of the critical
    TMs under ``w``: stage 3's objective."""
    return float(np.asarray(crit, np.float64).sum(axis=0)
                 @ np.asarray(w, np.float64).sum(axis=1))


def mlu(tms: np.ndarray, w: np.ndarray, cap: np.ndarray) -> np.ndarray:
    """Per-TM maximum link utilisation of ``tms @ w`` over live links."""
    cap = np.asarray(cap, np.float64)
    live = cap > LIVE
    load = np.asarray(tms, np.float64) @ np.asarray(w, np.float64)
    return (load[:, live] / cap[live]).max(axis=1)


def topology_violations(fab: dict, n_trunk: np.ndarray,
                        cap: np.ndarray) -> int:
    """Trunks that are not whole non-negative link counts, pods over their
    radix, and directed capacities other than ``n_e * min(s_i, s_j)``."""
    n = np.asarray(n_trunk, np.float64)
    radix = np.asarray(fab["radix"], np.float64)
    t = gen.trunks(radix.size)
    deg = np.zeros(radix.size)
    np.add.at(deg, t[:, 0], n)
    np.add.at(deg, t[:, 1], n)
    want = gen.edge_capacities(fab, n)
    return (int((np.abs(n - np.round(n)) > 1e-9).sum() + (n < 0).sum())
            + int((deg > radix + 1e-9).sum())
            + int((~np.isclose(cap, want, rtol=1e-12, atol=0.0)).sum()))


def _loss_fraction(drop_sub, sub, n: int, n_sub: int, dt: float):
    """Dropped volume over offered demand volume per interval, clipped to 1."""
    drop = np.asarray(drop_sub, np.float64).reshape(n, n_sub).sum(axis=1)
    offered = np.asarray(sub, np.float64).sum(axis=1).reshape(n, n_sub).sum(
        axis=1) * dt
    return np.where(offered > 1e-12,
                    np.minimum(drop / np.maximum(offered, 1e-12), 1.0), 0.0)


def score_block(rows, w, cap, loss: dict, seed: int, interval_s: float):
    """Per-interval ``(mlu, loss)`` of one epoch's ``rows`` under ``w``: a
    fluid queue per link over the block's sub-steps, empty at its start."""
    rows = np.asarray(rows, np.float64)
    cap = np.asarray(cap, np.float64)
    sub = gen.expand(rows, loss["n_sub"], loss["burst"], seed)
    dt = interval_s / loss["n_sub"]
    load = sub @ np.asarray(w, np.float64)
    buf = cap * (loss["buffer_ms"] * 1e-3)
    q = np.zeros_like(cap)
    drop = np.empty(load.shape[0])
    for k in range(load.shape[0]):
        x = q + (load[k] - cap) * dt
        drop[k] = np.maximum(x - buf, 0.0).sum()
        q = np.clip(x, 0.0, buf)
    return (mlu(rows, w, cap),
            _loss_fraction(drop, sub, rows.shape[0], loss["n_sub"], dt))


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def dot_high(a, b) -> np.ndarray:
    """``a @ b`` at ``Precision.HIGH`` (bf16_3x), on the host: each float32
    operand split into a bf16 high part and a bf16 low part, and the three
    larger cross products (exact in float64) summed, then rounded to
    float32."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    a1, b1 = _bf16(a), _bf16(b)
    a2, b2 = _bf16(a - a1), _bf16(b - b1)
    f64 = lambda x: x.astype(np.float64)  # noqa: E731
    return (f64(a1) @ f64(b1)
            + (f64(a1) @ f64(b2) + f64(a2) @ f64(b1))).astype(np.float32)


def score_block_control(rows, w, cap, loss: dict, seed: int,
                        interval_s: float):
    """:func:`score_block` in float32 with its matmuls at ``HIGH``."""
    rows = np.asarray(rows, np.float64)
    cap32 = np.asarray(cap, np.float32)
    live = cap32 > LIVE
    util = dot_high(rows, w)[:, live] / cap32[live]
    sub = gen.expand(rows, loss["n_sub"], loss["burst"], seed)
    dt = np.float32(interval_s / loss["n_sub"])
    load = dot_high(sub, w)
    buf = cap32 * np.float32(loss["buffer_ms"] * 1e-3)
    q = np.zeros_like(cap32)
    drop = np.empty(load.shape[0], np.float32)
    for k in range(load.shape[0]):
        x = q + (load[k] - cap32) * dt
        drop[k] = np.maximum(x - buf, 0.0).sum(dtype=np.float32)
        q = np.clip(x, 0.0, buf)
    return (util.max(axis=1).astype(np.float64),
            _loss_fraction(drop, sub, rows.shape[0], loss["n_sub"], float(dt)))
