"""Plain reference of the §4.6 reconfigure-or-keep gate and its drain stages.

Nothing here imports ``repro``.  In float64 numpy and scipy's HiGHS (through
:mod:`chipbench.reference`), for one topology change given as per-panel
trunk counts (Thm. 4: a change re-targets jumpers inside panels and never
moves a fiber between them):

* :func:`panel_moves` — jumper moves per panel: every move disconnects one
  pod pair and connects another, so a panel needs the larger side of the
  multiset difference of its old and new link sets;
* :func:`assignment_violations` — a panel decomposition against the trunk
  topology it claims to carry;
* :func:`fiber_moves` — the ports each panel needs beyond its old wiring
  (0 when the change moves jumpers only, as Thm. 4 has it);
* :func:`stage_trunks` — the trunks live while each panel of a drain order
  is down (panels drained before it carry their new links, those after it
  their old ones);
* :func:`proxy_mlu`, :func:`best_order` — the drain scheduler's objective
  (capacity-proportional 1-/2-hop splits) and its optimum over every order;
* :func:`evaluate` — u* on the old, new and drained capacities
  (:func:`chipbench.reference.stage1_u`), benefit, disruption and the
  decision;
* :func:`judged` — the topology a gate evaluation must install and the
  trunks each of its drain stages must leave, for the check;
* :func:`stage_spans`, :func:`score_staged` — the staged epoch's intervals
  scored per drain stage, then under the new topology
  (:func:`chipbench.reference.score_block` per span).
"""

from __future__ import annotations

import itertools

import numpy as np

from chipbench import gen, reference


def panel_moves(old_counts: np.ndarray, new_counts: np.ndarray) -> np.ndarray:
    """``(P,)`` jumper moves: ``max(|old_p - new_p|, |new_p - old_p|)`` of
    each panel's link multisets, given as ``(P, E_u)`` trunk counts."""
    old = np.asarray(old_counts, np.int64)
    new = np.asarray(new_counts, np.int64)
    return np.maximum(np.maximum(old - new, 0).sum(axis=1),
                      np.maximum(new - old, 0).sum(axis=1))


def assignment_violations(n_pods: int, n_trunk: np.ndarray,
                          counts: np.ndarray) -> int:
    """Trunks whose ``(P, E_u)`` panel counts are negative or do not sum to
    the topology ``n_trunk`` they claim to carry."""
    counts = np.asarray(counts, np.int64)
    n = np.rint(np.asarray(n_trunk, np.float64)).astype(np.int64)
    return int((counts.sum(axis=0) != n).sum()
               + (counts < 0).any(axis=0).sum())


def _panel_degrees(counts: np.ndarray) -> np.ndarray:
    """``(P, V)`` links of each pod in each panel."""
    counts = np.asarray(counts, np.int64)
    v = int(round((1 + np.sqrt(1 + 8 * counts.shape[1])) / 2))
    t = gen.trunks(v)
    deg = np.zeros((counts.shape[0], v), np.int64)
    for p in range(counts.shape[0]):
        np.add.at(deg[p], t[:, 0], counts[p])
        np.add.at(deg[p], t[:, 1], counts[p])
    return deg


def fiber_moves(old_counts: np.ndarray, new_counts: np.ndarray) -> np.ndarray:
    """``(P,)`` pod ports each panel needs beyond what the pod had wired to
    it before: 0 everywhere iff the change re-targets jumpers only (Thm. 4);
    a port counted here would move a fiber between panels."""
    return np.maximum(_panel_degrees(new_counts) - _panel_degrees(old_counts),
                      0).sum(axis=1)


def stage_trunks(old_counts: np.ndarray, new_counts: np.ndarray,
                 order) -> np.ndarray:
    """``(S, E_u)`` trunks live during each drain of ``order``."""
    old = np.asarray(old_counts, np.int64)
    new = np.asarray(new_counts, np.int64)
    out = []
    for s, p in enumerate(order):
        live = [new[q] if q in order[:s] else old[q]
                for q in range(old.shape[0]) if q != p]
        out.append(np.sum(live, axis=0))
    return np.asarray(out, np.int64).reshape(len(order), old.shape[1])


def _proxy_splits(cap: np.ndarray, n_pods: int):
    """Each commodity's splits over its paths in proportion to the path's
    bottleneck capacity; None when a commodity has no live path."""
    comm, e1, e2 = reference._paths(n_pods)
    cap = np.asarray(cap, np.float64)
    neck = np.where(e2 >= 0, np.minimum(cap[e1], cap[np.maximum(e2, 0)]),
                    cap[e1])
    total = np.bincount(comm, weights=neck, minlength=comm.max() + 1)
    if (total <= 1e-12).any():
        return None
    return neck / total[comm]


def _stranded(cap: np.ndarray, n_pods: int) -> bool:
    """Some commodity has no path whose links are all live."""
    return _proxy_splits(cap, n_pods) is None


def proxy_mlu(tms: np.ndarray, cap: np.ndarray, n_pods: int) -> float:
    """MLU over the TMs of the capacity-proportional splits on ``cap``; inf
    when a commodity is stranded."""
    f = _proxy_splits(cap, n_pods)
    if f is None:
        return float("inf")
    comm, e1, e2 = reference._paths(n_pods)
    w = np.zeros((comm.max() + 1, len(cap)))
    np.add.at(w, (comm, e1), f)
    two = e2 >= 0
    np.add.at(w, (comm[two], e2[two]), f[two])
    cap = np.asarray(cap, np.float64)
    live = cap > reference.LIVE
    load = np.asarray(tms, np.float64) @ w
    return float((load[:, live] / cap[live]).max()) if live.any() else 0.0


def best_order(tms, fab: dict, old_counts, new_counts) -> tuple:
    """``(order, worst)``: the drain order of the panels with moves whose
    worst stage has the least proxy MLU, by enumerating every order."""
    v = len(fab["radix"])
    panels = tuple(int(p) for p in
                   np.flatnonzero(panel_moves(old_counts, new_counts)))
    best = ((), 0.0)
    for i, order in enumerate(itertools.permutations(panels)):
        worst = max(proxy_mlu(tms, gen.edge_capacities(fab, t), v)
                    for t in stage_trunks(old_counts, new_counts, order))
        if i == 0 or worst < best[1]:
            best = (order, worst)
    return best


def decide(benefit: float, disruption: float, hysteresis: float) -> bool:
    """Install iff the benefit is positive and beats ``(1 + hysteresis)``
    times the disruption."""
    return benefit > 0.0 and benefit > (1.0 + hysteresis) * disruption


def evaluate(tms, fab: dict, old_counts, new_counts, order,
             stage_intervals: int, horizon: int,
             hysteresis: float = 0.0) -> dict:
    """The gate's numbers for one change drained in ``order``: stage trunks
    and capacities, u* on the old, new and drained capacities (inf on a
    stranded stage), benefit, disruption and the decision."""
    v = len(fab["radix"])
    trunks = stage_trunks(old_counts, new_counts, order)
    caps = [gen.edge_capacities(fab, t) for t in trunks]
    u = lambda cap: (float("inf") if _stranded(cap, v)  # noqa: E731
                     else reference.stage1_u(tms, cap, v))
    u_old = u(gen.edge_capacities(fab, np.sum(old_counts, axis=0)))
    u_new = u(gen.edge_capacities(fab, np.sum(new_counts, axis=0)))
    stage_u = np.asarray([u(c) for c in caps])
    staged = len(order) * stage_intervals
    worst = float(stage_u.max()) if stage_u.size else u_new
    benefit = (u_old - u_new) * max(horizon - staged, 0)
    disruption = max(worst - u_old, 0.0) * staged
    return {"stage_trunks": trunks, "stage_caps": np.asarray(caps),
            "u_old": u_old, "u_new": u_new, "stage_u": stage_u,
            "benefit": benefit, "disruption": disruption,
            "apply": decide(benefit, disruption, hysteresis)}


def judged(tms, fab: dict, n_old, old_counts, new_counts, order,
           u_old: float, u_new: float, stage_u, stage_intervals: int,
           horizon: int, hysteresis: float = 0.0) -> dict:
    """What the check holds one gate evaluation to, given the installed old
    topology ``n_old``, the program's panel decomposition and drain order,
    and the program's u* on the old, new and drained capacities (each of
    which the check holds to HiGHS's by ``u_star_rel``):

    * ``order`` — the program's order if it drains exactly the panels with
      moves (:func:`panel_moves`) and its worst stage's proxy MLU is
      :func:`best_order`'s, else :func:`best_order`'s;
    * ``stage_trunks`` — the trunks live while each panel of that order is
      down, ``n_old`` plus the changes of the panels drained before it, less
      the panel's old links (a decomposition whose old side does not sum to
      ``n_old`` moves every stage off the program's);
    * ``new`` — ``n_old`` plus every panel's change;
    * ``install`` — ``new`` or ``n_old``, as :func:`decide` rules on the
      benefit and disruption of those u*s.
    """
    old = np.asarray(old_counts, np.int64)
    new = np.asarray(new_counts, np.int64)
    n_old = np.rint(np.asarray(n_old, np.float64)).astype(np.int64)
    panels = set(np.flatnonzero(panel_moves(old, new)).tolist())
    best, worst_best = best_order(tms, fab, old, new)
    order = tuple(int(p) for p in order)
    ok = len(order) == len(panels) and set(order) == panels
    if ok and order:
        v = len(fab["radix"])
        worst = max(proxy_mlu(tms, gen.edge_capacities(fab, t), v)
                    for t in stage_trunks(old, new, order))
        ok = worst <= worst_best * (1.0 + 1e-9)
    order = order if ok else best
    stage_u = np.asarray(stage_u, np.float64)
    staged = stage_u.size * stage_intervals
    worst_u = float(stage_u.max()) if stage_u.size else u_new
    benefit = (u_old - u_new) * max(horizon - staged, 0)
    disruption = max(worst_u - u_old, 0.0) * staged
    new_n = n_old + (new - old).sum(axis=0)
    return {"order": order,
            "stage_trunks": (stage_trunks(old, new, order)
                             + n_old - old.sum(axis=0)),
            "new": new_n,
            "install": (new_n if decide(benefit, disruption, hysteresis)
                        else n_old)}


def stage_spans(n_stages: int, stage_intervals: int, length: int) -> tuple:
    """``([(stage, lo, hi), ...], rest)``: the drain stages' spans of a
    ``length``-interval epoch (clipped to it) and where the new topology's
    steady remainder starts."""
    spans = [(k, k * stage_intervals,
              min((k + 1) * stage_intervals, length))
             for k in range(n_stages) if k * stage_intervals < length]
    return spans, min(n_stages * stage_intervals, length)


def score_staged(rows, n_stages: int, stage_intervals: int, stage_w,
                 stage_caps, w_new, cap_new, loss: dict, seed: int,
                 interval_s: float) -> tuple:
    """Per-interval ``(mlu, loss)`` of a staged epoch's ``rows``: each drain
    span under its stage's weights and capacities with burst seed
    ``seed + lo``, then the remainder under the new ones."""
    rows = np.asarray(rows, np.float64)
    spans, rest = stage_spans(n_stages, stage_intervals, rows.shape[0])
    parts = [(lo, hi, stage_w[k], stage_caps[k]) for k, lo, hi in spans]
    if rest < rows.shape[0]:
        parts.append((rest, rows.shape[0], w_new, cap_new))
    out = [reference.score_block(rows[lo:hi], w, cap, loss, seed + lo,
                                 interval_s) for lo, hi, w, cap in parts]
    return (np.concatenate([m for m, _ in out]),
            np.concatenate([x for _, x in out]))
