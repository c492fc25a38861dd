"""The comparison that decides ``correct``.

A driver hands over what the timed window produced (:class:`Answers`):
every routing decision with the critical TMs, weights, capacities and
topology it installed, and every block of intervals the window scored with
the program's per-interval MLU and loss.  Each number below is judged
against the plain reference (:mod:`chipbench.reference`) and held to the
limit of the same name in the configuration's ``limits``:

* ``crit_viol`` — critical TMs that are not the maxima of a cover of their
  window (exact: 0);
* ``topo_viol`` — installed topologies with a fractional or negative trunk, a
  pod over its radix, or a capacity other than ``n_e min(s_i, s_j)``
  (exact: 0);
* ``u_star_rel`` — ``|u* / u*_LP - 1|``, the program's stage-1 bound against
  the reference LP on the same critical TMs and capacities;
* ``w_split_err`` — the installed weights' worst departure from a routing
  (splits that do not sum to 1, a negative split, a transit path's hops
  that disagree, weight off the commodity's paths);
* ``w_mlu_rel`` — ``MLU(W on the critical TMs) / u*_LP - 1``;
* ``w_risk_rel`` — the hedging risk of ``W`` against the least risk of a
  routing within ``u*_LP (1 + stage_slack)`` (hedged epochs; the burst size
  divides out of both);
* ``w_stretch_rel`` — the carried volume of ``W`` (stage 3's objective)
  against the least within ``u*_LP`` and that least risk, each times
  ``1 + stage_slack``;
* ``mlu_rel`` — worst relative gap of a served interval's MLU;
* ``loss_rel`` — worst absolute gap of a served interval's loss fraction, as
  a share of the largest loss fraction the reference finds in the window
  (0 when neither finds any loss).

The ``w_*`` numbers hold the installed weights ``W`` to the three routing
stages.  ``w_mlu_rel``, ``w_risk_rel`` and ``w_stretch_rel`` are signed: a
``W`` solved to the program's own, slightly looser budgets may read below
0.  Only the numbers that the configuration's ``limits`` name are
compared; ``PERF.md`` says why the others are reported alone.  The first
seven are taken on a sample of the window's decisions drawn from the seed
(every topology epoch and ``sample`` routing epochs); the last two on every
interval the window scored.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference

NAMES = ("crit_viol", "topo_viol", "u_star_rel", "w_split_err", "w_mlu_rel",
         "w_risk_rel", "w_stretch_rel", "mlu_rel", "loss_rel")
SIGNED = ("w_mlu_rel", "w_risk_rel", "w_stretch_rel")


@dataclasses.dataclass
class Decided:
    window: np.ndarray  # (T, C) the TMs the decision was planned from
    tms: np.ndarray  # (k, C) critical TMs the program solved for
    w: np.ndarray  # (C, E) installed routing weights
    cap: np.ndarray  # (E,) installed directed capacities
    n_trunk: np.ndarray | None  # (E_u,) installed topology, if known
    u_star: float  # the program's stage-1 bound
    topology: bool  # a topology solve ran at this decision
    hedged: bool  # the strategy hedges (stage 2 runs)
    fab: dict  # the fabric's entry of the configuration


@dataclasses.dataclass
class Block:
    rows: np.ndarray  # (T_b, C) the intervals one epoch served
    w: np.ndarray
    cap: np.ndarray
    seed: int  # burst seed of the block
    mlu: np.ndarray  # (T_b,) the program's scores
    loss: np.ndarray


@dataclasses.dataclass
class Answers:
    decided: list
    blocks: list


def sample(decided: list, seed: int, n: int) -> list:
    """Every topology decision and ``n`` others, drawn from ``seed``."""
    topo = [i for i, d in enumerate(decided) if d.topology]
    rest = [i for i, d in enumerate(decided) if not d.topology]
    rng = np.random.default_rng([seed, 0x636B])
    pick = rng.choice(len(rest), size=min(n, len(rest)), replace=False)
    return [decided[i] for i in sorted(topo + [rest[j] for j in pick])]


def decision_readings(d: Decided, cfg: dict) -> dict:
    """The numbers of one decision: counts, and ``None`` for ``w_risk_rel``
    where stage 2 does not run."""
    v = len(d.fab["radix"])
    slack = 1.0 + cfg["stage_slack"]
    out = {"crit_viol": reference.crit_violations(d.window, d.tms),
           "topo_viol": 0 if d.n_trunk is None else
           reference.topology_violations(d.fab, d.n_trunk, d.cap)}
    u_lp = reference.stage1_u(d.tms, d.cap, v)
    out["u_star_rel"] = abs(d.u_star / u_lp - 1.0)
    f, out["w_split_err"] = reference.splits(d.w, v)
    out["w_mlu_rel"] = float(reference.mlu(d.tms, d.w, d.cap).max()) / u_lp - 1
    out["w_risk_rel"] = rho_b = None  # stage 2 runs where the strategy
    if d.hedged and (d.window > d.window.mean(axis=0)).any():  # hedges and
        rho = reference.stage2_rho(d.tms, d.cap, v, u_lp * slack)  # burst > 0
        out["w_risk_rel"] = reference.risk(f, d.cap, v) / rho - 1.0
        rho_b = rho * slack
    s_lp = reference.stage3_stretch(d.tms, d.cap, v, u_lp * slack, rho_b)
    out["w_stretch_rel"] = reference.stretch(d.tms, d.w) / s_lp - 1.0
    return out


def readings(ans: Answers, cfg: dict, seed: int, n_sample: int = 12,
             score=reference.score_block, rows: list | None = None) -> dict:
    """Each number of :data:`NAMES` for these answers: the decision numbers
    summed (counts) or at their worst over the seed's sample, unless
    ``rows`` gives :func:`decision_readings` already taken (``score`` may be
    the control's scoring, put in the program's place)."""
    if rows is None:
        rows = [decision_readings(d, cfg)
                for d in sample(ans.decided, seed, n_sample)]
    out = {k: sum(r[k] for r in rows) for k in ("crit_viol", "topo_viol")}
    for k in ("u_star_rel", "w_split_err") + SIGNED:
        vals = [r[k] for r in rows if r[k] is not None]
        out[k] = max(vals) if vals else (0.0 if k == "w_risk_rel"
                                         else float("nan"))
    out["mlu_rel"] = 0.0
    interval_s = cfg["interval_minutes"] * 60.0
    loss_gap = loss_max = 0.0
    for b in ans.blocks:
        m, loss = score(b.rows, b.w, b.cap, cfg["loss"], b.seed, interval_s)
        out["mlu_rel"] = max(out["mlu_rel"], float(
            (np.abs(b.mlu - m) / np.maximum(m, 1e-12)).max()))
        loss_gap = max(loss_gap, float(np.abs(b.loss - loss).max()))
        loss_max = max(loss_max, float(loss.max()))
    out["loss_rel"] = loss_gap / loss_max if loss_max > 0 else (
        0.0 if loss_gap == 0 else float("inf"))
    return out


def judge(values: dict, limits: dict) -> tuple[bool, list]:
    """``(correct, [[name, value, limit], ...])``: every number that the
    configuration gives a limit at or under it, and finite.  The others are
    reported and not compared."""
    rows = [[k, values[k], limits[k]] for k in NAMES if k in limits]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok), rows
