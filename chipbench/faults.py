"""Faults planted in the program underneath a run, to show that the check
catches them (``chipbench/tests/test_check.py`` on the CPU,
``chipbench/readings.py --fault`` on the chip at the cell's own size).

Each fault breaks the timed path where it is produced and leaves the rest
of the run as it is:

* ``state_unchanged`` — after the first epoch the routing solve returns its
  first answer again, weights and ``u*`` alike;
* ``w_stale`` — each epoch solves afresh and reports its new ``u*``, but the
  weights of the epoch before stay installed;
* ``w_frozen`` — the same, but the first epoch's weights stay installed;
* ``w_garbled`` — the installed weights' commodities are shifted by one;
* ``stages23_skipped`` — stages 2 and 3 do not run: stage 1's splits are
  installed;
* ``stage2_skipped`` — the hedging stage does not run;
* ``stage3_skipped`` — the stretch stage does not run;
* ``half_batch`` — half of each scored block is scored, the rest's mean
  fills the other half;
* ``answer_altered`` — one scored MLU of each block is altered by 0.1 %.
"""

from __future__ import annotations

import contextlib

import numpy as np

KINDS = ("state_unchanged", "w_stale", "w_frozen", "w_garbled",
         "stages23_skipped", "stage2_skipped", "stage3_skipped", "half_batch",
         "answer_altered")


def _solve_patch(kind):
    from repro.serve import controller as sc

    solve0 = sc.StreamingController._solve_routing

    def solve(self, tms, delta):
        if kind == "state_unchanged":
            if self._w is None:
                self._u_kept = solve0(self, tms, delta)
            return self._u_kept
        w_prev = getattr(self, "_w_solved", None)
        u = solve0(self, tms, delta)
        self._w_solved = self._w
        if kind == "w_stale" and w_prev is not None:
            self._w = w_prev
        elif kind == "w_frozen" and w_prev is not None:
            self._w = self._w_solved = w_prev
        elif kind == "w_garbled":
            self._w = np.roll(self._w, 1, axis=0)
        return u
    return sc.StreamingController, "_solve_routing", solve


def _stage_patch(kind):
    from repro.core import jaxlp

    warm0 = jaxlp.JaxRoutingSolver.solve_routing_warm

    def warm(self, tms, capacities, hedging, delta=0.0, skip_stage3=False,
             anchor_state=None):
        if kind in ("stages23_skipped", "stage2_skipped"):
            hedging, delta = False, 0.0
        if kind in ("stages23_skipped", "stage3_skipped"):
            skip_stage3 = True
        return warm0(self, tms, capacities, hedging, delta, skip_stage3,
                     anchor_state)
    return jaxlp.JaxRoutingSolver, "solve_routing_warm", warm


def _score_patch(kind):
    from repro.serve import controller as sc

    if kind == "half_batch":
        from repro.core.simulator import IntervalMetrics

        score0 = sc.route_metrics

        def half(demand, *a, **k):
            n = len(demand)
            m = score0(demand[: max(1, n // 2)], *a, **k)
            fill = lambda x: None if x is None else np.concatenate(  # noqa
                [x, np.full(n - x.size, x.mean())])
            return IntervalMetrics(fill(m.mlu), fill(m.alu), fill(m.olr),
                                   fill(m.stretch), fill(m.loss))
        return sc, "route_metrics", half
    from repro.kernels.linkload import ops

    f0 = ops.link_metrics

    def altered(*a, **k):
        mlu, *rest = f0(*a, **k)
        mlu = np.asarray(mlu).copy()
        mlu[-1] *= 1.001
        return (mlu, *rest)
    return ops, "link_metrics", altered


@contextlib.contextmanager
def planted(kind: str):
    """Plant fault ``kind`` (one of :data:`KINDS`) for the ``with`` block."""
    if kind in ("state_unchanged", "w_stale", "w_frozen", "w_garbled"):
        owner, name, fn = _solve_patch(kind)
    elif kind.startswith("stage"):
        owner, name, fn = _stage_patch(kind)
    elif kind in ("half_batch", "answer_altered"):
        owner, name, fn = _score_patch(kind)
    else:
        raise KeyError(f"no fault {kind!r}; known: {KINDS}")
    before = getattr(owner, name)
    setattr(owner, name, fn)
    try:
        yield
    finally:
        setattr(owner, name, before)
