"""Serve driver with Gemini's §4.6 gate: ``StreamingController`` on one
fabric with ``ControllerConfig.transition`` set, closed loop.

Every daily topology change after the first is diffed onto patch panels,
its panel drains ordered, and its old, new and drained capacities solved in
one PDHG batch; the change is installed iff its predicted benefit beats its
disruption, and an installed change's drain stages are scored under their
residual capacities (the configuration's ``transition`` block).  The program
hands each evaluation over as ``Decision.gate``.

Set-up ingests through the first gated topology epoch (the second topology
epoch; the first installs unconditionally) and the TM that scores it, then
runs each gate program once at its fixed shapes: the batch of
``n_panels + 2`` solves, with and without a dead link, the staged scoring
padded to the most stages an epoch holds, and the steady remainder of every
length a staged epoch can leave.  The cell's trace has its changes kept,
but the decision is the program's to make: set-up pays for the staged
programs so that a window whose gate installs a change compiles nothing
either.  The window is :class:`chipbench.drivers.serve.Cell`'s: it ends at
a stream-day boundary, so it holds exactly one topology epoch a day.

The check judges every gate evaluation of the window against
:func:`chipbench.reference_gate.judged`.  The gated epoch's topology
decision carries the trunks the reference installs (the new topology or
the old, by the decision rule on the program's u* of the old, new and
drained capacities, each held to HiGHS's by ``u_star_rel``), so a program
that installed the other reads ``topo_viol``.  The old and new steady solves and each drain
stage are topology decisions too (TMs, weights, capacities, u*), each
stage with the trunks the reference leaves live in that drain slot of the
best order: a worse order, a panel drained or skipped against the panel
moves, or a decomposition that does not sum to the installed topology
reads ``topo_viol``.  An installed change's staged spans and remainder are
scored blocks.
"""

from __future__ import annotations

import dataclasses
import json
import sys

import numpy as np

from chipbench import check, reference_gate
from chipbench.drivers import serve


class Cell(serve.Cell):
    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.serve import StreamingController
        from repro.serve.controller import Decision
        from repro.transition import TransitionConfig

        if "gate" not in {f.name for f in dataclasses.fields(Decision)}:
            raise SystemExit("chipbench: this program's Decision has no "
                             "gate field; the serve_gated driver needs it")
        super().__init__(cfg, mix, seed)
        c = self.ctrl
        self.tc = TransitionConfig(**cfg["transition"])
        self.ctrl = StreamingController(
            c.fabric, c.stream, c.strategy,
            dataclasses.replace(c.cc, transition=self.tc), c.sc)
        self.agg = self.ctrl.agg
        self.topo_step = self.ctrl.topo_step
        self.gates: dict = {}  # epoch index -> the gate's evaluation

    def _record(self, d, t: int) -> None:
        super()._record(d, t)
        if d.gate is not None:
            self.gates[d.epoch] = {"ev": d.gate,
                                   "applied": d.topology_applied}

    def setup(self) -> None:
        end = self.agg + self.topo_step + self.step + 1
        for t in range(end):
            self._ingest(t)
        self.t = end
        self._warm_gate()

    def _warm_gate(self) -> None:
        """Run the gate's device programs once at every shape the window can
        ask for (their results are dropped)."""
        from repro.core.simulator import route_metrics, route_metrics_batched
        from repro.transition import score_stage_batch

        c, tc = self.ctrl, self.tc
        cc = c.cc
        cap = np.asarray(c._cap, np.float64)
        dead = cap.copy()
        dead[np.argmin(np.where(cap > 0, cap, np.inf))] = 0.0
        for caps in (cap[None], np.stack([cap, dead])):
            score_stage_batch(c.fabric, c._tms_prev, caps, 1.0,
                              c.strategy.hedging, c.sc, cc,
                              pad_to=tc.n_panels + 2)
        rows = self.feed.rows(self.t, self.t + self.step)
        interval_s = self.cfg["interval_minutes"] * 60.0
        spans, _ = reference_gate.stage_spans(
            tc.n_panels, tc.stage_intervals, self.step)
        most = min(tc.n_panels, -(-self.step // tc.stage_intervals))
        lo, hi = spans[0][1:]
        route_metrics_batched([rows[lo:hi]], c._w[None], cap[None],
                              cc.overload_threshold, backend=cc.backend,
                              loss_cfg=cc.loss, loss_seeds=[0],
                              interval_seconds=interval_s, pad_to=most)
        rests = {self.step - reference_gate.stage_spans(
            s, tc.stage_intervals, self.step)[1]
            for s in range(1, tc.n_panels + 1)}
        for n in sorted(rests - {0, self.step}):
            route_metrics(rows[:n], c._w, cap, cc.overload_threshold,
                          backend=cc.backend, loss_cfg=cc.loss,
                          interval_seconds=interval_s)

    def window(self, seconds: float) -> dict:
        """The serve window; logs its gate counts to standard error."""
        win = super().window(seconds)
        print(f"chipbench: gate {json.dumps(self.layer_context()['gate'])}",
              file=sys.stderr, flush=True)
        return win

    def _window_gates(self) -> list:
        return [(e, self.gates[e]) for e in self.window_epochs
                if e in self.gates]

    def layer_context(self) -> dict:
        """The serve counters, and the window's gate counts (``fiber_moves``:
        ports a change would re-home between panels, which Thm. 4 rules
        out; counted, not judged)."""
        out = super().layer_context()
        topo = sum(self.epochs[e]["topology"] for e in self.window_epochs)
        evs = [g["ev"] for _, g in self._window_gates()]
        applied = sum(g["applied"] for _, g in self._window_gates())
        out["gate"] = {
            "gated_epochs": topo, "evaluations": len(evs),
            "applied": applied, "vetoed": len(evs) - applied,
            "no_moves": topo - len(evs),
            "moves": sum(ev.diff.total_moves for ev in evs),
            "fiber_moves": sum(ev.diff.total_fiber_moves for ev in evs),
            "stages": sum(ev.n_stages for ev in evs),
            "pdhg_iters": sum(ev.pdhg_iters for ev in evs)}
        return out

    # ---- correctness -----------------------------------------------------------

    def _judged(self, e: int, g: dict) -> dict:
        """The reference gate's verdict on epoch ``e``'s evaluation."""
        ev = g["ev"]
        j = reference_gate.judged(
            self.epochs[e]["tms"], self.fab, self.epochs[e - 1]["n"],
            ev.diff.old_counts, ev.diff.new_counts, ev.order, ev.u_old,
            ev.u_new, ev.stage_u, ev.stage_intervals, self.topo_step,
            self.tc.hysteresis)
        if not self.tc.decide:
            j["install"] = j["new"]
        return j

    def answers(self, served: dict) -> check.Answers:
        """The serve answers, with every staged epoch scored per stage, the
        gated epochs' trunks as the reference gate installs them, and each
        gate evaluation's solves as topology decisions (a drain slot that
        only one side has is judged against the new topology)."""
        ans = super().answers(served)
        t_first, n = served["first_t"], served["mlu"].size
        staged = {e for e, g in self.gates.items()
                  if g["applied"] and not self.tc.instantaneous}
        blocks = []
        for e in sorted(self.epochs):
            r = self.epochs[e]
            t, lo = r["t"], r["t"] - t_first
            if not (0 <= lo and lo + self.step <= n):
                continue
            parts = [(lo, lo + self.step, r["w"], r["cap"])]
            if e in staged:
                ev = self.gates[e]["ev"]
                spans, rest = reference_gate.stage_spans(
                    ev.n_stages, ev.stage_intervals, self.step)
                parts = [(lo + a, lo + b, ev.stage_w[k], ev.stage_caps[k])
                         for k, a, b in spans]
                if rest < self.step:
                    parts.append((lo + rest, lo + self.step, r["w"],
                                  r["cap"]))
            for a, b, w, cap in parts:
                blocks.append(check.Block(
                    rows=self.feed.rows(t_first + a, t_first + b), w=w,
                    cap=cap, seed=self.loss_seed + t_first + a,
                    mlu=served["mlu"][a:b], loss=served["loss"][a:b]))
        judged = {e: self._judged(e, g) for e, g in self._window_gates()}
        decided = [dataclasses.replace(d, n_trunk=judged[e]["install"])
                   if e in judged else d
                   for e, d in zip(self.window_epochs, ans.decided)]
        for e, g in self._window_gates():
            r, ev, j = self.epochs[e], g["ev"], judged[e]
            window = self.feed.rows(r["t"] - self.agg, r["t"])
            new = (ev.steady_w[1], ev.steady_caps[1], ev.u_new)
            solves = [(ev.steady_w[0], ev.steady_caps[0], ev.u_old,
                       self.epochs[e - 1]["n"]), new + (j["new"],)]
            for k in range(max(ev.n_stages, len(j["order"]))):
                ours = ((ev.stage_w[k], ev.stage_caps[k], ev.stage_u[k])
                        if k < ev.n_stages else new)
                solves.append(ours + (j["stage_trunks"][k]
                                      if k < len(j["order"]) else j["new"],))
            decided += [check.Decided(
                window=window, tms=r["tms"], w=w, cap=cap, n_trunk=nt,
                u_star=float(u), topology=True, hedged=self.hedged,
                fab=self.fab) for w, cap, u, nt in solves if np.isfinite(u)]
        return check.Answers(decided=decided, blocks=blocks)
