"""Serve driver: ``StreamingController.ingest`` on one fabric, closed loop.

Set-up ingests the 7-day warm-up window, the first routing epoch (a topology
epoch under a nonuniform strategy) and the TM that opens the second epoch,
which scores the first epoch's block and runs the first warm-started solve:
every program the window uses is then compiled.  The window feeds one TM as
soon as the previous ``ingest`` returns and ends at the first stream-day
boundary (every ``intervals_per_day`` TMs) after ``seconds``.  Any run of
that many consecutive TMs opens the same number of routing epochs and
exactly one topology epoch.

Time-to-new-weights is the wall time of an ``ingest`` call that returns a
:class:`~repro.serve.controller.Decision`: it covers scoring the finished
epoch, planning and solving the new one, and installing its weights.

After each decision the driver keeps references to what the controller
decided (critical TMs, weights, capacities, topology), for the check.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import check, gen


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.burst import BurstParams, LossConfig
        from repro.core import ControllerConfig, SolverConfig, Strategy
        from repro.core.graph import Fabric
        from repro.serve import StreamingController, TMStream

        if len(cfg["fabrics"]) != 1:
            raise ValueError("the serve driver runs one fabric")
        self.cfg, self.seed = cfg, seed
        self.fab = fab = cfg["fabrics"][0]
        self.feed = gen.Feed(fab, cfg, mix.get("trace_seed", seed))
        self.ipd = self.feed.ipd
        loss = cfg["loss"]
        self.loss_seed = seed
        cc = ControllerConfig(
            routing_interval_hours=cfg["routing_interval_hours"],
            topology_interval_days=cfg["topology_interval_days"],
            aggregation_days=cfg["window_days"], k_critical=cfg["k_critical"],
            pdhg_tol=cfg["pdhg_tol"], pdhg_max_iters=cfg["pdhg_max_iters"],
            overload_threshold=cfg["overload_threshold"],
            backend=cfg["backend"], solver_backend=cfg["solver_backend"],
            solver_precision=cfg["solver_precision"],
            loss=LossConfig(burst=BurstParams(**loss["burst"]),
                            n_sub=loss["n_sub"], buffer_ms=loss["buffer_ms"],
                            seed=self.loss_seed))
        fabric = Fabric(name=fab["name"], radix=np.asarray(fab["radix"]),
                        speed=np.asarray(fab["speed"]))
        stream = TMStream(name=fab["name"], intervals=iter(()),
                          interval_minutes=cfg["interval_minutes"],
                          n_pods=len(fab["radix"]))
        self.ctrl = StreamingController(fabric, stream,
                                        Strategy(**mix["strategy"]), cc,
                                        SolverConfig())
        self.agg = self.ctrl.agg
        self.step = self.ctrl.route_step
        self.t = 0
        self.epochs: dict = {}  # epoch index -> what was decided
        self.hedged = bool(mix["strategy"]["hedging"])
        self.raws_at = self.rows_at = 0
        self.window_epochs: list = []

    # ---- the program's state, read after each decision ------------------------

    def _record(self, d, t: int) -> None:
        c = self.ctrl
        self.epochs[d.epoch] = {
            "t": t, "u_star": d.u_star, "topology": d.topology_solved,
            "tms": c._tms_prev, "w": c._w, "cap": c._cap,
            "n": c._n_realized}

    def _ingest(self, t: int):
        d = self.ctrl.ingest(self.feed.row(t))
        if d is not None:
            self._record(d, t)
        return d

    def setup(self) -> None:
        for t in range(self.agg + self.step + 1):
            self._ingest(t)
        self.t = self.agg + self.step + 1

    def trace_unit(self, epochs: int = 8) -> int:
        """Ingest the TMs of ``epochs`` routing epochs (the profiled part of a
        traced run); returns the number of epochs decided."""
        import jax

        n = 0
        for t in range(self.t, self.t + epochs * self.step):
            with jax.profiler.TraceAnnotation("chipbench.ingest"):
                n += self._ingest(t) is not None
        self.t += epochs * self.step
        return n

    def window(self, seconds: float) -> dict:
        """Closed-loop ingest until the first day boundary after ``seconds``."""
        c = self.ctrl
        self.raws_at, self.rows_at = len(c._pdhg_raws), c._metrics.mlu.size
        fb0 = c._n_fallbacks
        lat, kinds, t = [], [], self.t
        t0 = time.perf_counter()
        day = 0
        while True:
            for t in range(t, t + self.ipd):
                a = time.perf_counter()
                d = c.ingest(self.feed.row(t))
                b = time.perf_counter()
                if d is not None:
                    lat.append(b - a)
                    kinds.append(d.topology_solved)
                    self._record(d, t)
                    self.window_epochs.append(d.epoch)
            t += 1
            day += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        self.t = t
        lat = np.asarray(lat)
        raws = c._pdhg_raws[self.raws_at:]
        cap = self.cfg["pdhg_max_iters"]
        capped = {s: sum(int(np.sum(r[s]["iters"] >= cap)) for r in raws
                         if r.get(s) is not None)
                  for s in ("stage1", "stage2", "stage3")}
        self.capped = capped
        return {"wall_s": wall, "days": day, "latencies_s": lat,
                "capped": capped,
                "topology_s": lat[np.asarray(kinds, bool)].tolist(),
                "attempted": int(lat.size),
                "failed": int(c._n_fallbacks - fb0),
                "end_to_end": {
                    "ttnw_p50_s": float(np.percentile(lat, 50)),
                    "ttnw_p95_s": float(np.percentile(lat, 95)),
                    "ttnw_mean_s": float(lat.sum() / lat.size)}}

    def layer_context(self) -> dict:
        """Counters of the window for the per-layer readers."""
        raws = self.ctrl._pdhg_raws[self.raws_at:]
        iters = sum(int(np.sum(r[s]["iters"])) for r in raws
                    for s in ("stage1", "stage2", "stage3")
                    if r.get(s) is not None)
        return {"entry": "serve", "epochs": len(self.window_epochs),
                "pdhg_iters": iters, "capped": self.capped}

    # ---- correctness -----------------------------------------------------------

    def finish(self) -> dict:
        """Take from the program what the check needs and drop the rest."""
        c = self.ctrl
        m = c._metrics
        hi = m.mlu.size
        out = {"mlu": np.asarray(m.mlu[self.rows_at:hi]),
               "loss": np.asarray(m.loss[self.rows_at:hi]),
               "first_t": self.agg + self.rows_at}
        self.ctrl = None
        return out

    def answers(self, served: dict) -> check.Answers:
        """What the window decided and scored, in the reference's terms."""
        ep = [self.epochs[e] for e in self.window_epochs]
        scored = []  # epochs whose block the window scored
        t_first = served["first_t"]
        n = served["mlu"].size
        for e in sorted(self.epochs):
            r = self.epochs[e]
            lo = r["t"] - t_first
            if 0 <= lo and lo + self.step <= n:
                scored.append(check.Block(
                    rows=self.feed.rows(r["t"], r["t"] + self.step),
                    w=r["w"], cap=r["cap"], seed=self.loss_seed + r["t"],
                    mlu=served["mlu"][lo: lo + self.step],
                    loss=served["loss"][lo: lo + self.step]))
        decided = [check.Decided(
            window=self.feed.rows(r["t"] - self.agg, r["t"]), tms=r["tms"],
            w=r["w"], cap=r["cap"], n_trunk=r["n"], u_star=r["u_star"],
            topology=r["topology"], hedged=self.hedged, fab=self.fab)
            for r in ep]
        return check.Answers(decided=decided, blocks=scored)
