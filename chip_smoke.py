#!/usr/bin/env python3
"""Smoke run of the controller's two main paths on a TPU.

    python chip_smoke.py               # serve + fleet phases on one chip
    python chip_smoke.py --four-chips  # the sharded fleet sweep on four chips

Phases (plain functions with size arguments; the defaults are the paper's
cadence: 5-minute TMs, 15-minute routing, daily topology, a one-week window,
k = 12):

* :func:`serve_phase` — :class:`repro.serve.StreamingController` on F21 with
  PDHG routing and Pallas scoring.  Checked against the same run on HiGHS
  (decisions, topology sequence, per-epoch stage-1 ``u*``) and against numpy
  scoring of the same served weights.
* :func:`fleet_phase` — :func:`repro.core.run_fleet` over four fabrics on one
  device, checked against the per-fabric batched controller.
* :func:`sharded_fleet_phase` (``--four-chips`` only) — ``run_fleet`` sharded
  over every visible device (``mesh="auto"``) over the eight largest fabrics,
  checked against the unsharded sweep on one device.

Every check raises :class:`SmokeFailure`.  Each phase prints one JSON line of
results; the last line of stdout is ``{"ok": true, "device": {...}}`` and is
printed only after every phase passed.  :func:`main` refuses to run unless
JAX's first device is a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"
P999 = ("p999_mlu", "p999_alu", "p999_olr", "p999_stretch")
# scoring parity (pallas vs numpy on the same weights), as the engine tests
SCORE_REL, SCORE_ABS = 1e-3, 1e-4
LOSS_RTOL, LOSS_ATOL = 2e-3, 1e-5
# fleet sweep vs per-fabric controller / sharded vs unsharded sweep
FLEET_REL, FLEET_ABS = 1e-3, 1e-6
SHARD_REL, SHARD_ABS = 1e-6, 1e-9


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def _check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _spec(name: str):
    from repro.core.fleet import FLEET_SPECS

    return next(s for s in FLEET_SPECS if s.name == name)


def _fabric_trace(name: str, days: float, interval_minutes: float, seed: int):
    from repro.core.fleet import make_fabric, make_trace

    spec = _spec(name)
    fabric = make_fabric(spec, seed)
    return fabric, make_trace(spec, fabric, days=days,
                              interval_minutes=interval_minutes, seed=seed)


def _approx(a: float, b: float, rel: float, abs_: float) -> bool:
    """``a == pytest.approx(b, rel=rel, abs=abs_)``."""
    return abs(a - b) <= max(rel * abs(b), abs_)


def _rel_dev(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float((np.abs(a - b) / np.maximum(np.abs(b), 1e-12)).max())


def _stage_iters(stats) -> dict:
    """Median / max PDHG iterations per stage, and the solves (epoch
    indices) that hit the iteration cap."""
    out = {}
    for name, st in stats.stages.items():
        it = np.asarray(st.iters)
        out[name] = {"n": int(it.size), "median": float(np.median(it)),
                     "max": int(it.max()),
                     "capped": np.flatnonzero(it >= stats.max_iters).tolist()}
    return out


def _tiles(family: str, t: int, c: int) -> list:
    """(bt, be, bc) the kernel wrapper resolves for a (t, c) -> c block."""
    from repro.kernels.autotune.table import resolve_tiles, shrink_bt

    bt, be, bc = resolve_tiles(family, t, c, c)
    return [shrink_bt(bt, t), be, bc]


def _controller_config(window_days, routing_hours, topology_days, k_critical,
                       loss_fabric):
    from repro.burst import LossConfig
    from repro.core import ControllerConfig
    from repro.core.fleet import sub_burst_params

    return ControllerConfig(
        routing_interval_hours=routing_hours,
        topology_interval_days=topology_days, aggregation_days=window_days,
        k_critical=k_critical, backend="pallas", solver_backend="pdhg",
        loss=LossConfig(burst=sub_burst_params(_spec(loss_fabric))))


def serve_phase(fabric_name: str = "F21", interval_minutes: float = 5.0,
                window_days: float = 7.0, serve_hours: float = 24.25,
                routing_hours: float = 0.25, topology_days: float = 1.0,
                k_critical: int = 12, seed: int = 0) -> dict:
    """Stream a window plus ``serve_hours`` of TMs through the controller.

    The default 24¼ hours of serving is 97 routing epochs: the first solves
    the topology, and the last re-solves it a day later.
    """
    from repro.core import SolverConfig, Strategy
    from repro.serve import StreamingController, TMStream

    fabric, trace = _fabric_trace(fabric_name, window_days + serve_hours / 24,
                                  interval_minutes, seed)
    cc = _controller_config(window_days, routing_hours, topology_days,
                            k_critical, fabric_name)
    sc = SolverConfig()
    strategy = Strategy(nonuniform=True, hedging=True)

    def run(**over):
        ctrl = StreamingController(fabric, TMStream.from_trace(trace),
                                   strategy, dataclasses.replace(cc, **over),
                                   sc)
        t0 = time.perf_counter()
        res = ctrl.run()
        return res, time.perf_counter() - t0

    served, served_s = run()
    numpy_scored, numpy_s = run(backend="numpy")
    highs, highs_s = run(solver_backend="scipy", backend="numpy")
    out, ref = served.result, highs.result

    # the routing solver against HiGHS
    _check(out.solver_stats.n_fallbacks == 0,
           f"{out.solver_stats.n_fallbacks} PDHG epochs fell back to HiGHS")
    _check(out.n_routing_updates == ref.n_routing_updates,
           f"routing updates {out.n_routing_updates} != {ref.n_routing_updates}")
    topo = [(d.topology_solved, d.topology_applied) for d in served.decisions]
    _check(topo == [(d.topology_solved, d.topology_applied)
                    for d in highs.decisions], "topology sequences differ")
    _check(np.array_equal(out.final_topology, ref.final_topology),
           "final topologies differ")
    u = np.asarray([d.u_star for d in served.decisions])
    u_ref = np.asarray([d.u_star for d in highs.decisions])
    u_devs = np.abs(u - u_ref) / np.abs(u_ref)
    u_dev = float(u_devs.max())
    _check(u_dev <= cc.pdhg_tol,
           f"stage-1 u* off HiGHS by rel {u_dev} > {cc.pdhg_tol}")

    # the Pallas kernels against numpy, scoring the same weights
    _check(np.array_equal(u, [d.u_star for d in numpy_scored.decisions]),
           "the pallas- and numpy-scored runs served different weights")
    score = _score_parity(out, numpy_scored.result)

    lat = np.asarray(served.latencies_s)
    c = fabric.n_pods * (fabric.n_pods - 1)
    block = max(1, round(routing_hours * 60 / interval_minutes))
    return {
        "fabric": fabric_name, "pods": fabric.n_pods,
        "intervals": served.n_intervals, "epochs": out.n_routing_updates,
        "topology_epochs": [i for i, (s, _) in enumerate(topo) if s],
        "wall_s": {"pdhg_pallas": served_s, "pdhg_numpy": numpy_s,
                   "highs_numpy": highs_s},
        "latency_s": {"cold_first_epoch": float(lat[0]),
                      "steady_p50": float(np.median(lat[1:])),
                      "steady_max": float(lat[1:].max()),
                      "topology_epochs": [float(lat[i]) for i, (s, _)
                                          in enumerate(topo) if s]},
        "stage_times_s": out.stage_times,
        "pdhg_iters": _stage_iters(out.solver_stats),
        "fallbacks": out.solver_stats.n_fallbacks,
        "u_star_rel_dev_vs_highs": {"max": u_dev,
                                    "worst_epoch": int(u_devs.argmax()),
                                    "median": float(np.median(u_devs))},
        "score_parity": score,
        "tiles": {"linkload": _tiles("linkload", block, c),
                  "queueloss": _tiles("queueloss", block * cc.loss.n_sub, c)},
    }


def _score_parity(out, ref) -> dict:
    """Pallas-scored result ``out`` against numpy-scored ``ref`` (same
    weights): p99.9 MLU/ALU/OLR/stretch and per-interval loss."""
    dev = {}
    for k in P999:
        _check(_approx(out.summary[k], ref.summary[k], SCORE_REL, SCORE_ABS),
               f"{k}: pallas {out.summary[k]} vs numpy {ref.summary[k]}")
        dev[k] = _rel_dev(out.summary[k], ref.summary[k])
    for k in ("mlu", "alu", "olr", "stretch"):
        dev[f"interval_{k}_max_rel"] = _rel_dev(getattr(out.metrics, k),
                                                getattr(ref.metrics, k))
    loss, loss_ref = out.metrics.loss, ref.metrics.loss
    _check(loss is not None and loss.shape == loss_ref.shape,
           "loss not tracked on both backends")
    _check(np.allclose(loss, loss_ref, rtol=LOSS_RTOL, atol=LOSS_ATOL),
           f"loss off numpy by {np.abs(loss - loss_ref).max()}")
    dev["interval_loss_max_abs"] = float(np.abs(loss - loss_ref).max())
    dev["interval_loss_max"] = float(loss_ref.max())
    return dev


def _fleet_jobs(names, interval_minutes, window_days, serve_hours,
                routing_hours, topology_days, k_critical, seed):
    """One uniform-topology hedged sweep per fabric.  The loss config is part
    of the bucket key, so every sweep takes the burst model of the most
    volatile fabric and the fleet shares one bucket per padded pod count."""
    from repro.core import FleetJob, SolverConfig, Strategy

    burst = max(names, key=lambda n: _spec(n).burst_rate)
    cc = _controller_config(window_days, routing_hours, topology_days,
                            k_critical, burst)
    strategy = Strategy(nonuniform=False, hedging=True)
    return [FleetJob(*_fabric_trace(n, window_days + serve_hours / 24,
                                    interval_minutes, seed),
                     strategy, cc, SolverConfig()) for n in names]


def _summary_devs(out, ref, rel: float, abs_: float, label: str) -> float:
    _check(out.n_routing_updates == ref.n_routing_updates
           and out.n_topology_updates == ref.n_topology_updates,
           f"{label}: decision counts differ")
    _check(out.solver_stats.n_fallbacks == 0
           and ref.solver_stats.n_fallbacks == 0,
           f"{label}: PDHG fell back to HiGHS")
    worst = 0.0
    for k in P999 + ("p999_loss",):
        _check(_approx(out.summary[k], ref.summary[k], rel, abs_),
               f"{label} {k}: {out.summary[k]} vs {ref.summary[k]}")
        worst = max(worst, _rel_dev(out.summary[k], ref.summary[k]))
    return worst


def fleet_phase(fabric_names=("F1", "F3", "F21", "F22"),
                interval_minutes: float = 5.0, window_days: float = 7.0,
                serve_hours: float = 24.0, routing_hours: float = 0.25,
                topology_days: float = 1.0, k_critical: int = 12,
                seed: int = 0) -> dict:
    """``run_fleet`` on one device against the per-fabric controller."""
    from repro.core import run_controller, run_fleet
    from repro.core.fleet import pad_pods

    jobs = _fleet_jobs(fabric_names, interval_minutes, window_days,
                       serve_hours, routing_hours, topology_days, k_critical,
                       seed)
    t0 = time.perf_counter()
    fleet = run_fleet(jobs, mesh=None)
    fleet_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    refs = [run_controller(j.fabric, j.trace, j.strategy,
                           dataclasses.replace(j.cc, engine="batched"), j.sc)
            for j in jobs]
    per_fabric_s = time.perf_counter() - t0
    dev = {j.fabric.name: _summary_devs(o, r, FLEET_REL, FLEET_ABS,
                                        j.fabric.name)
           for j, o, r in zip(jobs, fleet, refs)}
    vp = pad_pods(max(j.fabric.n_pods for j in jobs))
    block = max(1, round(routing_hours * 60 / interval_minutes))
    sub = block * jobs[0].cc.loss.n_sub
    return {
        "fabrics": list(fabric_names),
        "epochs": sum(o.n_routing_updates for o in fleet),
        "wall_s": {"run_fleet": fleet_s, "per_fabric": per_fabric_s},
        "stage_times_s": fleet[0].stage_times,
        "pdhg_iters": {j.fabric.name: _stage_iters(o.solver_stats)
                       for j, o in zip(jobs, fleet)},
        "fallbacks": sum(o.solver_stats.n_fallbacks for o in fleet),
        "summary_rel_dev_vs_per_fabric": dev,
        "tiles": {"linkload_fleet": _tiles("linkload_fleet", block,
                                           vp * (vp - 1)),
                  "queueloss_fleet": _tiles("queueloss_fleet", sub,
                                            vp * (vp - 1))},
    }


def _largest_fabrics(n: int) -> tuple:
    """Names of the ``n`` fabrics of the synthetic fleet with the most pods."""
    from repro.core.fleet import FLEET_SPECS

    return tuple(s.name for s in sorted(FLEET_SPECS,
                                        key=lambda s: -s.n_pods)[:n])


def sharded_fleet_phase(n_fabrics: int = 8, interval_minutes: float = 5.0,
                        window_days: float = 7.0, serve_hours: float = 24.0,
                        routing_hours: float = 0.25,
                        topology_days: float = 1.0, k_critical: int = 12,
                        seed: int = 0) -> dict:
    """``run_fleet(mesh="auto")`` (sharded over every visible device) over the
    ``n_fabrics`` largest fabrics, against ``mesh=None`` (one device)."""
    import jax

    from repro.core import run_fleet

    fabric_names = _largest_fabrics(n_fabrics)
    jobs = _fleet_jobs(fabric_names, interval_minutes, window_days,
                       serve_hours, routing_hours, topology_days, k_critical,
                       seed)
    t0 = time.perf_counter()
    sharded = run_fleet(jobs, mesh="auto")
    sharded_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = run_fleet(jobs, mesh=None)
    single_s = time.perf_counter() - t0
    dev = {j.fabric.name: _summary_devs(a, b, SHARD_REL, SHARD_ABS,
                                        j.fabric.name)
           for j, a, b in zip(jobs, sharded, single)}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    return {
        "fabrics": list(fabric_names), "devices": len(jax.devices()),
        "epochs": sum(o.n_routing_updates for o in sharded),
        "wall_s": {"sharded": sharded_s, "one_device": single_s},
        "pdhg_iters": {j.fabric.name: _stage_iters(o.solver_stats)
                       for j, o in zip(jobs, sharded)},
        "summary_rel_dev_sharded_vs_one": dev,
        "peak_bytes_in_use": peaks,
    }


class _CompileClock:
    """Backend compile time and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is timed as its read)."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def _timed_phase(name: str, fn, clock: _CompileClock, **kw) -> dict:
    before = clock.snapshot()
    t0 = time.perf_counter()
    out = fn(**kw)
    after = clock.snapshot()
    out = {"phase": name, "phase_wall_s": time.perf_counter() - t0,
           "compile_s": after[0] - before[0],
           "compiles": after[1] - before[1],
           "cache_hits": after[2] - before[2], **out}
    print(json.dumps(out, default=float), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet sweep sharded over four chips")
    args = ap.parse_args(argv)
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))  # the checkout's package, uninstalled

    if not os.environ.get("JAX_PLATFORMS"):
        # a TPU that fails to start must be an error, not a CPU run
        os.environ["JAX_PLATFORMS"] = "tpu"
    import jax

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    jax.config.update("jax_enable_x64", False)

    from repro.compile_cache import enable_compile_cache

    clock = _CompileClock()
    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "backend": jax.default_backend(),
                      "device_kind": devices[0].device_kind,
                      "devices": len(devices)}), flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        out = _timed_phase("sharded_fleet", sharded_fleet_phase, clock)
        peaks = out["peak_bytes_in_use"]
        _check(len(peaks) == 4 and all(p for p in peaks),
               f"not every device held memory: {peaks}")
    else:
        _timed_phase("serve", serve_phase, clock)
        _timed_phase("fleet", fleet_phase, clock)
    print(json.dumps({"total_wall_s": time.perf_counter() - t0,
                      "compile_s": clock.seconds,
                      "compiles": clock.compiles,
                      "cache_hits": clock.cache_hits}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
