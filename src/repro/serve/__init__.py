"""repro.serve — Gemini as a long-running online controller service.

Everything else in this repo replays traces offline in batch; this package is
the *online* mode of the paper's §4.6 control loop: a long-lived controller
that

1. ingests traffic-matrix intervals as a stream (:class:`TMStream` — replay
   over recorded/synthetic fleet traces, or any iterable of TM rows),
2. maintains the rolling prediction window *incrementally*
   (:class:`RollingWindow`: O(C) ring-buffer push per interval, no per-epoch
   window recopy),
3. re-plans routing with **warm-started PDHG** — each epoch's primal/dual
   iterates seed the next (:meth:`repro.core.jaxlp.JaxRoutingSolver.
   solve_routing_warm`) instead of the batch engine's cold middle-epoch
   anchor,
4. emits routing/topology decisions through the existing
   :func:`repro.transition.should_reconfigure` gate, and
5. measures a decision-latency SLO: per-epoch *time-to-new-weights* (TM
   arrival, through scoring the finished epoch, → installed weight
   matrix), exported through :mod:`repro.obs` (the ``serve.epoch`` span
   tree + histograms) and gated in CI
   (``benchmarks/bench_serve.py`` + the ``latency_slo`` regression-spec
   kind).

Replay parity is the correctness contract: streaming over a recorded trace
reproduces the offline batch engine's decisions and metrics within solver
tolerance (``tests/test_serve.py``).
"""

from .controller import ServeConfig, ServeResult, StreamingController
from .stream import TMStream, stream_fleet_fabric
from .window import RollingWindow

__all__ = [
    "TMStream", "stream_fleet_fabric", "RollingWindow",
    "ServeConfig", "ServeResult", "StreamingController",
]
