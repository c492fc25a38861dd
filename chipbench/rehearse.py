#!/usr/bin/env python3
"""CPU rehearsal of a cell at a tiny size, before chip time is spent on it.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload f21-serve-paper

Runs the same driver, readers and check as ``chipbench/run.py`` on the CPU
(Pallas kernels in interpret mode) with the cell's configuration cut to a
tiny cadence: hourly TMs, a 2-day window, 3-hour routing epochs, k = 4 and
2 generated days.  It checks control flow and correctness only: it prints
the checks and ``correct``, and no timing under a metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
TINY = {"interval_minutes": 60.0, "window_days": 2.0,
        "routing_interval_hours": 3.0, "k_critical": 4, "trace_days": 2}


def tiny_cell(name: str) -> dict:
    from chipbench import harness

    cell = harness.load_cell(name)
    cell["config"] = {**cell["config"], **TINY}
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax

    from chipbench import harness

    jax.config.update("jax_enable_x64", False)
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, cell=tiny_cell(args.workload))
    print(json.dumps({"rehearsal": "cpu, tiny cadence, not a measurement",
                      "correct": out["correct"],
                      "attempted": out["attempted"], "failed": out["failed"],
                      "readers_found": sorted(out["metrics"]),
                      "window_units": out["window"]["units"],
                      "checks": out["checks"]}))
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
