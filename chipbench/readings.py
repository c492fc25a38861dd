#!/usr/bin/env python3
"""Readings that the check's limits are set from: the program's and the
control's numbers on many seeds, in one process.

    python3 chipbench/readings.py --workload f21-serve-paper \\
        --seeds 1,2,3 --seconds 10 [--fault KIND] [--set KEY=JSON]
        [--every | --samples K] [--tiny]

For each seed it runs the cell's set-up and a short window at the cell's
own size, as ``run.py`` does, then compares what the window produced with
the reference twice: once as the program scored it, once with the control
(the reference's scoring in float32 at ``Precision.HIGH``) put in the
program's place.  One JSON line per seed, with the window's epochs whose
PDHG stages stopped at the iteration cap.

* ``--fault`` plants one of :data:`chipbench.faults.KINDS` underneath the
  run (the control is then not read); a comma-separated list runs each in
  turn, and an empty entry runs the program as it is.
* ``--set`` overrides a key of the configuration, such as
  ``solver_precision="bf16"`` or a lower ``pdhg_max_iters``.
* ``--every`` judges every decision of the window instead of the seed's
  sample and prints each decision's numbers.
* ``--samples K`` judges the samples of seeds ``seed`` to ``seed + K - 1``
  of the same window, one line each (where the cell's trace is fixed, its
  decisions do not depend on the seed).
* ``--tiny`` runs the rehearsal's tiny cadence on the CPU.

The benchmark's own runs never run the control or a fault.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--every", action="store_true")
    ap.add_argument("--samples", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    os.environ.setdefault("JAX_PLATFORMS", "cpu" if args.tiny else "tpu")
    import contextlib

    import jax

    from chipbench import check, faults, harness, reference, rehearse

    jax.config.update("jax_enable_x64", False)
    harness.enable_compile_cache()
    cell = (rehearse.tiny_cell(args.workload) if args.tiny
            else harness.load_cell(args.workload))
    cfg = cell["config"]
    for kv in args.set:
        k, v = kv.split("=", 1)
        cfg[k] = json.loads(v)
    driver = harness.load_module(harness.HERE / "drivers"
                                 / f"{cfg['entry']}.py")
    runs = [(fault, int(seed)) for fault in (args.fault or "").split(",")
            for seed in args.seeds.split(",")]
    for fault, seed in runs:
        t0 = time.perf_counter()
        with (faults.planted(fault) if fault else contextlib.nullcontext()):
            c = driver.Cell(cfg, cell["mix"], seed)
            c.setup()
            setup_s = time.perf_counter() - t0
            win = c.window(args.seconds)
            ans = c.answers(c.finish())
        n = len(ans.decided) if args.every else 12
        for judge_seed in range(seed, seed + args.samples):
            t0 = time.perf_counter()
            rows = [check.decision_readings(d, cfg)
                    for d in check.sample(ans.decided, judge_seed, n)]
            prog = check.readings(ans, cfg, seed, rows=rows)
            ref_s = time.perf_counter() - t0
            ctrl = None if fault else check.readings(
                ans, cfg, seed, rows=rows,
                score=reference.score_block_control)
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "sample_seed": judge_seed, "fault": fault or None,
                "set": args.set, "platform": jax.devices()[0].platform,
                "attempted": win["attempted"], "decisions": len(ans.decided),
                "judged": len(rows), "capped": win["capped"],
                "blocks": len(ans.blocks), "setup_s": setup_s,
                "window_s": win["wall_s"], "check_s": ref_s,
                "program": prog, "control": ctrl,
                "decisions_judged": rows if args.every else None}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
