#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload f21-serve-paper --seed 7 \\
        --seconds 30 --trace 0

Needs a TPU: without one, or with fewer chips than the cell asks for, it
exits 3 and prints no result.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, the
device's busy and window seconds and a breakdown of the traced window.  The
last line of standard output is the result as one JSON object; the numbers
the check compared, each with its limit, are the last lines of standard
error and the result's last key, ``checks``.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    import jax

    devices = jax.devices()
    need = int(cell["workload"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chipbench: needs {need} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    jax.config.update("jax_enable_x64", False)
    harness.enable_compile_cache()
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, cell=cell)
    for name, value in out["reported"].items():
        print(f"report {name} {value!r} (not compared)", file=sys.stderr)
    for name, value, limit in out["checks"]:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
