#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python3 chipbench/tests/record_trace.py OUT_DIR     # on a TPU

Traces, under the benchmark's anchor and window annotations, three launches
of the fleet link-load kernel at a small shape separated by 50 ms host
sleeps (annotated ``chipbench.sleep``), and writes the ``.xplane.pb`` and the
anchor's ``perf_counter_ns`` to ``OUT_DIR``.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out_dir: str) -> int:
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    import jax
    import numpy as np

    from chipbench import trace_reduce
    from repro.kernels.linkload import ops

    rng = np.random.default_rng(0)
    f, b, t, c = 1, 2, 3, 132
    dem = rng.random((f, b, t, c))
    w = rng.random((f, b, c, c)) / c
    cap = np.full((f, b, c), 10.0)
    ops.link_metrics_fleet(dem, w, cap, 0.8, backend="pallas")  # compile
    tmp = pathlib.Path(out_dir) / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    anchor = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
        pass
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                time.sleep(0.05)
            ops.link_metrics_fleet(dem, w, cap, 0.8, backend="pallas")
    jax.profiler.stop_trace()
    src = next(tmp.rglob("*.xplane.pb"))
    shutil.copy(src, pathlib.Path(out_dir) / "fleet_kernel.xplane.pb")
    shutil.rmtree(tmp)
    (pathlib.Path(out_dir) / "fleet_kernel.json").write_text(json.dumps(
        {"anchor_perf_ns": anchor, "device_kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
