"""The trace reduction, on synthetic intervals and on a small trace recorded
on a TPU v5e by ``record_trace.py`` (the source paths in its HLO metadata
rewritten, byte for byte, to ``/work/tree/``)."""

import json
import pathlib

import pytest

from chipbench import trace_reduce

FIX = pathlib.Path(__file__).parent / "fixtures"


def test_union_merges_and_clips():
    iv = [(5, 10), (0, 3), (2, 4), (9, 12), (20, 30)]
    assert trace_reduce.union(iv, 1, 25) == [[1, 4], [5, 12], [20, 25]]
    assert trace_reduce.union(iv, 13, 19) == []


def test_op_name_drops_hlo_text_and_instance():
    assert trace_reduce.op_name(
        "%linkload_pallas_fleet.1 = (f32[1,2,8,1]{3,2,1,0}) custom-call(...)"
    ) == "linkload_pallas_fleet"
    assert trace_reduce.op_name("%reduce = f32[2,8] reduce(...)") == "reduce"
    assert trace_reduce.op_name("while.3") == "while"
    assert trace_reduce.op_name("copy-start") == "copy-start"


@pytest.mark.parametrize("t, want", [
    (5, "outer"), (15, "inner"), (25, "mid"), (35, "outer"), (55, "late"),
    (65, "outer"), (150, "host:other"), (-1, "host:other")])
def test_label_is_the_innermost_span(t, want):
    spans = [("outer", 0, 100), ("mid", 10, 30), ("inner", 12, 20),
             ("late", 50, 60)]
    assert trace_reduce.label(trace_reduce.timeline(spans), t) == want


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    meta = json.loads((FIX / "fleet_kernel.json").read_text())
    pd = ProfileData.from_file(str(FIX / "fleet_kernel.xplane.pb"))
    return pd, meta


def test_recorded_trace_reduces(recorded):
    pd, _ = recorded
    tr = trace_reduce.reduce(pd)
    assert tr["devices"] == 1
    # three 50 ms sleeps inside the window, the device idle through each
    assert tr["window_s"] > 0.15
    assert 0 < tr["busy_s"] < tr["window_s"] - 0.15
    gaps = dict(tr["idle_gaps"])
    assert gaps["chipbench.sleep"] >= 0.15
    assert max(gaps, key=gaps.get) == "chipbench.sleep"
    assert tr["op_s"]["linkload_pallas_fleet"] > 0  # the Pallas kernel
    assert tr["device_ops"][0][0] == "linkload_pallas_fleet"
    assert sum(tr["op_s"].values()) >= tr["busy_s"] * 0.999


def test_recorded_anchor_puts_host_spans_on_the_trace_clock(recorded):
    pd, meta = recorded
    off = trace_reduce.clock_offset(pd, meta["anchor_perf_ns"])
    (name, start, _), = [e for e in trace_reduce.host_events(pd)
                         if e[0] == trace_reduce.ANCHOR]
    assert start - off == meta["anchor_perf_ns"]
