"""The span readers, on synthetic span lists: nesting by time on one
thread, waits taken out of host time, per-epoch division, and ``None`` where
the program records nothing to read."""

import pathlib

import pytest

from chipbench import harness, spans

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def _read(name, ctx):
    return harness.load_module(METRICS / f"{name}.py").read(ctx)


def X(name, ts, dur, tid=1, **args):
    e = {"ph": "X", "name": name, "ts_us": float(ts), "dur_us": float(dur),
         "tid": tid, "depth": 0}
    if args:
        e["args"] = args
    return e


def _epoch(t0, topology=False, tid=1):
    """One served epoch of 100 us from ``t0``: score 20, plan 30 (+ 400 on a
    topology epoch), solve 50, with waits of 5 in score and 2 x 10 in solve."""
    plan = 30 + (400 if topology else 0)
    ev = [X("serve.epoch", t0, 100 + plan - 30, tid),
          X("serve.score", t0, 20, tid),
          X("score.linkload", t0 + 1, 10, tid),
          X("score.wait", t0 + 5, 5, tid),
          X("serve.plan", t0 + 20, plan, tid),
          X("serve.plan.critical_tms", t0 + 21, 25, tid)]
    if topology:
        ev.append(X("serve.plan.topology", t0 + 47, 400, tid, highs_s=0.0003))
    s = t0 + 20 + plan
    ev += [X("serve.solve", s, 50, tid),
           X("jaxlp.warm_stage1", s + 2, 3, tid),
           X("jaxlp.wait", s + 5, 10, tid, stage=1),
           X("jaxlp.wait", s + 30, 10, tid, stage=3)]
    return ev


def _ctx(ev, epochs):
    return {"obs": ev, "layer": {"epochs": epochs}}


def test_inside_is_by_time_on_one_thread():
    outer = [X("o", 0, 10, tid=1), X("o", 20, 10, tid=2)]
    inner = [X("a", 0, 10, tid=1), X("b", 5, 6, tid=1), X("c", 22, 3, tid=1),
             X("d", 22, 3, tid=2), X("e", 29, 2, tid=2)]
    held = spans.inside(outer, inner)
    assert [[e["name"] for e in h] for h in held] == [["a"], ["d"]]


def test_host_us_takes_the_waits_out():
    ev = _epoch(0) + _epoch(1000)
    assert spans.host_us(ev, "serve.solve") == pytest.approx(2 * (50 - 20))
    assert spans.host_us(ev, "serve.score") == pytest.approx(2 * (20 - 5))
    assert spans.host_us(ev, "serve.nothing") is None
    no_waits = [e for e in ev if not spans.is_wait(e)]
    assert spans.host_us(no_waits, "serve.solve") is None


def test_readers_per_epoch():
    ev = _epoch(0, topology=True) + _epoch(1000) + _epoch(2000)
    ctx = _ctx(ev, 3)
    assert _read("host_wait_ms.serve", ctx) == pytest.approx(
        3 * (5 + 20) * 1e-3 / 3)
    assert _read("solve_host_ms.serve", ctx) == pytest.approx(30e-3)
    assert _read("score_host_ms.serve", ctx) == pytest.approx(15e-3)
    assert _read("critical_tms_ms.serve", ctx) == pytest.approx(25e-3)
    assert _read("topology_s.serve", ctx) == pytest.approx(400e-6)
    assert _read("compile_ms.serve", ctx) == 0.0
    ev.append(X("jax.compile", 1010, 60, cached=False))
    assert _read("compile_ms.serve", _ctx(ev, 3)) == pytest.approx(20e-3)


def test_readers_find_nothing_where_the_program_records_nothing():
    """A program with only the three layer spans (no epoch, sub-spans,
    waits or compile events) reads ``None`` in every new reader, and so does
    a window with no decided epoch."""
    layers = [X("serve.score", 0, 20), X("serve.plan", 20, 30),
              X("serve.solve", 50, 50)]
    for ctx in (_ctx(layers, 1), _ctx(_epoch(0), 0)):
        for name in ("host_wait_ms.serve", "solve_host_ms.serve",
                     "score_host_ms.serve", "critical_tms_ms.serve",
                     "compile_ms.serve"):
            assert _read(name, ctx) is None, name
    assert _read("topology_s.serve", _ctx(_epoch(0), 1)) is None
