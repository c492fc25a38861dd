"""The check at a tiny size on the CPU: a sound run is correct; the control
and each fault of :mod:`chipbench.faults` that the check can see are not.

The cell's own 12-pod fabric runs at the rehearsal's tiny cadence
(:data:`chipbench.rehearse.TINY`), whose decisions move from epoch to
epoch, so a stale or frozen W shows."""

import json
import time

import pytest

from chipbench import check, faults, harness, reference, rehearse


def run(name, seed=3):
    return harness.run(name, seed, 0.0, False, time.perf_counter(),
                       cell=rehearse.tiny_cell(name), log=lambda *a: None)


def _readings(name, seed=3, **kw):
    """(values, limits) of one tiny window, scored by ``kw`` if given."""
    cell = rehearse.tiny_cell(name)
    cfg = cell["config"]
    mod = harness.load_module(harness.HERE / "drivers" / f"{cfg['entry']}.py")
    c = mod.Cell(cfg, cell["mix"], seed)
    c.setup()
    c.window(0.0)
    return check.readings(c.answers(c.finish()), cfg, seed, **kw), \
        cfg["limits"]


@pytest.mark.parametrize("name", ["f21-serve-routing"])
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    limits = rehearse.tiny_cell(name)["config"]["limits"]
    assert [r[0] for r in out["checks"]] == [
        k for k in check.NAMES if k in limits]
    assert sorted(out["reported"]) == sorted(set(check.NAMES) - set(limits))
    assert json.loads(json.dumps(out))["checks"] == out["checks"]


@pytest.mark.parametrize("name", ["f21-serve-routing"])
def test_control_is_not_correct(name):
    values, limits = _readings(name, score=reference.score_block_control)
    ok, rows = check.judge(values, limits)
    assert not ok, rows


@pytest.mark.parametrize("kind", ["state_unchanged", "w_stale", "w_frozen",
                                  "w_garbled", "stage2_skipped", "half_batch",
                                  "answer_altered"])
def test_serve_fault_is_not_correct(kind):
    with faults.planted(kind):
        out = run("f21-serve-routing")
    assert not out["correct"], out["checks"]
