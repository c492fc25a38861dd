"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size (F17,
2-hour intervals, a 2-day window, k = 4, Pallas in interpret mode) with every
parity check on, its refusal to run without a TPU, and the compile-cache
helper it calls."""

import importlib.util
import pathlib

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(interval_minutes=120.0, window_days=2.0, routing_hours=6.0,
            topology_days=1.0, k_critical=4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_tiny(smoke):
    # 30 hours of serving = 5 epochs of 3 intervals; topology at 0 and 4
    out = smoke.serve_phase(fabric_name="F17", serve_hours=30.0, **TINY)
    assert out["epochs"] == 5
    assert out["topology_epochs"] == [0, 4]
    assert out["fallbacks"] == 0
    assert out["u_star_rel_dev_vs_highs"]["max"] <= 1e-2
    assert set(out["pdhg_iters"]) == {"stage1", "stage2", "stage3"}
    assert out["tiles"] == {"linkload": [8, 128, 128],
                            "queueloss": [40, 128, 128]}


def test_fleet_phase_tiny(smoke):
    out = smoke.fleet_phase(fabric_names=("F17", "F18"), serve_hours=24.0,
                            **TINY)
    assert out["epochs"] == 2 * 4  # 24 hours of 6-hour epochs per fabric
    assert out["fallbacks"] == 0
    assert set(out["summary_rel_dev_vs_per_fabric"]) == {"F17", "F18"}


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    assert '"ok": true' not in capsys.readouterr().out


@pytest.fixture()
def cache_config():
    """Restore JAX's cache directory after a test that sets it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_environment(monkeypatch, tmp_path,
                                            cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left alone


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
