"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The scoring kernels, the PDHG stage programs and the served solve are
compiled by the TPU's own compiler at the widths the controller runs: F21/F22
have 12 pods, so C = E = 132 commodities/links, padded to 256 by the kernel
wrappers; the time tiles are what ``shrink_bt`` makes of a 3-interval
routing epoch (8 rows) and of its 36 loss sub-steps (40 rows).  A compile
that passes here is not a chip run: it proves only that Mosaic/XLA accept the
tiling, the VMEM use and the sharding.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.graph import Fabric
from repro.core.jaxlp import JaxRoutingSolver
from repro.kernels.linkload import linkload
from repro.kernels.queueloss import queueloss

V = M = 12  # F21/F22 pods; k = 12 critical TMs
CP = 256  # C = E = 132, padded to two 128-lane tiles
FLEET_N = 384  # four fabrics × 96 routing epochs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def solver():
    return JaxRoutingSolver(Fabric(name="v12", radix=np.full(V, 64),
                                   speed=np.full(V, 100.0)), M)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(family, lead, bt, sh):
    """Pre-padded kernel inputs with leading axes ``lead``."""
    s = lambda *shape: _shape(sh, lead + shape)  # noqa: E731
    scalar = _shape(sh, (1, 1))
    if family == "linkload":
        return s(bt, CP), s(CP, CP), s(1, CP), scalar
    return s(bt, CP), s(CP, CP), s(1, CP), s(1, CP), scalar


KERNELS = {
    ("linkload", "single"): (linkload.linkload_pallas, ()),
    ("linkload", "batched"): (linkload.linkload_pallas_batched, (96,)),
    ("linkload", "fleet"): (linkload.linkload_pallas_fleet, (4, 97)),
    ("queueloss", "single"): (queueloss.queueloss_pallas, ()),
    ("queueloss", "batched"): (queueloss.queueloss_pallas_batched, (96,)),
    ("queueloss", "fleet"): (queueloss.queueloss_pallas_fleet, (4, 97)),
}


@pytest.mark.parametrize("bt", [8, 40])
@pytest.mark.parametrize("family,variant", list(KERNELS))
def test_scoring_kernel_compiles_for_v5e(one_chip, family, variant, bt):
    fn, lead = KERNELS[family, variant]
    compiled = fn.lower(*_kernel_args(family, lead, bt, one_chip),
                        bt=bt, be=128, bc=128).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stage_args(sh, b, warm, stage):
    """Argument shapes of one batched PDHG stage program."""
    s = lambda *shape: _shape(sh, (b,) + shape)  # noqa: E731
    args = [s(M, V, V), s(V, V), _shape(sh, (b, V, V, V), jnp.bool_)]
    if stage == "risk":
        args += [s(), s()]  # u_star, delta
    elif stage == "stretch":
        args += [s(), s(), s(), s(V, V, V)]  # u_star, r_star, delta, f_init
    if warm:
        args += {"mlu": [s(V, V, V), s(M, V, V)],
                 "risk": [s(V, V, V), s(M, V, V), s(V, V, V, 2)],
                 "stretch": [s(M, V, V)]}[stage]
    return args


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("stage", ["mlu", "risk", "stretch"])
def test_pdhg_stage_compiles_for_v5e(one_chip, solver, stage, warm):
    """The ``*_batch`` / ``*_batch_warm`` programs at B = 1; the f32
    operators must carry full f32 precision."""
    name = f"_solve_{stage}_batch" + ("_warm" if warm else "")
    lowered = getattr(JaxRoutingSolver, name).lower(
        solver, *_stage_args(one_chip, 1, warm, stage))
    assert "HIGHEST" in lowered.as_text()
    assert lowered.compile().memory_analysis() is not None


GATE_B = 6  # the §4.6 gate's batch: old, new and 4 drain stages


@pytest.mark.parametrize("program", ["mlu", "risk", "stretch", "project"])
def test_gate_batch_compiles_for_v5e(one_chip, solver, program):
    """The gate's fixed-size batch: the warm stage programs at B = 6 and the
    projection of the last stage's splits onto the path simplex."""
    if program == "project":
        lowered = JaxRoutingSolver._proj_f_batch.lower(
            solver, _shape(one_chip, (GATE_B, V, V, V)),
            _shape(one_chip, (GATE_B, V, V, V), jnp.bool_))
    else:
        lowered = getattr(JaxRoutingSolver, f"_solve_{program}_batch_warm").lower(
            solver, *_stage_args(one_chip, GATE_B, True, program))
        assert "HIGHEST" in lowered.as_text()
    assert lowered.compile().memory_analysis() is not None


@pytest.mark.parametrize("skip_stage3", [False, True],
                         ids=["stages123", "skip_stage3"])
def test_served_solve_compiles_for_v5e(one_chip, solver, skip_stage3):
    """The streaming controller's one program per epoch (stages 1 → [2] →
    [3], B = 1); its f32 operators must carry full f32 precision."""
    s = lambda *shape: _shape(one_chip, (1,) + shape)  # noqa: E731
    args = [s(M, V, V), s(V, V), _shape(one_chip, (1, V, V, V), jnp.bool_),
            s(), _shape(one_chip, (4,), jnp.bool_),  # delta, flags
            s(V, V, V), s(M, V, V), s(V, V, V), s(M, V, V), s(V, V, V, 2),
            s(M, V, V)]  # carried f1, y1, f2, y2, z2, y3
    lowered = JaxRoutingSolver._solve_served.lower(solver, skip_stage3, *args)
    assert "HIGHEST" in lowered.as_text()
    assert lowered.compile().memory_analysis() is not None


@pytest.mark.parametrize("devices", [1, 4], ids=["one_chip", "mesh2x2"])
@pytest.mark.parametrize("stage", ["mlu", "risk", "stretch"])
def test_fleet_stage_compiles_for_v5e(topo, solver, stage, devices):
    """The fleet sweep's batched stages, unsharded on one chip and
    ``shard_map``-sharded over the four chips of a 2x2 host."""
    if devices == 1:
        fn = solver._fleet_fns(None)[stage]
        sh = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.asarray(topo.devices), ("fleet",))
        fn = solver._fleet_fns(mesh)[stage]
        sh = NamedSharding(mesh, PartitionSpec("fleet"))
    compiled = fn.lower(*_stage_args(sh, FLEET_N, True, stage)).compile()
    assert compiled.memory_analysis() is not None
