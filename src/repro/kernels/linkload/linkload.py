"""Pallas TPU kernel: fused link-load matmul + utilization metric reductions.

The simulator's hot loop is ``load[t, e] = Σ_c demand[t, c] · W[c, e]`` followed
by four row-wise reductions (MLU, ALU-sum, overloaded-link count, total load).
Materializing ``load`` costs ``T·E`` HBM writes + reads; for fleet-scale sweeps
(22 fabrics × 4 strategies × months of 5-minute intervals) that dominates. This
kernel keeps each ``(bt, be)`` load tile in VMEM, contracts over commodity
tiles with the MXU, and folds the tile directly into per-interval accumulators
— the only HBM traffic besides inputs is ``4·T`` floats of output.

Grid: ``(nT, nE, nC)`` — TPU grids iterate sequentially with the last axis
fastest, so for a fixed ``(t, e)`` the scratch accumulator sees all ``nC``
contraction steps, and for a fixed ``t`` the four output blocks stay resident
across all ``(e, c)`` steps, which makes cross-tile max/sum accumulation safe.

Inputs must be pre-padded to tile multiples (see ``ops.py``):
  demand  (T, C)  f32      W        (C, E)  f32
  inv_cap (1, E)  f32 (zero on padded/zero-capacity links)
Outputs (each (T, 1) f32): mlu, alu_sum, overload_count, load_sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["linkload_metrics_kernel", "linkload_pallas",
           "linkload_batched_kernel", "linkload_pallas_batched",
           "linkload_fleet_kernel", "linkload_pallas_fleet"]

# Mosaic's default contraction precision rounds f32 operands to bf16; on a
# TPU v5e that moved p99.9 MLU by 2.5e-3 relative to numpy.  The loads are
# specified in f32, so every dot asks for full f32.
_F32 = jax.lax.Precision.HIGHEST


def linkload_metrics_kernel(dem_ref, w_ref, invcap_ref, thr_ref,
                            mlu_ref, alu_ref, olr_ref, tot_ref, acc_ref):
    """One (bt, be) tile step of the fused matmul+metrics computation."""
    e_idx = pl.program_id(1)
    c_idx = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        dem_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=_F32)

    @pl.when(jnp.logical_and(c_idx == n_c - 1, e_idx == 0))
    def _init_out():
        mlu_ref[...] = jnp.zeros_like(mlu_ref)
        alu_ref[...] = jnp.zeros_like(alu_ref)
        olr_ref[...] = jnp.zeros_like(olr_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    @pl.when(c_idx == n_c - 1)
    def _reduce_tile():
        load = acc_ref[...]  # (bt, be)
        util = load * invcap_ref[...]  # broadcast (1, be)
        thr = thr_ref[0, 0]
        mlu_ref[...] = jnp.maximum(mlu_ref[...], util.max(axis=1, keepdims=True))
        alu_ref[...] += util.sum(axis=1, keepdims=True)
        olr_ref[...] += (util > thr).astype(jnp.float32).sum(axis=1, keepdims=True)
        tot_ref[...] += load.sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bt", "be", "bc", "interpret"))
def linkload_pallas(demand, w, inv_cap, threshold,
                    bt: int = 256, be: int = 128, bc: int = 128,
                    interpret: bool = False):
    """Fused metrics over pre-padded inputs. Returns (mlu, alu_sum, olr_count,
    load_sum), each of shape (T,)."""
    t, c = demand.shape
    _, e = w.shape
    assert t % bt == 0 and c % bc == 0 and e % be == 0, "inputs must be padded"
    grid = (t // bt, e // be, c // bc)
    out_shape = [jax.ShapeDtypeStruct((t, 1), jnp.float32)] * 4
    out_spec = pl.BlockSpec((bt, 1), lambda ti, ei, ci: (ti, 0))
    mlu, alu, olr, tot = pl.pallas_call(
        linkload_metrics_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bc), lambda ti, ei, ci: (ti, ci)),
            pl.BlockSpec((bc, be), lambda ti, ei, ci: (ci, ei)),
            pl.BlockSpec((1, be), lambda ti, ei, ci: (0, ei)),
            pl.BlockSpec((1, 1), lambda ti, ei, ci: (0, 0)),
        ],
        out_specs=[out_spec] * 4,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, be), jnp.float32)],
        interpret=interpret,
        name="linkload",
    )(demand, w, inv_cap, threshold)
    return mlu[:, 0], alu[:, 0], olr[:, 0], tot[:, 0]


def linkload_batched_kernel(dem_ref, w_ref, invcap_ref, thr_ref,
                            mlu_ref, alu_ref, olr_ref, tot_ref, acc_ref):
    """One (b, bt, be) tile step of the epoch-batched matmul+metrics sweep.

    Identical accumulation logic to :func:`linkload_metrics_kernel`, but with a
    leading batch/epoch grid axis: every epoch carries its own routing-weight
    matrix and capacity row, and the whole fleet sweep is one kernel launch —
    loads stay in VMEM across the (e, c) contraction of each (b, t) tile.
    """
    e_idx = pl.program_id(2)
    c_idx = pl.program_id(3)
    n_c = pl.num_programs(3)

    @pl.when(c_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        dem_ref[0], w_ref[0], preferred_element_type=jnp.float32,
        precision=_F32)

    @pl.when(jnp.logical_and(c_idx == n_c - 1, e_idx == 0))
    def _init_out():
        mlu_ref[...] = jnp.zeros_like(mlu_ref)
        alu_ref[...] = jnp.zeros_like(alu_ref)
        olr_ref[...] = jnp.zeros_like(olr_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    @pl.when(c_idx == n_c - 1)
    def _reduce_tile():
        load = acc_ref[...]  # (bt, be)
        util = load * invcap_ref[0]  # broadcast (1, be)
        thr = thr_ref[0, 0]
        mlu_ref[0] = jnp.maximum(mlu_ref[0], util.max(axis=1, keepdims=True))
        alu_ref[0] += util.sum(axis=1, keepdims=True)
        olr_ref[0] += (util > thr).astype(jnp.float32).sum(axis=1, keepdims=True)
        tot_ref[0] += load.sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bt", "be", "bc", "interpret"))
def linkload_pallas_batched(demand, w, inv_cap, threshold,
                            bt: int = 256, be: int = 128, bc: int = 128,
                            interpret: bool = False):
    """Epoch-batched fused metrics over pre-padded inputs.

    demand (B, T, C), w (B, C, E), inv_cap (B, 1, E), threshold (1, 1); returns
    (mlu, alu_sum, olr_count, load_sum), each of shape (B, T).
    """
    b, t, c = demand.shape
    _, _, e = w.shape
    assert t % bt == 0 and c % bc == 0 and e % be == 0, "inputs must be padded"
    grid = (b, t // bt, e // be, c // bc)
    out_shape = [jax.ShapeDtypeStruct((b, t, 1), jnp.float32)] * 4
    out_spec = pl.BlockSpec((1, bt, 1), lambda bi, ti, ei, ci: (bi, ti, 0))
    mlu, alu, olr, tot = pl.pallas_call(
        linkload_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda bi, ti, ei, ci: (bi, ti, ci)),
            pl.BlockSpec((1, bc, be), lambda bi, ti, ei, ci: (bi, ci, ei)),
            pl.BlockSpec((1, 1, be), lambda bi, ti, ei, ci: (bi, 0, ei)),
            pl.BlockSpec((1, 1), lambda bi, ti, ei, ci: (0, 0)),
        ],
        out_specs=[out_spec] * 4,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, be), jnp.float32)],
        interpret=interpret,
        name="linkload_batched",
    )(demand, w, inv_cap, threshold)
    return mlu[..., 0], alu[..., 0], olr[..., 0], tot[..., 0]


def linkload_fleet_kernel(dem_ref, w_ref, invcap_ref, thr_ref,
                          mlu_ref, alu_ref, olr_ref, tot_ref, acc_ref):
    """One (f, b, bt, be) tile step of the fleet-batched matmul+metrics sweep.

    Identical accumulation logic to :func:`linkload_batched_kernel`, with one
    more leading *fabric* grid axis on top of the epoch axis: every
    (fabric, epoch) pair carries its own routing-weight matrix and capacity
    row, so an entire fleet bucket — every fabric's every scoring block —
    is a single kernel launch.
    """
    e_idx = pl.program_id(3)
    c_idx = pl.program_id(4)
    n_c = pl.num_programs(4)

    @pl.when(c_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        dem_ref[0, 0], w_ref[0, 0], preferred_element_type=jnp.float32,
        precision=_F32)

    @pl.when(jnp.logical_and(c_idx == n_c - 1, e_idx == 0))
    def _init_out():
        mlu_ref[...] = jnp.zeros_like(mlu_ref)
        alu_ref[...] = jnp.zeros_like(alu_ref)
        olr_ref[...] = jnp.zeros_like(olr_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    @pl.when(c_idx == n_c - 1)
    def _reduce_tile():
        load = acc_ref[...]  # (bt, be)
        util = load * invcap_ref[0, 0]  # broadcast (1, be)
        thr = thr_ref[0, 0]
        mlu_ref[0, 0] = jnp.maximum(mlu_ref[0, 0],
                                    util.max(axis=1, keepdims=True))
        alu_ref[0, 0] += util.sum(axis=1, keepdims=True)
        olr_ref[0, 0] += (util > thr).astype(jnp.float32).sum(axis=1,
                                                              keepdims=True)
        tot_ref[0, 0] += load.sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bt", "be", "bc", "interpret"))
def linkload_pallas_fleet(demand, w, inv_cap, threshold,
                          bt: int = 256, be: int = 128, bc: int = 128,
                          interpret: bool = False):
    """Fleet-batched fused metrics over pre-padded inputs.

    demand (F, B, T, C), w (F, B, C, E), inv_cap (F, B, 1, E), threshold
    (1, 1); returns (mlu, alu_sum, olr_count, load_sum), each (F, B, T).
    """
    f, b, t, c = demand.shape
    _, _, _, e = w.shape
    assert t % bt == 0 and c % bc == 0 and e % be == 0, "inputs must be padded"
    grid = (f, b, t // bt, e // be, c // bc)
    out_shape = [jax.ShapeDtypeStruct((f, b, t, 1), jnp.float32)] * 4
    out_spec = pl.BlockSpec((1, 1, bt, 1), lambda fi, bi, ti, ei, ci: (fi, bi, ti, 0))
    mlu, alu, olr, tot = pl.pallas_call(
        linkload_fleet_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bt, bc), lambda fi, bi, ti, ei, ci: (fi, bi, ti, ci)),
            pl.BlockSpec((1, 1, bc, be), lambda fi, bi, ti, ei, ci: (fi, bi, ci, ei)),
            pl.BlockSpec((1, 1, 1, be), lambda fi, bi, ti, ei, ci: (fi, bi, 0, ei)),
            pl.BlockSpec((1, 1), lambda fi, bi, ti, ei, ci: (0, 0)),
        ],
        out_specs=[out_spec] * 4,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, be), jnp.float32)],
        interpret=interpret,
        name="linkload_fleet",
    )(demand, w, inv_cap, threshold)
    return mlu[..., 0], alu[..., 0], olr[..., 0], tot[..., 0]
