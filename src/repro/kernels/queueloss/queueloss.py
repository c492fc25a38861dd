"""Pallas TPU kernel: fused link-load matmul + fluid-queue loss scan.

The burst-loss hot loop (:mod:`repro.burst.queue`) is
``load[k, e] = Σ_c sub_demand[k, c] · W[c, e]`` followed by a *sequential*
per-link queue recurrence over sub-steps ``k``:

    x       = q[e] + (load[k, e] - cap[e]) * dt
    drop   += max(0, x - buf[e])
    q[e]    = clip(x, 0, buf[e])

Materializing ``load`` costs ``TS·E`` HBM traffic, and the recurrence makes
the time axis sequential.  This kernel contracts commodity tiles with the MXU
into a VMEM load tile, then walks the tile's rows in-register, carrying the
full per-link queue vector in a VMEM scratch that persists across time tiles —
the only HBM traffic besides inputs is ``2·TS`` floats of output.

Grid: ``(nT, nE, nC)`` — TPU grids iterate sequentially with the last axis
fastest, so for a fixed ``(t, e)`` the load accumulator sees all ``nC``
contraction steps, the two output blocks stay resident for a fixed ``t``
across all ``(e, c)`` steps, and successive ``t`` tiles see monotonically
increasing time, which makes the queue-state carry across tiles exact.

Inputs must be pre-padded to tile multiples (see ``ops.py``):
  demand (TS, C) f32    W (C, E) f32
  cap    (1, E)  f32 (Gb/s; 0 on padded links)
  buf    (1, E)  f32 (Gb;   0 on padded links)
  dt     (1, 1)  f32 (s)
Padded links carry zero load against zero capacity, so they never drop.
Outputs (each (TS, 1) f32): drop_sum (Gb), load_sum (Gb/s), summed over links.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["queueloss_kernel", "queueloss_pallas",
           "queueloss_batched_kernel", "queueloss_pallas_batched",
           "queueloss_fleet_kernel", "queueloss_pallas_fleet"]

# Mosaic's default contraction precision rounds f32 operands to bf16; on a
# TPU v5e the same dot in the linkload kernel moved p99.9 MLU by 2.5e-3
# relative to numpy.  The loads are specified in f32, so every dot asks for
# full f32.
_F32 = jax.lax.Precision.HIGHEST


def queueloss_kernel(dem_ref, w_ref, cap_ref, buf_ref, dt_ref,
                     drop_ref, tot_ref, acc_ref, q_ref):
    """One (bt, be) tile step of the fused matmul + queue-scan computation."""
    t_idx = pl.program_id(0)
    e_idx = pl.program_id(1)
    c_idx = pl.program_id(2)
    n_c = pl.num_programs(2)
    bt = acc_ref.shape[0]
    be = acc_ref.shape[1]

    @pl.when(jnp.logical_and(t_idx == 0, jnp.logical_and(e_idx == 0, c_idx == 0)))
    def _init_queue():
        q_ref[...] = jnp.zeros_like(q_ref)

    @pl.when(c_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        dem_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=_F32)

    @pl.when(jnp.logical_and(c_idx == n_c - 1, e_idx == 0))
    def _init_out():
        drop_ref[...] = jnp.zeros_like(drop_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    @pl.when(c_idx == n_c - 1)
    def _scan_tile():
        tot_ref[...] += acc_ref[...].sum(axis=1, keepdims=True)
        cap_row = cap_ref[...]  # (1, be)
        buf_row = buf_ref[...]  # (1, be)
        dt = dt_ref[0, 0]
        q_slice = pl.ds(e_idx * be, be)

        def body(k, q):
            load_row = acc_ref[pl.ds(k, 1), :]  # (1, be)
            x = q + (load_row - cap_row) * dt
            drop = jnp.maximum(x - buf_row, 0.0)
            drop_ref[pl.ds(k, 1), :] += drop.sum(axis=1, keepdims=True)
            return jnp.clip(x, 0.0, buf_row)

        q0 = q_ref[:, q_slice]  # (1, be) carried from the previous time tile
        q_ref[:, q_slice] = jax.lax.fori_loop(0, bt, body, q0)


@functools.partial(jax.jit, static_argnames=("bt", "be", "bc", "interpret"))
def queueloss_pallas(demand, w, cap, buf, dt,
                     bt: int = 128, be: int = 128, bc: int = 128,
                     interpret: bool = False):
    """Fused queue-loss scan over pre-padded inputs. Returns (drop_sum,
    load_sum), each of shape (TS,)."""
    ts, c = demand.shape
    _, e = w.shape
    assert ts % bt == 0 and c % bc == 0 and e % be == 0, "inputs must be padded"
    grid = (ts // bt, e // be, c // bc)
    out_shape = [jax.ShapeDtypeStruct((ts, 1), jnp.float32)] * 2
    out_spec = pl.BlockSpec((bt, 1), lambda ti, ei, ci: (ti, 0))
    drop, tot = pl.pallas_call(
        queueloss_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bc), lambda ti, ei, ci: (ti, ci)),
            pl.BlockSpec((bc, be), lambda ti, ei, ci: (ci, ei)),
            pl.BlockSpec((1, be), lambda ti, ei, ci: (0, ei)),
            pl.BlockSpec((1, be), lambda ti, ei, ci: (0, ei)),
            pl.BlockSpec((1, 1), lambda ti, ei, ci: (0, 0)),
        ],
        out_specs=[out_spec] * 2,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bt, be), jnp.float32),  # load tile accumulator
            pltpu.VMEM((1, e), jnp.float32),  # per-link queue state (all E)
        ],
        interpret=interpret,
        name="queueloss",
    )(demand, w, cap, buf, dt)
    return drop[:, 0], tot[:, 0]


def queueloss_batched_kernel(dem_ref, w_ref, cap_ref, buf_ref, dt_ref,
                             drop_ref, tot_ref, acc_ref, q_ref):
    """One (b, bt, be) tile step of the epoch-batched matmul + queue scan.

    Same recurrence as :func:`queueloss_kernel` with a leading batch/epoch
    grid axis: each epoch has its own routing weights, capacities, and buffer
    depths, and its queue state starts empty — the (t, e, c) sub-grid restarts
    at (0, 0, 0) when the batch index advances, which is exactly when the
    queue scratch is re-zeroed, so epochs are independent (the controller's
    block-boundary queue reset).
    """
    t_idx = pl.program_id(1)
    e_idx = pl.program_id(2)
    c_idx = pl.program_id(3)
    n_c = pl.num_programs(3)
    bt = acc_ref.shape[0]
    be = acc_ref.shape[1]

    @pl.when(jnp.logical_and(t_idx == 0, jnp.logical_and(e_idx == 0, c_idx == 0)))
    def _init_queue():  # start of this epoch's sweep
        q_ref[...] = jnp.zeros_like(q_ref)

    @pl.when(c_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        dem_ref[0], w_ref[0], preferred_element_type=jnp.float32,
        precision=_F32)

    @pl.when(jnp.logical_and(c_idx == n_c - 1, e_idx == 0))
    def _init_out():
        drop_ref[...] = jnp.zeros_like(drop_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    @pl.when(c_idx == n_c - 1)
    def _scan_tile():
        tot_ref[0] += acc_ref[...].sum(axis=1, keepdims=True)
        cap_row = cap_ref[0]  # (1, be)
        buf_row = buf_ref[0]  # (1, be)
        dt = dt_ref[0, 0]
        q_slice = pl.ds(e_idx * be, be)

        def body(k, q):
            load_row = acc_ref[pl.ds(k, 1), :]  # (1, be)
            x = q + (load_row - cap_row) * dt
            drop = jnp.maximum(x - buf_row, 0.0)
            drop_ref[0, pl.ds(k, 1), :] += drop.sum(axis=1, keepdims=True)
            return jnp.clip(x, 0.0, buf_row)

        q0 = q_ref[:, q_slice]  # (1, be) carried from the previous time tile
        q_ref[:, q_slice] = jax.lax.fori_loop(0, bt, body, q0)


@functools.partial(jax.jit, static_argnames=("bt", "be", "bc", "interpret"))
def queueloss_pallas_batched(demand, w, cap, buf, dt,
                             bt: int = 128, be: int = 128, bc: int = 128,
                             interpret: bool = False):
    """Epoch-batched fused queue-loss scan over pre-padded inputs.

    demand (B, TS, C), w (B, C, E), cap/buf (B, 1, E), dt (1, 1); returns
    (drop_sum, load_sum), each of shape (B, TS).
    """
    b, ts, c = demand.shape
    _, _, e = w.shape
    assert ts % bt == 0 and c % bc == 0 and e % be == 0, "inputs must be padded"
    grid = (b, ts // bt, e // be, c // bc)
    out_shape = [jax.ShapeDtypeStruct((b, ts, 1), jnp.float32)] * 2
    out_spec = pl.BlockSpec((1, bt, 1), lambda bi, ti, ei, ci: (bi, ti, 0))
    drop, tot = pl.pallas_call(
        queueloss_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda bi, ti, ei, ci: (bi, ti, ci)),
            pl.BlockSpec((1, bc, be), lambda bi, ti, ei, ci: (bi, ci, ei)),
            pl.BlockSpec((1, 1, be), lambda bi, ti, ei, ci: (bi, 0, ei)),
            pl.BlockSpec((1, 1, be), lambda bi, ti, ei, ci: (bi, 0, ei)),
            pl.BlockSpec((1, 1), lambda bi, ti, ei, ci: (0, 0)),
        ],
        out_specs=[out_spec] * 2,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bt, be), jnp.float32),  # load tile accumulator
            pltpu.VMEM((1, e), jnp.float32),  # queue state, reset per epoch
        ],
        interpret=interpret,
        name="queueloss_batched",
    )(demand, w, cap, buf, dt)
    return drop[..., 0], tot[..., 0]


def queueloss_fleet_kernel(dem_ref, w_ref, cap_ref, buf_ref, dt_ref,
                           drop_ref, tot_ref, acc_ref, q_ref):
    """One (f, b, bt, be) tile step of the fleet-batched matmul + queue scan.

    Same recurrence as :func:`queueloss_batched_kernel` with one more leading
    *fabric* grid axis: the (t, e, c) sub-grid restarts at (0, 0, 0) whenever
    either leading index advances, which is exactly when the queue scratch is
    re-zeroed — every (fabric, block) pair scans independently from an empty
    queue, so a whole fleet bucket is a single kernel launch.
    """
    t_idx = pl.program_id(2)
    e_idx = pl.program_id(3)
    c_idx = pl.program_id(4)
    n_c = pl.num_programs(4)
    bt = acc_ref.shape[0]
    be = acc_ref.shape[1]

    @pl.when(jnp.logical_and(t_idx == 0, jnp.logical_and(e_idx == 0, c_idx == 0)))
    def _init_queue():  # start of this (fabric, block) scan
        q_ref[...] = jnp.zeros_like(q_ref)

    @pl.when(c_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        dem_ref[0, 0], w_ref[0, 0], preferred_element_type=jnp.float32,
        precision=_F32)

    @pl.when(jnp.logical_and(c_idx == n_c - 1, e_idx == 0))
    def _init_out():
        drop_ref[...] = jnp.zeros_like(drop_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    @pl.when(c_idx == n_c - 1)
    def _scan_tile():
        tot_ref[0, 0] += acc_ref[...].sum(axis=1, keepdims=True)
        cap_row = cap_ref[0, 0]  # (1, be)
        buf_row = buf_ref[0, 0]  # (1, be)
        dt = dt_ref[0, 0]
        q_slice = pl.ds(e_idx * be, be)

        def body(k, q):
            load_row = acc_ref[pl.ds(k, 1), :]  # (1, be)
            x = q + (load_row - cap_row) * dt
            drop = jnp.maximum(x - buf_row, 0.0)
            drop_ref[0, 0, pl.ds(k, 1), :] += drop.sum(axis=1, keepdims=True)
            return jnp.clip(x, 0.0, buf_row)

        q0 = q_ref[:, q_slice]  # (1, be) carried from the previous time tile
        q_ref[:, q_slice] = jax.lax.fori_loop(0, bt, body, q0)


@functools.partial(jax.jit, static_argnames=("bt", "be", "bc", "interpret"))
def queueloss_pallas_fleet(demand, w, cap, buf, dt,
                           bt: int = 128, be: int = 128, bc: int = 128,
                           interpret: bool = False):
    """Fleet-batched fused queue-loss scan over pre-padded inputs.

    demand (F, B, TS, C), w (F, B, C, E), cap/buf (F, B, 1, E), dt (1, 1);
    returns (drop_sum, load_sum), each of shape (F, B, TS).
    """
    f, b, ts, c = demand.shape
    _, _, _, e = w.shape
    assert ts % bt == 0 and c % bc == 0 and e % be == 0, "inputs must be padded"
    grid = (f, b, ts // bt, e // be, c // bc)
    out_shape = [jax.ShapeDtypeStruct((f, b, ts, 1), jnp.float32)] * 2
    out_spec = pl.BlockSpec((1, 1, bt, 1), lambda fi, bi, ti, ei, ci: (fi, bi, ti, 0))
    drop, tot = pl.pallas_call(
        queueloss_fleet_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bt, bc), lambda fi, bi, ti, ei, ci: (fi, bi, ti, ci)),
            pl.BlockSpec((1, 1, bc, be), lambda fi, bi, ti, ei, ci: (fi, bi, ci, ei)),
            pl.BlockSpec((1, 1, 1, be), lambda fi, bi, ti, ei, ci: (fi, bi, 0, ei)),
            pl.BlockSpec((1, 1, 1, be), lambda fi, bi, ti, ei, ci: (fi, bi, 0, ei)),
            pl.BlockSpec((1, 1), lambda fi, bi, ti, ei, ci: (0, 0)),
        ],
        out_specs=[out_spec] * 2,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bt, be), jnp.float32),  # load tile accumulator
            pltpu.VMEM((1, e), jnp.float32),  # queue state, reset per block
        ],
        interpret=interpret,
        name="queueloss_fleet",
    )(demand, w, cap, buf, dt)
    return drop[..., 0], tot[..., 0]
