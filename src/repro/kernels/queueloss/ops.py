"""Jit'd public wrapper for the fused queue-loss kernel.

Handles padding to tile multiples and backend selection: the Pallas kernel
(interpret-mode on CPU), the pure-jnp scan reference, or the float64 numpy
oracle (:func:`repro.burst.queue.queue_loss_numpy` — kept jax-free there;
the f32 casts below apply to the kernel backends only).  All backends
implement the same finite-buffer fluid-queue recurrence; padded links get
``cap = buf = 0`` and carry zero load, so they never drop.

Tile sizes default to ``None`` = consult the autotune table
(:mod:`repro.kernels.autotune`); explicit values pin them.  Table winners are
certified bit-identical against the default tiling, and the short-block
time-tile clamp (``shrink_bt``) applies on top of either, so a 3-sub-step
drain stage pads to 8 rows, never 128.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.autotune.table import (pad_to as _pad_to,
                                          resolve_tiles,
                                          shrink_bt as _shrink_bt)
from repro.kernels.queueloss.queueloss import (queueloss_pallas,
                                               queueloss_pallas_batched,
                                               queueloss_pallas_fleet)
from repro.kernels.queueloss.ref import (queueloss_batched_ref,
                                         queueloss_fleet_ref, queueloss_ref)

__all__ = ["queue_loss", "queue_loss_batched", "queue_loss_fleet"]


def queue_loss(demand, weights, capacities, buffers, dt: float,
               backend: str = "pallas",
               bt: int | None = None, be: int | None = None,
               bc: int | None = None):
    """Per-sub-step (drop_sum, load_sum) for a (TS, C) sub-interval demand
    block routed by ``weights (C, E)`` over links with ``capacities (E,)``
    (Gb/s) and finite buffers ``buffers (E,)`` (Gb); ``dt`` is the sub-step
    duration in seconds.

    Returns ``(drop, tot)``: dropped volume (Gb) and offered load (Gb/s) per
    sub-step, each summed over links, shape ``(TS,)`` float64.
    """
    if backend not in ("pallas", "jnp", "jax"):  # numpy: float64 end to end
        from repro.burst.queue import queue_loss_numpy

        return queue_loss_numpy(demand, weights, capacities, buffers, dt)
    demand = np.asarray(demand, np.float32)
    weights = np.asarray(weights, np.float32)
    cap = np.asarray(capacities, np.float32)
    buf = np.asarray(buffers, np.float32)
    ts_orig = demand.shape[0]
    if backend == "pallas":
        bt, be, bc = resolve_tiles("queueloss", ts_orig, demand.shape[1],
                                   weights.shape[1], backend, bt, be, bc)
        bt = _shrink_bt(bt, ts_orig)
        d = _pad_to(demand, 0, bt)
        d = _pad_to(d, 1, bc)
        w = _pad_to(weights, 0, bc)
        w = _pad_to(w, 1, be)
        cp = _pad_to(cap[None, :], 1, be)
        bf = _pad_to(buf[None, :], 1, be)
        interpret = jax.default_backend() == "cpu"
        drop, tot = queueloss_pallas(
            jnp.asarray(d), jnp.asarray(w), jnp.asarray(cp), jnp.asarray(bf),
            jnp.full((1, 1), dt, jnp.float32),
            bt=bt, be=be, bc=bc, interpret=interpret)
        with obs.span("score.wait"):
            out = [np.asarray(x) for x in (drop, tot)]
        drop, tot = (x.astype(np.float64)[:ts_orig] for x in out)
    else:  # jnp / jax
        drop, tot = (np.asarray(x, np.float64) for x in queueloss_ref(
            jnp.asarray(demand), jnp.asarray(weights),
            jnp.asarray(cap), jnp.asarray(buf), jnp.float32(dt)))
    return drop, tot


def queue_loss_batched(demand, weights, capacities, buffers, dt: float,
                       backend: str = "pallas",
                       bt: int | None = None, be: int | None = None,
                       bc: int | None = None):
    """Epoch-batched :func:`queue_loss`: one call scans every routing epoch.

    Args:
      demand: (B, TS, C) sub-interval demand blocks, one epoch per row
        (zero-padded trailing sub-steps only drain queues, never add drops
        for the real prefix — trim the outputs to each epoch's length).
      weights: (B, C, E); capacities/buffers: (B, E); dt: sub-step seconds.

    Queue state starts empty in every epoch (the controller's block-boundary
    reset).  Returns (drop, tot), each (B, TS) float64.
    """
    if backend not in ("pallas", "jnp", "jax"):  # numpy: float64 end to end
        from repro.burst.queue import queue_loss_numpy

        out = [queue_loss_numpy(d, w, c, bf, dt)
               for d, w, c, bf in zip(demand, weights, capacities, buffers)]
        return (np.stack([o[0] for o in out]), np.stack([o[1] for o in out]))
    demand = np.asarray(demand, np.float32)
    weights = np.asarray(weights, np.float32)
    cap = np.asarray(capacities, np.float32)
    buf = np.asarray(buffers, np.float32)
    ts_orig = demand.shape[1]
    if backend == "pallas":
        bt, be, bc = resolve_tiles("queueloss_batched", ts_orig,
                                   demand.shape[2], weights.shape[2],
                                   backend, bt, be, bc)
        bt = _shrink_bt(bt, ts_orig)
        d = _pad_to(_pad_to(demand, 1, bt), 2, bc)
        w = _pad_to(_pad_to(weights, 1, bc), 2, be)
        cp = _pad_to(cap[:, None, :], 2, be)
        bf = _pad_to(buf[:, None, :], 2, be)
        interpret = jax.default_backend() == "cpu"
        drop, tot = queueloss_pallas_batched(
            jnp.asarray(d), jnp.asarray(w), jnp.asarray(cp), jnp.asarray(bf),
            jnp.full((1, 1), dt, jnp.float32),
            bt=bt, be=be, bc=bc, interpret=interpret)
        drop, tot = (np.asarray(x, np.float64)[:, :ts_orig] for x in (drop, tot))
    else:  # jnp / jax
        drop, tot = (np.asarray(x, np.float64) for x in queueloss_batched_ref(
            jnp.asarray(demand), jnp.asarray(weights),
            jnp.asarray(cap), jnp.asarray(buf), jnp.float32(dt)))
    return drop, tot


def queue_loss_fleet(demand, weights, capacities, buffers, dt: float,
                     backend: str = "pallas",
                     bt: int | None = None, be: int | None = None,
                     bc: int | None = None):
    """Fabric-batched :func:`queue_loss_batched`: one call scans every scoring
    block of every fabric in a fleet bucket.

    Args:
      demand: (F, B, TS, C) sub-interval demand blocks (zero-padded trailing
        sub-steps and all-zero padded blocks only drain queues, never drop).
      weights: (F, B, C, E); capacities/buffers: (F, B, E); dt: sub-step
        seconds.

    Queue state starts empty in every (fabric, block) pair.  Returns
    (drop, tot), each (F, B, TS) float64.
    """
    if backend not in ("pallas", "jnp", "jax"):  # numpy: float64 end to end
        from repro.burst.queue import queue_loss_numpy

        out = [[queue_loss_numpy(d, w, c, bf, dt)
                for d, w, c, bf in zip(df, wf, cf, bff)]
               for df, wf, cf, bff in zip(demand, weights, capacities, buffers)]
        return (np.stack([[o[0] for o in row] for row in out]),
                np.stack([[o[1] for o in row] for row in out]))
    demand = np.asarray(demand, np.float32)
    weights = np.asarray(weights, np.float32)
    cap = np.asarray(capacities, np.float32)
    buf = np.asarray(buffers, np.float32)
    ts_orig = demand.shape[2]
    if backend == "pallas":
        bt, be, bc = resolve_tiles("queueloss_fleet", ts_orig,
                                   demand.shape[3], weights.shape[3],
                                   backend, bt, be, bc)
        bt = _shrink_bt(bt, ts_orig)
        d = _pad_to(_pad_to(demand, 2, bt), 3, bc)
        w = _pad_to(_pad_to(weights, 2, bc), 3, be)
        cp = _pad_to(cap[:, :, None, :], 3, be)
        bf = _pad_to(buf[:, :, None, :], 3, be)
        interpret = jax.default_backend() == "cpu"
        drop, tot = queueloss_pallas_fleet(
            jnp.asarray(d), jnp.asarray(w), jnp.asarray(cp), jnp.asarray(bf),
            jnp.full((1, 1), dt, jnp.float32),
            bt=bt, be=be, bc=bc, interpret=interpret)
        drop, tot = (np.asarray(x, np.float64)[:, :, :ts_orig]
                     for x in (drop, tot))
    else:  # jnp / jax
        drop, tot = (np.asarray(x, np.float64) for x in queueloss_fleet_ref(
            jnp.asarray(demand), jnp.asarray(weights),
            jnp.asarray(cap), jnp.asarray(buf), jnp.float32(dt)))
    return drop, tot
