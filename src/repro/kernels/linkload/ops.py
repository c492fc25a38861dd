"""Jit'd public wrapper for the fused link-load metrics kernel.

Handles padding to tile multiples, capacity normalization, dead-link masking,
and converting the kernel's raw accumulators (sums/counts) into the simulator's
MLU / ALU / OLR / total-load metrics.  ``backend`` selects the Pallas kernel
(interpret-mode on CPU), the pure-jnp reference, or numpy.

Tile sizes default to ``None`` = consult the autotune table
(:mod:`repro.kernels.autotune`) for this device/shape; pass explicit values
to pin them.  Any tiling the table can return yields bit-identical outputs
(tuner-certified), so this is purely a speed knob.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.autotune.table import (pad_to as _pad_to,
                                          resolve_tiles,
                                          shrink_bt as _shrink_bt)
from repro.kernels.linkload.linkload import (linkload_pallas,
                                             linkload_pallas_batched,
                                             linkload_pallas_fleet)
from repro.kernels.linkload.ref import (linkload_metrics_batched_ref,
                                        linkload_metrics_fleet_ref,
                                        linkload_metrics_ref)

__all__ = ["link_metrics", "link_metrics_batched", "link_metrics_fleet"]


def link_metrics(demand, weights, capacities, threshold: float = 0.8,
                 backend: str = "pallas",
                 bt: int | None = None, be: int | None = None,
                 bc: int | None = None):
    """Per-interval (mlu, alu, olr, total_load) for a (T, C) demand block.

    ALU and OLR are averaged over *live* links (capacity > 0) only; padded
    columns have inv_cap = 0 so they never contribute.
    """
    demand = np.asarray(demand, np.float32)
    weights = np.asarray(weights, np.float32)
    cap = np.asarray(capacities, np.float64)
    live = cap > 1e-9
    n_live = max(int(live.sum()), 1)
    inv_cap = np.where(live, 1.0 / np.maximum(cap, 1e-9), 0.0).astype(np.float32)

    t_orig = demand.shape[0]
    if backend == "pallas":
        bt, be, bc = resolve_tiles("linkload", t_orig, demand.shape[1],
                                   weights.shape[1], backend, bt, be, bc)
        bt = _shrink_bt(bt, t_orig)
        d = _pad_to(demand, 0, bt)
        d = _pad_to(d, 1, bc)
        w = _pad_to(weights, 0, bc)
        w = _pad_to(w, 1, be)
        ic = _pad_to(inv_cap[None, :], 1, be)
        interpret = jax.default_backend() == "cpu"
        mlu, alu_sum, olr_cnt, tot = linkload_pallas(
            jnp.asarray(d), jnp.asarray(w), jnp.asarray(ic),
            jnp.full((1, 1), threshold, jnp.float32),
            bt=bt, be=be, bc=bc, interpret=interpret)
        with obs.span("score.wait"):
            out = [np.asarray(x) for x in (mlu, alu_sum, olr_cnt, tot)]
        mlu, alu_sum, olr_cnt, tot = (x[:t_orig] for x in out)
    elif backend == "jnp":
        mlu, alu_sum, olr_cnt, tot = (
            np.asarray(x) for x in linkload_metrics_ref(
                jnp.asarray(demand), jnp.asarray(weights),
                jnp.asarray(inv_cap[None, :]), threshold))
    else:  # numpy
        load = demand.astype(np.float64) @ weights.astype(np.float64)
        util = load * inv_cap[None, :]
        mlu = util.max(axis=1)
        alu_sum = util.sum(axis=1)
        olr_cnt = (util > threshold).sum(axis=1)
        tot = load.sum(axis=1)
    return mlu, alu_sum / n_live, olr_cnt / n_live, tot


def link_metrics_batched(demand, weights, capacities, threshold: float = 0.8,
                         backend: str = "pallas",
                         bt: int | None = None, be: int | None = None,
                         bc: int | None = None):
    """Epoch-batched :func:`link_metrics`: one call scores every routing epoch
    of a controller sweep.

    Args:
      demand: (B, T, C) per-epoch demand blocks (zero-padded rows are fine —
        they are scored but typically trimmed by the caller).
      weights: (B, C, E) per-epoch routing-weight matrices.
      capacities: (B, E) per-epoch directed capacities (topology epochs can
        differ).
      threshold / backend / block sizes: as :func:`link_metrics`.

    Returns (mlu, alu, olr, total_load), each of shape (B, T); ALU/OLR are
    averaged over each epoch's own live links.
    """
    demand = np.asarray(demand)
    weights = np.asarray(weights)
    cap = np.asarray(capacities, np.float64)
    live = cap > 1e-9  # (B, E)
    n_live = np.maximum(live.sum(axis=1), 1)[:, None]  # (B, 1)
    inv_cap = np.where(live, 1.0 / np.maximum(cap, 1e-9), 0.0)

    t_orig = demand.shape[1]
    if backend == "pallas":
        bt, be, bc = resolve_tiles("linkload_batched", t_orig,
                                   demand.shape[2], weights.shape[2],
                                   backend, bt, be, bc)
        bt = _shrink_bt(bt, t_orig)
        d = _pad_to(_pad_to(demand.astype(np.float32), 1, bt), 2, bc)
        w = _pad_to(_pad_to(weights.astype(np.float32), 1, bc), 2, be)
        ic = _pad_to(inv_cap[:, None, :].astype(np.float32), 2, be)
        interpret = jax.default_backend() == "cpu"
        mlu, alu_sum, olr_cnt, tot = linkload_pallas_batched(
            jnp.asarray(d), jnp.asarray(w), jnp.asarray(ic),
            jnp.full((1, 1), threshold, jnp.float32),
            bt=bt, be=be, bc=bc, interpret=interpret)
        mlu, alu_sum, olr_cnt, tot = (
            np.asarray(x)[:, :t_orig] for x in (mlu, alu_sum, olr_cnt, tot))
    elif backend in ("jnp", "jax"):
        mlu, alu_sum, olr_cnt, tot = (
            np.asarray(x) for x in linkload_metrics_batched_ref(
                jnp.asarray(demand, jnp.float32),
                jnp.asarray(weights, jnp.float32),
                jnp.asarray(inv_cap[:, None, :], jnp.float32), threshold))
    else:  # numpy
        load = demand.astype(np.float64) @ weights.astype(np.float64)  # (B,T,E)
        util = load * inv_cap[:, None, :]
        mlu = util.max(axis=2)
        alu_sum = util.sum(axis=2)
        olr_cnt = (util > threshold).sum(axis=2)
        tot = load.sum(axis=2)
    return mlu, alu_sum / n_live, olr_cnt / n_live, tot


def link_metrics_fleet(demand, weights, capacities, threshold: float = 0.8,
                       backend: str = "pallas",
                       bt: int | None = None, be: int | None = None,
                       bc: int | None = None):
    """Fabric-batched :func:`link_metrics_batched`: one call scores every
    scoring block of every fabric in a fleet bucket.

    Args:
      demand: (F, B, T, C) per-(fabric, block) demand (zero rows/blocks are
        padding — scored but trimmed by the caller).
      weights: (F, B, C, E) per-(fabric, block) routing-weight matrices.
      capacities: (F, B, E) per-(fabric, block) directed capacities (zero on
        padded links and padded blocks).
      threshold / backend / block sizes: as :func:`link_metrics`.

    Returns (mlu, alu, olr, total_load), each of shape (F, B, T); ALU/OLR
    are averaged over each block's own live links.
    """
    demand = np.asarray(demand)
    weights = np.asarray(weights)
    cap = np.asarray(capacities, np.float64)
    live = cap > 1e-9  # (F, B, E)
    n_live = np.maximum(live.sum(axis=2), 1)[..., None]  # (F, B, 1)
    inv_cap = np.where(live, 1.0 / np.maximum(cap, 1e-9), 0.0)

    t_orig = demand.shape[2]
    if backend == "pallas":
        bt, be, bc = resolve_tiles("linkload_fleet", t_orig,
                                   demand.shape[3], weights.shape[3],
                                   backend, bt, be, bc)
        bt = _shrink_bt(bt, t_orig)
        d = _pad_to(_pad_to(demand.astype(np.float32), 2, bt), 3, bc)
        w = _pad_to(_pad_to(weights.astype(np.float32), 2, bc), 3, be)
        ic = _pad_to(inv_cap[:, :, None, :].astype(np.float32), 3, be)
        interpret = jax.default_backend() == "cpu"
        mlu, alu_sum, olr_cnt, tot = linkload_pallas_fleet(
            jnp.asarray(d), jnp.asarray(w), jnp.asarray(ic),
            jnp.full((1, 1), threshold, jnp.float32),
            bt=bt, be=be, bc=bc, interpret=interpret)
        mlu, alu_sum, olr_cnt, tot = (
            np.asarray(x)[:, :, :t_orig] for x in (mlu, alu_sum, olr_cnt, tot))
    elif backend in ("jnp", "jax"):
        mlu, alu_sum, olr_cnt, tot = (
            np.asarray(x) for x in linkload_metrics_fleet_ref(
                jnp.asarray(demand, jnp.float32),
                jnp.asarray(weights, jnp.float32),
                jnp.asarray(inv_cap[:, :, None, :], jnp.float32), threshold))
    else:  # numpy
        load = demand.astype(np.float64) @ weights.astype(np.float64)  # (F,B,T,E)
        util = load * inv_cap[:, :, None, :]
        mlu = util.max(axis=3)
        alu_sum = util.sum(axis=3)
        olr_cnt = (util > threshold).sum(axis=3)
        tot = load.sum(axis=3)
    return mlu, alu_sum / n_live, olr_cnt / n_live, tot
