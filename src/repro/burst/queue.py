"""Finite-buffer fluid-queue loss model over sub-interval link loads.

Each directed link ``e`` is a fluid queue drained at capacity ``cap[e]``
(Gb/s) with a finite buffer ``buf[e]`` (Gb) sized in time units of the line
rate (``buffer_ms``, the switch-buffer depth).  Over sub-steps of duration
``dt`` seconds with offered load ``load[k, e]``:

    x[k]    = q[k] + (load[k, e] - cap[e]) · dt      # fluid level
    drop[k] = max(0, x[k] - buf[e])                  # overflowed volume (Gb)
    q[k+1]  = clip(x[k], 0, buf[e])

The per-interval **loss fraction** is dropped volume over *offered demand*
volume (the expanded sub-interval demand, bursts included), aggregated over
links and the interval's ``n_sub`` sub-steps and clipped to 1 — loads are not
flow-conserving across hops, so in deep saturation the same traffic can be
dropped at both hops of a transit path and double-count.  Normalizing by
demand rather than by routed link volume keeps the metric comparable across
strategies: a high-stretch (hedged) routing must not look better merely
because each byte is counted at more queues.  When every sub-step load is
below capacity (e.g. MLU < 1 with zero-size bursts) queues never build and
loss is exactly zero.

Queue state carries across the intervals *within one call* (one controller
routing block) and starts empty at block boundaries — at these sub-step
timescales buffers fill or drain within a single step whenever loads cross
capacity, so the boundary reset is observable only under sustained overload
spanning a reconfiguration, where real queues would also be rebuilt.

Timescale assumptions: ``dt`` (seconds to tens of seconds) is far above the
packet RTT, so TCP backoff / drop-tail dynamics are abstracted into fluid
overflow — the same first-order model the paper's loss discussion (§3, §5)
relies on; buffers (``buffer_ms`` at line rate, tens of ms) only matter for
excursions shorter than ``buf/(load-cap)``, which makes the model an upper
bound on bufferable bursts and exact in the bufferless limit.

Backends: ``numpy`` (float64 loop), ``jax`` (jnp scan), ``pallas`` (fused
matmul + queue-scan kernel, :mod:`repro.kernels.queueloss`).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.burst.expander import BurstParams, expand

__all__ = ["LossConfig", "link_buffer_gb", "interval_loss",
           "interval_loss_batched", "interval_loss_fleet", "queue_loss_numpy"]


@dataclasses.dataclass(frozen=True)
class LossConfig:
    """Configuration of the burst-loss pipeline (expander + fluid queue).

    Attributes:
      burst: sub-interval burst model (:class:`BurstParams`).
      n_sub: sub-samples per TM interval (S).
      buffer_ms: per-link buffer depth in milliseconds at line rate.
      seed: burst realization seed (same seed ⇒ same bursts ⇒ paired
        comparisons across strategies).
    """

    burst: BurstParams = BurstParams.zero()
    n_sub: int = 12
    buffer_ms: float = 25.0
    seed: int = 0


def link_buffer_gb(capacities: np.ndarray, buffer_ms: float) -> np.ndarray:
    """Buffer depth per link in Gb: ``cap (Gb/s) × buffer_ms``."""
    return np.asarray(capacities, np.float64) * (buffer_ms * 1e-3)


def queue_loss_numpy(demand: np.ndarray, weights: np.ndarray, cap: np.ndarray,
                     buf: np.ndarray, dt: float):
    """Float64, jax-free queue-loss oracle (the precision reference).

    Same contract as :func:`repro.kernels.queueloss.ops.queue_loss`:
    returns per-sub-step ``(drop, tot)`` — dropped Gb and offered load Gb/s,
    each summed over links, shape ``(TS,)`` float64.
    """
    demand = np.asarray(demand, np.float64)
    load = demand @ np.asarray(weights, np.float64)
    cap = np.asarray(cap, np.float64)
    buf = np.asarray(buf, np.float64)
    ts = demand.shape[0]
    q = np.zeros_like(cap)
    drop = np.empty(ts, np.float64)
    tot = np.empty(ts, np.float64)
    for k in range(ts):
        x = q + (load[k] - cap) * dt
        drop[k] = np.maximum(x - buf, 0.0).sum()
        q = np.clip(x, 0.0, buf)
        tot[k] = load[k].sum()
    return drop, tot


def _loss_fractions(drop: np.ndarray, sub: np.ndarray, t: int, n_sub: int,
                    dt: float) -> np.ndarray:
    """Aggregate per-sub-step drops (Gb) and sub-interval demand into the
    per-interval loss fraction (dropped over offered volume, clipped to 1).
    Shared by the sequential and batched paths so their arithmetic can never
    drift apart (the paired-seed parity contract)."""
    drop_i = drop.reshape(t, n_sub).sum(axis=1)  # Gb dropped
    offered_i = sub.sum(axis=1).reshape(t, n_sub).sum(axis=1) * dt  # Gb demanded
    return np.where(offered_i > 1e-12,
                    np.minimum(drop_i / np.maximum(offered_i, 1e-12), 1.0), 0.0)


def interval_loss(
    demand: np.ndarray,
    weights: np.ndarray,
    capacities: np.ndarray,
    interval_seconds: float,
    cfg: LossConfig,
    backend: str = "numpy",
) -> np.ndarray:
    """Per-interval loss fraction for a ``(T, C)`` demand block.

    Expands the block into sub-interval samples (:mod:`repro.burst.expander`),
    routes them with ``weights (C, E_d)``, runs the fluid queue per link, and
    aggregates dropped over offered *demand* volume per original interval.
    Returns a ``(T,)`` float64 array in [0, 1].  ``backend="numpy"`` stays
    jax-free (:func:`queue_loss_numpy`).
    """
    demand = np.asarray(demand, dtype=np.float64)
    t = demand.shape[0]
    if t == 0:
        return np.zeros((0,))
    cap = np.asarray(capacities, dtype=np.float64)
    with obs.span("score.bursts"):
        sub = expand(demand, cfg.n_sub, cfg.burst, cfg.seed)
    dt = interval_seconds / cfg.n_sub
    buf = link_buffer_gb(cap, cfg.buffer_ms)
    if backend == "numpy":
        drop, _ = queue_loss_numpy(sub, weights, cap, buf, dt)
    else:
        from repro.kernels.queueloss import ops as qlops

        with obs.span("score.queueloss"):
            drop, _ = qlops.queue_loss(sub, weights, cap, buf, dt,
                                       backend=backend)
    return _loss_fractions(drop, sub, t, cfg.n_sub, dt)


def interval_loss_batched(
    blocks: list,
    weights: np.ndarray,
    capacities: np.ndarray,
    interval_seconds: float,
    cfg: LossConfig,
    seeds: list,
    backend: str = "numpy",
) -> list:
    """Batched :func:`interval_loss` over a controller sweep's routing epochs.

    Args:
      blocks: list of per-epoch ``(T_b, C)`` demand blocks (lengths may vary).
      weights: ``(B, C, E_d)`` per-epoch routing-weight matrices.
      capacities: ``(B, E_d)`` per-epoch directed capacities.
      seeds: per-epoch burst seeds (the controller uses ``cfg.seed + start``
        so comparisons stay paired across strategies).

    Burst expansion stays per-epoch (each epoch draws its own realization
    from its seed, bit-identical to the sequential controller); the queue
    scan runs as one epoch-batched call on the jax/pallas backends
    (:func:`repro.kernels.queueloss.ops.queue_loss_batched`), zero-padding
    short epochs — padded sub-steps only drain queues and never drop.
    Returns a list of per-epoch ``(T_b,)`` loss-fraction arrays.
    """
    b = len(blocks)
    if b == 0:
        return []
    cap = np.asarray(capacities, np.float64)
    dt = interval_seconds / cfg.n_sub
    subs, lens = [], []
    for block, seed in zip(blocks, seeds):
        block = np.asarray(block, np.float64)
        lens.append(block.shape[0])
        subs.append(expand(block, cfg.n_sub, cfg.burst, seed))
    ts_max = max(lens) * cfg.n_sub
    sub_b = np.zeros((b, ts_max, subs[0].shape[1]), np.float64)
    for i, s in enumerate(subs):
        sub_b[i, : s.shape[0]] = s
    buf_b = np.stack([link_buffer_gb(c, cfg.buffer_ms) for c in cap])
    from repro.kernels.queueloss import ops as qlops

    drop_b, _ = qlops.queue_loss_batched(sub_b, weights, cap, buf_b, dt,
                                         backend=backend)
    return [_loss_fractions(drop_b[i, : n * cfg.n_sub], s, n, cfg.n_sub, dt)
            for i, (s, n) in enumerate(zip(subs, lens))]


def interval_loss_fleet(
    blocks_fleet: list,
    weights_fleet: list,
    capacities_fleet: list,
    interval_seconds: float,
    cfg: LossConfig,
    seeds_fleet: list,
    backend: str = "numpy",
    slots_fleet: list | None = None,
) -> list:
    """Fleet-fused :func:`interval_loss_batched` over many fabrics' sweeps.

    Args:
      blocks_fleet: per-fabric lists of ``(T_b, C)`` demand blocks in each
        fabric's **native** commodity layout — burst expansion is
        deterministic per (seed, block shape), so expanding a padded block
        would draw different bursts than the sequential controller and break
        the paired-seed contract.
      weights_fleet: per-fabric ``(B_f, C_p, E_p)`` routing-weight stacks in
        the (possibly padded) bucket layout.
      capacities_fleet: per-fabric ``(B_f, E_p)`` capacities, same layout.
      seeds_fleet: per-fabric lists of per-block burst seeds (must match the
        sequential controller's ``cfg.seed + start`` for paired comparisons).
      slots_fleet: per-fabric commodity-slot embeddings
        (:func:`repro.core.fleet.commodity_slots`) into the bucket layout
        (whose width comes from ``weights_fleet``); ``None`` when the blocks
        already match the weights.

    Burst expansion stays per-block, per-seed, and native-layout
    (bit-identical to the sequential controller); the expanded sub-samples
    are then scattered into the bucket layout and the queue scan runs as one
    fabric-batched call (:func:`repro.kernels.queueloss.ops.queue_loss_fleet`)
    — a (F, B, TS, C_p) launch whose padded commodities carry zero demand
    against zero capacity and can never drop.  Returns per-fabric lists of
    ``(T_b,)`` loss fractions.
    """
    f = len(blocks_fleet)
    if f == 0:
        return []
    dt = interval_seconds / cfg.n_sub
    subs, lens = [], []
    for blocks, seeds in zip(blocks_fleet, seeds_fleet):
        row_subs, row_lens = [], []
        for block, seed in zip(blocks, seeds):
            block = np.asarray(block, np.float64)
            row_lens.append(block.shape[0])
            row_subs.append(expand(block, cfg.n_sub, cfg.burst, seed))
        subs.append(row_subs)
        lens.append(row_lens)
    b_max = max(len(row) for row in subs)
    ts_max = max((n for row in lens for n in row), default=1) * cfg.n_sub
    c = weights_fleet[0].shape[1]
    e = weights_fleet[0].shape[2]
    sub_b = np.zeros((f, b_max, max(ts_max, 1), c), np.float64)
    w_b = np.zeros((f, b_max, c, e), np.float64)
    cap_b = np.zeros((f, b_max, e), np.float64)
    buf_b = np.zeros((f, b_max, e), np.float64)
    for fi in range(f):
        slots = None if slots_fleet is None else slots_fleet[fi]
        for bi, s in enumerate(subs[fi]):
            if slots is None:
                sub_b[fi, bi, : s.shape[0]] = s
            else:  # embed the native-layout expansion into the bucket layout
                sub_b[fi, bi, : s.shape[0], :][:, slots] = s
        nb = len(subs[fi])
        w_b[fi, :nb] = np.asarray(weights_fleet[fi], np.float64)
        cap_b[fi, :nb] = np.asarray(capacities_fleet[fi], np.float64)
        buf_b[fi, :nb] = link_buffer_gb(cap_b[fi, :nb], cfg.buffer_ms)
    from repro.kernels.queueloss import ops as qlops

    drop_b, _ = qlops.queue_loss_fleet(sub_b, w_b, cap_b, buf_b, dt,
                                       backend=backend)
    return [[_loss_fractions(drop_b[fi, bi, : n * cfg.n_sub], s, n, cfg.n_sub,
                             dt)
             for bi, (s, n) in enumerate(zip(subs[fi], lens[fi]))]
            for fi in range(f)]
