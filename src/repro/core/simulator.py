"""Trace-driven link-utilization simulator (paper §5.2 methodology, §3 metrics).

Given a routing-weight matrix ``W (C, E_d)`` (from
:func:`repro.core.paths.routing_weight_matrix`) and directed capacities
``cap (E_d,)``, per-interval loads are one matmul:

    load[t, e] = Σ_c demand[t, c] · W[c, e]

Metrics per interval (paper §3 / §5.2):
  * MLU      — max_e load/C (links with zero capacity are excluded);
  * ALU      — mean_e load/C;
  * OLR      — fraction of links with utilization > 0.8 (overloaded);
  * stretch  — total load / total demand (≥ 1; 2-hop transit raises it).

Summaries report the p99.9 over intervals (paper footnote 6).  Backends:
``numpy`` (default), ``jax`` (jnp matmul), ``pallas`` (fused
``kernels/linkload`` kernel — loads never materialize in HBM).

When a :class:`repro.burst.LossConfig` is supplied, each interval also gets a
burst-level **loss fraction** from the sub-interval fluid-queue model
(:mod:`repro.burst`) — the paper's headline §3/§5 metric; the loss pipeline
reuses the metrics backend (``pallas`` selects the fused
``kernels/queueloss`` matmul+scan kernel).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs

__all__ = ["IntervalMetrics", "route_metrics", "route_metrics_batched",
           "route_metrics_fleet", "p999", "summarize"]


def _concat_loss(a, a_size: int, b, b_size: int):
    """Concatenate optional loss arrays; an empty side adopts the other's
    tracking state, and mixing tracked with untracked drops loss entirely."""
    if a is None and b is None:
        return None
    if a is None:
        return b if a_size == 0 else None
    if b is None:
        return a if b_size == 0 else None
    return np.concatenate([a, b])


@dataclasses.dataclass
class IntervalMetrics:
    mlu: np.ndarray  # (T,)
    alu: np.ndarray  # (T,)
    olr: np.ndarray  # (T,)
    stretch: np.ndarray  # (T,)
    loss: np.ndarray | None = None  # (T,) burst-level loss fraction, if tracked

    def concat(self, other: "IntervalMetrics") -> "IntervalMetrics":
        return IntervalMetrics(
            mlu=np.concatenate([self.mlu, other.mlu]),
            alu=np.concatenate([self.alu, other.alu]),
            olr=np.concatenate([self.olr, other.olr]),
            stretch=np.concatenate([self.stretch, other.stretch]),
            loss=_concat_loss(self.loss, self.mlu.size, other.loss, other.mlu.size),
        )

    @staticmethod
    def empty() -> "IntervalMetrics":
        z = np.zeros((0,))
        return IntervalMetrics(z, z, z, z)


def p999(x: np.ndarray) -> float:
    return float(np.percentile(x, 99.9)) if x.size else float("nan")


def summarize(m: IntervalMetrics) -> dict:
    out = {
        "p999_mlu": p999(m.mlu),
        "p999_alu": p999(m.alu),
        "p999_olr": p999(m.olr),
        "p999_stretch": p999(m.stretch),
        "mean_mlu": float(m.mlu.mean()) if m.mlu.size else float("nan"),
        "mean_alu": float(m.alu.mean()) if m.alu.size else float("nan"),
        "mean_stretch": float(m.stretch.mean()) if m.stretch.size else float("nan"),
    }
    if m.loss is not None:
        out["p999_loss"] = p999(m.loss)
        out["mean_loss"] = float(m.loss.mean()) if m.loss.size else float("nan")
    return out


def route_metrics(
    demand: np.ndarray,
    weights: np.ndarray,
    capacities: np.ndarray,
    overload_threshold: float = 0.8,
    backend: str = "numpy",
    loss_cfg=None,
    interval_seconds: float | None = None,
) -> IntervalMetrics:
    """Compute per-interval MLU/ALU/OLR/stretch for a (T, C) demand block.

    With ``loss_cfg`` (a :class:`repro.burst.LossConfig`) and
    ``interval_seconds``, also attaches the per-interval burst-level loss
    fraction computed by :func:`repro.burst.interval_loss` on ``backend``.
    """
    demand = np.asarray(demand, dtype=np.float64)
    cap = np.asarray(capacities, dtype=np.float64)
    # Dead links (capacity exactly 0 — masked out by a failure scenario or a
    # transition drain) carry no utilization: they are excluded from MLU and
    # from the ALU/OLR live-link averages on every backend (the batched/fleet
    # kernel wrappers already work on live-masked inv_cap).  Demand whose
    # weights still point at a dead link is NOT rerouted here — it counts in
    # stretch/total load as offered, and the burst-loss queue model drops it
    # (zero buffer drain), so failures surface as loss, never as inf/NaN MLU.
    # An all-dead capacity vector defines MLU/ALU/OLR = 0.
    live = cap > 1e-9
    if backend == "pallas":
        from repro.kernels.linkload import ops as llops

        with obs.span("score.linkload"):
            mlu, alu, olr, load_tot = llops.link_metrics(
                demand, weights, cap, overload_threshold)
        mlu, alu, olr, load_tot = (np.asarray(x) for x in (mlu, alu, olr, load_tot))
    elif backend == "jax":
        import jax.numpy as jnp

        load = jnp.asarray(demand) @ jnp.asarray(weights)  # (T, E) once
        if live.any():
            util = load[:, live] / jnp.asarray(cap[live])[None, :]
            mlu = np.asarray(util.max(axis=1))
            alu = np.asarray(util.mean(axis=1))
            olr = np.asarray((util > overload_threshold).mean(axis=1))
        else:
            mlu = alu = olr = np.zeros(demand.shape[0])
        load_tot = np.asarray(load.sum(axis=1))
    else:
        load = demand @ weights  # (T, E_d)
        if live.any():
            util = load[:, live] / cap[None, live]
            mlu = util.max(axis=1)
            alu = util.mean(axis=1)
            olr = (util > overload_threshold).mean(axis=1)
        else:
            mlu = alu = olr = np.zeros(demand.shape[0])
        load_tot = load.sum(axis=1)
    tot_dem = demand.sum(axis=1)
    stretch = np.where(tot_dem > 1e-12, load_tot / np.maximum(tot_dem, 1e-12), 1.0)
    loss = None
    if loss_cfg is not None:
        if interval_seconds is None:
            raise ValueError("loss tracking requires interval_seconds")
        from repro.burst import interval_loss

        loss = interval_loss(demand, weights, cap, interval_seconds, loss_cfg,
                             backend=backend)
    return IntervalMetrics(mlu=mlu, alu=alu, olr=olr, stretch=stretch, loss=loss)


def route_metrics_batched(
    blocks: list,
    weights: np.ndarray,
    capacities: np.ndarray,
    overload_threshold: float = 0.8,
    backend: str = "numpy",
    loss_cfg=None,
    loss_seeds: list | None = None,
    interval_seconds: float | None = None,
    pad_to: int | None = None,
) -> IntervalMetrics:
    """Single-pass scoring of an entire controller sweep.

    Instead of one :func:`route_metrics` call per routing epoch, the whole
    trace's per-epoch weight matrices are evaluated in one batched call —
    on the ``pallas`` backend this is a single launch of the epoch-batched
    ``kernels/linkload`` (and ``kernels/queueloss``) kernels, so loads and
    queue state stay in VMEM across the sweep.  Reconfiguration-transition
    drain stages (:mod:`repro.transition`) ride the same leading batch axis:
    a stage is just another block with its own residual capacities and
    re-solved weights.

    Args:
      blocks: list of per-epoch ``(T_b, C)`` demand blocks, in trace order
        (lengths may differ; short epochs are zero-padded internally).
      weights: ``(B, C, E_d)`` per-epoch routing-weight matrices.
      capacities: ``(B, E_d)`` per-epoch directed capacities.
      loss_cfg / loss_seeds / interval_seconds: with a
        :class:`repro.burst.LossConfig` and per-epoch seeds, also computes
        the burst-level loss fraction (seeds must match the sequential
        controller's ``cfg.seed + start`` so comparisons stay paired).
      pad_to: score at least this many elements, repeating the last one, so
        that calls with fewer blocks run the same compiled kernels; the
        repeats are dropped.

    Returns the concatenated :class:`IntervalMetrics` over all epochs, in
    epoch order — identical layout to the sequential controller's concat.
    """
    from repro.kernels.linkload import ops as llops

    b = len(blocks)
    if b == 0:
        return IntervalMetrics.empty()
    n_rep = max(b, pad_to or 0) - b
    if n_rep:
        blocks = list(blocks) + [blocks[-1]] * n_rep
        weights = np.concatenate([weights, np.repeat(weights[-1:], n_rep, 0)])
        capacities = np.concatenate([capacities,
                                     np.repeat(capacities[-1:], n_rep, 0)])
        if loss_seeds is not None:
            loss_seeds = list(loss_seeds) + [loss_seeds[-1]] * n_rep
    lens = [np.asarray(bl).shape[0] for bl in blocks]
    t_pad = max(lens)
    c = np.asarray(blocks[0]).shape[1]
    demand_b = np.zeros((len(blocks), t_pad, c), np.float64)
    for i, bl in enumerate(blocks):
        demand_b[i, : lens[i]] = np.asarray(bl, np.float64)
    kernel_backend = {"numpy": "numpy", "jax": "jnp", "pallas": "pallas"}[backend]
    mlu_b, alu_b, olr_b, tot_b = llops.link_metrics_batched(
        demand_b, weights, capacities, overload_threshold,
        backend=kernel_backend)
    dem_tot = demand_b.sum(axis=2)  # (B, T_pad)
    stretch_b = np.where(dem_tot > 1e-12,
                         tot_b / np.maximum(dem_tot, 1e-12), 1.0)
    loss_list = None
    if loss_cfg is not None:
        if interval_seconds is None or loss_seeds is None:
            raise ValueError("loss tracking requires interval_seconds and seeds")
        from repro.burst import interval_loss_batched

        loss_list = interval_loss_batched(
            blocks, weights, capacities, interval_seconds, loss_cfg,
            loss_seeds, backend=backend)
    trim = lambda arr: np.concatenate(
        [np.asarray(arr[i][: lens[i]], np.float64) for i in range(b)])
    return IntervalMetrics(
        mlu=trim(mlu_b), alu=trim(alu_b), olr=trim(olr_b), stretch=trim(stretch_b),
        loss=np.concatenate(loss_list[:b]) if loss_list is not None else None)


def route_metrics_fleet(
    blocks_fleet: list,
    weights_fleet: list,
    caps_fleet: list,
    overload_threshold: float = 0.8,
    backend: str = "numpy",
    loss_cfg=None,
    loss_seeds_fleet: list | None = None,
    interval_seconds: float | None = None,
    loss_blocks_fleet: list | None = None,
    loss_slots_fleet: list | None = None,
) -> list:
    """Single fused scoring pass over an entire fleet bucket.

    The fleet-scale analogue of :func:`route_metrics_batched`: every fabric's
    scoring blocks are stacked onto a new leading *fabric* axis — on the
    ``pallas`` backend one launch of the fabric-batched
    ``kernels/linkload`` (and ``kernels/queueloss``) kernels scores the whole
    bucket.  The fleet engine pads all fabrics to one commodity/edge layout;
    block-count and interval-count padding happens here (padded blocks carry
    zero demand against zero capacity and are trimmed before returning).

    Args:
      blocks_fleet: per-fabric lists of ``(T_b, C)`` demand blocks, in trace
        order (lengths may differ within and across fabrics).
      weights_fleet: per-fabric ``(B_f, C, E_d)`` routing-weight stacks.
      caps_fleet: per-fabric ``(B_f, E_d)`` directed capacities.
      loss_cfg / loss_seeds_fleet / interval_seconds: with a
        :class:`repro.burst.LossConfig` and per-fabric seed lists, also
        computes burst-level loss fractions (paired-seed contract as in
        :func:`route_metrics_batched`).
      loss_blocks_fleet / loss_slots_fleet: burst expansion is deterministic
        per (seed, block shape), so when ``blocks_fleet`` lives in a padded
        commodity layout the caller must provide the same blocks in each
        fabric's native layout plus their commodity-slot embeddings
        (:func:`repro.core.fleet.commodity_slots`) — losses then match the
        per-fabric controller bit-for-bit.

    Returns a list of per-fabric :class:`IntervalMetrics`, each identical in
    layout to the sequential controller's concatenated metrics.
    """
    from repro.kernels.linkload import ops as llops

    f = len(blocks_fleet)
    if f == 0:
        return []
    lens = [[np.asarray(b).shape[0] for b in blocks] for blocks in blocks_fleet]
    b_max = max(len(blocks) for blocks in blocks_fleet)
    t_pad = max((n for row in lens for n in row), default=1)
    c = np.asarray(weights_fleet[0]).shape[1]
    e = np.asarray(weights_fleet[0]).shape[2]
    demand_b = np.zeros((f, b_max, max(t_pad, 1), c), np.float64)
    weights_b = np.zeros((f, b_max, c, e), np.float64)
    caps_b = np.zeros((f, b_max, e), np.float64)
    for fi, blocks in enumerate(blocks_fleet):
        for bi, bl in enumerate(blocks):
            demand_b[fi, bi, : lens[fi][bi]] = np.asarray(bl, np.float64)
        nb = len(blocks)
        weights_b[fi, :nb] = np.asarray(weights_fleet[fi], np.float64)
        caps_b[fi, :nb] = np.asarray(caps_fleet[fi], np.float64)
    kernel_backend = {"numpy": "numpy", "jax": "jnp", "pallas": "pallas"}[backend]
    mlu_b, alu_b, olr_b, tot_b = llops.link_metrics_fleet(
        demand_b, weights_b, caps_b, overload_threshold,
        backend=kernel_backend)
    dem_tot = demand_b.sum(axis=3)  # (F, B, T_pad)
    stretch_b = np.where(dem_tot > 1e-12,
                         tot_b / np.maximum(dem_tot, 1e-12), 1.0)
    loss_fleet = None
    if loss_cfg is not None:
        if interval_seconds is None or loss_seeds_fleet is None:
            raise ValueError("loss tracking requires interval_seconds and seeds")
        from repro.burst import interval_loss_fleet

        loss_fleet = interval_loss_fleet(
            loss_blocks_fleet if loss_blocks_fleet is not None else blocks_fleet,
            weights_fleet, caps_fleet, interval_seconds,
            loss_cfg, loss_seeds_fleet, backend=backend,
            slots_fleet=loss_slots_fleet)
    out = []
    for fi, blocks in enumerate(blocks_fleet):
        trim = lambda arr: np.concatenate(
            [np.asarray(arr[fi][bi][: lens[fi][bi]], np.float64)
             for bi in range(len(blocks))]) if blocks else np.zeros((0,))
        out.append(IntervalMetrics(
            mlu=trim(mlu_b), alu=trim(alu_b), olr=trim(olr_b),
            stretch=trim(stretch_b),
            loss=(np.concatenate(loss_fleet[fi])
                  if loss_fleet is not None else None)))
    return out
