"""Traffic generator of the benchmark: fabrics and TM traces from parameters.

A copy of the synthetic-fleet generator (``repro.core.fleet.make_trace``),
kept here so that a change to the program cannot move the yardstick.  The
fabric's parameters come from the configuration file (one entry of its
``fabrics`` list); the trace is drawn from the traffic mix's
``trace_seed``, or from ``--seed`` where the mix gives none.

* :func:`make_trace` — a ``(T, C)`` float64 trace: gravity-model base TM,
  diurnal and weekly envelopes, saturating AR(1) noise, Pareto bursts, scaled
  so that the uniform topology sees ``target_uniform_mlu`` at the mean.
* :func:`expand` — the sub-interval burst expansion the loss model scores
  (the same draws as ``repro.burst.expander.expand``).
* :class:`Feed` — the stream a window replays: the warm-up window, then the
  served days in a cycle.
"""

from __future__ import annotations

import zlib

import numpy as np


def directed_edges(n_pods: int) -> np.ndarray:
    """``(V(V-1), 2)`` ordered pod pairs, lexicographic: the commodity and
    directed-edge enumeration."""
    return np.asarray([(i, j) for i in range(n_pods) for j in range(n_pods)
                       if i != j], np.int64)


def trunks(n_pods: int) -> np.ndarray:
    """``(V(V-1)/2, 2)`` unordered pod pairs ``i < j``, lexicographic."""
    return np.asarray([(i, j) for i in range(n_pods)
                       for j in range(i + 1, n_pods)], np.int64)


def trunk_of_edge(n_pods: int) -> np.ndarray:
    lut = {tuple(t): k for k, t in enumerate(trunks(n_pods).tolist())}
    return np.asarray([lut[(min(i, j), max(i, j))]
                       for i, j in directed_edges(n_pods).tolist()], np.int64)


def edge_capacities(fab: dict, n_trunk: np.ndarray) -> np.ndarray:
    """Directed capacities ``n_e * min(s_i, s_j)`` from trunk link counts."""
    speed = np.asarray(fab["speed"], np.float64)
    t = trunks(len(speed))
    per_trunk = np.asarray(n_trunk, np.float64) * np.minimum(speed[t[:, 0]],
                                                             speed[t[:, 1]])
    return per_trunk[trunk_of_edge(len(speed))]


def uniform_trunks(fab: dict) -> np.ndarray:
    """``min_i R_i / (V - 1)`` links on every pod pair (fractional)."""
    v = len(fab["radix"])
    return np.full(v * (v - 1) // 2, min(fab["radix"]) / (v - 1), np.float64)


def _stable_seed(name: str, seed: int, kind: str) -> int:
    return zlib.crc32(f"{name}/{seed}/{kind}".encode())


def make_trace(fab: dict, days: float, interval_minutes: float,
               seed: int) -> np.ndarray:
    """Generate a ``(T, C)`` trace for the fabric described by ``fab``."""
    rng = np.random.default_rng(_stable_seed(fab["name"], seed, "trace"))
    v = len(fab["radix"])
    c = v * (v - 1)
    ipd = int(round(24 * 60 / interval_minutes))
    t = int(round(days * ipd))
    noise = fab["noise"]

    mass = rng.lognormal(mean=0.0, sigma=fab["skew_sigma"], size=v)
    pairs = directed_edges(v)
    base = mass[pairs[:, 0]] * mass[pairs[:, 1]]
    base = base / base.mean()

    vol = max(0.0, (noise - 0.05) / 0.3)
    hours = np.arange(t) * (interval_minutes / 60.0)
    phase = rng.uniform(0, 2 * np.pi, size=c)
    amp_d = rng.uniform(0.1, 0.35, size=c)
    diurnal = 1.0 + amp_d[None, :] * np.sin(
        2 * np.pi * hours[:, None] / 24.0 + phase[None, :])
    amp_w = 0.15 * min(1.0, 2.0 * vol)
    weekly = 1.0 + amp_w * np.sin(
        2 * np.pi * hours[:, None] / (24.0 * 7) + phase[None, :] / 2)

    ar = np.empty((t, c))
    x = rng.normal(0, noise, size=c)
    rho = 0.9
    innov = rng.normal(0, noise, size=(t, c))
    for k in range(t):
        x = rho * x + np.sqrt(1 - rho**2) * innov[k]
        ar[k] = x
    clip_hi = noise * max(0.0, 4.0 * (vol - 0.35))
    ar = np.exp(np.clip(ar + noise, None, clip_hi) - clip_hi)

    demand = base[None, :] * diurnal * weekly * ar

    n_bursts = rng.binomial(t * c, fab["burst_rate"])
    if n_bursts > 0:
        bi = rng.integers(0, t, size=n_bursts)
        bj = rng.integers(0, c, size=n_bursts)
        mag = fab["burst_scale"] * (
            rng.pareto(fab["burst_shape"], size=n_bursts) + 1.0)
        dur = rng.integers(1, max(2, ipd // 8), size=n_bursts)
        for b in range(n_bursts):
            demand[bi[b]: bi[b] + dur[b], bj[b]] += mag[b] * base[bj[b]]

    cap = edge_capacities(fab, uniform_trunks(fab))
    mlu_now = float((demand.mean(axis=0) / cap).max())
    return demand * (fab["target_uniform_mlu"] / max(mlu_now, 1e-12))


def expand(block: np.ndarray, n_sub: int, burst: dict, seed: int) -> np.ndarray:
    """``(T, C)`` interval means to ``(T * n_sub, C)`` sub-steps with
    Bernoulli-placed, clipped Pareto bursts drawn from ``seed``."""
    sub = np.repeat(np.asarray(block, np.float64), n_sub, axis=0)
    if burst["rate"] == 0.0 or burst["scale"] == 0.0:
        return sub
    rng = np.random.default_rng(seed)
    hit = rng.random(sub.shape) < burst["rate"]
    mag = burst["scale"] * (rng.pareto(burst["shape"], size=sub.shape) + 1.0)
    return sub * (1.0 + hit * np.minimum(mag, burst["clip"]))


class Feed:
    """The TM stream of one fabric: ``warm`` warm-up intervals, then
    ``days`` served days replayed in a cycle (``row(t)`` for any ``t``)."""

    def __init__(self, fab: dict, cfg: dict, seed: int):
        self.ipd = int(round(24 * 60 / cfg["interval_minutes"]))
        self.warm = int(round(cfg["window_days"] * self.ipd))
        self.period = int(cfg["trace_days"]) * self.ipd
        self.demand = make_trace(fab, cfg["window_days"] + cfg["trace_days"],
                                 cfg["interval_minutes"], seed)

    def index(self, t: int) -> int:
        return t if t < self.warm else self.warm + (t - self.warm) % self.period

    def row(self, t: int) -> np.ndarray:
        return self.demand[self.index(t)]

    def rows(self, lo: int, hi: int) -> np.ndarray:
        return self.demand[[self.index(t) for t in range(lo, hi)]]
