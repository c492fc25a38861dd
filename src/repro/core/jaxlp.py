"""JAX-native LP solver for the routing stages (PDHG / Chambolle–Pock).

The Controller re-solves *routing* every 15 minutes (paper §4.6) — in a fleet
of hundreds of fabrics that is the production hot path, and a general-purpose
simplex in the loop is wasteful.  The routing stages with a fixed topology are
small structured LPs over the per-commodity path simplex:

  stage 1:  min u  s.t.  U(f)_{t,e} ≤ u            (U = capacity-normalized load)
  stage 2:  min r  s.t.  U(f) ≤ u*,  f_p δ/C_e ≤ r  ∀ e ∈ p
  stage 3:  min Σ_t Σ_p f_p d_{t,c(p)} len(p)  s.t.  U(f) ≤ u*, risk ≤ r*

All three are solved with an over-relaxed primal–dual hybrid gradient (PDHG)
iteration that is fully jit-compiled and **vmap-batchable** across routing
epochs (the plan/execute engine solves every routing-only epoch of a trace in
one call).  Three structural choices make the iteration fast on accelerators:

* **Pod-tensor operators.**  Path splits are carried as a dense ``(V, V, V)``
  tensor ``f3[i, j, k]`` (commodity ``i→j`` via transit ``k``; the ``k = j``
  slot is the direct path), so the load operator and its adjoint are two
  ``einsum`` contractions of ``O(V³·m)`` work — no gathers or scatters in the
  hot loop, and a leading batch axis vectorizes them trivially.
* **Matrix-game duals.**  The scalar stage objectives (``u`` = max
  utilization, ``r`` = max risk) are eliminated: ``min_f max_e`` is solved as
  a saddle point over the probability simplex of constraint rows.  This
  removes the badly-scaled ±1 coupling column of the scalar variable; the
  dual simplex projection uses a top-k threshold (the optimal dual support —
  the active constraints — is small) and the primal per-commodity projection
  uses Michelot's algorithm (a few masked-sum passes, no sorting).
* **Convergence-based early exit.**  The iteration runs in a
  ``lax.while_loop`` and stops when the objective has stalled (relative
  change ≤ ``tol`` over ``check_every`` iterations) *and* the iterate is
  feasible — under ``vmap`` a batch runs until every element has converged,
  converged elements being frozen by the batching rule.

Every core additionally takes an explicit ``valid`` slot mask (normally the
structural ``(V, V, V)`` mask of the solver's pod count).  The fleet engine
(:mod:`repro.core.fleet_engine`) exploits this to batch *different-sized*
fabrics through one solver: a fabric with ``v < V`` pods is zero-padded into
the ``V``-pod commodity layout and its per-element mask
(:meth:`JaxRoutingSolver.valid_for_pods`) excludes padded endpoints and
padded transit pods, so dead zero-capacity links can never masquerade as free
capacity.  :meth:`JaxRoutingSolver.solve_routing_fleet` runs the whole
fleet's routing epochs — flattened onto one leading batch axis, warm-started
from one anchor solve per fabric — in three vmapped jit calls, optionally
``shard_map``-sharded across devices (:func:`repro.parallel.sharding.fleet_mesh`).

Accuracy: PDHG is a first-order method; we run to a relative tolerance that
matches the binary-search tolerance of the paper's solver (≈1e-3), and tests
cross-check every stage against scipy/HiGHS.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import Fabric, directed_edge_index
from repro.core.paths import PathSet, build_paths

__all__ = ["JaxRoutingSolver", "RoutingWarmState", "project_simplex_rows"]


@dataclasses.dataclass
class RoutingWarmState:
    """Converged primal/dual iterates of one routing solve, reusable as the
    next epoch's starting point (:meth:`JaxRoutingSolver.solve_routing_warm`).

    The streaming controller's consecutive epochs share all but one window
    interval, so the previous optimum is near-feasible and near-optimal for
    the next solve — PDHG started there exits at (or near) its first
    convergence check instead of re-deriving the solution from the uniform
    cold start.  Stage-2/3 fields are ``None`` when the producing solve did
    not run that stage (no hedging / ``skip_stage3``); a ``None`` field falls
    back to the cold init for just that stage.  Arrays stay device-resident
    (jax arrays) so carrying the state adds no host round-trips.
    """

    f1: object  # (V, V, V) stage-1 primal splits
    y1: object  # (m, V, V) stage-1 dual
    f2: object | None = None  # stage-2 primal splits
    y2: object | None = None  # stage-2 MLU dual
    z2: object | None = None  # stage-2 risk dual
    y3: object | None = None  # stage-3 MLU dual


def project_simplex_rows(x: jax.Array) -> jax.Array:
    """Euclidean projection of each row of ``x`` onto the probability simplex."""
    n = x.shape[-1]
    u = jnp.sort(x, axis=-1)[..., ::-1]
    css = jnp.cumsum(u, axis=-1) - 1.0
    idx = jnp.arange(1, n + 1, dtype=x.dtype)
    cond = u - css / idx > 0
    # rho ≥ 1 always holds mathematically (the largest entry satisfies
    # u_max - (u_max - 1) = 1 > 0), but guard against NaN/degenerate inputs
    # so the division below can never be 0/0.
    rho = jnp.maximum(jnp.sum(cond, axis=-1), 1)
    theta = jnp.take_along_axis(css, (rho - 1)[..., None], axis=-1) / rho[..., None].astype(x.dtype)
    return jnp.maximum(x - theta, 0.0)


def _michelot_rows(x: jax.Array, valid: jax.Array, passes: int) -> jax.Array:
    """Masked per-row simplex projection via Michelot's algorithm.

    Entries where ``valid`` is False take no mass.  ``passes`` ≥ the number of
    valid entries per row guarantees exactness; each pass is a masked sum and
    a compare — no sorting, so it vectorizes well under vmap.
    """
    x = jnp.where(valid, x, 0.0)
    act0 = jnp.broadcast_to(valid, x.shape)

    def body(_, carry):
        act, _ = carry
        nact = act.sum(-1).astype(x.dtype)
        s = jnp.where(act, x, 0.0).sum(-1)
        theta = (s - 1.0) / jnp.maximum(nact, 1.0)
        return act & (x - theta[..., None] > 0), theta

    _, theta = jax.lax.fori_loop(0, passes, body,
                                 (act0, jnp.zeros(x.shape[:-1], x.dtype)))
    return jnp.where(valid, jnp.maximum(x - theta[..., None], 0.0), 0.0)


def _capped_simplex_rows(x: jax.Array, ub: jax.Array, valid: jax.Array,
                         iters: int = 24) -> jax.Array:
    """Masked per-row projection onto the capped simplex
    ``{f : Σf = 1, 0 ≤ f ≤ ub}`` by bisection on the threshold θ of
    ``f = clip(x - θ, 0, ub)`` (Σ is monotone in θ).  Rows whose caps sum to
    less than 1 saturate at ``ub`` (the nearest box point)."""
    x = jnp.where(valid, x, -1e18)
    ub = jnp.where(valid, ub, 0.0)
    target = jnp.minimum(1.0, jnp.where(valid, ub, 0.0).sum(-1))
    lo = jnp.where(valid, x - ub, jnp.inf).min(-1) - 1.0
    hi = jnp.where(valid, x, -jnp.inf).max(-1)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        s = jnp.clip(x - mid[..., None], 0.0, ub).sum(-1)
        return jnp.where(s > target, mid, lo), jnp.where(s > target, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    theta = 0.5 * (lo + hi)
    return jnp.where(valid, jnp.clip(x - theta[..., None], 0.0, ub), 0.0)


def _project_simplex_topk(x: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """Projection of flat ``x`` onto the simplex using only the top-``k``
    entries to locate the threshold — exact whenever the projection's support
    has ≤ k entries (the active constraint set of the routing duals is small).
    """
    flat = jnp.where(valid, x, -1e9).reshape(-1)
    k = min(k, flat.shape[0])
    top, _ = jax.lax.top_k(flat, k)
    css = jnp.cumsum(top) - 1.0
    idx = jnp.arange(1, k + 1, dtype=x.dtype)
    rho = jnp.maximum(jnp.sum(top - css / idx > 0), 1)
    theta = css[rho - 1] / rho.astype(x.dtype)
    out = jnp.maximum(flat - theta, 0.0).reshape(x.shape)
    out = jnp.where(valid, out, 0.0)
    # when more than k entries clear the top-k threshold the thresholded
    # point over-weighs; renormalizing keeps the iterate on the simplex, so
    # the duality-gap certificate (which evaluates the dual at this point)
    # stays a sound lower bound
    return out / jnp.maximum(out.sum(), 1e-30)


def _fetch(stage: int, *xs) -> list:
    """Device-to-host copies of ``xs``, the host waiting on the device for
    PDHG stage ``stage``: a ``jaxlp.wait`` span holds the copies and nothing
    else."""
    with obs.span("jaxlp.wait", stage=stage):
        return [np.asarray(x) for x in xs]


@dataclasses.dataclass(eq=False)  # identity hash: each instance owns a jit cache
class JaxRoutingSolver:
    """Per-(fabric, m) jitted PDHG routing solver.

    Call :meth:`solve_mlu`, :meth:`solve_risk`, :meth:`solve_stretch` with the
    (m, C) critical TMs and (E_d,) capacities; returns numpy results.  The
    ``*_batch`` variants take a leading batch axis (one element per routing
    epoch) and solve all epochs in a single vmapped, jitted call;
    :meth:`solve_routing_batch` runs the full stage 1 → [2] → 3 pipeline.

    ``check_every``/``tol`` drive the convergence-based early exit of the
    ``lax.while_loop``; ``max_iters`` bounds it.  ``last_iters`` records the
    iteration count of the most recent single-instance stage-1 solve.
    """

    fabric: Fabric
    m: int  # number of critical TMs (static for jit)
    max_iters: int = 3000
    check_every: int = 100
    tol: float = 5e-3
    restart_every: int = 150  # Halpern anchor-restart period
    # support cap for the dual simplex projection; None = consult the
    # autotune table (repro.kernels.autotune) for this (pods, m) shape
    dual_topk: int | None = None
    # fleet-path batch quantization: leading batch axes round up to these so
    # differently-sized run_fleet calls (predict sweeps vs test sweeps) reuse
    # one jit trace per stage instead of retracing the while_loop per shape.
    # Padding replays real elements, which converge with their originals —
    # compile time dwarfs the wasted iterations at any realistic scale.
    # None = consult the autotune table.
    fleet_batch_quantum: int | None = None
    fleet_anchor_quantum: int = 4
    # "f32" (default) or "bf16": mixed-precision inner loop — the einsum
    # matvecs of _util/_util_adj run with bf16 operands (f32 accumulation),
    # while projections, step sizes, and every convergence-check quantity
    # (the duality-gap certificate) stay f32.  Opt-in via
    # ControllerConfig.solver_precision; parity is test-bounded.
    precision: str = "f32"

    def __post_init__(self):
        assert self.precision in ("f32", "bf16"), self.precision
        self._mp = self.precision == "bf16"
        if self.dual_topk is None or self.fleet_batch_quantum is None:
            from repro.kernels.autotune import solver_knobs

            knobs = solver_knobs(self.fabric.n_pods, self.m)
            if self.dual_topk is None:
                self.dual_topk = knobs["dual_topk"]
            if self.fleet_batch_quantum is None:
                self.fleet_batch_quantum = knobs["fleet_batch_quantum"]
        v = self.fabric.n_pods
        paths: PathSet = build_paths(v)
        self.paths = paths
        self.V = v
        self.C = paths.n_commodities
        self.E = paths.n_directed
        self.K = paths.commodity_paths.shape[1]  # paths per commodity = V-1
        self.last_iters = -1
        self._fleet_fns_cache: dict = {}  # (mesh fingerprint) -> jitted stages

        # commodity c = (i, j) enumeration == directed-edge enumeration
        comm = directed_edge_index(v)  # (C, 2)
        self._comm_flat = comm[:, 0].astype(np.int64) * v + comm[:, 1]

        # path p ↔ dense slot (i, j, k): direct path stored at k = j
        slot = np.empty(paths.n_paths, dtype=np.int64)
        for c in range(self.C):
            i, j = int(comm[c, 0]), int(comm[c, 1])
            ps = paths.commodity_paths[c]
            slot[ps[0]] = (i * v + j) * v + j  # direct
            ks = [k for k in range(v) if k != i and k != j]
            for s_idx, k in enumerate(ks):
                slot[ps[1 + s_idx]] = (i * v + j) * v + k
        self._path_slot = jnp.asarray(slot)

        ii, jj, kk = np.meshgrid(np.arange(v), np.arange(v), np.arange(v),
                                 indexing="ij")
        self.valid = jnp.asarray((ii != jj) & (kk != ii))  # usable f3 slots
        self.notdiag = jnp.asarray(ii[:, :, 0] != jj[:, :, 0])  # (V, V) edges
        self.mask_kj = jnp.asarray(1.0 - np.eye(v), np.float32)  # [k != j]
        # path length per slot: 1 for the direct slot (k = j), else 2
        self._len3 = jnp.asarray(np.where(kk == jj, 1.0, 2.0), jnp.float32)

    # ---- dense conversions ---------------------------------------------------

    def _dense_tms(self, tms: np.ndarray) -> jnp.ndarray:
        """(m, C) commodity TMs → (m, V, V) dense pod matrices."""
        tms = np.asarray(tms, np.float32)
        out = np.zeros((tms.shape[0], self.V * self.V), np.float32)
        out[:, self._comm_flat] = tms
        return jnp.asarray(out.reshape(tms.shape[0], self.V, self.V))

    def _dense_inv_cap(self, capacities: np.ndarray) -> jnp.ndarray:
        """(E,) directed capacities → (V, V) dense inverse capacities."""
        cap = np.asarray(capacities, np.float64)
        ic = np.where(cap > 1e-9, 1.0 / np.maximum(cap, 1e-9), 0.0)
        out = np.zeros((self.V * self.V,), np.float32)
        out[self._comm_flat] = ic
        return jnp.asarray(out.reshape(self.V, self.V))

    def _flat_f(self, f3: np.ndarray) -> np.ndarray:
        """(..., V, V, V) splits → (..., P) in the PathSet layout."""
        f3 = np.asarray(f3, np.float64)
        flat = f3.reshape(f3.shape[:-3] + (-1,))
        return flat[..., np.asarray(self._path_slot)]

    # ---- linear operators on the pod tensor ---------------------------------

    # DEFAULT matmul precision on a TPU runs f32 operands as one bf16 pass;
    # the f32 operators ask for full f32 explicitly so the contract holds on
    # every backend (the CPU computes f32 either way).
    _F32 = jax.lax.Precision.HIGHEST

    def _util_f32(self, f3, d3, ic):
        """U[t, a, b] = capacity-normalized load of edge (a, b) under TM t —
        always in f32 (the certificate / reported-objective path)."""
        load1 = jnp.einsum("mij,ijk->mik", d3, f3,  # first hops (+ direct)
                           precision=self._F32)
        load2 = jnp.einsum("mij,ijk->mkj", d3, f3 * self.mask_kj[None],
                           precision=self._F32)
        return (load1 + load2) * ic[None]

    def _util_adj_f32(self, y, d3, ic):
        """Adjoint: y (m, V, V) → gradient on f3 (V, V, V) — always f32."""
        yn = y * ic[None]
        g1 = jnp.einsum("mij,mik->ijk", d3, yn, precision=self._F32)
        g2 = jnp.einsum("mij,mkj->ijk", d3, yn,
                        precision=self._F32) * self.mask_kj[None]
        return g1 + g2

    def _util(self, f3, d3, ic):
        """Hot-loop load operator: bf16 operands with f32 accumulation when
        ``precision == "bf16"`` (first-order steps tolerate rounded
        directions), the exact f32 path otherwise."""
        if not self._mp:
            return self._util_f32(f3, d3, ic)
        bf = jnp.bfloat16
        fk = (f3 * self.mask_kj[None]).astype(bf)
        d3c, f3c = d3.astype(bf), f3.astype(bf)
        load1 = jnp.einsum("mij,ijk->mik", d3c, f3c,
                           preferred_element_type=jnp.float32)
        load2 = jnp.einsum("mij,ijk->mkj", d3c, fk,
                           preferred_element_type=jnp.float32)
        return (load1 + load2) * ic[None]

    def _util_adj(self, y, d3, ic):
        """Hot-loop adjoint (see :meth:`_util` for the precision contract)."""
        if not self._mp:
            return self._util_adj_f32(y, d3, ic)
        bf = jnp.bfloat16
        ync = (y * ic[None]).astype(bf)
        d3c = d3.astype(bf)
        g1 = jnp.einsum("mij,mik->ijk", d3c, ync,
                        preferred_element_type=jnp.float32)
        g2 = jnp.einsum("mij,mkj->ijk", d3c, ync,
                        preferred_element_type=jnp.float32) * self.mask_kj[None]
        return g1 + g2

    def _opnorm(self, d3, ic, valid, iters: int = 30):
        """Power iteration for ‖U‖ (as an operator on f3) — kept f32 even in
        mixed-precision mode (the step sizes it sets gate convergence)."""

        def body(_, vv):
            v2 = self._util_adj_f32(self._util_f32(vv, d3, ic), d3, ic)
            return v2 / (jnp.linalg.norm(v2) + 1e-30)

        v0 = jnp.where(valid, 1.0, 0.0).astype(d3.dtype)
        vv = jax.lax.fori_loop(0, iters, body, v0 / jnp.linalg.norm(v0))
        return jnp.linalg.norm(self._util_f32(vv, d3, ic))

    def _proj_f(self, f3, valid):
        return _michelot_rows(f3, valid, self.V)

    def _dual_min(self, coeff, valid):
        """Σ over commodities of ``min_k coeff[i, j, k]`` (valid slots only) —
        the exact minimum of a linear functional over the product of
        per-commodity simplices, i.e. the Lagrangian dual's inner problem."""
        per_row = jnp.where(valid, coeff, jnp.inf).min(axis=-1)
        return jnp.where(jnp.isfinite(per_row), per_row, 0.0).sum()

    def _hop_inv_caps(self, ic):
        """Per-slot inverse capacities of the two hops of each path."""
        v = self.V
        ic0 = jnp.broadcast_to(ic[:, None, :], (v, v, v))  # hop 1: edge (i, k)
        # hop 2: edge (k, j) — ic1[i, j, k] = ic[k, j]; zero on the direct
        # slot (single hop)
        ic1 = jnp.broadcast_to(ic.T[None], (v, v, v)) * self.mask_kj[None]
        return ic0, ic1

    # ---- stage 1: min u  ≡  min_f max_{t,e} U(f) (matrix game) --------------

    def _halpern(self, halves, anchors, k):
        """Reflected-Halpern update: blend the reflected PDHG step with the
        anchor at weight 1/(k+2); restart the anchor every ``restart_every``
        iterations.  Cuts the iteration count 2–4× on hard (near-uniform TM)
        instances versus plain over-relaxation."""
        lam = (k + 1.0) / (k + 2.0)
        k = k + 1.0
        rs = (k % self.restart_every) == 0
        out, new_anchors = [], []
        for (w, w_h), wa in zip(halves, anchors):
            w_new = lam * (2.0 * w_h - w) + (1.0 - lam) * wa
            out.append(w_new)
            new_anchors.append(jnp.where(rs, w_new, wa))
        return out, new_anchors, jnp.where(rs, 0.0, k)

    def _f_uniform(self, valid, dtype=jnp.float32):
        n_slots = jnp.maximum(valid.sum(-1, keepdims=True), 1).astype(dtype)
        return jnp.where(valid, 1.0, 0.0).astype(dtype) / n_slots

    def _mlu_inits(self, d3, ic, valid):
        """Cold-start point: uniform splits, dual softmax-concentrated near
        the binding constraints."""
        notdiag = valid.any(-1)
        f0 = self._f_uniform(valid, d3.dtype)
        u0 = self._util(f0, d3, ic)
        y0 = jax.nn.softmax(
            jnp.where(notdiag[None], u0, -jnp.inf).reshape(-1)
            / (0.02 * jnp.maximum(u0.max(), 1e-12))).reshape(u0.shape)
        return f0, y0

    def _mlu_core(self, d3, ic, valid, f0, y0):
        notdiag = valid.any(-1)
        norm = self._opnorm(d3, ic, valid)
        tau = 0.99 / jnp.maximum(norm, 1e-12)
        sig = tau

        def cond(s):
            # state: (f, y, fa, ya, k, it, done, last, gap)
            return jnp.logical_and(s[5] < self.max_iters,
                                   jnp.logical_not(s[6]))

        def body(s):
            f, y, fa, ya, k, it, done, last, gap = s
            g = self._util_adj(y, d3, ic)
            f_h = self._proj_f(f - tau * g, valid)
            fb = 2.0 * f_h - f
            y_h = _project_simplex_topk(y + sig * self._util(fb, d3, ic),
                                        notdiag[None], self.dual_topk)
            (f, y), (fa, ya), k = self._halpern(
                [(f, f_h), (y, y_h)], [fa, ya], k)
            it = it + 1

            def check(op):
                # exact duality gap of the matrix game: primal = max util of
                # f; dual lower bound = min_f' <y, U f'> (closed form).
                # Certificate quantities are always f32, even in bf16 mode.
                obj = self._util_f32(f, d3, ic).max()
                lb = self._dual_min(self._util_adj_f32(y, d3, ic), valid)
                gap_ok = obj - lb <= self.tol * jnp.maximum(obj, 1e-6)
                rel = (obj - lb) / jnp.maximum(obj, 1e-6)
                return gap_ok, obj, rel

            done, last, gap = jax.lax.cond(
                it % self.check_every == 0, check,
                lambda op: (jnp.asarray(False),) + op, (last, gap))
            return f, y, fa, ya, k, it, done, last, gap

        big = jnp.asarray(jnp.inf, d3.dtype)
        f, y, fa, ya, k, it, done, last, gap = jax.lax.while_loop(
            cond, body, (f0, y0, f0, y0, jnp.asarray(0.0, d3.dtype),
                         jnp.int32(0), jnp.asarray(False), big, big))
        return f, self._util_f32(f, d3, ic).max(), it, y, gap

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_mlu(self, d3, ic, valid):
        return self._mlu_core(d3, ic, valid, *self._mlu_inits(d3, ic, valid))

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_mlu_batch(self, d3, ic, valid):
        return jax.vmap(
            lambda d, c, v: self._mlu_core(
                d, c, v, *self._mlu_inits(d, c, v)))(d3, ic, valid)

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_mlu_batch_warm(self, d3, ic, valid, f0, y0):
        return jax.vmap(self._mlu_core)(d3, ic, valid, f0, y0)

    def _tile_valid(self, b: int) -> jnp.ndarray:
        return jnp.broadcast_to(self.valid, (b,) + self.valid.shape)

    def solve_mlu(self, tms: np.ndarray, capacities: np.ndarray):
        f3, u, it, _, _ = self._solve_mlu(self._dense_tms(tms),
                                          self._dense_inv_cap(capacities),
                                          self.valid)
        self.last_iters = int(it)
        return self._flat_f(f3), float(u)

    def solve_mlu_batch(self, tms: np.ndarray, capacities: np.ndarray):
        """Batched stage 1: tms (B, m, C), capacities (B, E) → (f (B, P), u (B,))."""
        d3 = jnp.stack([self._dense_tms(t) for t in tms])
        ic = jnp.stack([self._dense_inv_cap(c) for c in capacities])
        f3, u, _, _, _ = self._solve_mlu_batch(d3, ic,
                                               self._tile_valid(d3.shape[0]))
        return self._flat_f(np.asarray(f3)), np.asarray(u, np.float64)

    # ---- stage 2: min r  ≡  min_f max(δ f / C) s.t. U(f) ≤ u* ---------------

    def _zvalid(self, valid):
        zv = valid[..., None] & jnp.asarray([True, True])
        return zv & jnp.concatenate(
            [jnp.ones_like(zv[..., :1]),
             jnp.broadcast_to((self.mask_kj > 0)[None, :, :, None],
                              zv[..., 1:].shape)], axis=-1)

    def _risk_inits(self, d3, valid):
        f0 = self._f_uniform(valid, d3.dtype)
        y0 = jnp.zeros((self.m, self.V, self.V), d3.dtype)
        z0 = self._zvalid(valid).astype(d3.dtype)
        z0 = z0 / jnp.maximum(z0.sum(), 1.0)
        return f0, y0, z0

    def _risk_core(self, d3, ic, valid, u_star, delta, f0, y0, z0):
        norm = self._opnorm(d3, ic, valid)
        ic0, ic1 = self._hop_inv_caps(ic)
        rnorm = delta * ic.max() * jnp.sqrt(2.0)
        tau = 0.99 / jnp.maximum(norm + rnorm, 1e-12)
        sig = tau
        zvalid = self._zvalid(valid)

        def risk_of(f3):
            return jnp.stack([delta * f3 * ic0, delta * f3 * ic1], axis=-1)

        def cond(s):
            # state: (f, y, z, fa, ya, za, k, it, done, last, gap)
            return jnp.logical_and(s[7] < self.max_iters,
                                   jnp.logical_not(s[8]))

        def body(s):
            f, y, z, fa, ya, za, k, it, done, last, gap = s
            gf = (self._util_adj(y, d3, ic)
                  + delta * (z[..., 0] * ic0 + z[..., 1] * ic1))
            f_h = self._proj_f(f - tau * gf, valid)
            fb = 2.0 * f_h - f
            y_h = jnp.maximum(y + sig * (self._util(fb, d3, ic) - u_star), 0.0)
            z_h = _project_simplex_topk(z + sig * risk_of(fb), zvalid,
                                        self.dual_topk)
            (f, y, z), (fa, ya, za), k = self._halpern(
                [(f, f_h), (y, y_h), (z, z_h)], [fa, ya, za], k)
            it = it + 1

            def check(op):
                # Lagrangian dual lower bound: d(y, z) = -u*·Σy + Σ_c min_k
                # [Uᵀy + δ(z·ic)].  The bound certifies fast exits when tight;
                # the risk objective is often minuscule (δ/C units), where the
                # last-iterate bound oscillates — an objective-stall test at a
                # 10·tol relative threshold covers that regime.
                last = op[0]
                obj = risk_of(f).max()
                u_chk = self._util_f32(f, d3, ic).max()
                coeff = (self._util_adj_f32(y, d3, ic)
                         + delta * (z[..., 0] * ic0 + z[..., 1] * ic1))
                lb = self._dual_min(coeff, valid) - u_star * y.sum()
                gap_ok = obj - lb <= self.tol * jnp.maximum(obj, 1e-9)
                stall = jnp.abs(obj - last) <= 10.0 * self.tol * jnp.maximum(
                    obj, 1e-9)
                feas = u_chk <= u_star * (1.0 + 2.0 * self.tol) + 1e-9
                rel = (obj - lb) / jnp.maximum(obj, 1e-9)
                return (jnp.logical_and(jnp.logical_or(gap_ok, stall), feas),
                        obj, rel)

            done, last, gap = jax.lax.cond(
                it % self.check_every == 0, check,
                lambda op: (jnp.asarray(False),) + op, (last, gap))
            return f, y, z, fa, ya, za, k, it, done, last, gap

        big = jnp.asarray(jnp.inf, d3.dtype)
        state = (f0, y0, z0, f0, y0, z0, jnp.asarray(0.0, d3.dtype),
                 jnp.int32(0), jnp.asarray(False), big, big)
        out = jax.lax.while_loop(cond, body, state)
        f, y, z = out[:3]
        it, gap = out[7], out[10]
        return (f, risk_of(f).max(), self._util_f32(f, d3, ic).max(),
                y, z, it, gap)

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_risk(self, d3, ic, valid, u_star, delta):
        return self._risk_core(d3, ic, valid, u_star, delta,
                               *self._risk_inits(d3, valid))

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_risk_batch(self, d3, ic, valid, u_star, delta):
        return jax.vmap(lambda d, c, v, u, dl: self._risk_core(
            d, c, v, u, dl, *self._risk_inits(d, v)))(d3, ic, valid, u_star, delta)

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_risk_batch_warm(self, d3, ic, valid, u_star, delta, f0, y0, z0):
        return jax.vmap(self._risk_core)(d3, ic, valid, u_star, delta, f0, y0, z0)

    def solve_risk(self, tms, capacities, u_star, delta):
        f3, r, u = self._solve_risk(self._dense_tms(tms),
                                    self._dense_inv_cap(capacities),
                                    self.valid,
                                    jnp.float32(u_star),
                                    jnp.float32(delta))[:3]
        return self._flat_f(f3), float(r), float(u)

    # ---- stage 3: min stretch s.t. U(f) ≤ u*, risk ≤ r* ---------------------

    def _stretch_core(self, d3, ic, valid, u_star, r_star, delta, f_init, y0):
        """min <cost, f> over the *capped* simplex — the risk budget
        ``δ·f·ic ≤ r*`` is a per-slot upper bound ``f ≤ r*/(δ·max ic)``, so it
        is enforced exactly by projection (no slow risk duals); only the MLU
        budget keeps a Lagrange dual ``y``."""
        norm = self._opnorm(d3, ic, valid)
        ic0, ic1 = self._hop_inv_caps(ic)
        tau = 0.99 / jnp.maximum(norm, 1e-12)
        sig = tau
        dsum = d3.sum(axis=0)  # (V, V)
        cost = jnp.where(valid, dsum[:, :, None] * self._len3, 0.0)
        cost = cost / (jnp.abs(cost).max() + 1e-30)  # scale-free objective
        ub = r_star / jnp.maximum(delta * jnp.maximum(ic0, ic1), 1e-30)
        ub = jnp.minimum(ub, 1.0)  # simplex rows never exceed 1 anyway
        f0 = _capped_simplex_rows(f_init, ub, valid)  # risk-feasible start

        def cond(s):
            # state: (f, y, fa, ya, k, it, done, last, gap)
            return jnp.logical_and(s[5] < self.max_iters,
                                   jnp.logical_not(s[6]))

        def body(s):
            f, y, fa, ya, k, it, done, last, gap = s
            gf = cost + self._util_adj(y, d3, ic)
            f_h = _capped_simplex_rows(f - tau * gf, ub, valid)
            fb = 2.0 * f_h - f
            y_h = jnp.maximum(y + sig * (self._util(fb, d3, ic) - u_star), 0.0)
            (f, y), (fa, ya), k = self._halpern([(f, f_h), (y, y_h)],
                                                [fa, ya], k)
            it = it + 1

            def check(op):
                # dual lower bound: -u*·Σy + Σ_c min_k [cost + Uᵀy] (the
                # uncapped min is a valid, slightly loose bound); objective
                # stall covers the oscillating-bound regime.  Risk is exact
                # by construction; only the MLU budget needs checking.
                last = op[0]
                obj = (cost * f).sum()
                u_chk = self._util_f32(f, d3, ic).max()
                coeff = cost + self._util_adj_f32(y, d3, ic)
                lb = self._dual_min(coeff, valid) - u_star * y.sum()
                gap_ok = obj - lb <= self.tol * jnp.maximum(jnp.abs(obj), 1e-9)
                stall = jnp.abs(obj - last) <= 10.0 * self.tol * jnp.maximum(
                    jnp.abs(obj), 1e-9)
                feas = u_chk <= u_star * (1.0 + 2.0 * self.tol) + 1e-9
                rel = (obj - lb) / jnp.maximum(jnp.abs(obj), 1e-9)
                return (jnp.logical_and(jnp.logical_or(gap_ok, stall), feas),
                        obj, rel)

            done, last, gap = jax.lax.cond(
                it % self.check_every == 0, check,
                lambda op: (jnp.asarray(False),) + op, (last, gap))
            return f, y, fa, ya, k, it, done, last, gap

        big = jnp.asarray(jnp.inf, d3.dtype)
        state = (f0, y0, f0, y0, jnp.asarray(0.0, d3.dtype),
                 jnp.int32(0), jnp.asarray(False), big, big)
        out = jax.lax.while_loop(cond, body, state)
        return out[0], out[1], out[5], out[8]

    def _stretch_inits(self, d3):
        return (jnp.zeros((self.m, self.V, self.V), d3.dtype),)

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_stretch(self, d3, ic, valid, u_star, r_star, delta, f_init):
        return self._stretch_core(d3, ic, valid, u_star, r_star, delta, f_init,
                                  *self._stretch_inits(d3))

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_stretch_batch(self, d3, ic, valid, u_star, r_star, delta,
                             f_init):
        return jax.vmap(lambda d, c, v, u, r, dl, f: self._stretch_core(
            d, c, v, u, r, dl, f, *self._stretch_inits(d)))(
                d3, ic, valid, u_star, r_star, delta, f_init)

    @functools.partial(jax.jit, static_argnums=0)
    def _solve_stretch_batch_warm(self, d3, ic, valid, u_star, r_star, delta,
                                  f_init, y0):
        return jax.vmap(self._stretch_core)(d3, ic, valid, u_star, r_star,
                                            delta, f_init, y0)

    def solve_stretch(self, tms, capacities, u_star, r_star, delta):
        d3 = self._dense_tms(tms)
        ic = self._dense_inv_cap(capacities)
        r = jnp.float32(r_star if r_star is not None else 1e9)
        dl = jnp.float32(delta if (r_star is not None and delta) else 0.0)
        f3 = self._solve_stretch(d3, ic, self.valid, jnp.float32(u_star),
                                 r, dl, self._f_uniform(self.valid))[0]
        return self._flat_f(f3)

    # ---- full routing pipeline, batched over epochs -------------------------

    def solve_routing_batch(self, tms: np.ndarray, capacities: np.ndarray,
                            hedging: bool, deltas: np.ndarray | None = None,
                            skip_stage3: bool = False):
        """Stages 1 → [2] → 3 for a batch of routing epochs in three vmapped
        jit calls, warm-started from a single **anchor** solve.

        The batch's middle epoch is solved cold first; its primal splits *and*
        dual iterates seed every element (controller epochs are sliding-window
        neighbours, so the anchor is near-optimal for most of the batch and
        the warm elements exit at their first convergence check).

        Args:
          tms: (B, m, C) critical TMs, zero-padded to the static ``m``.
          capacities: (B, E) realized directed capacities per epoch.
          hedging: run stage 2 (elements with ``deltas == 0`` keep stage 1's f).
          deltas: (B,) burst sizes (ignored unless ``hedging``).
          skip_stage3: skip the stretch-minimization stage.

        Returns dict with ``f`` (B, P), ``u_star`` (B,), ``r_star`` (B,) or
        None, and ``stats`` — per-epoch PDHG telemetry per stage (iteration
        counts, final certified relative gaps, Halpern restart counts; stage 2
        carries an ``active`` mask for the elements that actually hedge).
        The telemetry is always part of the jitted programs' outputs, so
        enabling/disabling tracing cannot retrace or perturb the solve.
        """
        b = tms.shape[0]
        d3 = jnp.stack([self._dense_tms(t) for t in tms])
        ic = jnp.stack([self._dense_inv_cap(c) for c in capacities])
        a = b // 2  # anchor epoch
        valid_b = self._tile_valid(b)
        anchor_s = 0.0

        def tile(x):
            return jnp.broadcast_to(x[None], (b,) + x.shape)

        with obs.timed("jaxlp.anchor", stage="mlu") as t:
            f_a, _, _, y_a, _ = jax.block_until_ready(
                self._solve_mlu(d3[a], ic[a], self.valid))
        anchor_s += t.seconds
        with obs.span("jaxlp.stage1", b=b):
            f3, u, it1, _, gap1 = self._solve_mlu_batch_warm(
                d3, ic, valid_b, tile(f_a), tile(y_a))
        u = jnp.asarray(u)
        u_budget = u * 1.005 + 1e-9
        stats = {"stage1": self._stage_stats(it1, gap1)}
        r_star = None
        if hedging:
            dl = jnp.asarray(np.asarray(deltas, np.float32))
            with obs.timed("jaxlp.anchor", stage="risk") as t:
                f2_a, _, _, y2_a, z2_a, _, _ = jax.block_until_ready(
                    self._solve_risk(d3[a], ic[a], self.valid,
                                     u_budget[a], dl[a]))
            anchor_s += t.seconds
            with obs.span("jaxlp.stage2", b=b):
                f3r, r, _, _, _, it2, gap2 = self._solve_risk_batch_warm(
                    d3, ic, valid_b, u_budget, dl,
                    tile(f2_a), tile(y2_a), tile(z2_a))
            use = (dl > 0)[:, None, None, None]
            f3 = jnp.where(use, f3r, f3)
            r_star = jnp.where(dl > 0, jnp.asarray(r), np.inf)
            stats["stage2"] = self._stage_stats(it2, gap2,
                                                active=np.asarray(dl > 0))
        if not skip_stage3:
            if r_star is None:
                r_in = jnp.full((b,), 1e9, jnp.float32)
                dl_in = jnp.zeros((b,), jnp.float32)
            else:
                r_in = jnp.where(jnp.isfinite(r_star),
                                 r_star * 1.005 + 1e-12, 1e9).astype(jnp.float32)
                dl_in = jnp.where(jnp.isfinite(r_star),
                                  jnp.asarray(np.asarray(deltas, np.float32)), 0.0)
            f3 = jnp.asarray(f3)
            with obs.timed("jaxlp.anchor", stage="stretch") as t:
                _, y3_a, _, _ = jax.block_until_ready(self._solve_stretch(
                    d3[a], ic[a], self.valid, u_budget[a], r_in[a],
                    dl_in[a], f3[a]))
            anchor_s += t.seconds
            with obs.span("jaxlp.stage3", b=b):
                f3, _, it3, gap3 = self._solve_stretch_batch_warm(
                    d3, ic, valid_b, u_budget, r_in, dl_in, f3, tile(y3_a))
            stats["stage3"] = self._stage_stats(it3, gap3)
        f = self._flat_f(np.asarray(f3))
        out_r = None
        if r_star is not None:
            rr = np.asarray(r_star, np.float64)
            out_r = np.where(np.isfinite(rr), rr, np.nan)
        stats["anchor_seconds"] = anchor_s
        return {"f": f, "u_star": np.asarray(u, np.float64), "r_star": out_r,
                "stats": stats}

    def _stage_stats(self, it, gap, active=None) -> dict:
        """Host-side per-element telemetry for one batched stage.  Restarts
        are implied by the deterministic Halpern schedule (one every
        ``restart_every`` iterations), so no extra while-loop state."""
        iters = np.asarray(it, np.int64).reshape(-1)
        out = {"iters": iters,
               "gap": np.asarray(gap, np.float64).reshape(-1),
               "restarts": iters // max(self.restart_every, 1)}
        if active is not None:
            out["active"] = np.asarray(active, bool).reshape(-1)
        return out

    # ---- single-epoch streaming solve, warm-started across epochs -----------

    def solve_routing_warm(self, tms: np.ndarray, capacities: np.ndarray,
                           hedging: bool, delta: float = 0.0,
                           skip_stage3: bool = False,
                           anchor_state: RoutingWarmState | None = None):
        """Stages 1 → [2] → 3 for ONE routing epoch, warm-started from the
        previous epoch's converged iterates.

        This is the streaming-controller counterpart of
        :meth:`solve_routing_batch`: instead of a batch anchored on a cold
        middle-epoch solve, each epoch seeds every stage's primal *and* dual
        from ``anchor_state`` (the state returned by the previous call).
        Convergence is unchanged — the duality-gap certificate / feasibility
        checks gate the exit exactly as in the cold path, so the result
        matches a cold solve to solver tolerance (test-enforced); only the
        iteration count drops.

        Reuses the ``*_batch`` jitted programs at ``B = 1``, so a process that
        already ran the batched engine pays no extra compiles.

        Args:
          tms: (m, C) critical TMs, zero-padded to the static ``m``.
          capacities: (E,) realized directed capacities.
          hedging: run stage 2 when ``delta > 0``.
          delta: burst size (ignored unless ``hedging``).
          skip_stage3: skip the stretch-minimization stage.
          anchor_state: previous epoch's :class:`RoutingWarmState`, or None
            for a cold start (first epoch / topology change invalidating the
            carried iterates).

        Returns ``(out, state)``: ``out`` has ``f`` (P,), ``u_star``,
        ``r_star`` (None unless hedged), and ``stats`` (raw per-stage
        telemetry in the :meth:`solve_routing_batch` schema, batch length 1);
        ``state`` seeds the next call.
        """
        with obs.span("jaxlp.warm_inputs"):
            d3 = self._dense_tms(tms)[None]
            ic = self._dense_inv_cap(capacities)[None]
            valid_b = self._tile_valid(1)

        def one(x):
            return jnp.asarray(x)[None]

        with obs.span("jaxlp.warm_stage1"):
            if anchor_state is None:
                f3, u, it1, y1, gap1 = self._solve_mlu_batch(d3, ic, valid_b)
            else:
                f3, u, it1, y1, gap1 = self._solve_mlu_batch_warm(
                    d3, ic, valid_b, one(anchor_state.f1), one(anchor_state.y1))
        state = RoutingWarmState(f1=f3[0], y1=y1[0])
        u_budget = jnp.asarray(u) * 1.005 + 1e-9
        stats = {"stage1": self._stage_stats(*_fetch(1, it1, gap1)),
                 "anchor_seconds": 0.0}
        r_star = None
        run2 = hedging and delta > 0
        if run2:
            dl = jnp.asarray([delta], jnp.float32)
            with obs.span("jaxlp.warm_stage2"):
                if anchor_state is None or anchor_state.f2 is None:
                    f3r, r, _, y2, z2, it2, gap2 = self._solve_risk_batch(
                        d3, ic, valid_b, u_budget, dl)
                else:
                    f3r, r, _, y2, z2, it2, gap2 = self._solve_risk_batch_warm(
                        d3, ic, valid_b, u_budget, dl,
                        one(anchor_state.f2), one(anchor_state.y2),
                        one(anchor_state.z2))
            f3 = f3r
            state.f2, state.y2, state.z2 = f3r[0], y2[0], z2[0]
            (r,) = _fetch(2, r)
            r_star = float(r[0])
            stats["stage2"] = self._stage_stats(*_fetch(2, it2, gap2),
                                                active=np.asarray([True]))
        if not skip_stage3:
            r_in = jnp.asarray([r_star * 1.005 + 1e-12 if run2 else 1e9],
                               jnp.float32)
            dl_in = jnp.asarray([delta if run2 else 0.0], jnp.float32)
            f3 = jnp.asarray(f3)
            with obs.span("jaxlp.warm_stage3"):
                if anchor_state is None or anchor_state.y3 is None:
                    f3, y3, it3, gap3 = self._solve_stretch_batch(
                        d3, ic, valid_b, u_budget, r_in, dl_in, f3)
                else:
                    f3, y3, it3, gap3 = self._solve_stretch_batch_warm(
                        d3, ic, valid_b, u_budget, r_in, dl_in, f3,
                        one(anchor_state.y3))
            state.y3 = y3[0]
            stats["stage3"] = self._stage_stats(*_fetch(3, it3, gap3))
        last = 3 if not skip_stage3 else 2 if run2 else 1  # f3's stage
        f3, u = _fetch(last, f3, u)
        f = self._flat_f(f3)[0]
        return ({"f": f, "u_star": float(u[0]), "r_star": r_star,
                 "stats": stats}, state)

    # ---- fleet batch: many fabrics (padded to this solver's V) at once ------

    def valid_for_pods(self, n_real: int) -> np.ndarray:
        """Slot mask for a fabric with ``n_real ≤ V`` pods embedded in this
        solver's ``V``-pod layout: commodities with a padded endpoint vanish,
        and padded pods are excluded as transit — their zero-capacity links
        carry ``inv_cap = 0`` and would otherwise look like free capacity."""
        v = self.V
        ii, jj, kk = np.meshgrid(np.arange(v), np.arange(v), np.arange(v),
                                 indexing="ij")
        real = (ii < n_real) & (jj < n_real) & (kk < n_real)
        return np.asarray(self.valid) & real

    def _fleet_fns(self, mesh):
        """Jitted batched stage solves for the fleet path, optionally
        ``shard_map``-sharded over the leading (flattened fabric×epoch) axis.
        Cached per mesh fingerprint — building shard_map closures is cheap but
        jit traces are not."""
        key = (None if mesh is None else
               (mesh.axis_names, tuple(d.id for d in mesh.devices.flat)))
        if key not in self._fleet_fns_cache:
            def mlu(d3, ic, valid, f0, y0):
                return jax.vmap(self._mlu_core)(d3, ic, valid, f0, y0)

            def risk(d3, ic, valid, u, dl, f0, y0, z0):
                return jax.vmap(self._risk_core)(d3, ic, valid, u, dl,
                                                 f0, y0, z0)

            def stretch(d3, ic, valid, u, r, dl, f0, y0):
                return jax.vmap(self._stretch_core)(d3, ic, valid, u, r, dl,
                                                    f0, y0)

            fns = {"mlu": mlu, "risk": risk, "stretch": stretch}
            if mesh is not None:
                from repro.parallel.sharding import shard_leading

                # repack=True: shard_leading deals the (quantized, not
                # mesh-aligned) batch round-robin across devices and handles
                # any remainder itself — no caller-side mesh padding
                fns = {k: shard_leading(fn, mesh, repack=True)
                       for k, fn in fns.items()}
            self._fleet_fns_cache[key] = {k: jax.jit(fn)
                                          for k, fn in fns.items()}
        return self._fleet_fns_cache[key]

    @staticmethod
    def _pad_leading(args, target: int):
        """Pad every array's leading axis to ``target`` by replaying its last
        element (a real element, so padding converges with its original)."""
        return tuple(
            a if a.shape[0] >= target else jnp.concatenate(
                [a, jnp.broadcast_to(a[-1:], (target - a.shape[0],)
                                     + a.shape[1:])])
            for a in args)

    def _batch_target(self, n: int, quantum: int) -> int:
        """Quantize a batch size for jit-shape stability.  Mesh-size rounding
        is gone: the repack-aware ``shard_leading`` splits any remainder
        across devices itself."""
        return -(-n // max(quantum, 1)) * max(quantum, 1)

    def _fleet_run(self, mesh, stage: str, *args):
        """Run one batched stage, quantizing the batch size (shape-stable jit
        traces across differently-sized fleet calls); padded rows are
        stripped on return."""
        fn = self._fleet_fns(mesh)[stage]
        n = args[0].shape[0]
        args = self._pad_leading(
            args, self._batch_target(n, self.fleet_batch_quantum))
        out = fn(*args)
        return tuple(o[:n] for o in out)

    def _anchor_run(self, fn, *args):
        """Run a batched cold anchor solve at a quantized batch size."""
        n = args[0].shape[0]
        args = self._pad_leading(
            args, self._batch_target(n, self.fleet_anchor_quantum))
        out = fn(*args)
        return tuple(o[:n] for o in out)

    def solve_routing_fleet(self, tms: np.ndarray, capacities: np.ndarray,
                            valids: np.ndarray, anchor_elems: np.ndarray,
                            anchor_of: np.ndarray, hedging: bool,
                            deltas: np.ndarray | None = None,
                            skip_stage3: bool = False, mesh=None):
        """Stages 1 → [2] → 3 for the routing epochs of *many fabrics* at once.

        The flattened batch concatenates every fabric's epochs; element ``i``
        belongs to the fabric whose anchor is ``anchor_elems[anchor_of[i]]``.
        All ``F`` fabric anchors are solved cold in one batched call, then the
        full batch runs warm-started from its own fabric's anchor — the exact
        fleet-wide analogue of :meth:`solve_routing_batch`'s single-fabric
        anchor scheme, so per-element results match the per-fabric path to
        solver tolerance.

        Args:
          tms: (N, m, C) critical TMs in this solver's (padded) layout.
          capacities: (N, E) directed capacities (zero on padded links).
          valids: (N, V, V, V) per-element slot masks
            (:meth:`valid_for_pods`).
          anchor_elems: (F,) element index of each fabric's anchor epoch.
          anchor_of: (N,) index into ``anchor_elems`` per element.
          hedging / deltas / skip_stage3: as :meth:`solve_routing_batch`.
          mesh: optional 1-D :class:`jax.sharding.Mesh`
            (:func:`repro.parallel.sharding.fleet_mesh`) — shards every
            batched solve over its device axis via ``shard_map``.

        Returns dict with ``f`` (N, P), ``u_star`` (N,), ``r_star`` (N,)|None,
        and ``stats`` per-element solver telemetry (see
        :meth:`solve_routing_batch`; slice per job with
        :func:`repro.obs.slice_raw_stats`).
        """
        d3 = jnp.stack([self._dense_tms(t) for t in tms])
        ic = jnp.stack([self._dense_inv_cap(c) for c in capacities])
        valids = jnp.asarray(valids)
        a_el = np.asarray(anchor_elems)
        ga = np.asarray(anchor_of)
        anchor_s = 0.0

        with obs.timed("jaxlp.fleet_anchor", stage="mlu") as t:
            f_a, _, _, y_a, _ = jax.block_until_ready(self._anchor_run(
                self._solve_mlu_batch, d3[a_el], ic[a_el], valids[a_el]))
        anchor_s += t.seconds
        with obs.span("jaxlp.fleet_stage1", n=int(d3.shape[0])):
            f3, u, it1, _, gap1 = self._fleet_run(
                mesh, "mlu", d3, ic, valids,
                jnp.asarray(f_a)[ga], jnp.asarray(y_a)[ga])
        u = jnp.asarray(u)
        u_budget = u * 1.005 + 1e-9
        stats = {"stage1": self._stage_stats(it1, gap1)}
        r_star = None
        if hedging:
            dl = jnp.asarray(np.asarray(deltas, np.float32))
            with obs.timed("jaxlp.fleet_anchor", stage="risk") as t:
                f2_a, _, _, y2_a, z2_a, _, _ = jax.block_until_ready(
                    self._anchor_run(
                        self._solve_risk_batch, d3[a_el], ic[a_el],
                        valids[a_el], u_budget[a_el], dl[a_el]))
            anchor_s += t.seconds
            with obs.span("jaxlp.fleet_stage2", n=int(d3.shape[0])):
                f3r, r, _, _, _, it2, gap2 = self._fleet_run(
                    mesh, "risk", d3, ic, valids, u_budget, dl,
                    jnp.asarray(f2_a)[ga], jnp.asarray(y2_a)[ga],
                    jnp.asarray(z2_a)[ga])
            use = (dl > 0)[:, None, None, None]
            f3 = jnp.where(use, f3r, f3)
            r_star = jnp.where(dl > 0, jnp.asarray(r), np.inf)
            stats["stage2"] = self._stage_stats(it2, gap2,
                                                active=np.asarray(dl > 0))
        if not skip_stage3:
            n = d3.shape[0]
            if r_star is None:
                r_in = jnp.full((n,), 1e9, jnp.float32)
                dl_in = jnp.zeros((n,), jnp.float32)
            else:
                r_in = jnp.where(jnp.isfinite(r_star),
                                 r_star * 1.005 + 1e-12, 1e9).astype(jnp.float32)
                dl_in = jnp.where(jnp.isfinite(r_star),
                                  jnp.asarray(np.asarray(deltas, np.float32)), 0.0)
            f3 = jnp.asarray(f3)
            with obs.timed("jaxlp.fleet_anchor", stage="stretch") as t:
                _, y3_a, _, _ = jax.block_until_ready(self._anchor_run(
                    self._solve_stretch_batch,
                    d3[a_el], ic[a_el], valids[a_el], u_budget[a_el],
                    r_in[a_el], dl_in[a_el], f3[a_el]))
            anchor_s += t.seconds
            with obs.span("jaxlp.fleet_stage3", n=int(d3.shape[0])):
                f3, _, it3, gap3 = self._fleet_run(
                    mesh, "stretch", d3, ic, valids, u_budget, r_in, dl_in,
                    f3, jnp.asarray(y3_a)[ga])
            stats["stage3"] = self._stage_stats(it3, gap3)
        f = self._flat_f(np.asarray(f3))
        out_r = None
        if r_star is not None:
            rr = np.asarray(r_star, np.float64)
            out_r = np.where(np.isfinite(rr), rr, np.nan)
        stats["anchor_seconds"] = anchor_s
        return {"f": f, "u_star": np.asarray(u, np.float64), "r_star": out_r,
                "stats": stats}
