"""Sharding rules and activation-constraint context (DP/TP/SP/EP).

Model code calls :func:`constrain` with *logical* axis tuples; when an active
mesh is installed (launcher / dry-run) these become
``jax.lax.with_sharding_constraint`` with the mesh's physical axes, otherwise
they are no-ops (CPU smoke tests run the same code unsharded).

Logical → physical convention:
  "dp"     → ("pod", "data") if the mesh has a pod axis, else ("data",)
  "tp"     → "model"           (Megatron tensor parallelism)
  "sp"     → "model"           (sequence sharding of the residual stream)
  None     → replicated

Parameter rules are path-regex → PartitionSpec, FSDP-style: every large
matrix shards one dim over "tp" and the other over the dp axes, so parameter
+ optimizer memory scales with the full device count (ZeRO-3 analogue under
XLA SPMD; the all-gathers XLA inserts are the DP-axis collectives the
roofline and Gemini's traffic monitor account for).
"""

from __future__ import annotations

import re
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE_MESH: Mesh | None = None

# Parameter-sharding profile (hillclimb knob; see EXPERIMENTS.md §Perf):
#   "fsdp"     — params sharded over (dp × tp): ZeRO-3 memory, per-use gathers
#   "fsdp_pod" — FSDP over the intra-pod "data" axis only: no param gathers
#                ever cross the DCNI (pod axis carries grad all-reduce only)
#   "tp"       — params sharded over "model" only (replicated across dp):
#                no param gathers at all; optimizer memory × dp
_PROFILE = "fsdp"


def set_profile(profile: str):
    global _PROFILE
    assert profile in ("fsdp", "fsdp_pod", "tp")
    _PROFILE = profile


def get_profile() -> str:
    return _PROFILE


def set_active_mesh(mesh: Mesh | None):
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Mesh | None:
    return _ACTIVE_MESH


@contextmanager
def use_mesh(mesh: Mesh):
    prev = _ACTIVE_MESH
    set_active_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        set_active_mesh(prev)


def fleet_mesh(devices=None) -> Mesh:
    """1-D mesh over the visible devices with axis ``"fleet"``.

    The fleet engine (:mod:`repro.core.fleet_engine`) shards its flattened
    fabric×epoch batch axis over this mesh; with a single device the mesh is
    still a valid ``shard_map`` target (the smoke-test configuration), it just
    holds the whole batch on one shard.
    """
    import numpy as np

    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), ("fleet",))


def shard_leading(fn, mesh: Mesh, repack: bool = False):
    """``shard_map`` a batched function over the leading axis of every input
    and output, along ``mesh``'s first axis.

    ``fn`` must be elementwise along its leading batch axis (e.g. a
    ``jax.vmap``-wrapped per-element solve) so sharding it is a pure data
    split — no collectives.

    With ``repack=False`` callers pad the batch to a multiple of the axis
    size (the legacy contract).  With ``repack=True`` any batch size works:
    the wrapper pads the remainder by replaying real leading elements (the
    donated rows converge with their originals) and deals elements to devices
    **round-robin** instead of in contiguous blocks — element ``i`` lands on
    device ``i % D``.  Per-device programs run independently until the final
    gather, and neighbouring elements (sliding-window epochs, same-fabric
    blocks) have correlated solve difficulty, so contiguous sharding hands
    one device all the hard elements; the round-robin deal splits both the
    remainder and the workload evenly.  Outputs are inverse-permuted and
    trimmed, so results are elementwise identical to the unsharded call.
    """
    import jax.numpy as jnp
    import numpy as np

    spec = P(mesh.axis_names[0])
    sm = jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    if not repack:
        return sm

    d = int(mesh.devices.size)

    def repacked(*args):
        n = int(args[0].shape[0])
        if d == 1 or n % d == 0:
            # shard-major == round-robin is irrelevant when even; skip the
            # gathers (and keep the d == 1 smoke path bit-trivial)
            return sm(*args)
        rows = -(-n // d)  # per-device rows after the deal
        target = rows * d
        # position p (shard-major) holds element ((p % rows) * d + p // rows),
        # cycled over the real prefix for the replayed remainder
        p = np.arange(target)
        gather = jnp.asarray(((p % rows) * d + p // rows) % n)
        out = sm(*[a[gather] for a in args])
        # element e sits at position (e % d) * rows + e // d
        e = np.arange(n)
        inv = jnp.asarray((e % d) * rows + e // d)
        return jax.tree_util.tree_map(lambda o: o[inv], out)

    return repacked


def dp_axes(mesh: Mesh | None = None):
    mesh = mesh or _ACTIVE_MESH
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


def _resolve(axis):
    if axis is None:
        return None
    if axis == "dp":
        return dp_axes()
    if axis in ("tp", "sp"):
        return "model"
    return axis


def spec(*axes) -> P:
    return P(*[_resolve(a) for a in axes])


def constrain(x, *axes):
    """Apply a sharding constraint if a mesh is active; no-op otherwise."""
    if _ACTIVE_MESH is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACTIVE_MESH, spec(*axes)))


# ---- parameter partition rules ---------------------------------------------
# (regex on param path, PartitionSpec in logical axes). First match wins.
# Paths look like "blocks/attn/wq", "embed", "blocks/moe/w_gate", ...
# Stacked-layer leading axes (L or n_super) are replicated (None prefix added
# automatically for arrays with more dims than the rule).

PARAM_RULES = [
    (r"embed$", ("tp", "dp")),  # (V, d): vocab over tp, d over dp
    (r"unembed$", ("dp", "tp")),  # (d, V)
    (r"router$", (None, None)),  # tiny
    (r"moe/(w_gate|w_up|w_down)$", ("tp", "dp", None)),  # (E, d|ff, ·): EP over tp
    (r"(w_gate|w_up)$", ("dp", "tp")),  # (d, ff)
    (r"w_down$", ("tp", "dp")),  # (ff, d)
    (r"w(q|k|v)$", ("dp", "tp")),  # (d, H*hd): heads over tp
    (r"wo$", ("tp", "dp")),  # (H*hd, d)
    (r"(w_in|w_in_gate|w_in_rec)$", ("dp", "tp")),
    (r"w_out$", ("tp", "dp")),
    (r"(w_a|w_x)$", ("dp", "tp")),
    (r"conv_w$", (None, "tp")),
    (r".*", (None,)),  # norms, biases, scalars: replicated
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        name = getattr(k, "key", getattr(k, "idx", None))
        parts.append(str(name))
    return "/".join(parts)


def _resolve_param(axis):
    """Parameter-dim resolver honoring the sharding profile."""
    if axis == "dp":
        if _PROFILE == "tp":
            return None
        if _PROFILE == "fsdp_pod":
            return "data"
        return dp_axes()
    return _resolve(axis)


def param_spec_for(path: str, ndim: int) -> P:
    for pattern, axes in PARAM_RULES:
        if re.search(pattern, path):
            resolved = [_resolve_param(a) for a in axes]
            if len(resolved) < ndim:  # stacked layer/expert leading axes
                resolved = [None] * (ndim - len(resolved)) + resolved
            elif len(resolved) > ndim:
                resolved = resolved[-ndim:] if ndim else []
            return P(*resolved)
    return P()


def fit_spec(mesh: Mesh, shape, pspec: P) -> P:
    """Drop axes whose size does not divide the dim (jit in_shardings require
    exact divisibility; non-dividing dims stay replicated — e.g. odd vocab
    sizes, mamba2's 3352-wide in-projection)."""
    out = []
    for d, axes in enumerate(tuple(pspec) + (None,) * (len(shape) - len(tuple(pspec)))):
        if axes is None:
            out.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for a in ax_tuple:
            size *= mesh.shape[a]
        out.append(axes if shape[d] % size == 0 else None)
    return P(*out)


def param_shardings(mesh: Mesh, params_shape_tree):
    """NamedSharding pytree for a params eval_shape tree (divisibility-safe)."""

    def one(path, leaf):
        spec = param_spec_for(_path_str(path), len(leaf.shape))
        return NamedSharding(mesh, fit_spec(mesh, leaf.shape, spec))

    return jax.tree_util.tree_map_with_path(one, params_shape_tree)
