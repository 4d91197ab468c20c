"""Runtime distribution context.

Model code is mesh-agnostic by default; the launcher installs a mesh context
so layers that need EXPLICIT distribution (shard_map expert parallelism)
can find it at trace time.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

from repro.analysis import hot_path

_state = threading.local()


def current_mesh():
    return getattr(_state, "mesh", None)


def data_axes() -> Tuple[str, ...]:
    return getattr(_state, "data_axes", ("data",))


def model_axis() -> str:
    return getattr(_state, "model_axis", "model")


def activation_sharding() -> bool:
    return getattr(_state, "activation_sharding", True)


@contextlib.contextmanager
def mesh_context(mesh, *, data_axes_: Optional[Tuple[str, ...]] = None,
                 model_axis_: str = "model", activation_sharding_: bool = True):
    prev = (getattr(_state, "mesh", None), getattr(_state, "data_axes", None),
            getattr(_state, "model_axis", None),
            getattr(_state, "activation_sharding", True))
    _state.mesh = mesh
    _state.data_axes = data_axes_ or tuple(
        a for a in mesh.axis_names if a != model_axis_)
    _state.model_axis = model_axis_
    _state.activation_sharding = activation_sharding_
    try:
        yield
    finally:
        (_state.mesh, _state.data_axes, _state.model_axis,
         _state.activation_sharding) = prev


def _dp_count(mesh) -> int:
    n = 1
    for a in data_axes():
        n *= mesh.shape[a]
    return n


def kernel_axes(batch: int, heads: int):
    """Mesh axes a Pallas call's operands split over: ``(dp, mp)``, the
    data axes when ``batch`` divides over them and ``"model"`` when
    ``heads`` divides over it, else None for that side.  ``(None, None)``
    outside a mesh context."""
    mesh = current_mesh()
    if mesh is None:
        return None, None
    dp = data_axes()
    n_dp = _dp_count(mesh)
    mp = model_axis()
    m = mesh.shape.get(mp, 1)
    return (dp if n_dp > 1 and batch % n_dp == 0 else None,
            mp if m > 1 and heads % m == 0 else None)


def shard_kernel(kernel, in_specs, out_specs):
    """Run a Pallas kernel per shard inside a mesh context.  A Mosaic call
    cannot be partitioned automatically, so under a mesh it runs in
    ``jax.shard_map`` with the given specs; operands sharded otherwise are
    resharded to them at the boundary.  Identity off-mesh."""
    mesh = current_mesh()
    if mesh is None:
        return kernel
    import jax
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@hot_path
def gather_wave(*arrays):
    """All-gather a grouped escalation wave across the data axes in ONE
    explicit collective (``shard_map`` + ``lax.all_gather``), so the
    tensor-parallel cloud verifier sees every data shard's draft tape at
    once.  Each array is (G, ...) with G sharded over the data axes on
    entry; the result is fully replicated over them.  Identity (and
    trace-identical) outside a mesh context or when G does not divide —
    the single-device path never sees a collective.  ``@hot_path``: this
    runs inside every escalation wave, so repro-lint rule R1 keeps host
    syncs out of it."""
    mesh = current_mesh()
    if mesh is None:
        return arrays if len(arrays) > 1 else arrays[0]
    n_dp = _dp_count(mesh)
    if n_dp <= 1 or any(a.ndim == 0 or a.shape[0] % n_dp != 0
                        for a in arrays):
        return arrays if len(arrays) > 1 else arrays[0]
    import jax
    from jax.sharding import PartitionSpec as P
    dp = data_axes()

    def gather(*xs):
        return tuple(jax.lax.all_gather(x, dp, axis=0, tiled=True)
                     for x in xs)

    in_specs = tuple(P(dp, *([None] * (a.ndim - 1))) for a in arrays)
    out_specs = tuple(P(*([None] * a.ndim)) for a in arrays)
    # check_vma=False: the all-gather's output IS replicated over the data
    # axes, but the static replication checker cannot infer that
    out = jax.shard_map(gather, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)(*arrays)
    return out if len(arrays) > 1 else out[0]


@hot_path
def scatter_wave(x):
    """Constrain a (G, ...) wave result back to per-slot data sharding —
    the scatter half of the wave's mesh crossing.  No-op outside a mesh
    context or when G does not divide."""
    return shard_activation(x)


def shard_activation(x):
    """Constrain a (B, ...) activation to batch-sharding over the data axes
    (replicated over 'model').  No-op outside a mesh context or when the
    batch does not divide.  Perf iteration #1 (EXPERIMENTS.md §Perf): without
    this, XLA's SPMD resolves the FSDP-params x DP-batch conflict by
    replicating attention compute over the model axis."""
    mesh = current_mesh()
    if mesh is None or not activation_sharding():
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = data_axes()
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if x.ndim == 0 or x.shape[0] % n_dp != 0:
        return x
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
