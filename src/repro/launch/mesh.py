"""Production mesh construction.

Single pod: (16, 16) = 256 v5e chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — "pod" is
extra data parallelism across the DCI/ICI boundary (and models the survey's
cloud/edge pool boundary for the collaborative engine).

Functions, not module constants: importing this module must not touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).

Every mesh is built by ``make_mesh`` with ``AxisType.Auto`` axes: the
serving path places arrays with ``NamedSharding``s and
``with_sharding_constraint`` and lets the partitioner propagate the rest,
which ``jax.make_mesh``'s default Explicit axes refuse.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``; ``devices`` defaults to
    the local devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU tests (requires xla_force_host_platform_device_count
    >= data*model in the test process)."""
    return make_mesh((data, model), ("data", "model"))


def _balanced_factor(rem: int, k: int) -> int:
    """Smallest divisor of ``rem`` >= rem**(1/k) — peeling these off from
    the TRAILING axis backward splits ``rem`` into k near-balanced factors
    with the larger shares on later axes (the 'model' axis sits last in
    serving specs, and tensor parallelism wants the bigger/faster slice)."""
    if k <= 1:
        return rem
    t = rem ** (1.0 / k)
    for f in range(max(2, math.ceil(t)), rem + 1):
        if rem % f == 0:
            return f
    return rem


def parse_mesh_arg(spec: str):
    """Mesh from a CLI axis spec over the LOCAL devices.

    ``"data,model"`` sizes the axes automatically (near-balanced factors of
    ``jax.device_count()``, larger factors trailing: 8 devices -> (2, 4));
    ``"data=2,model=4"`` pins sizes explicitly (mixes allowed — pinned
    axes are honored, the rest split the remaining devices)."""
    names, sizes = [], []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        names.append(name)
        sizes.append(int(size) if size else 0)
    if not names:
        raise ValueError(f"empty mesh spec {spec!r}")
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate axis in mesh spec {spec!r}")
    ndev = jax.device_count()
    fixed = math.prod(s for s in sizes if s)
    if fixed == 0 or ndev % fixed != 0:
        raise ValueError(f"mesh spec {spec!r} needs a divisor of the "
                         f"{ndev} local devices, got fixed product {fixed}")
    rem = ndev // fixed
    free = [i for i, s in enumerate(sizes) if s == 0]
    for j, i in enumerate(reversed(free)):
        f = _balanced_factor(rem, len(free) - j)
        sizes[i] = f
        rem //= f
    if rem != 1:
        raise ValueError(f"mesh spec {spec!r} does not use all {ndev} "
                         f"local devices (shape {tuple(sizes)})")
    return make_mesh(sizes, names)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
