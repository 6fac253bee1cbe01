"""Mesh factories: production, digit-sharded, and virtual-CPU testing.

FUNCTIONS (not module constants) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before any jax init, tests keep 1
device.
"""

from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

#: XLA flag that splits the host CPU into N virtual devices — the only
#: way to exercise real GSPMD partitioning / shard_map collectives
#: without accelerators.  MUST be set before jax initializes a backend
#: (use a subprocess: tests/test_distributed_rns.py, benchmarks/
#: bench_dist.py), which is why this is a string helper, not a setter.
VIRTUAL_CPU_FLAG = "--xla_force_host_platform_device_count={n}"


def virtual_cpu_env(n: int, base: dict | None = None) -> dict:
    """Environment for a subprocess with ``n`` virtual CPU devices."""
    env = dict(base if base is not None else os.environ)
    env["XLA_FLAGS"] = VIRTUAL_CPU_FLAG.format(n=n)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes are all ``Auto``: GSPMD places what the sharding
    rules leave open (``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which an unannotated gather such as ``embed``'s ``jnp.take``
    of a vocab-sharded table is a type error)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_digit_mesh(n_model: int | None = None, *, n_data: int = 1):
    """``("data", "model")`` mesh for residue-channel sharding.

    The ``model`` axis carries RNS digit groups (size it to divide the
    profile's digit count: 8 devices x rns16 -> 2 digits/device, the
    paper's one-slice-per-unit layout as a mesh axis); ``data`` carries
    batch rows.  ``n_model=None`` uses every device not consumed by
    ``n_data``.  Works on 1 device too (1x1 mesh — shard_map still runs,
    partitioning is a no-op), so programs are mesh-agnostic.
    """
    n_dev = jax.device_count()
    if n_model is None:
        if n_dev % n_data:
            raise ValueError(f"{n_dev} devices not divisible by "
                             f"n_data={n_data}")
        n_model = n_dev // n_data
    if n_data * n_model > n_dev:
        raise ValueError(
            f"mesh ({n_data}, {n_model}) needs {n_data * n_model} devices, "
            f"have {n_dev} (CPU testing: set XLA_FLAGS="
            f"{VIRTUAL_CPU_FLAG.format(n=n_data * n_model)} before jax "
            "initializes)")
    return make_mesh((n_data, n_model), ("data", "model"))
