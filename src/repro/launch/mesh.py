"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

TPU v5e hardware constants used by the roofline analysis live here too.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType

# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link


def make_mesh(shape, axes=("data", "model"), *, devices=None):
    """Arbitrary (small) device meshes — e.g. ``(2, 4)`` data×model on a
    host forced to 8 CPU devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

    ``devices`` defaults to ``jax.devices()``; the first ``prod(shape)``
    are used, so disjoint sub-clusters can be carved by passing explicit
    device slices."""
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} / axes {tuple(axes)} mismatch")
    n = int(np.prod(shape))
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before jax "
            "initialises (see repro.launch.dryrun)"
        )
    # Auto axes: the engine's jits are annotated with NamedShardings and
    # leave the rest to GSPMD propagation (Explicit axes would demand
    # out_sharding on every gather)
    return jax.make_mesh(shape, tuple(axes), devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def pod_meshes(mesh):
    """Split a (…, data, model) mesh into independent single-axis
    ``("model",)`` meshes, one per data row — the serving topology: each
    data-parallel pod is a tensor-parallel island (no collective ever
    crosses pods; the frontend places whole requests on one pod)."""
    if "model" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'model' axis: {mesh.axis_names}")
    tp = int(mesh.devices.shape[list(mesh.axis_names).index("model")])
    rows = np.asarray(mesh.devices).reshape(-1, tp)
    return [make_mesh((tp,), ("model",), devices=list(row)) for row in rows]


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
