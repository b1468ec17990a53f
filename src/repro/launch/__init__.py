"""Distribution layer: production mesh, partition rules, dry-run, drivers."""
from repro.launch.mesh import (
    batch_axes,
    make_mesh,
    make_production_mesh,
    pod_meshes,
)
from repro.launch.shapes import LONG_CONTEXT_WINDOW, SHAPES, InputShape, input_specs, supported

__all__ = [
    "InputShape",
    "LONG_CONTEXT_WINDOW",
    "SHAPES",
    "batch_axes",
    "input_specs",
    "make_mesh",
    "make_production_mesh",
    "pod_meshes",
    "supported",
]
