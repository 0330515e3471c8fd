"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  Single pod: 16×16 = 256 chips
(v5e pod); multi-pod: 2×16×16 = 512 chips with a leading "pod" axis (DP
across pods over DCN, TP kept inside the pod over ICI).
"""

from __future__ import annotations

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_local_mesh(devices, model: int = 1):
    """A ``(data = len(devices) / model, model)`` mesh over exactly the
    given devices — host CPU devices in tests, chips on a TPU host.  The
    caller names the devices, so no run lands on device 0 by default."""
    devices = list(devices)
    return compat.make_mesh((len(devices) // model, model),
                            ("data", "model"), devices=devices)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


__all__ = ["make_production_mesh", "make_local_mesh", "mesh_axis_sizes"]
