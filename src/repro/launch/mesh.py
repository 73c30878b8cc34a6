"""Mesh construction for the production pods and local testing.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax call, and eager mesh construction here would break that.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(
    data: int | None = None, model: int = 1, pod: int = 1, cand: int = 1
):
    """Mesh over whatever devices exist (CPU tests: 1 or 8 fake devices).

    ``cand > 1`` prepends a candidate axis (the FCA ShardPlan's 2-D
    frontier-axis decomposition picks it up by name)."""
    n = len(jax.devices())
    if data is None:
        data = n // (model * pod * cand)
    dims = []
    if cand > 1:
        dims.append(("cand", cand))
    if pod > 1:
        dims.append(("pod", pod))
    dims += [("data", data), ("model", model)]
    return jax.make_mesh(
        tuple(s for _, s in dims), tuple(a for a, _ in dims)
    )


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes present in this mesh (pod first).

    Same vocabulary the FCA ShardPlan uses for its object partition —
    one definition, shared via repro.dist.partition.
    """
    from repro.dist.partition import object_axes

    return object_axes(mesh)
