"""Production meshes (single- and multi-pod).

A FUNCTION, not a module constant, so importing this module never touches
jax device state (smoke tests see 1 device).

Mesh anatomy (TPU v5e pods of 256 chips):
  single pod  : (16, 16)       axes ("data", "model")
  two pods    : (2, 16, 16)    axes ("pod", "data", "model")

"model" is the high-bandwidth tensor/expert-parallel axis (keep it inside
an ICI torus dimension), "data" carries FSDP + batch parallelism, and
"pod" is the outer pure-DP axis crossing the data-center interconnect —
gradients reduce hierarchically: reduce-scatter on "data" (from FSDP
sharding propagation) then all-reduce across "pod".
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return jax.make_mesh((n // mp, mp), ("data", "model"))


def resolve_mesh(mesh):
    """Normalize an engine-style ``mesh=`` opt-in (DESIGN.md §12).

    ``None``/``False`` → no mesh (the unsharded offline pass),
    ``True`` → `make_host_mesh()` over whatever devices exist, and a
    `jax.sharding.Mesh` passes through untouched.  A 1-device mesh is
    deliberately NOT collapsed to None: the sharded pass on one device
    is the parity baseline the multi-device CI leg digests against."""
    if mesh is None or mesh is False:
        return None
    if mesh is True:
        return make_host_mesh()
    return mesh
