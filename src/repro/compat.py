"""JAX API layer — the single import point for symbols whose spelling
has moved between JAX releases.

Library code in ``repro`` does not reach into ``jax.experimental`` for
these symbols; it imports them from here, so the next API move is a
one-file change.  Only the installed JAX is supported: no branch here
serves a release that is not installed.

* ``shard_map``    — ``jax.shard_map`` with ``axis_names`` accepted as any
  iterable of mesh axes.
* ``axis_size``    — ``jax.lax.axis_size``.
* ``ANY`` / ``VMEM`` / ``SMEM`` — Pallas TPU memory spaces.
* ``prefetch_scalar_grid_spec`` — ``pltpu.PrefetchScalarGridSpec``.
* ``tpu_params``   — ``pltpu.CompilerParams``.
* ``on_tpu()``     — backend probe shared by the kernel wrappers to pick
  interpret mode on CPU hosts.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.experimental.pallas import tpu as _pltpu


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names: Optional[Any] = None):
    """``jax.shard_map``; ``axis_names`` (the mesh axes the body is Manual
    over — all axes when ``None``) may be any iterable."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


#: Size of a mapped mesh axis, inside a Manual region.
axis_size = jax.lax.axis_size

ANY = _pltpu.MemorySpace.ANY
VMEM = _pltpu.MemorySpace.VMEM
SMEM = _pltpu.MemorySpace.SMEM


def prefetch_scalar_grid_spec(*, num_scalar_prefetch, grid, in_specs,
                              out_specs, scratch_shapes=()):
    """``pltpu.PrefetchScalarGridSpec`` — the TPU grid spec whose scalar
    operands are available to BlockSpec index maps (the mechanism behind
    the compacted tile-index grid of the pruned retrieval route).
    ``scratch_shapes`` (e.g. ``VMEM(shape, dtype)``) persist across grid
    steps."""
    return _pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


def tpu_params(*, dimension_semantics):
    """``pltpu.CompilerParams``: ``"parallel"`` or ``"arbitrary"`` (visited
    in order on one core) per grid dimension."""
    return _pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU (kernels compile);
    False on CPU/GPU hosts (kernels run in interpret mode).  A backend
    that fails to initialise raises here: a broken chip must not be
    served as a silent CPU fallback."""
    return jax.devices()[0].platform == "tpu"
