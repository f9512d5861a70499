"""One import surface for the JAX sharding and Pallas APIs the tree uses.

The tree targets the installed JAX (0.9).  Call sites import these names
from here rather than from JAX directly, so a future API move is a change
to this module only:

  * ``AxisType``            — ``jax.sharding.AxisType``;
  * ``make_mesh``           — ``jax.make_mesh`` with ``Auto`` axis types by
                              default;
  * ``set_mesh``            — ``jax.set_mesh``;
  * ``get_abstract_mesh``   — ``jax.sharding.get_abstract_mesh`` (empty when
                              no mesh is active; callers check ``.empty``);
  * ``axis_size``           — ``jax.lax.axis_size``;
  * ``named_shardings``     — ``jax.jit`` sharding trees (raw specs pass
                              through under a set mesh);
  * ``tpu_compiler_params`` — ``pltpu.CompilerParams``;
  * ``shard_map``           — ``jax.shard_map`` with ``check_vma``;
  * ``host_mesh``           — device-count-validated mesh construction used
                              by both the legacy launch meshes and the
                              engine's sharded execution plans.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    axis_types: Optional[Sequence] = None,
    **kwargs,
):
    """``jax.make_mesh`` with ``Auto`` axis types unless told otherwise."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=tuple(axis_types), **kwargs)


def set_mesh(mesh) -> None:
    """Set (or, with ``None``, clear) the process-global mesh."""
    jax.set_mesh(mesh)


def get_abstract_mesh():
    """The active mesh, or an *empty* mesh object when none is set; either
    way the result supports ``.empty``, ``.axis_names`` and ``.shape``."""
    return jax.sharding.get_abstract_mesh()


def axis_size(axis_name):
    """Size of a named mesh axis inside a ``shard_map`` body."""
    return jax.lax.axis_size(axis_name)


def named_shardings(mesh, spec_tree):
    """The ``in_shardings``/``out_shardings`` tree for ``jax.jit``.

    Under a set mesh ``jax.jit`` accepts raw ``PartitionSpec``s (and
    ``None``, meaning "unconstrained, compiler's choice"), so the tree passes
    through untouched.
    """
    del mesh
    return spec_tree


def host_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """Build a mesh over the host's devices, with a readable size check.

    One construction path for every mesh in the tree — the legacy launch
    meshes (``launch/mesh.py``) and the engine's sharded execution plans
    (``engine/sharding.py``) — so device-count errors surface the same way
    everywhere instead of as backend-specific assembly failures.
    """
    need = 1
    for s in axis_shapes:
        need *= int(s)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axis_names, axis_shapes))} needs {need} devices "
            f"but the host has {have}; on CPU force more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    return make_mesh(tuple(int(s) for s in axis_shapes), tuple(axis_names),
                     axis_types=(AxisType.Auto,) * len(tuple(axis_names)))


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check`` maps onto ``check_vma`` (callers here
    always use explicit collectives, so the default is off)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check)


def tpu_compiler_params(**kwargs):
    """Pallas TPU compiler params (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
