"""Pallas kernels on operands placed over several devices.

XLA cannot partition a Mosaic kernel: a jitted program whose kernel
operands live on a multi-device mesh is refused at compile time
("Mosaic kernels cannot be automatically partitioned") unless the call
sits inside a ``shard_map``. :func:`call` puts it there, with every
operand and result replicated over the mesh axes that are not manual
already, so each device runs the whole kernel. The mesh is read from
the operands: JAX keeps a ``NamedSharding``'s mesh in the abstract value
of every array computed from it. On one device, or inside a
``shard_map`` over every mesh axis (the FL engines' client-sharded
programs), the kernel is called as it is.

Interpret mode (off the TPU) takes the same path, so multi-device CPU
runs rehearse what the chip compiles.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P


def free_mesh(args):
    """``(mesh, axes)``: the first mesh an operand in ``args`` is placed
    on with axes of size > 1 that are not manual, and those axes;
    ``(None, ())`` when there is none."""
    for leaf in jax.tree.leaves(args):
        if isinstance(leaf, jax.core.Tracer):
            sharding = jax.typeof(leaf).sharding
        else:
            sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        if mesh is None or mesh.empty:
            continue
        axes = tuple(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t != AxisType.Manual and mesh.shape[a] > 1)
        if axes:
            return mesh, axes
    return None, ()


def call(kernel, *args):
    """``kernel(*args)``, under ``jax.shard_map`` with replicated
    operands when they sit on a multi-device mesh (module docstring).
    ``kernel`` may be differentiable: the gradient flows through the
    ``shard_map``."""
    mesh, axes = free_mesh(args)
    if mesh is None:
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=frozenset(axes), check_vma=False)(*args)
