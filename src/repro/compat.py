"""Two JAX helpers shared by the kernels and the mesh constructors."""
from __future__ import annotations

import jax


def out_shape_struct(shape, dtype, operands=()):
    """``jax.ShapeDtypeStruct`` carrying the joint vma of ``operands``.

    Under ``shard_map(check_vma=True)`` a ``pallas_call`` out_shape must
    declare the mesh axes its outputs vary over.
    """
    vma = frozenset()
    for operand in operands:
        try:
            vma = vma | jax.typeof(operand).vma
        except AttributeError:  # plain arrays outside shard_map
            pass
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
