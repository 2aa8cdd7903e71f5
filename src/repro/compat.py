"""The one home of JAX APIs this repo pins to the installed release (0.9).

Call sites import ``shard_map``, ``make_mesh``, ``abstract_mesh``,
``axis_size`` and ``is_batch_tracer`` from here, never from ``jax``
directly (``tests/test_lint_imports.py``), so the next API move is one
edit.

Meshes: since JAX 0.7 ``jax.make_mesh`` defaults every axis to
``AxisType.Explicit``, under which a gather or scatter on a sharded
operand raises ``ShardingTypeError`` and one mesh axis may not appear
twice in a ``PartitionSpec``. This repo's sharding is written for the
compiler-propagated (``Auto``) semantics, so every mesh is made here with
``AxisType.Auto`` on each axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """Device-free mesh of the given shape, every axis ``AxisType.Auto``."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


def is_batch_tracer(x) -> bool:
    """True when ``x`` is a ``jax.vmap`` batching tracer.

    The plan API uses it to turn the opaque shape/hash errors a vmapped
    ``InteractionPlan`` produces into a descriptive ``TypeError`` pointing
    at ``PlanBatch``. The tracer class is internal (it left
    ``jax.interpreters.batching``), so it is recognised by name.
    """
    return isinstance(x, jax.core.Tracer) \
        and type(x).__name__ == "BatchTracer"
