"""Median wall of one SPMD join program on the mesh, issue to fetched,
as the store records it (`kernel._mesh_join_shard`: one observation a
conjunction, since the mesh store sends one conjunction a program). Of
the window's device answers (`_spans`). None where the program records
no such family (the parent of the PR that added it)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("kernel._mesh_join_shard")
