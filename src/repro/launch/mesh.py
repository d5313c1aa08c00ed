"""Production mesh construction + sharding helpers.

``make_production_mesh`` is a FUNCTION (never touched at import time) so that
importing this module never initializes jax device state — only
launch/dryrun.py (which sets XLA_FLAGS first) builds the 256/512-way mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False,
                         model_parallel: int = 16) -> Mesh:
    """v5e pod mesh: 16x16 = 256 chips single pod; 2x16x16 = 512 multi-pod.

    ``model_parallel`` reshapes the within-pod 256 chips between the data and
    model axes (a §Perf knob: llama3-405b wants model=64). Default 16x16.
    Axes are ``Auto`` (GSPMD propagates shardings through the program), as
    every caller's ``PartitionSpec`` annotations assume.
    """
    per_pod = 256
    assert per_pod % model_parallel == 0
    data = per_pod // model_parallel
    if multi_pod:
        return jax.make_mesh((2, data, model_parallel),
                             ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def worker_axes(mesh) -> tuple:
    """Mesh axes that carry the Byzantine worker dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_workers(mesh) -> int:
    n = 1
    for a in worker_axes(mesh):
        n *= mesh.shape[a]
    return n


def shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def sanitize_specs(mesh, abs_tree, spec_tree):
    """Drop named axes from PartitionSpecs whose dimension size is not
    divisible by the axis size (e.g. vocab 50280 on a 16-way model axis).
    abs_tree: matching pytree of ShapeDtypeStructs / arrays."""

    def fix(aval, spec):
        if spec is None or not isinstance(spec, P):
            return spec
        dims = tuple(spec) + (None,) * (len(aval.shape) - len(tuple(spec)))
        out = []
        for size, entry in zip(aval.shape, dims):
            if entry is not None and size % _axis_size(mesh, entry) != 0:
                entry = None
            out.append(entry)
        return P(*out)

    return jax.tree.map(fix, abs_tree, spec_tree,
                        is_leaf=lambda s: s is None or isinstance(s, P))
