"""Shared jaxpr scanner for zero-copy pins.

Walks every equation reachable from a jaxpr WITHOUT descending into
``pallas_call`` bodies: ops inside the kernel run in VMEM and are the
whole point of the fused pipeline, so only the host-side (HBM) trace is
audited. Used by test_norm_agg.py (fused attack phase) and
test_wire.py (fused compressed-wire phase).
"""
import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

_JAXPR_TYPES = (Jaxpr, ClosedJaxpr)


def iter_eqns(jaxpr):
    """All eqns reachable from ``jaxpr``, NOT descending into pallas_call."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        yield eqn
        for v in eqn.params.values():
            for sub in jax.tree.leaves(
                    v, is_leaf=lambda x: isinstance(x, _JAXPR_TYPES)):
                if isinstance(sub, ClosedJaxpr):
                    yield from iter_eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from iter_eqns(sub)
