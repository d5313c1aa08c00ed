"""Small pytree helpers shared by the trainers."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def scoped(name: str):
    """Decorator: trace every call under ``jax.named_scope(name)``, so the
    ops it stages carry ``name`` in their HLO ``op_name`` (the round's
    layer and round-kind scopes, DESIGN.md §5). A fresh scope per call:
    ``jax.named_scope`` used as a decorator reuses one context object, and
    a nested call of the same function would restore the wrong name stack
    on exit."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(s, a):
    return jax.tree.map(lambda x: (s * x.astype(jnp.float32)).astype(x.dtype), a)


def tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


def tree_dot(a, b):
    return sum(jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tree_norm_sq(a):
    return tree_dot(a, a)


def tree_size(a):
    return sum(x.size for x in jax.tree.leaves(a))


def tree_broadcast_leading(a, n):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), a)


def masked_mean_std(xs, good_mask, sanitize: bool = False):
    """Per-coordinate mean/std over the good workers of a stacked pytree.

    xs leaves: (n, ...). good_mask: (n,) bool. Returns (mean_tree, std_tree).

    ``sanitize`` (fault guard, DESIGN.md §6): select-replace masked-out rows
    before the weighted sums — a zero weight does NOT neutralize a
    non-finite row (0·NaN = NaN), so guarded callers whose excluded rows may
    be fault-poisoned must pass True. Static, so the default path's jaxpr is
    unchanged.
    """
    g = good_mask.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(g), 1.0)

    def mean_leaf(a):
        w = g.reshape((-1,) + (1,) * (a.ndim - 1))
        af = a.astype(jnp.float32)
        if sanitize:
            af = jnp.where(w > 0.0, af, 0.0)
        return jnp.sum(af * w, axis=0) / cnt

    means = jax.tree.map(mean_leaf, xs)

    def std_leaf(a, m):
        w = g.reshape((-1,) + (1,) * (a.ndim - 1))
        af = a.astype(jnp.float32)
        if sanitize:
            af = jnp.where(w > 0.0, af, m[None])
        var = jnp.sum(jnp.square(af - m[None]) * w,
                      axis=0) / cnt
        return jnp.sqrt(jnp.maximum(var, 0.0))

    stds = jax.tree.map(std_leaf, xs, means)
    return means, stds


def per_worker_keys(key, n, *, common: bool = False):
    if common:
        return jnp.broadcast_to(key, (n,) + key.shape)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))


@scoped("compress")
def compress_tree(compressor, key, tree):
    """Apply an unbiased compressor leaf-wise (block compression). Each leaf
    gets its own fold_in'd key so RandK supports differ across leaves."""
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        out.append(compressor.compress(k, leaf))
    return jax.tree.unflatten(treedef, out)
