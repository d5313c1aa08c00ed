"""The traffic generator: every round's token batches, made on the device
from the run's seed and the traffic file's sizes.

A training mix fixes ``n_workers`` x ``per_worker_batch`` sequences of
``seq_len`` tokens per minibatch, and an anchor batch of
``anchor_batches`` times as many for full-gradient rounds. Round ``it``'s
batches are drawn from ``fold_in(key, it)``, so every round and every
worker gets rows of its own, and one seed gives the same rows to the
program and to the reference. Tokens are uniform over the vocabulary;
labels are the next token (the last position is masked with -1). A
configuration with conditioning frames (``frontend_tokens``) also gets
``0.02 * N(0, 1)`` frame embeddings of width ``d_model``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


class Traffic:
    def __init__(self, arch: dict, traffic: dict, key):
        self.arch, self.traffic = arch, traffic
        self.k_mb, self.k_anchor = jax.random.split(key)
        b = traffic["per_worker_batch"]
        self._mb = jax.jit(functools.partial(_batch, arch, traffic, b))
        self._anchor = jax.jit(functools.partial(
            _batch, arch, traffic, b * traffic["anchor_batches"]))

    def minibatch(self, it, key=None):
        """Round ``it``'s minibatch; ``key`` (the loop's batch key) is not
        used: the rows come from the traffic's own key."""
        return self._mb(self.k_mb, it)

    def anchor(self, it):
        return self._anchor(self.k_anchor, it)

    def tokens_per_round(self) -> int:
        """Minibatch tokens of all workers in one round."""
        t = self.traffic
        return t["n_workers"] * t["per_worker_batch"] * t["seq_len"]


def _batch(arch, traffic, rows, base_key, it):
    key = jax.random.fold_in(base_key, it)
    n, s = traffic["n_workers"], traffic["seq_len"]
    shape = (n, rows, s)
    k = arch.get("num_codebooks", 1)
    if k > 1:
        shape += (k,)
    k_tok, k_front = jax.random.split(key)
    toks = jax.random.randint(k_tok, shape, 0, arch["vocab_size"],
                              dtype=jnp.int32)
    labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-1)
    batch = {"tokens": toks, "labels": labels}
    if arch.get("frontend_tokens", 0):
        batch["frontend"] = 0.02 * jax.random.normal(
            k_front, (n, rows, arch["frontend_tokens"], arch["d_model"]),
            jnp.float32)
    return batch
