"""The plain reference of the Byz-VR-MARINA round, written from the paper
(Alg. 1 of Gorbunov et al., ICLR 2023, with Alg. 2's bucketing) and from
the operators' definitions, with none of the program's code.

    g^0     = ARAgg(grad f_1(x^0), ..., grad f_n(x^0))        anchor batch
    x^{k+1} = x^k - lr g^k             stored in the configuration's dtype
    c_k     ~ Bernoulli(p)
    c_k = 1: g^{k+1} = ARAgg(grad f_i(x^{k+1}))                anchor batch
    c_k = 0: g^{k+1} = ARAgg(g^k + Q(grad f_i(x^{k+1}) - grad f_i(x^k)))
                                                                minibatch

ARAgg is ALIE (the first ``n_byz`` workers send mean - z std of the good
workers' messages, coordinate by coordinate) followed by the coordinate
median of bucket means (a random permutation of the workers, then buckets
of ``bucket_size``). Q is RandK: per leaf, a uniform subset of
``ratio`` of the selection units, scaled by units / kept (a leaf of more
than 2^22 coordinates is selected in contiguous blocks, so that there are
at most 2^22 units). The random draws follow the training loop's key
schedule: round ``k`` splits ``fold_in(k_run, k + 1)`` into its step and
batch keys (a run drives a chosen subsequence of the schedule's rounds), and the step key into the named streams ``RNG``; worker ``i``
folds ``i`` into its stream, and RandK folds in the leaf's index.

Everything is float32, worker by worker and leaf by leaf, so that it fits
on the chip beside nothing else: each worker's gradient goes to host
memory as it is made, and the aggregation puts one leaf's (n, ...) stack
on the device at a time.

It models Byz-VR-MARINA with ALIE, CM with bucketing, and the identity or
RandK compressor; it refuses a traffic mix that asks for anything else,
rather than compare the program with a round it does not run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.harness import BenchError
from benchlib.refops import make_policy, round_to

RNG = ("bern", "grad", "q", "attack", "agg")
MAX_UNITS = 1 << 22
# the rules it models, and for each the keyword arguments it reads
MODELLED = {"method": {"marina": ()}, "aggregator": {"cm": ()},
            "attack": {"ALIE": ("z",)},
            "compressor": {"identity": (), "randk": ("ratio",)}}
# traffic keys that set sizes and rates it reads, or that do not change
# the round's arithmetic (remat, the aggregation backend)
PLAIN_KEYS = ("n_workers", "n_byz", "bucket_size", "p", "lr", "seq_len",
              "per_worker_batch", "anchor_batches", "remat", "agg_mode")


def check_modelled(traffic: dict) -> None:
    """Raise ``BenchError`` unless the reference models ``traffic``."""
    for key, rules in MODELLED.items():
        rule = traffic.get(key)
        if rule not in rules:
            raise BenchError(f"the reference does not model {key} {rule!r}")
        kw = key + "_kwargs"
        extra = sorted(set(traffic.get(kw, {})) - set(rules[rule]))
        if extra:
            raise BenchError(f"the reference does not model {kw} {extra} "
                             f"of {rule!r}")
    extra = sorted(set(traffic) - set(MODELLED) - set(PLAIN_KEYS)
                   - {k + "_kwargs" for k in MODELLED})
    if extra:
        raise BenchError(f"the reference does not model traffic keys "
                         f"{extra}")


def randk(key, x, ratio):
    d = x.size
    blk = max(-(-d // MAX_UNITS), 1)
    n_units = -(-d // blk)
    k_units = max(int(ratio * n_units), 1)
    perm = jax.random.permutation(key, n_units)
    keep = jnp.zeros((n_units,), bool).at[perm[:k_units]].set(True)
    keep = jnp.repeat(keep, blk)[:d].reshape(x.shape)
    return jnp.where(keep, x * (n_units / k_units), 0.0)


def alie_bucketed_median(cand, perm, n_byz, z, bucket):
    """cand (n, ...): ALIE on the first ``n_byz`` rows, then the coordinate
    median of the bucket means in ``perm``'s order."""
    n = cand.shape[0]
    shape = (n,) + (1,) * (cand.ndim - 1)
    good = (jnp.arange(n) >= n_byz).reshape(shape)
    w = good.astype(jnp.float32)
    cnt = jnp.sum(w)
    mean = jnp.sum(cand * w, 0) / cnt
    std = jnp.sqrt(jnp.sum(jnp.square(cand - mean) * w, 0) / cnt)
    sent = jnp.where(good, cand, (mean - z * std)[None])
    if bucket > 1:
        xp = sent[perm]
        nb = -(-n // bucket)
        pad = nb * bucket - n
        if pad:
            xp = jnp.concatenate(
                [xp, jnp.broadcast_to(jnp.mean(xp, 0, keepdims=True),
                                      (pad,) + xp.shape[1:])], 0)
        sent = jnp.mean(xp.reshape((nb, bucket) + cand.shape[1:]), 1)
    xs = jnp.sort(sent, axis=0)
    m = xs.shape[0]
    return xs[m // 2] if m % 2 else 0.5 * (xs[m // 2 - 1] + xs[m // 2])


def _spread(per_worker, n_byz):
    """Per leaf: the norm of the good workers' mean gradient and the mean
    of their gradients' norms (how far the aggregate's inputs cancel)."""
    good = per_worker[n_byz:]
    mean = [_leaf_norms([sum(jnp.asarray(w[j]) for w in good)
                         / len(good)])[0]
            for j in range(len(good[0]))]
    each = np.mean([_leaf_norms(w) for w in good], axis=0)
    return {"mean_grad": mean, "worker_grad": [float(v) for v in each]}


def _leaf_norms(leaves):
    return [float(v) for v in jax.device_get(
        [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
         for a in leaves])]


class Reference:
    """The round in float32 (``precision="f32"``), or with the model's
    tensors rounded as ``benchlib.refops.make_policy`` says (``"fp8"``, the
    control; ``"bf16"``, the witness). ``batch_fault`` optionally rewrites
    a batch before the loss (a planted fault: half of each sequence left
    out)."""

    def __init__(self, model, arch: dict, traffic: dict, precision="f32",
                 batch_fault=None):
        check_modelled(traffic)
        self.arch, self.t = arch, traffic
        self.store = jnp.dtype(arch["dtype"])
        policy = make_policy(precision)
        fault = batch_fault or (lambda b: b)

        def loss(p, b):
            return model.loss(p, fault(b), arch, policy)

        vg = jax.value_and_grad(loss)
        self._vg = jax.jit(vg)

        def vg_diff(xn, xo, b):
            ln, gn = vg(xn, b)
            _, go = vg(xo, b)
            return ln, jax.tree.map(jnp.subtract, gn, go)

        self._vg_diff = jax.jit(vg_diff)
        t = traffic
        self._agg = jax.jit(functools.partial(
            alie_bucketed_median, n_byz=t["n_byz"],
            z=t["attack_kwargs"]["z"], bucket=t["bucket_size"]))
        ratio = t["compressor_kwargs"].get("ratio")
        self._q = (jax.jit(functools.partial(randk, ratio=ratio))
                   if t["compressor"] == "randk" else None)
        self._round_down = jax.jit(lambda x, g: round_to(x - t["lr"] * g,
                                                         self.store))

    def _worker(self, batch, i):
        return jax.tree.map(lambda a: a[i], batch)

    def _aggregate(self, per_worker, k_agg, base=None, k_q=None):
        """per_worker: n lists of leaves in host memory -> aggregated
        leaves on the device. Consumes ``per_worker`` leaf by leaf."""
        n = len(per_worker)
        perm = jax.random.permutation(k_agg, n)
        out = []
        for j in range(len(per_worker[0])):
            rows = []
            for i in range(n):
                v = jnp.asarray(per_worker[i][j])
                per_worker[i][j] = None
                if base is not None:
                    if self._q is not None:
                        v = self._q(jax.random.fold_in(
                            jax.random.fold_in(k_q, i), j), v)
                    v = base[j] + v
                rows.append(v)
            out.append(self._agg(jnp.stack(rows), perm))
            del rows
        return out

    def _full(self, x, anchor, k_agg, spread=None):
        losses, per_worker = [], []
        for i in range(self.t["n_workers"]):
            ln, g = self._vg(x, self._worker(anchor, i))
            losses.append(ln)
            per_worker.append(jax.device_get(jax.tree.leaves(g)))
        if spread is not None:
            spread.update(_spread(per_worker, self.t["n_byz"]))
        return float(np.mean(jax.device_get(losses))), self._aggregate(
            per_worker, k_agg)

    def _diff(self, xn, xo, mb, g, k_q, k_agg):
        losses, per_worker = [], []
        for i in range(self.t["n_workers"]):
            ln, dl = self._vg_diff(xn, xo, self._worker(mb, i))
            losses.append(ln)
            per_worker.append(jax.device_get(jax.tree.leaves(dl)))
        return float(np.mean(jax.device_get(losses))), self._aggregate(
            per_worker, k_agg, base=g, k_q=k_q)

    def run(self, params0, data, k_run, rounds) -> dict:
        """The rounds ``rounds`` of the key schedule, in order, from
        ``params0`` (the configuration's dtype) on ``data``'s batches: each
        round's loss, the leaf norms of g^0, of the parameters' change over
        them as stored (``dx``) and of the optimizer's summed update
        lr * sum g^k before storage rounds it (``step``)."""
        x0 = jax.tree.map(lambda a: a.astype(jnp.float32), params0)
        treedef = jax.tree.structure(x0)
        _, _, k_agg0 = jax.random.split(k_run, 3)
        spread = {}
        _, g = self._full(x0, data.anchor(0), k_agg0, spread)
        g0_norms = _leaf_norms(g)
        x, losses, coins = x0, [], []
        step = [jnp.zeros_like(a) for a in jax.tree.leaves(x0)]
        for it in rounds:
            k_step, _ = jax.random.split(jax.random.fold_in(k_run, it + 1))
            ks = dict(zip(RNG, jax.random.split(k_step, len(RNG))))
            xn = jax.tree.map(self._round_down, x,
                              jax.tree.unflatten(treedef, g))
            step = [a + self.t["lr"] * b for a, b in zip(step, g)]
            c_k = bool(jax.random.bernoulli(ks["bern"], self.t["p"]))
            if c_k:
                loss, g = self._full(xn, data.anchor(it), ks["agg"])
            else:
                loss, g = self._diff(xn, x, data.minibatch(it), g,
                                     ks["q"], ks["agg"])
            losses.append(loss)
            coins.append(int(c_k))
            x = xn
        dx = _leaf_norms([a - b for a, b in zip(jax.tree.leaves(x),
                                                jax.tree.leaves(x0))])
        return {"losses": losses, "g0": g0_norms, "dx": dx, "c_k": coins,
                "step": _leaf_norms(step), "spread": spread}
