"""Operations and bytes a round needs, from the configuration's shapes.

These are the work the algorithm requires, whatever implements it: they
do not change when a later change fuses, tiles or reorders the program.

* Model FLOPs of one forward and backward pass over a sequence, as in
  PaLM's accounting (Chowdhery et al. 2022, App. B): 6 FLOPs per matrix
  parameter per token, plus 12 L S d_attn per token for attention layers
  over S positions. The state-space scan of a Mamba2 layer and the
  elementwise work are not counted, so for Mamba2 it is a lower bound.
  Recomputation does not count.
* A difference round makes two passes (at x^{k+1} and at x^k) over the
  minibatch; a full-gradient round makes one pass over the anchor batch,
  ``anchor_batches`` times the minibatch.
* The aggregation's least HBM traffic: one read of the (n, d) candidate
  stack and one write of the d-vector aggregate, in the parameters' dtype.

A configuration whose layers this generic count does not know (latent
attention, experts, ...) gives its own count: its module
``configs/<name>.py`` defines ``sequence_pass_flops(arch, seq_len)`` by
the same accounting, and ``round_flops`` uses it.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchlib.harness import BenchError

ATTENTION = ("attention", "sliding_window")


def layer_kinds(arch: dict) -> list:
    pat = list(arch["block_pattern"])
    return (pat * -(-arch["num_layers"] // len(pat)))[:arch["num_layers"]]


def matmul_params(arch: dict) -> tuple:
    """(parameters in the layers' matrix products, in the output head's)."""
    d = arch["d_model"]
    layers = 0
    for kind in layer_kinds(arch):
        if kind == "mamba2":
            di = arch["ssm_expand"] * d
            n = arch["ssm_state"]
            nh = di // arch["ssm_headdim"]
            layers += d * (2 * di + 2 * n + nh) + di * d
        elif kind in ATTENTION:
            hq = arch["num_heads"] * arch["head_dim"]
            hkv = arch["num_kv_heads"] * arch["head_dim"]
            layers += 2 * d * hq + 2 * d * hkv + 3 * d * arch["d_ff"]
        else:
            raise BenchError(
                f"configuration {arch.get('name')!r}: the generic FLOP count "
                f"does not know layer kind {kind!r}; its module "
                "configs/<name>.py must define sequence_pass_flops(arch, "
                "seq_len)")
    head = arch.get("num_codebooks", 1) * d * arch["vocab_size"]
    return layers, head


def sequence_pass_flops(arch: dict, seq_len: int) -> float:
    """Forward + backward FLOPs of one sequence of ``seq_len`` tokens (the
    conditioning frames run through the layers, not the head)."""
    layers, head = matmul_params(arch)
    s_all = seq_len + arch.get("frontend_tokens", 0)
    n_attn = sum(k in ATTENTION for k in layer_kinds(arch))
    d_attn = arch.get("num_heads", 0) * (arch.get("head_dim") or 0)
    return (6.0 * (layers * s_all + head * seq_len)
            + 12.0 * n_attn * s_all * d_attn * s_all)


def round_flops(arch: dict, traffic: dict, full: bool, model=None) -> float:
    """Model FLOPs of one round; ``model`` is the configuration's module,
    whose own ``sequence_pass_flops`` is used where it defines one."""
    seqs = traffic["n_workers"] * traffic["per_worker_batch"]
    count = getattr(model, "sequence_pass_flops", sequence_pass_flops)
    per_seq = count(arch, traffic["seq_len"])
    if full:
        return traffic["anchor_batches"] * seqs * per_seq
    return 2 * seqs * per_seq


def aggregation_bytes(n_workers: int, n_params: int, dtype) -> float:
    """Least HBM bytes of one aggregation: read (n, d), write d."""
    return float((n_workers + 1) * n_params * jnp.dtype(dtype).itemsize)
