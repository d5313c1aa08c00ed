"""musicgen-medium (cut in depth): the benchmark's weights and its plain
float32 reference.

``init_params`` makes the weights from a key, in the program's parameter
layout (four codebook embeddings and heads, ``final_norm``, one stacked
group of decoder blocks with a leading layer axis), in the dtype the
configuration states.

``loss`` is the decoder's loss written from its equations, with none of the
program's code: the four codebook embeddings summed, the conditioning
frames prepended, then per layer causal softmax attention with rotary
positions and a gated SiLU MLP, each behind an RMSNorm (scale 1 + w), and
one head per codebook; the mean cross entropy over every codebook of every
audio position. It follows the program's decoder (see the configuration
file's ``departures``), so the comparison checks the training round.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchlib.refops import next_token_xent, normal, rms_norm


def init_params(key, a):
    layers, d, v, k = (a["num_layers"], a["d_model"], a["vocab_size"],
                       a["num_codebooks"])
    hq = a["num_heads"] * a["head_dim"]
    hkv = a["num_kv_heads"] * a["head_dim"]
    ff = a["d_ff"]
    dt = jnp.dtype(a["dtype"])
    ks = jax.random.split(key, 9)
    zeros = jnp.zeros((layers, d), dt)
    block = {
        "mixer": {"wq": normal(ks[2], (layers, d, hq), d ** -0.5, dt),
                  "wk": normal(ks[3], (layers, d, hkv), d ** -0.5, dt),
                  "wv": normal(ks[4], (layers, d, hkv), d ** -0.5, dt),
                  "wo": normal(ks[5], (layers, hq, d), hq ** -0.5, dt)},
        "ffn": {"w1": normal(ks[6], (layers, d, ff), d ** -0.5, dt),
                "w3": normal(ks[7], (layers, d, ff), d ** -0.5, dt),
                "w2": normal(ks[8], (layers, ff, d), ff ** -0.5, dt)},
        "norm1": zeros, "norm2": zeros,
    }
    return {"embed": normal(ks[0], (k, v, d), 0.02, dt),
            "unembed": normal(ks[1], (k, d, v), d ** -0.5, dt),
            "final_norm": jnp.zeros((d,), dt),
            "groups": (block,), "tail": ()}


def _rope(x, theta):
    """x (b,s,h,dh): rotate the two halves of each head by position."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (s, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, batch, a, policy):
    """The mean loss over every codebook of every audio position;
    ``policy.rd`` rounds where the program holds a tensor in its compute
    dtype, ``policy.mm`` is a weight product (benchlib/refops.py)."""
    rd, mm = policy.rd, policy.mm
    eps, dh = a["norm_eps"], a["head_dim"]
    nq, nkv = a["num_heads"], a["num_kv_heads"]
    tok = batch["tokens"]                                       # (b,s,K)
    x = rd(sum(params["embed"][c][tok[..., c]]
               for c in range(tok.shape[-1])))
    front = rd(batch["frontend"])
    n_front = front.shape[1]
    x = jnp.concatenate([front, x], axis=1)
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        m, f = p["mixer"], p["ffn"]
        h = rd(rms_norm(x, p["norm1"], eps))
        q = rd(_rope(mm("bsd,de->bse", h, m["wq"]).reshape(b, s, nq, dh),
                     a["rope_theta"]))
        k = rd(_rope(mm("bsd,de->bse", h, m["wk"]).reshape(b, s, nkv, dh),
                     a["rope_theta"]))
        v = mm("bsd,de->bse", h, m["wv"]).reshape(b, s, nkv, dh)
        k = jnp.repeat(k, nq // nkv, axis=2)
        v = jnp.repeat(v, nq // nkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / math.sqrt(dh)
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        o = rd(jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision="highest"))
        x = rd(x + mm("bse,ed->bsd", o.reshape(b, s, nq * dh), m["wo"]))
        h = rd(rms_norm(x, p["norm2"], eps))
        u = rd(rd(jax.nn.silu(mm("bsd,df->bsf", h, f["w1"])))
               * mm("bsd,df->bsf", h, f["w3"]))
        return rd(x + mm("bsf,fd->bsd", u, f["w2"])), None

    x, _ = jax.lax.scan(layer, x, params["groups"][0])
    x = rd(rms_norm(x, params["final_norm"], eps))[:, n_front:]
    logits = jnp.stack([mm("bsd,dv->bsv", x, params["unembed"][c])
                        for c in range(tok.shape[-1])], axis=2)
    return next_token_xent(logits, batch["labels"])
