"""mamba2-130m: the benchmark's weights and its plain float32 reference.

``init_params`` makes the weights from a key, in the program's parameter
layout (``embed``, ``final_norm`` and one stacked group of Mamba2 blocks
with a leading layer axis), in the dtype the configuration states.

``loss`` is the language-model loss written from the block's equations,
with none of the program's code: embedding, then per layer
``x += W_out RMSNorm(SSD(conv(silu(x_in W_in))) * silu(z))`` and the tied
head. The state-space scan is computed in its quadratic (dual) form,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s
          + D x_t,

exact for any chunking the program uses. The block follows the program's
Mamba2 variant (SiLU before the causal conv, no conv bias; see the
configuration file's ``departures``), so the comparison checks the
training round and not the model's departures from the paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib.refops import next_token_xent, normal, rms_norm

# the quadratic form keeps (b, t, t, heads) float32 tensors of every layer
# for the backward pass; past this many bytes of them a layer is computed
# again in the backward pass (``jax.checkpoint``: the same arithmetic, to
# float32 rounding), so that long sequences fit on the chip. 2,048 tokens
# would keep 19 GB; 128 keep 75 MB and are computed as before
SAVED_BYTES = 1 << 30


def _sizes(a):
    d = a["d_model"]
    di = a["ssm_expand"] * d
    n = a["ssm_state"]
    nh = di // a["ssm_headdim"]
    return d, di, n, nh


def init_params(key, a):
    """Random weights with Mamba2's published initialisation (the
    reference implementation's ``Mamba2`` and ``_init_weights``): uniform
    projections of bound 1/sqrt(fan_in), the output projection scaled by
    1/sqrt(n_layer), the time-step bias the inverse softplus of dt drawn
    log-uniform in [1e-3, 1e-1], A = U[1, 16], D = 1, embeddings N(0, 0.02),
    norm scales 1 (the program's ``1 + w`` form with w = 0)."""
    layers, v = a["num_layers"], a["vocab_size"]
    d, di, n, nh = _sizes(a)
    dt = jnp.dtype(a["dtype"])
    width = a["conv_width"]
    ks = jax.random.split(key, 6)

    def uniform(k, shape, bound):
        return jax.random.uniform(k, shape, jnp.float32, -bound,
                                  bound).astype(dt)

    step = jnp.exp(jax.random.uniform(ks[3], (layers, nh), jnp.float32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    step = jnp.maximum(step, 1e-4)
    mixer = {
        "w_in": uniform(ks[1], (layers, d, 2 * di + 2 * n + nh), d ** -0.5),
        "conv_w": uniform(ks[2], (layers, width, di + 2 * n), width ** -0.5),
        "a_log": jnp.log(jax.random.uniform(ks[4], (layers, nh), jnp.float32,
                                            1.0, 16.0)).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "d_skip": jnp.ones((layers, nh), dt),
        "out_norm": jnp.zeros((layers, di), dt),
        "w_out": uniform(ks[5], (layers, di, d),
                         di ** -0.5 / layers ** 0.5),
    }
    return {"embed": normal(ks[0], (v, d), 0.02, dt),
            "final_norm": jnp.zeros((d,), dt),
            "groups": ({"mixer": mixer,
                        "norm1": jnp.zeros((layers, d), dt)},),
            "tail": ()}


def _ssd(x, bm, cm, dt, a):
    """x (b,t,h,p), bm/cm (b,t,n), dt (b,t,h), a (h,) -> y (b,t,h,p)."""
    t = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)                        # (b,t,h)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    rel = jnp.where(causal, cum[:, :, None, :] - cum[:, None, :, :],
                    -jnp.inf)                                # (b,t,s,h)
    g = jnp.einsum("btn,bsn->bts", cm, bm, precision="highest")
    m = g[..., None] * jnp.exp(rel) * dt[:, None, :, :]
    return jnp.einsum("btsh,bshp->bthp", m, x, precision="highest")


def _conv(x, w):
    """Causal depthwise conv: out_t = sum_i x_{t-W+1+i} w_i."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t, :] * w[i] for i in range(width))


def loss(params, batch, a, policy):
    """The mean next-token loss; ``policy.rd`` rounds where the program
    holds a tensor in its compute dtype, ``policy.mm`` is a weight
    product (benchlib/refops.py)."""
    rd, mm = policy.rd, policy.mm
    d, di, n, nh = _sizes(a)
    hp, eps = a["ssm_headdim"], a["norm_eps"]
    emb = params["embed"]
    x = rd(emb[batch["tokens"]])
    b, t, _ = x.shape

    def layer(x, p):
        m = p["mixer"]
        h = rd(rms_norm(x, p["norm1"], eps))
        zxbcdt = mm("btd,de->bte", h, m["w_in"])
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                      zxbcdt[..., 2 * di + 2 * n:])
        xbc = rd(_conv(rd(jax.nn.silu(xbc)), m["conv_w"]))
        xi, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
        dt = jax.nn.softplus(dt + m["dt_bias"])
        xh = xi.reshape(b, t, nh, hp)
        y = _ssd(xh, bm, cm, dt, -jnp.exp(m["a_log"]))
        y = rd(y + xh * m["d_skip"][None, None, :, None]).reshape(b, t, di)
        y = rd(y * rd(jax.nn.silu(z)))
        y = rd(rms_norm(y, m["out_norm"], eps))
        return rd(x + mm("bte,ed->btd", y, m["w_out"])), None

    if a["num_layers"] * b * t * t * nh * 4 > SAVED_BYTES:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["groups"][0])
    x = rd(rms_norm(x, params["final_norm"], eps))
    return next_token_xent(mm("bsd,vd->bsv", x, emb), batch["labels"])
