"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Interpret-mode parity tests run the kernels' semantics on the CPU, but they
cannot see what the chip's compiler refuses: primitives Mosaic does not
lower (``sort``, ``top_k``, gathers), blocks that are not (8, 128)-aligned,
in-kernel reshapes with illegal layouts, or more VMEM than a kernel may
use. Each case here lowers one kernel (or rule driver) at real widths with
``interpret=False`` against a ``v5e:2x2`` topology that is described, not
attached, and asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture: only one process may
load the TPU library at a time, so it must never happen at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.attacks import get_attack
from repro.kernels import norm_agg, ops, quantize
from repro.kernels.robust_agg import robust_agg

N, D = 16, 1 << 20
# mamba2-130m's layer-stacked in-projection (24 x 768 x 3352), bf16, as the
# eight-worker candidate stack of the chip smoke run
MAMBA_W_IN = (8, 24 * 768 * 3352)
ALIE = get_attack("ALIE").coord_apply


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # without the TPU compiler there is nothing to check; with it, any
    # failure to describe the chip is a failure
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _dense(sh, n=N, d=D, dtype=jnp.float32):
    return _sds((n, d), dtype, sh)


def _attack_args(sh, n, d, nb):
    """(w_mat, mask, mean, std) of a bucketed, ALIE-attacked launch."""
    return (_sds((nb, n), jnp.float32, sh), _sds((n,), jnp.bool_, sh),
            _sds((d,), jnp.float32, sh), _sds((d,), jnp.float32, sh))


def _wire(sh, fmt, n=N, d=D, dtype=jnp.float32):
    """A worker-stacked wire payload with MARINA's shared (1, d) base."""
    if fmt == "sparse":
        k = d // 10                       # randk at ratio 0.1
        arrays = (("vals", _sds((n, k), dtype, sh)),
                  ("idx", _sds((n, k), jnp.int32, sh)))
    elif fmt == "int8":
        nb = -(-d // quantize.INT8_BLOCK)
        arrays = (("lev", _sds((n, nb * quantize.INT8_BLOCK), jnp.int8, sh)),
                  ("norms", _sds((n, nb), jnp.float32, sh)))
    elif fmt == "sign":
        arrays = (("signs", _sds((n, d), jnp.int8, sh)),
                  ("scale", _sds((n, 1), jnp.float32, sh)))
    else:
        arrays = (("vals", _sds((n, d), jnp.bfloat16, sh)),)
    return quantize.WireSrc(fmt=fmt, n=n, d=d, arrays=arrays,
                            base=_sds((1, d), dtype, sh), cand_dtype=dtype)


def _coord(rule, **kw):
    return lambda x, *a: robust_agg(x, *a, rule=rule, interpret=False, **kw)


def _bucketed_alie(rule):
    def fn(x, w, mask, mu, sd):
        return robust_agg(x, w, mask, mu, sd, rule=rule, attack_fn=ALIE,
                          interpret=False)
    return fn


def _masked(rule):
    def fn(x, w, valid, bvalid):
        return robust_agg(x, w, valid=valid, bvalid=bvalid, rule=rule,
                          interpret=False)
    return fn


def _wire_agg(rule):
    key = jax.random.PRNGKey(0)
    return lambda src: ops.wire_agg(src, key, bucket_size=2, rule=rule,
                                    iters=2, interpret=False)


# name -> sharding -> (fn, args)
CASES = {
    **{f"robust_agg_{r}": (lambda r: lambda sh: (_coord(r), (_dense(sh),)))(r)
       for r in ("mean", "median", "trimmed")},
    "robust_agg_median_contiguous_buckets": lambda sh: (
        _coord("median", bucket_size=2), (_dense(sh),)),
    **{f"robust_agg_{r}_bucketed_alie": (lambda r: lambda sh: (
        _bucketed_alie(r), (_dense(sh),) + _attack_args(sh, N, D, N // 2)))(r)
       for r in ("mean", "median", "trimmed")},
    **{f"robust_agg_{r}_guarded": (lambda r: lambda sh: (
        _masked(r), (_dense(sh), _sds((N // 2, N), jnp.float32, sh),
                     _sds((N,), jnp.bool_, sh),
                     _sds((N // 2,), jnp.bool_, sh))))(r)
       for r in ("median", "trimmed")},
    "robust_agg_median_mamba2_w_in_bf16": lambda sh: (
        _bucketed_alie("median"),
        (_dense(sh, *MAMBA_W_IN, dtype=jnp.bfloat16),)
        + _attack_args(sh, MAMBA_W_IN[0], MAMBA_W_IN[1], 4)),
    "pair_gram_bucketed_alie": lambda sh: (
        lambda x, w, m, mu, sd: norm_agg.pair_gram(
            x, w, m, mu, sd, attack_fn=ALIE, interpret=False),
        (_dense(sh),) + _attack_args(sh, N, D, N // 2)),
    "rfa_iter": lambda sh: (
        lambda x, w: norm_agg.rfa_iter(x, w, interpret=False),
        (_dense(sh), _sds((N,), jnp.float32, sh))),
    "weighted_sum": lambda sh: (
        lambda x, w: norm_agg.weighted_sum(x, w, interpret=False),
        (_dense(sh), _sds((N,), jnp.float32, sh))),
    "krum_segments": lambda sh: (
        lambda x: norm_agg.krum_segments([x], n_byz=3, interpret=False)[0],
        (_dense(sh),)),
    "rfa_segments": lambda sh: (
        lambda x: norm_agg.rfa_segments([x], iters=2, interpret=False)[0],
        (_dense(sh),)),
    # giant n after bucketing: 256 rows, two worker tiles a side
    "krum_segments_blocked": lambda sh: (
        lambda x: norm_agg.krum_segments_blocked(
            [x], n_byz=16, interpret=False)[0],
        (_dense(sh, n=256),)),
    "rfa_segments_blocked": lambda sh: (
        lambda x: norm_agg.rfa_segments_blocked(
            [x], iters=2, interpret=False)[0],
        (_dense(sh, n=256),)),
    **{f"wire_agg_{fmt}_{rule}": (lambda fmt, rule: lambda sh: (
        _wire_agg(rule), (_wire(sh, fmt),)))(fmt, rule)
       for fmt, rule in (("sparse", "median"), ("sparse", "trimmed"),
                         ("sparse", "krum"), ("int8", "median"),
                         ("int8", "rfa"), ("sign", "mean"),
                         ("sign", "krum"), ("bf16", "mean"),
                         ("bf16", "krum"))},
    "wire_agg_sparse_bf16_median": lambda sh: (
        _wire_agg("median"), (_wire(sh, "sparse", dtype=jnp.bfloat16),)),
    # topk_select's on-chip pass (its final top-k over the pool is XLA's)
    "topk_select_pool": lambda sh: (
        lambda x: quantize.topk_pool(x, 1024, tile=2048, interpret=False),
        (_sds((D,), jnp.float32, sh),)),
    "block_quantize": lambda sh: (
        lambda x, u: quantize.block_quantize(x, u, interpret=False),
        (_sds((D,), jnp.float32, sh), _sds((D,), jnp.float32, sh))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = CASES[name](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
