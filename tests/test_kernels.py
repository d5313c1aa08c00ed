"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.quantize import block_quantize
from repro.kernels.robust_agg import robust_agg
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("n", [4, 8, 16, 32])
@pytest.mark.parametrize("d", [128, 1000, 2048, 6000])
@pytest.mark.parametrize("rule", ["mean", "median", "trimmed"])
def test_robust_agg_matches_oracle(n, d, rule):
    x = jax.random.normal(jax.random.fold_in(KEY, n * d), (n, d))
    got = robust_agg(x, rule=rule, interpret=True)
    want = ref.robust_agg_ref(x, rule=rule)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,s", [(8, 2), (16, 2), (16, 4), (32, 2)])
def test_robust_agg_bucketing(n, s):
    x = jax.random.normal(KEY, (n, 3000))
    got = robust_agg(x, bucket_size=s, rule="median", interpret=True)
    want = ref.robust_agg_ref(x, bucket_size=s, rule="median")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,s", [(5, 2), (7, 2), (9, 4), (15, 4)])
@pytest.mark.parametrize("rule", ["mean", "median", "trimmed"])
def test_robust_agg_bucketing_non_divisible(n, s, rule):
    """n % bucket_size != 0: the kernel must pad the last bucket with the
    stacked mean like aggregators._bucketize_perm (Alg. 2), not drop the
    trailing workers."""
    from repro.core.aggregators import _bucketize_perm, coord_median, \
        coord_trimmed_mean
    x = jax.random.normal(jax.random.fold_in(KEY, 13 * n + s), (n, 1500))
    got = robust_agg(x, bucket_size=s, rule=rule, interpret=True)
    want = ref.robust_agg_ref(x, bucket_size=s, rule=rule)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # and the oracle itself must match the real Alg. 2 implementation
    y = _bucketize_perm(x, jnp.arange(n), s)
    alg2 = {"mean": lambda a: jnp.mean(a, axis=0),
            "median": coord_median,
            "trimmed": lambda a: coord_trimmed_mean(a, 1)}[rule](y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(alg2), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_robust_agg_dtypes(dtype):
    x = jax.random.normal(KEY, (16, 2048)).astype(dtype)
    got = robust_agg(x, rule="median", interpret=True)
    want = ref.robust_agg_ref(x, rule="median")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_robust_agg_tile_boundaries():
    # d smaller than, equal to, and non-multiple of the tile
    for d in [100, 2048, 2049, 4096]:
        x = jax.random.normal(jax.random.fold_in(KEY, d), (8, d))
        got = robust_agg(x, rule="median", tile_d=2048, interpret=True)
        want = ref.robust_agg_ref(x, rule="median")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


@pytest.mark.parametrize("rule", ["median", "trimmed"])
def test_robust_agg_nan_row_matches_sort_order(rule):
    """No fault guard: NaN sorts last, as in the jnp oracles (jnp.sort), so
    one NaN worker leaves the median finite; columns where the NaNs reach
    the selected ranks give NaN on both sides."""
    from repro.core.aggregators import coord_median, coord_trimmed_mean
    x = jax.random.normal(jax.random.fold_in(KEY, 7), (8, 1000))
    x = x.at[3].set(jnp.nan)                  # one NaN worker
    x = x.at[:6, 5].set(jnp.nan)              # NaN reaches the middle ranks
    x = x.at[1, 9].set(jnp.inf)               # +inf still sorts before NaN
    got = robust_agg(x, rule=rule, interpret=True)
    want = (coord_median(x) if rule == "median"
            else coord_trimmed_mean(x, 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    if rule == "median":
        assert np.isfinite(np.delete(np.asarray(got), 5)).all()


def test_ops_wrapper_with_permutation():
    x = jax.random.normal(KEY, (16, 512))
    out = ops.robust_agg(x, KEY, bucket_size=2, rule="median",
                         interpret=True)
    # permutation + bucket + median: compare against doing it by hand
    perm = jax.random.permutation(KEY, 16)
    want = ref.robust_agg_ref(x[perm], bucket_size=2, rule="median")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("d", [256, 2048, 5000])
@pytest.mark.parametrize("levels", [1, 4, 16])
def test_block_quantize_matches_oracle(d, levels):
    x = jax.random.normal(jax.random.fold_in(KEY, d), (d,))
    u = jax.random.uniform(jax.random.fold_in(KEY, d + 1), (d,))
    got = block_quantize(x, u, levels=levels, block=256, interpret=True)
    want = ref.block_quantize_ref(x, u, levels=levels, block=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_block_quantize_grid_values():
    """Dequantized magnitudes sit on the grid {norm * k / levels}."""
    d, lv, blk = 512, 8, 256
    x = jax.random.normal(KEY, (d,))
    u = jax.random.uniform(jax.random.fold_in(KEY, 1), (d,))
    q = np.asarray(block_quantize(x, u, levels=lv, block=blk,
                                  interpret=True)).reshape(-1, blk)
    xb = np.asarray(x).reshape(-1, blk)
    norms = np.linalg.norm(xb, axis=1, keepdims=True)
    lev = np.abs(q) / norms * lv
    np.testing.assert_allclose(lev, np.round(lev), atol=1e-3)


def test_block_quantize_unbiased_statistically():
    d = 2048
    x = jax.random.normal(KEY, (d,))
    acc = jnp.zeros((d,))
    n = 300
    for i in range(n):
        u = jax.random.uniform(jax.random.fold_in(KEY, i), (d,))
        acc = acc + block_quantize(x, u, levels=4, block=256, interpret=True)
    m = acc / n
    # per-coord std of the estimator ~ norm/(levels*sqrt(n))
    tol = 5.0 * float(jnp.linalg.norm(x.reshape(-1, 256), axis=1).max()) / (
        4 * n ** 0.5)
    assert float(jnp.max(jnp.abs(m - x))) < tol
