"""kernels/norm_agg + the zero-copy pallas message phase vs the jnp oracles.

Coverage pinned by ISSUE 4:
  * Pallas rfa/krum ≡ ``Aggregator.tree`` under every attack in the registry
  * non-bucket-multiple n, bf16 leaves, multi-leaf trees incl. the packed
    tiny-leaf buffer
  * in-kernel permutation (``bucket_matrix``) ≡ ``_bucketize_perm``
  * the fused message phase allocates no (n, d) attacked copy and no
    concatenated (n, D) flat intermediate (jaxpr scan)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ByzVRMarinaConfig, get_aggregator, get_attack
from repro.core.aggregators import Aggregator, _bucketize_perm
from repro.core.attacks import REGISTRY
from repro.core.engine import apply_attack, message_phase
from repro.core.sharded_agg import tree_aggregate_pallas
from repro.kernels import norm_agg, ops

KEY = jax.random.PRNGKey(0)


def _tree(key, n, dims, dtype=jnp.float32):
    ks = jax.random.split(key, len(dims))
    return {f"p{i}": jax.random.normal(k, (n,) + d).astype(dtype)
            for i, (k, d) in enumerate(zip(ks, dims))}


def _cfg(rule, bucket=0, attack="NA", n=8, n_byz=2, mode="pallas"):
    return ByzVRMarinaConfig(
        n_workers=n, n_byz=n_byz,
        aggregator=get_aggregator(rule, bucket_size=bucket, n_byz=n_byz),
        attack=get_attack(attack), agg_mode=mode)


# ---------------------------------------------------------------------------
# bucket_matrix: the in-kernel permutation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,s", [(8, 2), (16, 4), (5, 2), (7, 3), (9, 4)])
def test_bucket_matrix_matches_bucketize_perm(n, s):
    """W @ x ≡ aggregators._bucketize_perm(x, perm, s) — incl. the
    stacked-mean padding of a partial last bucket (Alg. 2)."""
    x = jax.random.normal(jax.random.fold_in(KEY, 11 * n + s), (n, 300))
    perm = jax.random.permutation(jax.random.fold_in(KEY, n - s), n)
    w = norm_agg.bucket_matrix(perm, n, s)
    assert w.shape == (-(-n // s), n)
    np.testing.assert_allclose(np.asarray(w @ x),
                               np.asarray(_bucketize_perm(x, perm, s)),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# flat kernels vs the Aggregator oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [5, 8, 16])
@pytest.mark.parametrize("d", [128, 1500])
@pytest.mark.parametrize("bucket", [0, 2, 3])
def test_rfa_kernel_matches_oracle(n, d, bucket):
    x = jax.random.normal(jax.random.fold_in(KEY, n * d + bucket), (n, d))
    agg = Aggregator("rfa", bucket_size=bucket)
    got = ops.rfa_agg(x, KEY, bucket_size=max(bucket, 1), interpret=True)
    want = agg(KEY, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n", [5, 8, 16])
@pytest.mark.parametrize("d", [128, 1500])
@pytest.mark.parametrize("bucket", [0, 2, 3])
def test_krum_kernel_matches_oracle(n, d, bucket):
    x = jax.random.normal(jax.random.fold_in(KEY, n * d - bucket), (n, d))
    agg = Aggregator("krum", bucket_size=bucket, n_byz=1)
    got = ops.krum_agg(x, KEY, bucket_size=max(bucket, 1), n_byz=1,
                       interpret=True)
    want = agg(KEY, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pair_gram_matches_sqdists_oracle():
    x = jax.random.normal(KEY, (8, 700))
    g = norm_agg.pair_gram(x, interpret=True)
    sq = jnp.diag(g)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * g, 0.0)
    np.testing.assert_allclose(np.asarray(d2),
                               np.asarray(ops.ref.pair_sqdists_ref(x)),
                               atol=1e-3)


def test_gram_dot_mxu_split_is_f32_accurate():
    """The Gram tile as compiled for the TPU (six bf16 products of the split
    operands), run here outside a kernel, against a float64 Gram. Keeping
    only the three largest products misses by about 4e-6 at this width."""
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (16, 256))
    got = np.asarray(jax.jit(
        lambda a: norm_agg._gram_dot(a, a, mxu=True))(x), np.float64)
    want = np.asarray(x, np.float64) @ np.asarray(x, np.float64).T
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


# ---------------------------------------------------------------------------
# tree path: every rule x every attack in the registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attack", sorted(REGISTRY))
@pytest.mark.parametrize("rule", ["mean", "cm", "tm", "rfa", "krum"])
def test_pallas_tree_matches_oracle_per_attack(rule, attack):
    """message_phase under agg_mode=pallas (fused attack where fusable) ≡
    materialized apply_attack + Aggregator.tree, for every registry attack."""
    cfg = _cfg(rule, bucket=2, attack=attack)
    cand = _tree(KEY, cfg.n_workers, [(40, 32), (17,)])
    k_attack, k_agg = jax.random.split(KEY)
    got = message_phase(cfg, k_attack, k_agg, cand)
    sent = apply_attack(cfg, k_attack, cand)
    want = cfg.aggregator.tree(k_agg, sent)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5), got, want)


@pytest.mark.parametrize("rule", ["cm", "rfa", "krum"])
def test_pallas_tree_non_bucket_multiple(rule):
    """n=7, s=2: the in-kernel permutation must pad the partial bucket with
    the stacked mean, exactly like the jnp oracle."""
    cfg = _cfg(rule, bucket=2, n=7, n_byz=1)
    cand = _tree(KEY, 7, [(33,), (6, 5)])
    got = tree_aggregate_pallas(cfg, KEY, cand)
    want = cfg.aggregator.tree(KEY, cand)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5), got, want)


@pytest.mark.parametrize("attack", ["NA", "ALIE"])
@pytest.mark.parametrize("rule", ["cm", "rfa", "krum"])
def test_pallas_tree_bf16_leaves(rule, attack):
    """bf16 candidates, clean and under a fused attack: the kernel prologue
    round-trips attacked values through the candidate dtype like
    apply_attack's .astype(h.dtype) (packed sub-tile leaves keep fp32 attack
    values — bounded by bf16 eps, covered by the tolerance here)."""
    cfg = _cfg(rule, bucket=2, attack=attack)
    cand = _tree(KEY, cfg.n_workers, [(1500,), (2000,)], dtype=jnp.bfloat16)
    k_attack, k_agg = jax.random.split(KEY)
    got = message_phase(cfg, k_attack, k_agg, cand)
    sent = apply_attack(cfg, k_attack, cand)
    want = cfg.aggregator.tree(k_agg, sent)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=4e-2)


def test_coord_attack_is_jit_cache_stable():
    """Two configs built from the same logical attack must share kernel jit
    cache entries: CoordAttack hashes by (kind, param), not closure id."""
    a1 = get_attack("ALIE").coord_apply
    a2 = get_attack("ALIE").coord_apply
    assert a1 == a2 and hash(a1) == hash(a2)
    assert get_attack("ALIE", z=2.0).coord_apply != a1
    x = jax.random.normal(KEY, (4, 256))
    mask = jnp.arange(4) < 1
    m = jnp.zeros((256,))
    s = jnp.ones((256,))
    norm_agg.pair_gram(x, None, mask, m, s, attack_fn=a1, interpret=True)
    before = norm_agg.pair_gram._cache_size()
    norm_agg.pair_gram(x, None, mask, m, s, attack_fn=a2, interpret=True)
    assert norm_agg.pair_gram._cache_size() == before


@pytest.mark.parametrize("rule", ["cm", "rfa", "krum"])
def test_pallas_tree_packs_tiny_leaves(rule):
    """Transformer-style trees (many sub-tile leaves) route through ONE
    packed flat buffer; the packed segmentation must not change results."""
    cfg = _cfg(rule, bucket=2)
    cand = _tree(KEY, cfg.n_workers, [(3,), (7,), (4, 2), (2000,), (11,)])
    got = tree_aggregate_pallas(cfg, KEY, cand)
    want = cfg.aggregator.tree(KEY, cand)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5), got, want)


def test_pack_rows_reuses_donated_buffer():
    """Eager packing reuses one preallocated buffer per layout (donated back
    each round) and keeps the zero tail intact."""
    from repro.core import sharded_agg as sa
    sa._PACK_CACHE.clear()
    flats = [jax.random.normal(jax.random.fold_in(KEY, i), (4, 11))
             for i in range(3)]
    p1 = sa._pack_rows(flats, "x")
    assert p1.shape == (4, 128) and len(sa._PACK_CACHE) == 1
    np.testing.assert_array_equal(np.asarray(p1[:, 33:]), 0.0)
    p2 = sa._pack_rows([f + 1.0 for f in flats], "x")
    np.testing.assert_allclose(np.asarray(p2[:, :11]),
                               np.asarray(flats[0] + 1.0), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(p2[:, 33:]), 0.0)
    assert len(sa._PACK_CACHE) == 1     # same layout -> same slot
    sa._PACK_CACHE.clear()


# ---------------------------------------------------------------------------
# zero-copy guarantee: jaxpr scan of the fused message phase
# ---------------------------------------------------------------------------

from _jaxpr_scan import iter_eqns as _iter_eqns  # noqa: E402


@pytest.mark.parametrize("rule", ["cm", "rfa", "krum"])
def test_fused_message_phase_is_zero_copy(rule):
    """With a fusable attack (ALIE) and large leaves, the traced pallas
    message phase must contain NO (n, d)-shaped attacked copy (select_n /
    where materialization) and NO concatenated (n, D_total) flat buffer —
    the roofline contract of ISSUE 4."""
    n = 8
    dims = [(1500,), (64, 32)]
    d_total = 1500 + 64 * 32
    cfg = _cfg(rule, bucket=2, attack="ALIE", n=n)
    cand = _tree(KEY, n, dims)
    k1, k2 = jax.random.split(KEY)
    jaxpr = jax.make_jaxpr(
        lambda c: message_phase(cfg, k1, k2, c))(cand).jaxpr
    for eqn in _iter_eqns(jaxpr):
        for out in eqn.outvars:
            shape = getattr(out.aval, "shape", ())
            if len(shape) >= 2 and shape[0] == n:
                assert eqn.primitive.name not in ("concatenate", "select_n"), (
                    f"{eqn.primitive.name} materializes {shape}")
                assert int(np.prod(shape)) < n * d_total, (
                    f"{eqn.primitive.name} allocates flat {shape}")


def test_unfused_message_phase_does_materialize():
    """Sanity check of the scanner itself: the RN (unfusable) path DOES
    select_n-materialize the attacked candidates."""
    n = 8
    cfg = _cfg("rfa", bucket=2, attack="RN", n=n)
    cand = _tree(KEY, n, [(1500,)])
    k1, k2 = jax.random.split(KEY)
    jaxpr = jax.make_jaxpr(
        lambda c: message_phase(cfg, k1, k2, c))(cand).jaxpr
    assert any(eqn.primitive.name == "select_n"
               and getattr(eqn.outvars[0].aval, "shape", ()) == (n, 1500)
               for eqn in _iter_eqns(jaxpr))
