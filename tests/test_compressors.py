"""Unit tests for unbiased compression operators (Def. 2.2)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressors import (INT8_LEVELS, _int8_decode, _int8_encode,
                                    bf16_cast, identity, int8_quantization,
                                    l2_dithering, natural_compression,
                                    rand_k, randk_mask, shuffle_rounds,
                                    sign_compressor, smallest_k, top_k,
                                    unit_partition)

KEY = jax.random.PRNGKey(7)


def _empirical_mean(comp, x, n=400):
    acc = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(n):
        acc = acc + comp.compress(jax.random.fold_in(KEY, i), x)
    return acc / n


@pytest.mark.parametrize("maker", [
    lambda: rand_k(0.25), lambda: l2_dithering(4),
    lambda: natural_compression(), lambda: identity()])
def test_unbiasedness(maker):
    comp = maker()
    x = jax.random.normal(KEY, (64,))
    m = _empirical_mean(comp, x)
    # statistical tolerance: 400 draws, per-coordinate std <= omega^0.5 |x|
    tol = 4.0 * (max(comp.omega(64), 0.01) ** 0.5) * float(
        jnp.max(jnp.abs(x))) / 20.0 + 0.05
    assert float(jnp.max(jnp.abs(m - x))) < tol


def test_randk_density_exact():
    comp = rand_k(0.25)
    x = jax.random.normal(KEY, (100,))
    q = comp.compress(KEY, x)
    assert int(jnp.sum(q != 0)) == 25
    # kept coords scaled by d/k = 4
    kept = q[q != 0]
    orig = x[q != 0]
    np.testing.assert_allclose(np.asarray(kept), np.asarray(orig) * 4.0,
                               rtol=1e-5)


def test_randk_variance_bound():
    comp = rand_k(0.5)
    x = jax.random.normal(KEY, (128,))
    omega = comp.omega(128)
    errs = []
    for i in range(300):
        q = comp.compress(jax.random.fold_in(KEY, i), x)
        errs.append(float(jnp.sum((q - x) ** 2)))
    emp = np.mean(errs)
    bound = omega * float(jnp.sum(x * x))
    assert emp <= bound * 1.15, (emp, bound)


def test_dithering_variance_bound():
    comp = l2_dithering(2)
    x = jax.random.normal(KEY, (64,))
    omega = comp.omega(64)
    errs = []
    for i in range(300):
        q = comp.compress(jax.random.fold_in(KEY, i), x)
        errs.append(float(jnp.sum((q - x) ** 2)))
    assert np.mean(errs) <= omega * float(jnp.sum(x * x)) * 1.15


def test_natural_compression_omega():
    comp = natural_compression()
    assert comp.omega(1000) == pytest.approx(1 / 8)
    x = jax.random.normal(KEY, (256,))
    errs = []
    for i in range(200):
        q = comp.compress(jax.random.fold_in(KEY, i), x)
        errs.append(float(jnp.sum((q - x) ** 2)))
    assert np.mean(errs) <= (1 / 8) * float(jnp.sum(x * x)) * 1.2


def test_natural_compression_powers_of_two():
    comp = natural_compression()
    x = jnp.asarray([0.3, -1.7, 5.0, 0.0])
    q = comp.compress(KEY, x)
    nz = np.asarray(q[q != 0])
    exps = np.log2(np.abs(nz))
    np.testing.assert_allclose(exps, np.round(exps), atol=1e-6)
    assert float(q[3]) == 0.0


def test_sign_compressor_is_sign():
    comp = sign_compressor()
    x = jnp.asarray([1.5, -2.0, 3.0])
    q = comp.compress(KEY, x)
    assert jnp.all(jnp.sign(q) == jnp.sign(x))


def test_topk_keeps_largest_unscaled():
    comp = top_k(0.25)
    x = jnp.asarray([0.1, -5.0, 0.3, 2.0, -0.2, 0.05, 1.0, -0.4])
    q = comp.compress(KEY, x)
    # k = 2 largest magnitudes kept raw (no unbiasedness scaling)
    np.testing.assert_allclose(
        np.asarray(q), [0, -5.0, 0, 2.0, 0, 0, 0, 0], atol=1e-7)


def test_topk_contractive_bound_deterministic():
    """||C(x) - x||^2 <= (1 - k/d) ||x||^2, with equality only when all
    magnitudes are equal — check on random vectors (top_k is deterministic,
    no sampling slack needed)."""
    comp = top_k(0.3)
    for i in range(20):
        x = jax.random.normal(jax.random.fold_in(KEY, i), (50,))
        q = comp.compress(KEY, x)
        err = float(jnp.sum((q - x) ** 2))
        bound = comp.contractive_delta(50) * float(jnp.sum(x * x))
        assert err <= bound + 1e-6, (err, bound)
    assert comp.contractive_delta(50) == pytest.approx(1 - 15 / 50)
    assert np.isnan(comp.omega(50))      # biased: no Def. 2.2 omega


def test_contractive_delta_surface():
    assert identity().contractive_delta(10) == 0.0
    assert sign_compressor().contractive_delta(10) == pytest.approx(0.9)
    assert rand_k(0.5).contractive_delta(10) is None     # unbiased, unscaled
    assert l2_dithering(2).contractive_delta(10) is None


def test_bits_accounting():
    d = 1000
    assert rand_k(0.1).bits_per_vector(d) == 100 * 64
    assert top_k(0.1).bits_per_vector(d) == 100 * 64
    assert identity().bits_per_vector(d) == 32 * d
    assert natural_compression().bits_per_vector(d) == 9 * d


def test_huge_leaf_block_selection():
    """Leaves above the unit cap switch to block selection, stay unbiased."""
    comp = rand_k(0.5)
    x = jnp.ones((1 << 23,))          # 8M coords -> block size 2
    q = comp.compress(KEY, x)
    # mean over coords of q should be ~1 (unbiased), support ratio ~0.5
    assert abs(float(q.mean()) - 1.0) < 0.01
    frac = float((q != 0).mean())
    assert abs(frac - 0.5) < 0.01


def _permutation_mask(key, n, k):
    """RandK's kept units as ``jax.random.permutation`` defines them."""
    return jnp.zeros((n,), bool).at[jax.random.permutation(key, n)[:k]].set(
        True)


# n at every boundary of the shuffle's round count (0 | 1 | 2 | 3 rounds) and
# mamba2-130m's smaller leaf sizes; the 3-round size runs on two keys only.
@pytest.mark.parametrize("typed", [False, True], ids=["raw_key", "typed_key"])
@pytest.mark.parametrize("n,rounds", [
    (1, 0), (2, 1), (576, 1), (1625, 1), (1626, 2), (18_432, 2),
    (172_032, 2), (2_642_246, 3)])
def test_randk_mask_matches_permutation(n, rounds, typed):
    assert shuffle_rounds(n) == rounds
    k = max(int(0.1 * n), 1)
    base = jax.random.key(11) if typed else jax.random.PRNGKey(11)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(2 if rounds == 3 else 8))
    got = jax.jit(jax.vmap(lambda kk: randk_mask(kk, n, k)))(keys)
    want = jax.jit(jax.vmap(lambda kk: _permutation_mask(kk, n, k)))(keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got).sum(axis=1) == k).all()


@pytest.mark.parametrize("n,hi,k", [
    (100, 3, 40), (1000, 1, 999), (1000, 4, 1), (50, 0, 7), (64, 2**32 - 1, 64),
    (300, 2**32 - 1, 150)])
def test_smallest_k_breaks_ties_by_position(n, hi, k):
    """Keys from a narrow range tie at the threshold: the kept set is the
    first k of a stable argsort, the set the last shuffle round keeps."""
    rng = np.random.default_rng(n + k)
    bits = rng.integers(0, hi, n, dtype=np.uint32, endpoint=True)
    want = np.zeros(n, bool)
    want[np.argsort(bits, kind="stable")[:k]] = True
    got = jax.jit(smallest_k, static_argnums=1)(jnp.asarray(bits), k)
    np.testing.assert_array_equal(np.asarray(got), want)


def _sort_operand_counts(stablehlo: str):
    return sorted(len(ops.split(",")) for ops in
                  re.findall(r'"?stablehlo\.sort"?\(([^)]*)\)', stablehlo))


def test_randk_lowered_draw_has_no_scatter():
    """A 3-round leaf (about 2^22 units) lowers to the two key-value sorts of
    the first shuffle rounds and one single-operand sort, with no scatter."""
    x = jax.ShapeDtypeStruct((4_118_938,), jnp.float32)
    text = jax.jit(rand_k(0.1).compress).lower(KEY, x).as_text()
    assert _sort_operand_counts(text) == [1, 2, 2]
    assert "scatter" not in text


def test_huge_leaf_blocked_3_round_draw_matches_permutation():
    """6M coordinates -> blocks of 2, 3M units, 3 shuffle rounds: the dense
    output is the one the permutation formula gives."""
    d, ratio = 6_000_000, 0.1
    blk, n_units = unit_partition(d)
    assert (blk, n_units, shuffle_rounds(n_units)) == (2, 3_000_000, 3)
    k_units = int(ratio * n_units)
    x = jax.random.normal(KEY, (d,))
    mask = _permutation_mask(KEY, n_units, k_units)
    xf = x.reshape(n_units, blk)
    want = jnp.where(mask[:, None], xf * (n_units / k_units), 0).reshape(-1)
    got = jax.jit(rand_k(ratio).compress)(KEY, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# kernel-native quantized wires (int8 / bf16) + the wire-format contract
# ---------------------------------------------------------------------------

def test_int8_unbiased_and_bounded():
    """Blockwise l2-dithering: unbiased, with per-block variance inside the
    QSGD omega bound; levels fit signed int8 exactly."""
    comp = int8_quantization()
    x = jax.random.normal(KEY, (600,))       # 3 blocks, last one partial
    m = _empirical_mean(comp, x)
    assert float(jnp.max(jnp.abs(m - x))) < 0.05 * float(
        jnp.max(jnp.abs(x))) + 0.02
    omega = comp.omega(600)
    errs = [float(jnp.sum((comp.compress(jax.random.fold_in(KEY, i), x)
                           - x) ** 2)) for i in range(200)]
    assert np.mean(errs) <= omega * float(jnp.sum(x * x)) * 1.2
    levels, norms = _int8_encode(KEY, x)
    assert levels.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(levels.astype(jnp.int32)))) <= INT8_LEVELS


def test_int8_roundtrip_matches_shared_encoder():
    """compress ≡ decode(encode(·)) for the encoder the wire packer shares —
    the fused kernels reconstruct bit-identical candidates."""
    comp = int8_quantization()
    x = jax.random.normal(KEY, (300,))
    want = _int8_decode(*_int8_encode(KEY, x))[:300]
    np.testing.assert_array_equal(np.asarray(comp.compress(KEY, x)),
                                  np.asarray(want))


def test_bf16_cast_contractive():
    comp = bf16_cast()
    x = jax.random.normal(KEY, (512,))
    q = comp.compress(KEY, x)
    err = float(jnp.sum((q - x) ** 2))
    assert err <= comp.contractive_delta(512) * float(jnp.sum(x * x)) + 1e-9
    # bf16 input passes through exactly
    xb = x.astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(comp.compress(KEY, xb), np.float32),
                                  np.asarray(xb, np.float32))


def test_quantized_bits_accounting():
    assert sign_compressor().bits_per_vector(1000) == 1000 + 32
    assert int8_quantization().bits_per_vector(1000) == 8 * 1000 + 32 * 4
    assert bf16_cast().bits_per_vector(1000) == 16 * 1000


def test_registry_wire_format_fail_closed():
    """Every registered compressor must either declare a kernel wire format
    (and it must be one kernels/quantize.py implements) or be explicitly
    fallback-only — never silently neither (CI fail-closed gate: a new
    compressor without a routing decision breaks here, not in a fleet)."""
    from repro.core.compressors import REGISTRY
    from repro.kernels import quantize
    for name, maker in REGISTRY.items():
        comp = maker()
        declared = comp.wire_format is not None
        assert declared or comp.fallback_only, (
            f"{name}: declare wire_format or set fallback_only=True")
        if declared:
            assert not comp.fallback_only, (
                f"{name}: wire_format and fallback_only are exclusive")
            assert comp.wire_format in quantize.WIRE_FORMATS, (
                f"{name}: unknown wire format {comp.wire_format!r}")
