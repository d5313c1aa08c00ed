"""Server-side aggregation throughput: jnp tree path vs Pallas kernels,
across ALL five rules × bucketed/unbucketed (interpret mode on CPU — on TPU
the kernel path is the compiled one). One row per (impl, rule, bucket, n, d),
both impls timed with the SAME ``time_fn`` iteration count.

Besides wall time, every row carries the analytic HBM-sweep count — tensor
traversals in units of the raw (n, d) stack, materialize-counted for the jnp
path (each jnp op reads its inputs and writes its result to HBM; sorting and
reductions on the s-bucketed matrix count 1/s) and read(n·d)+write(d) per
pass for the kernels. On a bandwidth-bound TPU, sweeps ∝ wall time;
``normalized_speedup`` = jnp_sweeps / pallas_sweeps is therefore the
interpret-overhead-free throughput ratio the fusion buys. The whole table is
recorded as ``experiments/bench/BENCH_agg.json`` (ISSUE 4 acceptance: fused
RFA ≤ 2 sweeps per Weiszfeld iteration, ≥ 2× normalized over jnp at
n=16, d=2^20).
"""
import json
import os

import jax

from benchmarks.common import ART_DIR, emit, time_fn
from repro.core.aggregators import COORD_KERNEL_RULE, get_aggregator
from repro.kernels import norm_agg, ops

KEY = jax.random.PRNGKey(0)
ITERS = 3          # same for BOTH impls (the old asymmetry made GB/s lies)
WARMUP = 1
RFA_T = 8          # paper default Weiszfeld iterations
BENCH_TILE_D = 1 << 16   # fewer grid steps -> less interpret-mode overhead

# giant-n scaling section (DESIGN.md §7): n-axis for the blocked tier.
# Interpret mode pays per-grid-step Python overhead, so the blocked kernels
# are only TIMED up to GIANT_PALLAS_MAX_N on CI hosts (at n=4096 one
# interpret-mode Gram exceeds 10 minutes); the n=4096 kernel row is carried
# analytically (sweep counts are exact), and on a real TPU the compiled
# kernels cover the full axis.
GIANT_NS = (256, 1024, 4096)
GIANT_D = 1 << 11
GIANT_RFA_T = 2
GIANT_PALLAS_MAX_N = 1024


def analytic_sweeps(impl: str, rule: str, s: int) -> float:
    """(n·d)-equivalent HBM traversals per call; materialize-counted."""
    if impl == "pallas":
        # every pass re-streams the raw stack once (bucketing is in-VMEM)
        return {"mean": 1.0, "cm": 1.0, "tm": 1.0,
                "rfa": RFA_T + 1.0, "krum": 2.0}[rule]
    bucketize = (3.0 + 1.0 / s) if s > 1 else 0.0   # gather r+w, mean r, w/s
    b = 1.0 / s if s > 1 else 1.0                   # bucketed-matrix sweep
    if rule == "mean":
        return 1.0
    if rule in ("cm", "tm"):                        # sort r+w, reduce r
        return bucketize + 3.0 * b
    if rule == "rfa":                               # init mean + per iter:
        # diff r+w, square-reduce r, weighted-sum r
        return bucketize + b + RFA_T * 4.0 * b
    if rule == "krum":                              # gram r + weighted-sum r
        return bucketize + 2.0 * b
    raise ValueError(rule)


def _pallas_fn(rule, bucket, agg):
    kw = dict(tile_d=BENCH_TILE_D)
    if rule in COORD_KERNEL_RULE:
        kernel_rule = COORD_KERNEL_RULE[rule]
        return lambda k, a: ops.robust_agg(
            a, k if bucket > 1 else None, bucket_size=bucket,
            rule=kernel_rule, trim=agg.trim, **kw)
    if rule == "rfa":
        return lambda k, a: ops.rfa_agg(
            a, k if bucket > 1 else None, bucket_size=bucket,
            iters=agg.iters, eps=agg.eps, **kw)
    return lambda k, a: ops.krum_agg(
        a, k if bucket > 1 else None, bucket_size=bucket, n_byz=agg.n_byz,
        **kw)


def run():
    rows = []
    for n, d in [(16, 1 << 16), (16, 1 << 20), (32, 1 << 16)]:
        x = jax.random.normal(KEY, (n, d))
        nbytes = n * d * 4
        for rule in ["mean", "cm", "tm", "rfa", "krum"]:
            for bucket in ([1] if rule == "mean" else [1, 2]):
                agg = get_aggregator(rule, bucket_size=bucket, n_byz=1)
                impls = {
                    "jnp": jax.jit(lambda k, a, agg=agg: agg(k, a)),
                    "pallas": _pallas_fn(rule, bucket, agg),
                }
                us = {}
                for impl, fn in impls.items():
                    us[impl] = time_fn(fn, KEY, x, warmup=WARMUP,
                                       iters=ITERS)
                    sweeps = analytic_sweeps(impl, rule, bucket)
                    name = f"agg/{impl}/{rule}/b{bucket}/n{n}/d{d}"
                    emit(name, us[impl],
                         f"GBps={nbytes / us[impl] / 1e3:.2f}"
                         f";sweeps={sweeps:g}")
                    rows.append({"impl": impl, "rule": rule,
                                 "bucket": bucket, "n": n, "d": d,
                                 "us": us[impl], "sweeps": sweeps})
                rows.append({
                    "impl": "speedup", "rule": rule, "bucket": bucket,
                    "n": n, "d": d,
                    "measured_interp": us["jnp"] / us["pallas"],
                    "normalized": (analytic_sweeps("jnp", rule, bucket)
                                   / analytic_sweeps("pallas", rule,
                                                     bucket))})
    rows += giant_n_rows()
    payload = {
        "schema": 2,
        "note": ("sweeps = (n*d)-equivalent HBM traversals per call, "
                 "materialize-counted for jnp; normalized speedup = "
                 "jnp_sweeps/pallas_sweeps (bandwidth-bound TPU ratio); "
                 "measured us are CPU interpret mode, same iters both "
                 "impls; tier=giant rows are the blocked/hierarchical "
                 "n-axis (DESIGN.md §7)"),
        "rfa_weiszfeld_iters": RFA_T,
        "rfa_pallas_sweeps_per_iter": (RFA_T + 1.0) / RFA_T,
        "rows": rows,
        "n_scaling": n_scaling_curve(rows),
    }
    os.makedirs(ART_DIR, exist_ok=True)
    with open(os.path.join(ART_DIR, "BENCH_agg.json"), "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


def giant_n_rows():
    """The n-axis of the blocked tier: Krum/RFA at n ∈ GIANT_NS, jnp
    (jit-compiled blocked Gram) at every n, the blocked Pallas drivers
    (interpret on CPU) up to GIANT_PALLAS_MAX_N."""
    rows = []
    for n in GIANT_NS:
        d = GIANT_D
        x = jax.random.normal(KEY, (n, d))
        nbytes = n * d * 4
        for rule in ["krum", "rfa"]:
            n_byz = max(1, n // 16)
            agg = get_aggregator(rule, bucket_size=1, n_byz=n_byz,
                                 iters=GIANT_RFA_T)
            if rule == "krum":
                def pallas_fn(k, a, n_byz=n_byz):
                    return norm_agg.krum_segments_blocked(
                        [a], n_byz=n_byz)[0]
            else:
                def pallas_fn(k, a):
                    return norm_agg.rfa_segments_blocked(
                        [a], iters=GIANT_RFA_T)[0]
            impls = {"jnp": jax.jit(lambda k, a, agg=agg: agg(k, a)),
                     "pallas": pallas_fn}
            for impl, fn in impls.items():
                row = {"impl": impl, "rule": rule, "bucket": 1, "n": n,
                       "d": d, "tier": "giant",
                       "sweeps": analytic_sweeps_giant(impl, rule)}
                if impl == "pallas" and n > GIANT_PALLAS_MAX_N:
                    row["us"] = None       # analytic-only on interpret hosts
                    rows.append(row)
                    continue
                us = time_fn(fn, KEY, x, warmup=1, iters=1)
                emit(f"agg_giant/{impl}/{rule}/n{n}/d{d}", us,
                     f"GBps={nbytes / us / 1e3:.2f}")
                row["us"] = us
                rows.append(row)
    return rows


def analytic_sweeps_giant(impl: str, rule: str) -> float:
    """(n·d)-equivalent traversals for the giant-n tier (bucket off).
    Blocked RFA pays 2 sweeps/iteration (weighted sum + distances) — the
    fused single-pass trick needs the whole worker axis in sublanes."""
    if impl == "pallas":
        return {"rfa": 2.0 * GIANT_RFA_T + 1.0, "krum": 2.0}[rule]
    if rule == "rfa":
        return 1.0 + GIANT_RFA_T * 4.0
    return 2.0


def n_scaling_curve(rows):
    """Per (impl, rule): the giant-tier n axis with per-worker cost — the
    scaling curve the docs/CI read. Krum's blocked Gram is O(n²·d) compute
    on O(n·d + n²) memory, so us/n grows ~linearly in n; RFA stays ~flat."""
    curve = {}
    for r in rows:
        if r.get("tier") != "giant" or r.get("us") is None:
            continue
        curve.setdefault(f"{r['impl']}/{r['rule']}", []).append(
            {"n": r["n"], "us": r["us"],
             "us_per_worker": r["us"] / r["n"]})
    for pts in curve.values():
        pts.sort(key=lambda p: p["n"])
    return curve


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
