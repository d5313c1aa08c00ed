"""Worker→server message throughput: the jnp Compressor path vs the fused
Pallas wire (compress → pack → in-kernel reconstruct → aggregate), across
every kernel wire format × d (interpret mode on CPU — on TPU the kernel
path is the compiled one). One row per (impl, compressor, d), both impls
timed with the SAME ``time_fn`` iteration count.

Besides wall time, every row carries the analytic HBM-sweep count in units
of the raw (n, d) fp32 stack. The jnp path materializes dense at every
stage: compress reads x and writes the dense q (2), the attack/corrupt
stage reads q and writes the sent copy (2), aggregation reads it once
more (1) — 5 sweeps, none of them smaller for having compressed. The
fused wire reads x once at pack time (1) and then moves only the wire
bytes: pack writes β, the aggregation kernel reads β, with
β = packed_bytes / (n·d·4). ``normalized_speedup`` = 5 / (1 + 2β) is the
bandwidth-bound ratio the wire buys; ``wire_bytes`` is the measured
per-round payload (``wire.measured_bits``/8 — pinned to
``theory.comm_bits_per_round`` by the conformance suite). Recorded as
``experiments/bench/BENCH_compress.json`` (ISSUE 6 acceptance: ≥ 1.5×
normalized at d=2^20 for every wire format).
"""
import json
import os

import jax

from benchmarks.common import ART_DIR, emit, time_fn
from repro.core import wire
from repro.core.aggregators import get_aggregator
from repro.core.compressors import get_compressor
from repro.core import tree_utils as tu
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)
N = 8
ITERS = 2          # same for BOTH impls
WARMUP = 1
BENCH_TILE_D = 1 << 16   # fewer grid steps -> less interpret-mode overhead
JNP_SWEEPS = 5.0   # compress r+w, attack/corrupt r+w, aggregate r
# sparse ratio: small enough that the in-kernel scatter's interpret-mode
# chunk loop stays bounded; the wire-byte accounting scales linearly in k
# so the roofline is ratio-independent
SPARSE_RATIO = 0.01

COMPRESSORS = [
    ("randk", {"ratio": SPARSE_RATIO}),
    ("topk", {"ratio": SPARSE_RATIO}),
    ("sign", {}),
    ("int8", {}),
    ("bf16", {}),
]


def _packed_beta(wc, n, d):
    """HBM bytes the wire actually moves, per (n·d·4) dense-stack bytes —
    the packed arrays as laid out (int8 signs count 1 byte: layout, not
    entropy; the semantic size is wire.measured_bits)."""
    nbytes = sum(a.nbytes for payload in wc.payloads
                 for a in payload.values())
    return nbytes / (n * d * 4)


def run():
    agg = get_aggregator("cm")
    for d in [1 << 16, 1 << 20]:
        x = jax.random.normal(KEY, (N, d))
        qkeys = tu.per_worker_keys(KEY, N)
        rows = []
        for name, kw in COMPRESSORS:
            comp = get_compressor(name, **kw)

            def jnp_fn(k, a, comp=comp):
                qs = jax.vmap(
                    lambda kq, g: tu.compress_tree(comp, kq, {"p": g})["p"]
                )(qkeys, a)
                return agg(k, qs)

            def wire_fn(k, a, comp=comp):
                wc = wire.pack_candidates(comp, qkeys, {"p": a})
                return ops.wire_agg(wire.wire_srcs(wc)[0], rule="median",
                                    tile_d=BENCH_TILE_D)

            wc = wire.pack_candidates(comp, qkeys, {"p": x})
            beta = _packed_beta(wc, N, d)
            wire_bytes = wire.measured_bits(wc) / 8.0
            sweeps = {"jnp": JNP_SWEEPS, "pallas": 1.0 + 2.0 * beta}
            us = {}
            for impl, fn in [("jnp", jax.jit(jnp_fn)), ("pallas", wire_fn)]:
                us[impl] = time_fn(fn, KEY, x, warmup=WARMUP, iters=ITERS)
                emit(f"compress/{impl}/{name}/n{N}/d{d}", us[impl],
                     f"sweeps={sweeps[impl]:.3f};wire_bytes={wire_bytes:.0f}")
                rows.append({"impl": impl, "compressor": name, "n": N,
                             "d": d, "us": us[impl],
                             "sweeps": sweeps[impl],
                             "wire_bytes_per_worker": wire_bytes})
            rows.append({"impl": "speedup", "compressor": name, "n": N,
                         "d": d, "beta": beta,
                         "measured_interp": us["jnp"] / us["pallas"],
                         "normalized": JNP_SWEEPS / (1.0 + 2.0 * beta)})
            _write(d, rows)


_ALL_ROWS = {}


def _write(d, rows):
    _ALL_ROWS[d] = rows
    payload = {
        "schema": 1,
        "note": ("sweeps = (n*d)-equivalent fp32 HBM traversals per round; "
                 "jnp = compress r+w, attack r+w, aggregate r (5); "
                 "wire = 1 + 2*beta with beta = packed_bytes/(n*d*4); "
                 "normalized speedup = 5/(1+2*beta) (bandwidth-bound TPU "
                 "ratio); wire_bytes_per_worker = semantic payload "
                 "(wire.measured_bits/8), conformance-pinned to "
                 "theory.comm_bits_per_round; measured us are CPU "
                 "interpret mode, same iters both impls"),
        "n": N,
        "sparse_ratio": SPARSE_RATIO,
        "rows": [r for rs in _ALL_ROWS.values() for r in rs],
    }
    os.makedirs(ART_DIR, exist_ok=True)
    with open(os.path.join(ART_DIR, "BENCH_compress.json"), "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
