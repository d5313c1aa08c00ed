"""Benchmark harness — one module per paper table/figure.

  fig1      Fig. 1  — 3 aggregators x 5 attacks optimality gaps (+ RandK)
  table2    Tbl. 2  — rounds-to-epsilon, Byz-VR-MARINA vs baselines
  fig8      Fig. 8  — optimality gap vs transmitted bits
  agg       (system) aggregation throughput, jnp vs Pallas, ALL five rules
            x bucketing; analytic HBM-sweep roofline accounting ->
            experiments/bench/BENCH_agg.json (the aggregator-perf
            trajectory, uploaded by the CI bench job)
  compress  (system) message path per wire format: jnp Compressor vs fused
            Pallas wire, measured wire bytes + HBM-sweep roofline ->
            experiments/bench/BENCH_compress.json (CI bench job)
  roofline  §Roofline terms from the dry-run artifacts
  sweep     (system) sweep engine: serial vs vmapped-batched grid execution
  serve     (system) buffered-async aggregation service: updates/sec +
            p50/p99 round latency, {gspmd, pallas} x {mean, krum} x
            buffer {64, 256} -> experiments/bench/BENCH_serve.json
            (CI bench job)
  obs       (system) telemetry overhead: steps/sec with the RoundTrace
            twin ON vs OFF, {gspmd, pallas} x {mean, krum, rfa} ->
            experiments/bench/BENCH_obs.json (CI bench job; bar is
            <= 5% overhead at log_every=10)
  faults    (system) fault-guard overhead: steps/sec with the fail-closed
            guard ON (live nan_grad plan) vs OFF, {gspmd, pallas} x
            {cm, krum, rfa} -> experiments/bench/BENCH_faults.json
            (CI chaos job)

Prints ``name,us_per_call,derived`` CSV. Select a subset with argv, e.g.
``python -m benchmarks.run fig1 roofline``. A failed suite prints
``<name>/SUITE-FAILED`` and the others still run; the exit code is then 1.
"""
import sys
import traceback


def main() -> int:
    from benchmarks import (bench_ablations, bench_aggregators,
                            bench_compressors, bench_faults, bench_fig1,
                            bench_fig8, bench_obs, bench_roofline,
                            bench_serve, bench_sweep, bench_table2,
                            bench_trainer)
    suites = {
        "ablate": bench_ablations.run,
        "sweep": bench_sweep.run,
        "trainer": bench_trainer.run,
        "agg": bench_aggregators.run,
        "compress": bench_compressors.run,
        "serve": bench_serve.run,
        "obs": bench_obs.run,
        "faults": bench_faults.run,
        "fig1": bench_fig1.run,
        "table2": bench_table2.run,
        "fig8": bench_fig8.run,
        "roofline": bench_roofline.run,
    }
    chosen = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    failed = []
    for name in chosen:
        try:
            suites[name]()
        except Exception as e:  # noqa: BLE001 — a broken suite must not
            traceback.print_exc()  # silence the others
            print(f"{name}/SUITE-FAILED,0,{type(e).__name__}: {e}")
            failed.append(name)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
