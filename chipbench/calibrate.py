#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, read in one process.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 --fault-seeds 1,2,3 \
        [--exchange-fault-seeds 1,2,3] [--witness-seeds 1,2]

For each seed of ``--seeds``: the program's first rounds (the same compiled
step and feed as a run, re-seeded), then the float32 reference; the gaps
between them are the program's readings (the lower end of each limit).
For each seed of ``--control-seeds``: the control (the reference with its
weight products in float8) against the float32 reference. For each of
``--fault-seeds``: the reference with half of every sequence's labels
left out against the whole. For each of ``--exchange-fault-seeds``: the
reference with the first worker's message in the aggregate's place (what
the first chip holds of the ``all_to_all`` aggregation without its
exchange) against the whole. For each of ``--witness-seeds``: the
reference computed in the configuration's own bfloat16 rounding, against
the float32 reference and against the program (a look at where a gap
comes from). One JSON line per reading on stdout, with the leaf norms
behind it, and a
summary last: the largest program reading and the smallest control and
fault reading of each number. A step that returns its state unchanged
reads 1 in ``dx_gap`` by the measure's definition and needs no run.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def calibrate(cell, seeds, control_seeds, fault_seeds, witness_seeds=(),
              exchange_fault_seeds=(), *, require_tpu=True, emit=print):
    from benchlib import check, harness
    from benchlib.refround import Reference

    harness.check_devices(cell.chips, require_tpu)
    harness.enable_cache()
    spec_seed, _, _, k_data = harness.seed_keys(seeds[0])
    exp = harness.build_program(cell, spec_seed)
    init, _ = harness.wire_feed(exp, cell, k_data)
    refs = {"f32": Reference(cell.model, cell.arch, cell.traffic)}
    if control_seeds:
        refs["control"] = Reference(cell.model, cell.arch, cell.traffic,
                                    "fp8")
    if witness_seeds:
        refs["witness"] = Reference(cell.model, cell.arch, cell.traffic,
                                    "bf16")
    if fault_seeds:
        refs["half_batch"] = Reference(
            cell.model, cell.arch, cell.traffic,
            batch_fault=functools.partial(harness.halve_labels, axis=1))
    if exchange_fault_seeds:
        refs["no_exchange"] = Reference(cell.model, cell.arch, cell.traffic,
                                        agg_fault="no_exchange")
    readings = []
    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds)
                       | set(witness_seeds) | set(exchange_fault_seeds)):
        traffic, k_init = harness.reseed(exp, cell, seed)
        _, _, k_run, _ = harness.seed_keys(seed)
        runs = []
        if seed in seeds:
            prog, state, _, _, _, _ = harness.check_rounds(
                exp, lambda k: harness.Schedule(k, cell.traffic["p"]))
            del state
            runs.append(("program", prog))
        rounds = harness.Schedule(k_run, cell.traffic["p"]).check
        params0 = init(k_init)
        t = time.perf_counter()
        ref = refs["f32"].run(params0, traffic, k_run, rounds)
        harness.log(f"seed {seed}: reference {time.perf_counter() - t:.1f} s")
        for kind, seed_set in (("control", control_seeds),
                               ("half_batch", fault_seeds),
                               ("no_exchange", exchange_fault_seeds),
                               ("witness", witness_seeds)):
            if seed in seed_set:
                runs.append((kind, refs[kind].run(params0, traffic, k_run,
                                                  rounds)))
        if seed in witness_seeds and seed in seeds:
            runs.append(("program_vs_witness", runs[0][1], runs[-1][1]))
        for kind, got, *against in runs:
            base = against[0] if against else ref
            nums = check.gaps(got, base)
            if got["c_k"] != base["c_k"]:
                nums["c_k_mismatch"] = True
            rec = {"seed": seed, "kind": kind, "numbers": nums,
                   "c_k": got["c_k"],
                   "leaves": {k: got[k] for k in ("losses", "g0", "dx",
                                                   "step")}}
            if not against:
                rec["ref"] = {k: ref[k] for k in ("losses", "g0", "dx",
                                                  "step")}
            if kind == "program":
                rec["ref"]["spread"] = ref["spread"]
            readings.append(rec)
            emit(json.dumps(rec))
    summary = {}
    for name in check.NUMBERS:
        prog = [r["numbers"][name] for r in readings if r["kind"] == "program"]
        summary[name] = {"lower": max(prog) if prog else None}
        for kind in ("control", "half_batch", "no_exchange"):
            vals = [r["numbers"][name] for r in readings if r["kind"] == kind]
            summary[name][kind] = min(vals) if vals else None
    emit(json.dumps({"summary": summary}))
    return readings, summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--exchange-fault-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from benchlib import harness

    def seeds(s):
        return [int(x) for x in s.split(",") if x]

    try:
        calibrate(harness.load_cell(args.workload), seeds(args.seeds),
                  seeds(args.control_seeds), seeds(args.fault_seeds),
                  seeds(args.witness_seeds), seeds(args.exchange_fault_seeds),
                  emit=lambda line: print(line, flush=True))
    except harness.BenchError as e:
        print(f"[bench] error: {e}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
