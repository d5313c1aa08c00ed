#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, and print its result.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``; its
configuration, traffic mix and correctness limits are the files of that
name under ``configs/``, ``traffic/`` and ``limits/``. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each number compared with its limit;
they are also the last lines of standard error). Without a TPU, or with
fewer chips than the cell asks for, it exits with 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[bench] error: no program at {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, src]
    from benchlib import harness
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_process=T_PROCESS)
    except harness.BenchError as e:
        print(f"[bench] error: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(_finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
