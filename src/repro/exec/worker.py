"""Subprocess worker entry point for the sweep scheduler.

``python -m repro.exec.worker --spec cell.spec.json --out cell.json``
runs ONE sweep cell in a fresh process and writes the artifact JSON
(``RunResult.to_dict()``) atomically. The scheduler launches this with
per-worker ``CUDA_VISIBLE_DEVICES`` / ``JAX_PLATFORMS`` already pinned in
the environment — device selection must happen before jax initializes,
which is exactly why un-batchable cells get a process each. Exit code 0
means the artifact was written; anything else (traceback on stderr) is a
failed cell the scheduler records and isolates.

``--fault crash|hang`` is the chaos layer's process-site injection
(repro.faults, DESIGN.md §6): the scheduler passes it on a cell's FIRST
attempt only, so the retry path must absorb an abrupt kill (exit 137,
before any artifact is written) or a hang (the pool's escalating timeout
reaps it) and the eventual artifact stays byte-identical to a fault-free
run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CRASH_EXIT_CODE = 137     # what a SIGKILLed worker would report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run one sweep cell")
    ap.add_argument("--spec", required=True,
                    help="path to the cell's RunSpec JSON")
    ap.add_argument("--out", required=True,
                    help="artifact path for RunResult.to_dict() JSON")
    ap.add_argument("--run-kw", default="{}",
                    help="JSON dict of loop knobs (log_every, warmup, ...)")
    ap.add_argument("--fault", choices=("crash", "hang"), default=None,
                    help="injected process fault (repro.faults chaos layer)")
    args = ap.parse_args(argv)

    if args.fault == "crash":
        print("repro.faults: injected crash (worker dies before running)",
              file=sys.stderr, flush=True)
        return CRASH_EXIT_CODE
    if args.fault == "hang":
        import time
        print("repro.faults: injected hang (worker sleeps until reaped)",
              file=sys.stderr, flush=True)
        while True:
            time.sleep(3600)

    from repro.api import RunSpec, run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    with open(args.spec) as f:
        spec = RunSpec.from_json(f.read())
    result = run(spec, **json.loads(args.run_kw))

    payload = result.to_dict()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    tmp = args.out + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
