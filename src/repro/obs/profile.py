"""Device traces for the launch CLIs, and the names a trace carries.

``profile_trace(dir)`` wraps a run in ``jax.profiler.trace`` so the launch
CLIs can dump a TensorBoard-loadable device trace with ``--profile-dir``.
The trace needs no compiler flag: the program names its own parts.

* Host spans (``obs.sink.span``, a ``jax.profiler.TraceAnnotation`` each):
  the training loop (``api.runner``) runs every round under a
  ``StepTraceAnnotation`` named ``round`` (its ``step_num`` is the round
  index), with ``feed``, ``dispatch``, ``log`` and ``checkpoint`` inside.
* Device scopes (``jax.named_scope``, read back from each op's HLO
  ``op_name``): ``LAYER_SCOPES`` inside the round step, and MARINA's
  ``ROUND_SCOPES`` around the two branches of its coin.
"""
from __future__ import annotations

import contextlib
import os

LAYER_SCOPES = ("grad", "compress", "attack", "aggregate", "update")
ROUND_SCOPES = ("full_round", "diff_round")
LOOP_SPANS = ("round", "feed", "dispatch", "log", "checkpoint")


@contextlib.contextmanager
def profile_trace(profile_dir=None):
    """``jax.profiler.trace`` context when ``profile_dir`` is set; a
    nullcontext otherwise, so call sites can wrap unconditionally."""
    if not profile_dir:
        yield
        return
    import jax
    os.makedirs(profile_dir, exist_ok=True)
    with jax.profiler.trace(profile_dir):
        yield


def add_cli_args(ap) -> None:
    """The shared observability CLI surface for the launch drivers."""
    ap.add_argument("--metrics-out-jsonl", metavar="PATH",
                    help="append metric events (rounds, traces, spans) as "
                         "one JSON line each — the obs.sink stream")
    ap.add_argument("--profile-dir", metavar="DIR",
                    help="dump a jax.profiler device trace here "
                         "(TensorBoard-loadable); round boundaries come "
                         "from the loop's StepTraceAnnotation spans")
