"""Span profiling: jax.profiler traces + the XLA step-marker idiom.

``profile_trace(dir)`` wraps a run in ``jax.profiler.trace`` so the launch
CLIs can dump a TensorBoard-loadable device trace with ``--profile-dir``.
``enable_step_markers()`` sets XLA's step-marker location to the outer
while loop (the training step; the default marks the program entry) so
profiler timelines show per-step boundaries; it must run before the first
backend touch, which is why the CLIs call it at parse time rather than
inside the run. The flag belongs in ``XLA_FLAGS``, spelled with its enum
name: jaxlib parses ``XLA_FLAGS`` on every backend and aborts the process
on a numeric value (``=1``), and libtpu's ``LIBTPU_INIT_ARGS`` does not
know the flag at all.
"""
from __future__ import annotations

import contextlib
import os


STEP_MARKER_FLAG = (
    "--xla_step_marker_location=STEP_MARK_AT_TOP_LEVEL_WHILE_LOOP")


def enable_step_markers() -> None:
    """Prepend the step-marker flag to XLA_FLAGS (idempotent). No-op once
    the backend is initialized — call before any jax import touches it."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_step_marker_location" in flags:
        return
    os.environ["XLA_FLAGS"] = (STEP_MARKER_FLAG + (" " + flags if flags
                                                  else ""))


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
                         "(TensorBoard-loadable) with XLA step markers")
