"""repro.obs — the observability layer (DESIGN.md §5).

Four pieces, shared by ``api.runner``, ``repro.exec`` and ``repro.serve``:

* ``trace``   — ``RoundTrace``: per-round aggregator-decision telemetry
                (who the rule picked, how much each worker influenced the
                aggregate) emitted from the *same* backend calls that
                compute the aggregate, gated by ``RunSpec.trace``.
* ``detect``  — host-side detection-quality metrics against the ground-
                truth byzantine mask (filter precision/recall, influence
                leakage).
* ``sink``    — the ``MetricSink`` event protocol (JSONL stream, in-memory
                ring, fan-out) plus ``span``: a wall-clock section that is
                also a ``jax.profiler.TraceAnnotation`` on the device
                trace's clock, and never forces a device sync.
* ``profile`` — ``jax.profiler`` trace context, wired into the launch CLIs
                as ``--profile-dir``; round boundaries come from the
                runner's ``StepTraceAnnotation`` spans, and the names of
                the loop's spans and the step's device scopes.
"""
from repro.obs.detect import detection_metrics, filtered_mask, summarize
from repro.obs.sink import (FanoutSink, JsonlSink, MetricSink, NullSink,
                            RingSink, TagSink, span, verify_jsonl)
from repro.obs.trace import (RoundTrace, to_host, traced_ingest_message_phase,
                             traced_message_phase)

__all__ = [
    "RoundTrace", "traced_message_phase", "traced_ingest_message_phase",
    "to_host", "detection_metrics", "filtered_mask", "summarize",
    "MetricSink", "JsonlSink", "RingSink", "FanoutSink", "NullSink",
    "TagSink", "span", "verify_jsonl",
]
