"""The ``--trace 1`` side of a run: from the traced window to the cell's
per-layer metrics, the device's busy and window seconds, and the
breakdown (the device operations that took most time, and the longest
idle gaps by what the host's Python thread was in)."""
from __future__ import annotations

import dataclasses
import os
import shutil

from benchlib import flops, harness, trace

TOP = 10


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    layer_ns: dict          # device self time per layer over the window
    rounds: int
    full_rounds: int
    window_s: float         # host clock, the traced window
    busy_s: float           # union of device op intervals, per chip
    trace_window_s: float   # first to last device op of the step program
    window_flops: float     # model FLOPs the window's rounds need
    agg_bytes: float        # least HBM bytes of one round's aggregation
    peaks: dict

    def layer_ms_per_round(self, layer):
        ns = self.layer_ns.get(layer)
        if not ns:
            return None
        return ns / 1e6 / self.rounds


def reduce(tdir, hlo_text, cell, rounds, coins, window_s, peaks):
    pd = trace.load(tdir)
    dev = trace.device_events(pd)
    if not dev:
        raise harness.BenchError("the trace holds no device op events")
    layers = trace.load_layers(os.path.join(harness.BENCH_DIR, "layers"))
    stacks = {trace.hlo_module_name(hlo_text): trace.hlo_stacks(hlo_text)}
    per_plane = [trace.layer_times(evs, stacks, layers)
                 for evs in dev.values()]
    layer_ns = {}
    for lt in per_plane:
        for k, v in lt.items():
            layer_ns[k] = layer_ns.get(k, 0.0) + v / len(per_plane)
    busy_ns = sum(trace.union_ns(evs) for evs in dev.values()) / len(dev)
    first = min(evs[0][0] for evs in dev.values())
    last = max(max(e for _, e, *_ in evs) for evs in dev.values())
    n_full = sum(coins)
    t = cell.traffic
    window_flops = (
        n_full * flops.round_flops(cell.arch, t, True, cell.model)
        + (rounds - n_full) * flops.round_flops(cell.arch, t, False,
                                                cell.model))
    n_params = _n_params(cell)
    ctx = Context(layer_ns=layer_ns, rounds=rounds, full_rounds=n_full,
                  window_s=window_s, busy_s=busy_ns / 1e9,
                  trace_window_s=(last - first) / 1e9,
                  window_flops=window_flops,
                  agg_bytes=flops.aggregation_bytes(
                      t["n_workers"], n_params, cell.arch["dtype"]),
                  peaks=peaks)
    total = sum(layer_ns.values()) or 1.0
    harness.log("device time by layer: " + ", ".join(
        f"{k} {v / 1e6:.3f} ms ({100 * v / total:.1f}%)"
        for k, v in sorted(layer_ns.items(), key=lambda kv: -kv[1])))
    harness.log(f"unattributed share of the step program's device time: "
                f"{100 * layer_ns.get('unattributed', 0.0) / total:.2f}%")

    metrics = {}
    for m in cell.per_layer:
        reader = harness.load_module(
            os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py"),
            "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    ops = {}
    name_of = {}
    for evs in dev.values():
        for mod, op, ns in trace.self_times(evs):
            key = f"{mod}:{op}"
            ops[key] = ops.get(key, 0.0) + ns / 1e9 / len(dev)
            if key not in name_of:
                st = stacks.get(trace.hlo_module_name(hlo_text), {})
                layer = trace.classify(st.get(op, []), layers) if (
                    mod.startswith(trace.hlo_module_name(hlo_text))) else None
                name_of[key] = f"{key} [{layer or '-'}]"
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    plane = next(iter(dev.values()))
    host = trace.host_events(pd)
    gaps = sorted(trace.idle_gaps(plane, first, last),
                  key=lambda g: g[0] - g[1])[:TOP]
    idle = [[_host_at(host, (s + e) / 2), (e - s) / 1e9] for s, e in gaps]
    shutil.rmtree(tdir, ignore_errors=True)
    return {"metrics": metrics, "busy_s": ctx.busy_s,
            "window_s": ctx.window_s,
            "breakdown": {"device_ops": [[name_of[k], v] for k, v in top_ops],
                          "idle_gaps": idle}}


def _host_at(host, t):
    """The innermost Python event of the host at time ``t``."""
    best = None
    for s, e, name, depth in host:
        if s <= t < e and (best is None or depth > best[1]):
            best = (name, depth)
    return best[0] if best else "(no host event)"


def _n_params(cell):
    import jax
    shapes = jax.eval_shape(lambda k: cell.model.init_params(k, cell.arch),
                            jax.random.PRNGKey(0))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))
