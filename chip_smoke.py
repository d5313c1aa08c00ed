#!/usr/bin/env python3
"""Smoke run of the Byzantine-robust training round on a TPU.

Drives the main path once, the way a user does — ``repro.api.RunSpec`` ->
``build`` -> ``Experiment.run``, as ``repro.launch.train`` does — with
mamba2-130m at its published width (24 layers, d_model 768, vocab 50 280,
bf16; random weights from the seed): Byz-VR-MARINA over 8 workers, 2 of
them Byzantine (ALIE), coordinate-wise median over buckets of 2, RandK at
ratio 0.1. ``agg_mode="auto"`` must resolve to the compiled Pallas kernels;
the same spec under ``gspmd`` (plain XLA, the paper-faithful path) is the
reference it is compared with.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only: all_to_all on a (4, 1) mesh
                                       # vs gspmd; 4 workers, mamba2-130m's
                                       # reduced preset, uncompressed

Every check below must hold, or the script exits non-zero without printing
a result line:

  * JAX sees a TPU (the script never carries on on the CPU);
  * (one chip) the kernels the step does not reach at this model's width —
    the RandK sparse wire, ``pair_gram_blocked``, the median over a NaN
    worker — match their jnp references on the chip (``KERNEL_RTOL``);
  * the compiled step holds Mosaic kernels (``tpu_custom_call``), so no
    kernel ran in interpret mode;
  * the two aggregation backends agree round by round on identical inputs.
    g^0 (``method.init``'s uncompressed aggregate) within ``G_RTOL``
    (relative l2 over the tree). Each of the ``STEPS`` rounds runs both
    compiled steps from the reference's state g^k. In a difference round
    (c_k = 0: RandK-compressed gradient differences on one chip) the
    step's own g^{k+1} is within ``G_RTOL``, and the round's increment
    g^{k+1} - g^k, projected on the reference's, is 1 +- ``INC_RTOL`` of
    it; at least one compared round is one. A full-gradient round's g^{k+1}
    is a fresh median, smaller than the gradients it aggregates, so the
    two programs' bf16 backward passes differ visibly in it (2.4e-2 at
    full width on a v5e, where the kernels' aggregate of one set of
    gradients is within 1.1e-5 of an f32 reference): it is printed, and
    the round's aggregation is compared on one set of gradients within
    ``G_RTOL``. Candidates, attack and g are bf16
    and the backends round the bucket means differently, so they agree to
    bf16 precision, not bitwise. That rounding is not aligned with the
    increment and leaves the projection at 1, while an increment that is
    missing, mis-scaled or scrambled moves it by its own size;
  * every loss of the user's run is finite (the runs' trajectories are not
    compared: in bf16 they part within a few rounds);
  * (four chips) the compiled step places each worker's batch on its own
    device and exchanges worker slices by all-to-all over the four.

Times and memory printed on the way are a smoke observation of one run,
not a benchmark. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

STEPS = 5
SEQ_LEN = 128          # 8 workers x 1 sequence; MARINA's two gradient
PER_WORKER_BATCH = 1   # passes then fit one v5e's 16 GB
LR = 1e-4              # SGD step small enough that the attacked, RandK-
                       # compressed trajectory stays stable at full width
G_RTOL = 1e-2
INC_RTOL = 0.05        # bf16 reduced preset on the CPU: within 1.1e-3
KERNEL_RTOL = 2e-5     # f32 kernels vs their references, relative to the
                       # largest reference entry


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def base_spec(n_workers: int, n_byz: int, bucket_size: int, agg_mode: str,
              compressor: str = "randk", reduced: bool = False):
    from repro.api import RunSpec
    return RunSpec(
        task="lm", arch="mamba2-130m", method="marina",
        n_workers=n_workers, n_byz=n_byz, attack="ALIE", aggregator="cm",
        bucket_size=bucket_size, agg_mode=agg_mode, compressor=compressor,
        compressor_kwargs={"ratio": 0.1} if compressor == "randk" else {},
        lr=LR, steps=STEPS, seed=0,
        data_kwargs={"seq_len": SEQ_LEN, "reduced": reduced,
                     "per_worker_batch": PER_WORKER_BATCH})


def rel_l2(a, b) -> float:
    import jax
    import jax.numpy as jnp
    num = sum(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)
              for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(jnp.sum(y.astype(jnp.float32) ** 2)
              for y in jax.tree.leaves(b))
    return float(jnp.sqrt(num) / jnp.maximum(jnp.sqrt(den), 1e-30))


def tree_inc(new, old):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old)


def tree_dot(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return float(sum(jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def check_kernels(d: int = 1 << 20) -> None:
    """The compiled kernels that mamba2-130m's step does not reach, at real
    widths, against their references: the RandK sparse wire (the step's
    leaves exceed its 2^22-unit limit and take the dense compressor), the
    giant-n Gram tiles, and the coordinate median over a NaN worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import (ByzVRMarinaConfig, get_aggregator, get_attack,
                            wire)
    from repro.core import tree_utils as tu
    from repro.core.aggregators import coord_median
    from repro.core.compressors import get_compressor
    from repro.core.engine import apply_attack
    from repro.kernels import norm_agg
    from repro.kernels.robust_agg import robust_agg

    def check(name, got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        if not (np.isnan(got) == np.isnan(want)).all():
            fail(f"kernel {name}: NaN where the reference has none")
        ok = ~np.isnan(want)
        scale = float(np.abs(want[ok]).max())
        err = float(np.abs(got[ok] - want[ok]).max()) / max(scale, 1e-30)
        log(f"kernel {name}: max abs err / max |ref| {err:.3e} "
            f"(scale {scale:.4g}, tolerance {KERNEL_RTOL})")
        if not err <= KERNEL_RTOL:
            fail(f"kernel {name} disagrees with its reference")

    key = jax.random.PRNGKey(0)
    n = 8
    # MARINA's compressed round through the sparse wire: candidates
    # g^k + RandK(delta_i), two ALIE workers, CM over buckets of 2
    comp = get_compressor("randk", ratio=0.1)
    cfg = ByzVRMarinaConfig(
        n_workers=n, n_byz=2, attack=get_attack("ALIE"), compressor=comp,
        aggregator=get_aggregator("cm", bucket_size=2, n_byz=2),
        agg_mode="pallas")
    ks = jax.random.split(key, 6)
    delta = {"w": jax.random.normal(ks[0], (n, d)),
             "b": jax.random.normal(ks[1], (n, 768))}
    base = {"w": jax.random.normal(ks[2], (d,)),
            "b": jax.random.normal(ks[3], (768,))}
    qkeys = jax.random.split(ks[4], n)
    k_attack, k_agg = jax.random.split(ks[5])
    if not wire.wire_supported(cfg, delta):
        fail("the sparse wire does not take the kernel-check candidates")
    got = jax.jit(lambda dl, bs: wire.wire_message_phase(
        cfg, k_attack, k_agg, wire.pack_candidates(
            comp, qkeys, dl, base=bs, base_shared=True)))(delta, base)
    qs = jax.vmap(lambda kq, g: tu.compress_tree(comp, kq, g))(qkeys, delta)
    cand = jax.tree.map(lambda b, q: b[None] + q, base, qs)
    want = cfg.aggregator.tree(k_agg, apply_attack(cfg, k_attack, cand))
    for leaf in ("w", "b"):
        check(f"wire_agg randk cm bucketed alie [{leaf}, d {d}]",
              got[leaf], want[leaf])

    x = jax.random.normal(key, (256, d // 4))
    x64 = np.asarray(x, np.float64)
    check(f"pair_gram_blocked (256 x {d // 4}) vs float64 on the host",
          jax.jit(norm_agg.pair_gram_blocked)(x), x64 @ x64.T)

    x = jax.random.normal(key, (n, d)).at[3].set(jnp.nan)
    x = x.at[:6, :128].set(jnp.nan)         # NaN reaches the median ranks
    check("robust_agg median, one NaN worker",
          jax.jit(lambda a: robust_agg(a, rule="median"))(x),
          coord_median(x))


def inspect_step(exp, args, *, expect_kernels: bool, spans: int = 1):
    """Compile the runner's step for ``args`` and check its program.
    ``spans`` > 1: the step must be one program over that many devices,
    each holding one worker, exchanging worker slices by all-to-all."""
    import jax
    mode = exp.spec.agg_mode
    t0 = time.perf_counter()
    compiled = exp.step.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    n_kernels = hlo.count("tpu_custom_call")
    if spans > 1:
        check_worker_shards(compiled, exp.spec.n_workers, args[1])
        used = {d.id for s in jax.tree.leaves(compiled.output_shardings)
                for d in s.device_set}
        log(f"{mode}: step program spans devices {sorted(used)}, "
            f"{hlo.count('all-to-all(')} all-to-all op(s)")
        if len(used) != spans or "all-to-all(" not in hlo:
            fail(f"{mode}: step does not spread the workers over "
                 f"{spans} devices")
    mem = compiled.memory_analysis()
    log(f"{mode}: step compile {compile_s:.1f} s, {n_kernels} Mosaic "
        f"kernel call(s); step program bytes: arguments "
        f"{mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}, "
        f"temporaries {mem.temp_size_in_bytes}")
    if expect_kernels and not n_kernels:
        fail(f"{mode}: compiled step holds no tpu_custom_call — a kernel "
             "ran in interpret mode")
    return compiled


def check_worker_shards(compiled, n: int, batch) -> None:
    """Where the compiled step puts each worker's minibatch: one worker per
    device, each on its own."""
    import jax
    seen = {}
    for sh, leaf in zip(jax.tree.leaves(compiled.input_shardings[0][1]),
                        jax.tree.leaves(batch)):
        for dev, idx in sh.devices_indices_map(leaf.shape).items():
            rows = range(*idx[0].indices(leaf.shape[0]))
            if len(rows) != 1:
                fail(f"device {dev.id} holds workers {list(rows)}")
            seen.setdefault(rows[0], set()).add(dev.id)
    devs = [sorted(seen.get(w, ())) for w in range(n)]
    log("worker -> device: " + ", ".join(
        f"{w} -> {ds}" for w, ds in enumerate(devs)))
    if any(len(ds) != 1 for ds in devs) or len(
            {ds[0] for ds in devs}) != n:
        fail("the workers' batches do not sit one per device")


def agree(got_exp, ref_exp, *, expect_kernels: bool, spans: int = 1):
    """Each round of ``got_exp``'s compiled step against ``ref_exp``'s, both
    fed the reference's state: g^0, then g^{k+1} and its increment."""
    import jax
    name, ref = got_exp.spec.agg_mode, ref_exp.spec.agg_mode
    g0 = got_exp.start()[0]["g"]
    state, k_run = ref_exp.start()
    g_err = rel_l2(g0, state["g"])
    log(f"{name} vs {ref}: g^0 rel. l2 {g_err:.3e} (tolerance {G_RTOL})")
    if not g_err <= G_RTOL:
        fail(f"{name} and {ref} disagree on g^0")
    del g0
    args = ref_exp.step_args(state, 0, k_run)
    steps = {}
    for e, kern, sp in ((got_exp, expect_kernels, spans), (ref_exp, False, 1)):
        c = inspect_step(e, args, expect_kernels=kern, spans=sp)
        steps[e.spec.agg_mode] = (c, c.input_shardings[0])
    n_vr = 0
    for it in range(got_exp.spec.steps):
        args = ref_exp.step_args(state, it, k_run)
        g_in = state["g"]
        c, sh = steps[name]
        out, m_got = c(*jax.device_put(args, sh))
        g_got = out["g"]
        del out
        c, sh = steps[ref]
        state, m_ref = c(*jax.device_put(args, sh))
        c_k = int(m_ref["c_k"])
        if int(m_got["c_k"]) != c_k:
            fail(f"round {it}: {name} and {ref} drew different coins")
        n_vr += c_k == 0
        g_err = rel_l2(g_got, state["g"])
        inc_got, inc_ref = tree_inc(g_got, g_in), tree_inc(state["g"], g_in)
        ref_sq = tree_dot(inc_ref, inc_ref)
        proj = tree_dot(inc_got, inc_ref) / max(ref_sq, 1e-30)
        gated = "" if c_k else " (gated)"
        log(f"{name} vs {ref}, round {it} "
            f"({'full-gradient' if c_k else 'difference'} round): "
            f"g^{it + 1} rel. l2 {g_err:.3e}{gated}; increment rel. l2 "
            f"{rel_l2(inc_got, inc_ref):.3e}, along the reference's "
            f"{proj:.6f}{gated}; |increment| / |g^{it}| "
            f"{math.sqrt(ref_sq / max(tree_dot(g_in, g_in), 1e-30)):.3e}; "
            f"loss {float(m_got['loss'])!r} vs {float(m_ref['loss'])!r}")
        del inc_got, inc_ref, g_got
        if c_k:
            # a fresh median of gradients, smaller than the gradients it
            # aggregates: bf16 differences between the two step programs'
            # backward passes reach it amplified, so the aggregation is
            # compared on one set of gradients
            ok = shared_grads_agreement(got_exp, ref_exp, args) <= G_RTOL
        else:
            ok = g_err <= G_RTOL and abs(proj - 1) <= INC_RTOL
        if not ok:
            fail(f"round {it}: {name} and {ref} disagree")
    if not n_vr:
        fail("no difference round (c_k = 0) was compared")


def shared_grads_agreement(got_exp, ref_exp, args) -> float:
    """A full-gradient round's message phase (attack, bucketing, CM) under
    both backends on one set of gradients: the round's own x^{k+1},
    anchor batch and keys, as in the step."""
    import jax
    from repro.core import tree_utils as tu
    from repro.core.engine import (maybe_corrupt, message_phase,
                                   param_update, stacked_grads)
    state, _, anchor, k_step = args
    cfg, rng = ref_exp.cfg, ref_exp.method.estimator.rng
    keys = dict(zip(rng, jax.random.split(k_step, len(rng))))
    wkeys = tu.per_worker_keys(keys["grad"], cfg.n_workers)
    x = param_update(cfg, state["params"], state["g"], state["opt_state"])[0]
    grads = jax.jit(lambda p, a: stacked_grads(ref_exp.loss_fn, p, a,
                                               wkeys)[1])(
        x, maybe_corrupt(cfg, ref_exp.corrupt_fn, anchor))
    got, want = (jax.jit(lambda g, c=e.cfg: message_phase(
        c, keys["attack"], keys["agg"], g))(grads) for e in (got_exp, ref_exp))
    err = rel_l2(got, want)
    log(f"{got_exp.spec.agg_mode} vs {ref_exp.spec.agg_mode}: the round's "
        f"aggregate on one set of gradients rel. l2 {err:.3e} (tolerance "
        f"{G_RTOL})")
    return err


def user_run(exp) -> None:
    t0 = time.perf_counter()
    result = exp.run(log_every=1, warmup=True)
    losses = [h["loss"] for h in result.history]
    log(f"{exp.spec.agg_mode}: run of {exp.spec.steps} steps in "
        f"{result.wall_s:.2f} s ({result.wall_s / exp.spec.steps:.3f} s/step "
        f"after warm-up; {time.perf_counter() - t0:.1f} s with init and "
        f"warm-up), losses {losses}")
    if len(losses) != exp.spec.steps or not all(map(math.isfinite, losses)):
        fail(f"{exp.spec.agg_mode}: losses not finite: {losses}")


def one_chip(dev) -> None:
    from repro.api import build, resolve_agg_mode
    mode = resolve_agg_mode("auto")
    if mode != "pallas":
        fail(f"agg_mode 'auto' resolved to {mode!r}, not 'pallas'")
    check_kernels()
    spec = base_spec(8, 2, 2, mode)
    pallas, gspmd = build(spec), build(spec.replace(agg_mode="gspmd"))
    agree(pallas, gspmd, expect_kernels=True)
    user_run(pallas)
    user_run(gspmd)
    stats = dev.memory_stats() or {}
    log(f"device peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def four_chips(devs) -> None:
    from repro.api import build
    if len(devs) != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    # what runs across chips is the sharded aggregation: its placement and
    # collectives. The reduced preset and no compression keep the compile
    # of both programs out of a call charged four times over.
    spec = base_spec(4, 1, 1, "all_to_all", compressor="identity",
                     reduced=True)
    a2a, gspmd = build(spec), build(spec.replace(agg_mode="gspmd"))
    agree(a2a, gspmd, expect_kernels=True, spans=4)
    user_run(a2a)
    user_run(gspmd)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the all_to_all path on four chips and "
                         "its gspmd reference")
    args = ap.parse_args()
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package at {src}: run from a checkout of the repo")
    sys.path.insert(0, src)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(devs)
    else:
        one_chip(dev)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s "
        "(smoke observation, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
