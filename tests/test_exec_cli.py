"""launch/sweep CLI + subprocess worker pool (pool test is slow: it spawns
fresh jax processes)."""
import json
import os

import pytest

from repro import exec as xc
from repro.api import RunSpec, Sweep
from repro.launch import sweep as sweep_cli

BASE_KW = dict(task="logreg", method="marina", n_workers=5, n_byz=1, p=0.3,
               lr=0.25, attack="ALIE", aggregator="cm", bucket_size=2,
               steps=3,
               data_kwargs={"n_samples": 60, "dim": 8, "batch_size": 8})


def _base_path(tmp_path):
    path = tmp_path / "base.json"
    path.write_text(RunSpec(**BASE_KW).to_json())
    return str(path)


def test_cli_list_expands_grid(tmp_path, capsys):
    out = sweep_cli.main(["--base", _base_path(tmp_path),
                          "--grid", '{"aggregator": ["mean", "cm"]}',
                          "--seeds", "0:2", "--list"])
    assert out is None
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 4
    assert "aggregator=mean__seed=0" in lines


def test_cli_runs_grid_and_writes_summary(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_ART_DIR", str(tmp_path / "bench"))
    out_dir = tmp_path / "cells"
    summary = sweep_cli.main([
        "--base", _base_path(tmp_path),
        "--grid", '{"aggregator": ["mean", "cm"]}', "--seeds", "0:2",
        "--out-dir", str(out_dir), "--name", "clitest", "--log-every", "3"])
    assert summary["n_cells"] == 4 and summary["n_groups"] == 2
    assert (out_dir / "ledger.jsonl").exists()
    assert (out_dir / "clitest_summary.json").exists()
    with open(tmp_path / "bench" / "clitest_summary.json") as f:
        assert json.load(f) == summary
    # resume: everything skips, summary identical bytes
    summary2 = sweep_cli.main([
        "--base", _base_path(tmp_path),
        "--grid", '{"aggregator": ["mean", "cm"]}', "--seeds", "0:2",
        "--out-dir", str(out_dir), "--name", "clitest", "--log-every", "3",
        "--resume"])
    assert json.dumps(summary, sort_keys=True) == \
           json.dumps(summary2, sort_keys=True)


def test_cli_set_overrides_and_seed_parsing():
    args = sweep_cli.build_parser().parse_args(
        ["--set", "lr=0.1", "--set", "attack=BF",
         "--set", "data_kwargs.dim=8", "--seeds", "0,2,5"])
    sweep = sweep_cli.sweep_from_args(args)
    assert sweep.base.lr == 0.1 and sweep.base.attack == "BF"
    assert sweep.base.data_kwargs["dim"] == 8
    assert sweep.grid["seed"] == (0, 2, 5)


@pytest.mark.slow
def test_worker_pool_subprocess_cells(tmp_path):
    """Un-batchable cells shard over pinned worker subprocesses; a bad cell
    fails in isolation."""
    cells = list(Sweep(RunSpec(**BASE_KW),
                       {"aggregator": ("mean", "cm")}).expand())
    pool = xc.WorkerPool(max_workers=2, timeout_s=300, jax_platform="cpu")
    srun = xc.run_cells(cells, out_dir=str(tmp_path), pool=pool,
                        batch=False, run_kw={"log_every": 3})
    assert not srun.failures
    assert srun.stats["subprocess_cells"] == 2
    for rid, _ in cells:
        assert srun[rid].history                      # loaded CompletedCell
        assert os.path.exists(tmp_path / f"{rid}.json")
    led = xc.Ledger(str(tmp_path / "ledger.jsonl"))
    assert led.completed() == {rid for rid, _ in cells}


@pytest.mark.parametrize("backend,platform,workers,refused", [
    ("cpu", None, 2, False),     # no chip anywhere
    ("tpu", None, 2, True),      # parent holds the chip, workers want it
    ("tpu", "cpu", 2, False),    # workers pinned off the chip
    ("cpu", "tpu", 2, True),     # two workers would share one chip
    ("cpu", "tpu", 1, False),    # one worker at a time owns it
])
def test_worker_pool_keeps_one_process_per_chip(monkeypatch, backend,
                                                platform, workers, refused):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    pool = xc.WorkerPool(max_workers=workers, jax_platform=platform)
    if refused:
        with pytest.raises(RuntimeError, match="WorkerPool"):
            pool.check_chip_owner()
    else:
        pool.check_chip_owner()


def test_run_cells_refuses_pool_on_held_chip(monkeypatch, tmp_path):
    """On a TPU host the pool is refused before any cell starts."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    cells = list(Sweep(RunSpec(**BASE_KW), {}).expand())
    with pytest.raises(RuntimeError, match="one process per chip"):
        xc.run_cells(cells, out_dir=str(tmp_path),
                     pool=xc.WorkerPool(max_workers=2), batch=False)
    assert not os.path.exists(tmp_path / "ledger.jsonl")
