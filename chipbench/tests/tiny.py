"""A cell at a size a CPU test run holds: the real configuration's block
and the real traffic mix, with small widths, depth and sequence."""
import json
import os

from benchlib import harness

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = {
    "mamba2": dict(num_layers=2, d_model=64, vocab_size=256, ssm_state=16,
                   ssm_headdim=16),
    "attention": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                      head_dim=16, d_ff=128, vocab_size=64,
                      frontend_tokens=4),
}


# the uncompressed mamba2 mix, not yet a cell of BENCHMARK.json: the RandK
# cell's configuration under its traffic file
UNCOMPRESSED = "mamba2-130m.vrmarina-uncompressed"


def load(workload):
    """``harness.load_cell``, and the uncompressed mamba2 mix."""
    if workload != UNCOMPRESSED:
        return harness.load_cell(workload)
    cell = harness.load_cell("mamba2-130m.vrmarina-randk")
    with open(os.path.join(harness.BENCH_DIR, "traffic",
                           "vrmarina-identity-s128x1.json")) as f:
        cell.traffic = json.load(f)
    cell.name = UNCOMPRESSED
    return cell


def tiny_cell(workload="mamba2-130m.vrmarina-randk", seq_len=32):
    cell = load(workload)
    arch = dict(cell.arch, **SMALL[cell.arch["block_pattern"][0]])
    arch["name"] = arch["name"] + "-tiny"
    cell.arch = arch
    cell.traffic = dict(cell.traffic, seq_len=seq_len)
    with open(os.path.join(HERE, "data", "limits-tiny.json")) as f:
        cell.limits = json.load(f)
    return cell
