"""The FLOP and byte counts of benchlib/flops.py against counts made by
hand from the configurations' published sizes."""
import json
import os
import types

import pytest

from benchlib import flops, harness
from tiny import load

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def arch(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["arch"]


def traffic(seq):
    return {"n_workers": 8, "per_worker_batch": 1, "seq_len": seq,
            "anchor_batches": 2}


def test_mamba2_130m_matmul_params():
    # per layer: w_in 768 x (2*1536 + 2*128 + 24) = 2,574,336
    #            w_out 1536 x 768                 = 1,179,648
    # 24 layers: 90,095,616; tied head 50,280 x 768 = 38,615,040
    assert flops.matmul_params(arch("mamba2-130m")) == (90_095_616,
                                                        38_615_040)


def test_mamba2_130m_round():
    # 6 x 128 tokens x (90,095,616 + 38,615,040) per sequence pass
    per_seq = 6 * 128 * (90_095_616 + 38_615_040)
    assert per_seq == 98_849_783_808
    a, t = arch("mamba2-130m"), traffic(128)
    assert flops.sequence_pass_flops(a, 128) == per_seq
    # a difference round: 2 passes x 8 workers x 1 sequence; a full round:
    # 1 pass x 8 workers x 2 anchor sequences: the same work
    assert flops.round_flops(a, t, full=False) == 16 * per_seq
    assert flops.round_flops(a, t, full=True) == 16 * per_seq


def test_musicgen_medium_cut_round():
    a = arch("musicgen-medium")
    # per layer: q, k, v, o 4 x 1536^2 + MLP 3 x 1536 x 6144 = 37,748,736
    # 3 layers 113,246,208; 4 codebook heads 4 x 1536 x 2048 = 12,582,912
    assert flops.matmul_params(a) == (113_246_208, 12_582_912)
    s_all = 1500 + 64                         # audio frames + conditioning
    layers = 6 * 113_246_208 * s_all          # 1,062,702,415,872
    head = 6 * 12_582_912 * 1500              # 113,246,208,000
    attn = 12 * 3 * s_all * 1536 * s_all      # 135,259,324,416
    assert layers + head + attn == 1_311_207_948_288
    assert flops.sequence_pass_flops(a, 1500) == 1_311_207_948_288
    assert flops.round_flops(a, traffic(1500), full=False) == (
        16 * 1_311_207_948_288)


def test_aggregation_bytes():
    # mamba2-130m holds 128,940,480 parameters: read 8 candidates, write 1
    assert flops.aggregation_bytes(8, 128_940_480, "bfloat16") == (
        9 * 128_940_480 * 2)


def test_mamba2_130m_parameter_count():
    import jax
    cell = harness.load_cell("mamba2-130m.vrmarina-randk")
    shapes = jax.eval_shape(
        lambda k: cell.model.init_params(k, cell.arch), jax.random.PRNGKey(0))
    assert sum(int(x.size) for x in jax.tree.leaves(shapes)) == 128_940_480


@pytest.mark.parametrize("full", [False, True])
@pytest.mark.parametrize("workload,want", [
    ("mamba2-130m.vrmarina-randk", 1_581_596_540_928),
    ("musicgen-medium.vrmarina-uncompressed", 20_979_327_172_608),
    ("mamba2-130m.vrmarina-uncompressed", 1_581_596_540_928),
    # 4 workers x 2 passes (or 2 anchor sequences) x 2,048 tokens: 8 x
    # 6 x 2,048 x 128,710,656
    ("mamba2-130m.vrmarina-a2a-4chip", 12_652_772_327_424),
])
def test_round_flops_of_each_cell(workload, want, full):
    # what step_mfu reads: the cell's own traffic and module
    cell = load(workload)
    assert flops.round_flops(cell.arch, cell.traffic, full,
                             cell.model) == want


MLA = {"name": "tiny-mla", "num_layers": 2, "d_model": 64,
       "vocab_size": 256, "block_pattern": ["mla"]}


def test_a_configuration_counts_its_own_layers():
    model = types.SimpleNamespace(
        sequence_pass_flops=lambda a, s: 1000.0 * a["num_layers"] * s)
    t = traffic(16)
    # a difference round: 2 passes x 8 sequences; a full round: 2 anchor
    # batches x 8 sequences
    assert flops.round_flops(MLA, t, False, model) == 16 * 32_000.0
    assert flops.round_flops(MLA, t, True, model) == 16 * 32_000.0


@pytest.mark.parametrize("model", [None, types.SimpleNamespace()])
def test_an_unknown_layer_kind_is_refused(model):
    with pytest.raises(harness.BenchError,
                       match="'tiny-mla'.*'mla'.*sequence_pass_flops"):
        flops.round_flops(MLA, traffic(16), False, model)
