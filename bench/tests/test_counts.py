"""The count functions against the program's own parameter count, its
blocking rule, and arithmetic done by hand."""

import harness
import pytest

import counts
from reference import qwen_dense as ref

CELLS = ("qwen2-0.5b.soi128-exact-every10",
         "qwen1.5-0.5b.soi1024-exact-every2")


@pytest.mark.parametrize("cell", CELLS)
def test_param_count_matches_the_program(cell):
    from repro.configs.base import ModelConfig

    c = harness.load_cell(cell)
    # the program's count adds the two norm scales of each layer
    norms = 2 * c.arch["n_layers"] * c.arch["d_model"]
    assert ref.param_count(c.arch) + norms == \
        ModelConfig(**c.config["program"]).param_count()


def test_param_count_by_hand():
    # qwen2-0.5b: 151936 x 896 tied embedding; per layer q 896x896,
    # k and v 896x128, o 896x896, three 896x4864 MLP matrices
    arch = harness.load_cell(CELLS[0]).arch
    per = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert ref.param_count(arch) == 151936 * 896 + 24 * per
    assert ref.param_count(arch) == 493_961_216


def test_flops_per_token_by_hand():
    arch = harness.load_cell(CELLS[0]).arch
    # 6 N + 3 passes x 2 matmuls (scores, mix) x 2 FLOPs x mean context
    # (T + 1) / 2 x 14 heads x 64 x 24 layers
    attn = 6 * 1025 * 14 * 64 * 24
    assert attn == 132_249_600
    assert ref.train_flops_per_token(arch, 1024) == \
        6 * 493_961_216 + attn


@pytest.mark.parametrize("d", [64, 128, 176, 896, 1024, 1408, 2816, 4864,
                               5000, 151936])
@pytest.mark.parametrize("cap", [32, 128, 1024])
def test_block_rule_matches_the_program(d, cap):
    from repro.core import soi

    assert ref.block_size_for(d, cap) == soi.block_size_for(d, cap)


def test_blocks_per_layer_by_hand():
    q2 = harness.load_cell(CELLS[0]).arch
    q15 = harness.load_cell(CELLS[1]).arch

    def tally(arch, cap):
        out = {}
        for nb, bs in ref.factor_blocks(arch, cap).values():
            out[bs] = out.get(bs, 0) + nb
        return out

    # qwen2 at 1024: A of q, o, gate and G of q, o, down are one
    # 896-block each; G of k, v one 128-block; G of gate, up and A of
    # down sixteen 304-blocks
    assert tally(q2, 1024) == {896: 6, 128: 2, 304: 48}
    # qwen1.5 at 1024 (MHA): eight 1024-blocks, 48 of 176
    assert tally(q15, 1024) == {1024: 8, 176: 48}
    # qwen2 at 128: 896 = 7 x 128, 4864 = 38 x 128
    assert tally(q2, 128) == {128: 6 * 7 + 2 + 3 * 38}


def test_inv_work_by_hand():
    flops, nbytes = counts.inv_work([(1024, 3), (176, 2)])
    assert flops == 2 * (3 * 1024 ** 3 + 2 * 176 ** 3)
    assert nbytes == 8 * (3 * 1024 ** 2 + 2 * 176 ** 2)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 1024-blocks: 2 n^3 / 8 n^2 = n / 4 = 256 FLOP/byte, over the
    # ridge of 197e12 / 819e9 = 240.5: compute-bound
    assert counts.least_time(*counts.inv_work([(1024, 1)]), peaks) == \
        pytest.approx(2 * 1024 ** 3 / 197e12)
    # 128-blocks: 32 FLOP/byte, memory-bound; four chips share it
    assert counts.least_time(*counts.inv_work([(128, 4)]), peaks, 4) == \
        pytest.approx(8 * 128 ** 2 / 819e9)
