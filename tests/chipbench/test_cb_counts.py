"""The operation and byte counters against hand sums at the paper's
shapes (d = 2048, 16 heads of 128, 16 experts of 2048, vocab 50304, two
layers, seq 1024)."""
import json

import pytest

from chipbench import counts
from cb_support import ROOT


def _model(top_k=1):
    m = json.loads((ROOT / "chipbench/configs/hetumoe-paper-16e.json")
                   .read_text())["model"]
    return dict(m, experts_per_token=top_k)


def test_step_model_flops_switch():
    # per token, per layer: q, k, v, o projections 4 * 2 * 2048 * 2048,
    # router 2 * 2048 * 16, one expert 2 * 2 * 2048 * 2048
    layer = 4 * 2 * 2048 * 2048 + 2 * 2048 * 16 + 2 * 2 * 2048 * 2048
    head = 2 * 2048 * 50304
    tokens = 8 * 1024
    attn = 2 * 4 * tokens * 512 * 16 * 128          # causal half, 2 layers
    want = 3 * (tokens * (2 * layer + head) + attn)
    got = counts.step_model_flops(_model(), 8, 1024)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(7.747e12, rel=1e-4)


def test_step_model_flops_gshard_counts_two_experts():
    extra = 3 * 8 * 1024 * 2 * (2 * 2 * 2048 * 2048)    # a second expert
    assert counts.step_model_flops(_model(top_k=2), 8, 1024) - \
        counts.step_model_flops(_model(), 8, 1024) == pytest.approx(extra)


def test_flash_attention_work():
    flops, nbytes = counts.flash_attention_work(_model(), 8, 1024)
    assert flops == pytest.approx(3 * 2 * 4 * 8 * 1024 * 512 * 16 * 128)
    row = 8 * 16 * 1024 * 128 * 2
    stat = 8 * 16 * 1024 * 4
    assert nbytes == 2 * (12 * row + 3 * stat)


def test_grouped_matmul_work_per_chip():
    # ep4: 32 * 1024 tokens, top-1, over 4 chips: 8192 rows and 4 experts
    flops, nbytes = counts.grouped_matmul_work(_model(), 32, 1024, 4)
    assert flops == pytest.approx(2 * 6 * 2 * 8192 * 2048 * 2048)
    one = 2 * (8192 * 2048 + 4 * 2048 * 2048 + 8192 * 2048)
    assert nbytes == pytest.approx(2 * 6 * one)
    # gshard on one chip: 16384 routed rows, all 16 experts
    flops, _ = counts.grouped_matmul_work(_model(top_k=2), 8, 1024, 1)
    assert flops == pytest.approx(2 * 6 * 2 * 16384 * 2048 * 2048)


def test_grouped_matmul_work_under_gshard_on_one_chip():
    # top-2, 8 x 1024 tokens on one chip: 16384 rows, all 16 experts
    flops, nbytes = counts.grouped_matmul_work(_model(top_k=2), 8, 1024, 1)
    assert flops == pytest.approx(1.649e12, rel=1e-3)
    assert nbytes == pytest.approx(3.221e9, rel=1e-3)
