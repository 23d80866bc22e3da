"""The operation and byte counts give the hand-worked numbers."""

import json

import pytest

from benchmark.lib import counts
from benchmark.lib.manifest import Manifest


def cfg(name):
    return Manifest().config(name)


def test_parameters_by_hand():
    m, x = cfg("mistral-7b"), cfg("mixtral-8x7b")
    # attention 4096*4096*2 + 2*4096*1024; SwiGLU 3*4096*14336; two norms
    assert counts.attention_params(m) == 41_943_040
    assert counts.expert_params(m) == 176_160_768
    assert counts.layer_params(m) == 218_112_000          # 218.1 M a layer
    assert counts.layer_params(x) == 1_451_270_144        # 1.451 B a layer
    assert counts.layer_matmul_params_active(x) == (
        41_943_040 + 2 * 176_160_768 + 4096 * 8)
    assert counts.total_params(m) == 4 * 218_112_000 + 2 * 32000 * 4096 + 4096
    assert round(counts.total_params(m) / 1e9, 3) == 1.135


def test_training_operations_per_token():
    m = cfg("mistral-7b")
    products = 4 * (41_943_040 + 176_160_768) + 4096 * 32000
    attn = 4 * 32 * 2 * 2 * 128 * (4096 * 4097 // 2) / 4096
    assert counts.train_flops_per_token(m, 4096) == pytest.approx(
        3 * (2 * products + attn))
    assert counts.train_flops_per_token(m, 4096) == pytest.approx(6.42e9, rel=2e-3)


def test_flash_kernel_at_4096_by_128():
    # one head, one row: 2 products of 128 per pair, 4096*4097/2 causal pairs
    assert counts.flash_flops("fwd", 1, 1, 4096, 128) == 2 * 2 * 128 * 8_390_656
    assert counts.flash_flops("fwd", 1, 1, 4096, 128) == 4_296_015_872
    assert counts.flash_flops("dq", 1, 1, 4096, 128) == 6_444_023_808
    assert counts.flash_flops("dkv", 1, 1, 4096, 128) == 8_592_031_744
    # q + k + v + o in bf16 and one float32 row of statistics
    assert counts.flash_bytes("fwd", 1, 1, 1, 4096, 128) == 4 * 4096 * 128 * 2 + 4096 * 4
    peaks = json.loads((Manifest().bench / "lib" / "peaks.json").read_text())
    t, bound = counts.roofline_seconds(
        counts.flash_flops("fwd", 4, 32, 4096, 128),
        counts.flash_bytes("fwd", 4, 32, 8, 4096, 128), peaks["TPU v5 lite"])
    assert bound == "compute" and t == pytest.approx(5.4989e11 / 197e12, rel=1e-3)
