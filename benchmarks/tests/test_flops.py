"""The benchmark's operation and byte counts against hand-worked values."""

import json
import os

import pytest

from benchmarks.harness import flops, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_resnet50_forward_is_about_7_7_gflop_an_image():
    # stem 0.236 + stages (stride on the first 1x1) + fc 0.004
    got = flops.resnet_forward_flops(_cfg("resnet50"))
    assert got == pytest.approx(7.72e9, rel=0.01)
    # by hand: stem 2*112*112*3*64*49, fc 2*2048*1000
    assert 2 * 112 * 112 * 3 * 64 * 49 == 236_027_904
    stage0_block0 = 2 * 56 * 56 * (64 * 64 + 64 * 64 * 9 + 64 * 256 * 2)
    assert stage0_block0 == 2 * 3136 * 73728


def test_bert_base_s512_is_96_6_gflop_an_example():
    cfg = _cfg("bert_base")
    # 2 * 84,934,656 weights * 512 tokens + 4 * 12 * 512^2 * 768
    assert 12 * (4 * 768 * 768 + 2 * 768 * 3072) == 84_934_656
    assert flops.bert_forward_flops(cfg, 512) == pytest.approx(96.6e9,
                                                               rel=0.002)
    att = 4 * 12 * 512 * 512 * 768
    assert att / flops.bert_forward_flops(cfg, 512) == pytest.approx(0.1,
                                                                     abs=0.01)
    assert flops.train_flops_per_example(cfg, {"seq_len": 512}) == \
        3 * flops.bert_forward_flops(cfg, 512)


def test_kernel_costs_and_roofline():
    pk = peaks.peaks_for("TPU v5 lite")
    ops, nbytes = flops.affine_act_cost(401408, 256, 2, backward=False)
    assert (ops, nbytes) == (2 * 401408 * 256, 2 * 401408 * 256 * 2)
    t, bound = flops.roofline_seconds(ops, nbytes, pk)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    ops, nbytes = flops.flash_cost(32, 12, 512, 64, 2, "fwd")
    assert ops == 4 * 32 * 12 * 512 * 512 * 64
    assert nbytes == 4 * 32 * 12 * 512 * 64 * 2
    # 256 operations a byte forward, just over the chip's 240: compute-bound
    assert flops.roofline_seconds(ops, nbytes, pk)[1] == "compute"
    # forward + dq + dkv = 12 b h t^2 d, three times the forward
    both = sum(flops.flash_cost(32, 12, 512, 64, 2, k)[0]
               for k in ("dq", "dkv"))
    assert both == 2 * ops
    assert flops.roofline_seconds(
        *flops.flash_cost(32, 12, 512, 64, 2, "dkv"), pk)[1] == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
