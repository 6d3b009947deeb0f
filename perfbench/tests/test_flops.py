"""Operations and bytes from the configuration's shapes, by hand."""

import types

import pytest

from perfbench.harness import flops, spec
from perfbench.operations import nature_cnn

LAYERS = nature_cnn.layers(
    spec.load_cell("ppo-pong").config, types.SimpleNamespace(num_actions=6)
)


def test_layer_shapes_as_published():
    by = {l.name: l for l in LAYERS}
    assert by["conv0"].macs == 20 * 20 * 32 * 8 * 8 * 4 == 3_276_800
    assert by["conv1"].macs == 9 * 9 * 64 * 4 * 4 * 32 == 2_654_208
    assert by["conv2"].macs == 7 * 7 * 64 * 3 * 3 * 64 == 1_806_336
    assert by["dense"].macs == 3136 * 512
    assert by["heads"].macs == 512 * 7
    assert by["conv0"].in_bytes == 1 and not by["conv0"].input_grad
    weights = sum(l.w_elems for l in LAYERS)
    assert weights == 8192 + 32768 + 36864 + 1605632 + 3584 == 1_687_040


def test_forward_and_train_operations():
    fwd = flops.forward_flops_per_sample(LAYERS)
    assert fwd == 2 * (3_276_800 + 2_654_208 + 1_806_336 + 1_605_632 + 3584)
    # backward: two products a layer, one for the layer that reads frames
    assert flops.train_flops_per_sample(LAYERS) == 3 * fwd - 2 * 3_276_800
    work = {"forward_samples": 10, "train_samples": 4}
    assert flops.model_flops(LAYERS, work) == 10 * fwd + 4 * (
        3 * fwd - 2 * 3_276_800
    )


def test_least_time_takes_the_larger_bound_per_product():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = {"forward_samples": 1000, "forward_calls": 0,
            "train_samples": 0, "train_calls": 0}
    got = flops.mxu_min_seconds(LAYERS, work, peaks)
    # conv0 forward: 6.55 MFLOP against 28,224 + 25,600 bytes a sample:
    # 33 ns of operations, 66 ns of bytes, so bytes bound it.
    conv0 = max(2 * 3_276_800 / 197e12, (28224 + 12800 * 2) / 819e9)
    assert conv0 == pytest.approx(65.7e-9, rel=1e-2)
    assert got["seconds"] > 1000 * conv0
    assert 0.5 < got["memory_bound_share"] <= 1.0
