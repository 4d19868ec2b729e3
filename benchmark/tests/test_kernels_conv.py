"""benchlib/kernels_conv.py: a short-convolution decoder's useful
operations and bytes against hand arithmetic at ``lfm2_dense_x1``'s own size
(2 sequences of 8,192 tokens; four ``conv`` layers of 2,048 channels and 3
taps; one ``full_attention`` layer of 32 query heads over 8 key-value heads
of 64)."""

import json
import os

import pytest

from benchlib import kernels_conv as k
from benchlib import kernels_mixed_gqa, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "lfm2_24b_a2b_ep8.json")) as f:
        return json.load(f)


def test_the_layers_run_are_one_period_after_a_dense_layer(config):
    assert config["layer_types_run"] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert k.layers_run(config, k.CONV) == 4
    assert k.layers_run(config, k.FULL) == 1
    # they are the published list's entries 1-5
    assert config["layer_types"][1:6] == config["layer_types_run"]
    assert len(config["layer_types"]) == 40


def test_the_gated_convolution_is_sixteen_bytes_a_channel_and_token(config):
    # B, C, z read and the result written, float32; one forward and the
    # backward at twice a forward; 4 layers x 16,384 tokens x 2,048 channels
    assert k.gated_conv_bytes_a_step(config, 2) == (
        4 * 16384 * 2048 * 16 * 3)
    assert round(k.gated_conv_bytes_a_step(config, 2) / 1e9, 2) == 6.44
    # three taps, a multiply and an add each
    assert k.gated_conv_flops_a_step(config, 2) == 4 * 16384 * 2048 * 6 * 3
    least, bound = k.gated_conv_roofline_seconds(config, 2, "TPU v5 lite")
    assert bound == "memory"
    assert least == k.gated_conv_bytes_a_step(config, 2) / peaks.peak(
        "TPU v5 lite", "hbm_bytes_per_s")
    assert 7.8e-3 < least < 7.9e-3


def test_the_operators_products_are_four_hidden_squares_a_token(config):
    one = 2 * 16384 * 2048 * (6144 + 2048)      # W_in and W_out, a layer
    assert k.products_flops_a_step(config, 2) == 4 * one * 3
    assert round(k.products_flops_a_step(config, 2) / 1e12, 1) == 6.6


def test_the_scores_are_the_triangle_at_thirty_two_heads_of_64(config):
    pairs = 8192 * 8193 // 2
    assert kernels_mixed_gqa.triangle_pairs(8192) == pairs == 33_558_528
    one = pairs * 32 * (2 * 64 + 2 * 64)
    assert k.scores_flops_a_step(config, 2) == 2 * one * 3
    assert k.scores_bytes_a_step(config, 2) == (
        16384 * (2 * 64) * (32 + 8) * 4 * 3)
    least, bound = k.scores_roofline_seconds(config, 2, "TPU v5 lite")
    assert bound == "compute" and 8.3e-3 < least < 8.4e-3


def test_a_full_layer_counts_as_one_of_the_mixed_module(config):
    """The same triangle, operations and bytes as ``kernels_mixed_gqa.py``
    counts for one full-attention layer of 32 heads over 8 key-value
    heads of 64."""
    mixed = dict(config, num_hidden_layers=1, layer_types=["full_attention"],
                 num_attention_heads_per_layer=[32])
    assert k.scores_flops_a_step(config, 2) == (
        kernels_mixed_gqa.scores_flops_a_step(mixed, "full_attention", 2))
    assert k.scores_bytes_a_step(config, 2) == (
        kernels_mixed_gqa.scores_bytes_a_step(mixed, "full_attention", 2))


def test_a_configuration_without_the_list_reads_nothing():
    """The readers hand back None for a configuration that does not say
    which layers run (every cell but this one), before any arithmetic."""
    class Ctx:
        config, trace = {}, None
    from benchlib import kernels_lm
    assert kernels_lm.sub_seconds(Ctx, ("gated_conv",)) is None
    assert k.roofline_share(Ctx, k.gated_conv_roofline_seconds,
                            "gated_conv") is None
