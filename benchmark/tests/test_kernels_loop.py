"""benchlib/kernels_loop.py: a looped decoder's useful operations and bytes
against hand arithmetic at ``ouro_dense_x1``'s own size (2 sequences of
4,096 tokens, 4 passes over 6 layers, 16 ungrouped heads of 128, hidden
2,048, SwiGLU 5,632, a head of 49,152 rows)."""

import json
import os

import pytest

from benchlib import kernels_loop as k
from benchlib import kernels_mixed_gqa, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "ouro_2_6b_l6.json")) as f:
        return json.load(f)


def test_a_step_runs_passes_times_layers_applications(config):
    assert k.applications(config) == 4 * 6 == 24
    assert k.applications(dict(config, total_ut_steps=1)) == 6


def test_the_scores_are_the_triangle_at_sixteen_heads(config):
    pairs = 4096 * 4097 // 2
    assert k.triangle_pairs(4096) == pairs == 8_390_656
    # q . k and p v, 2 x 128 operations each, a pair and head; one forward
    # and the backward at twice a forward; 2 sequences; 24 applications
    one = pairs * 16 * (2 * 128 + 2 * 128)
    assert k.scores_flops_a_step(config, 2) == 24 * 2 * one * 3
    assert round(k.scores_flops_a_step(config, 2) / 1e12, 2) == 9.9
    # q read and the output written (16 heads), k and v read (16 heads),
    # float32, once a pass
    assert k.scores_bytes_a_step(config, 2) == (
        24 * 8192 * (2 * 128) * (16 + 16) * 4 * 3)
    least, bound = k.scores_roofline_seconds(config, 2, "TPU v5 lite")
    assert bound == "compute"
    assert least == k.scores_flops_a_step(config, 2) / peaks.peak(
        "TPU v5 lite", "flops_bf16")
    assert 0.050 < least < 0.051


def test_one_application_counts_as_a_full_layer_of_the_mixed_module(config):
    """The same triangle, operations and bytes as
    ``kernels_mixed_gqa.py`` counts for one full-attention layer of 16
    heads over 16 key-value heads."""
    mixed = dict(config, num_hidden_layers=1,
                 layer_types=["full_attention"],
                 num_attention_heads_per_layer=[16])
    one = dict(config, num_hidden_layers=1, total_ut_steps=1)
    assert k.scores_flops_a_step(one, 2) == (
        kernels_mixed_gqa.scores_flops_a_step(mixed, "full_attention", 2))
    assert k.scores_bytes_a_step(one, 2) == (
        kernels_mixed_gqa.scores_bytes_a_step(mixed, "full_attention", 2))


def test_the_head_is_four_exits_of_three_products(config):
    one = 2 * 8192 * 2048 * 49152           # tokens x hidden x vocabulary
    assert k.head_flops_a_step(config, 2) == 4 * 3 * one
    assert round(k.head_flops_a_step(config, 2) / 1e12, 1) == 19.8


def test_the_swiglu_is_three_matrices_an_application(config):
    one = 2 * 8192 * 2048 * 5632
    assert k.mlp_flops_a_step(config, 2) == 24 * 3 * 3 * one
    assert round(k.mlp_flops_a_step(config, 2) / 1e12, 1) == 40.8


def test_useful_work_is_under_the_steps_reckoned_work(config):
    """The three counts are parts of the step's 90 TFLOP of useful
    products (the issue's 120 with the recomputation): no share built on
    them can pass 100 % while the step takes what its products take."""
    parts = (k.scores_flops_a_step(config, 2) + k.head_flops_a_step(config, 2)
             + k.mlp_flops_a_step(config, 2))
    projections = 24 * 4 * 2 * 8192 * 2048 * 2048 * 3
    assert 88e12 < parts + projections < 92e12
