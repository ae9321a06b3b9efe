"""The benchmark's own arithmetic: parameters and model FLOPs per token
against the program's model and ``bench.py``'s function it was copied from,
and the kernels' operations and bytes on shapes worked by hand."""

import jax
import numpy as np
import pytest

from perfbench import flops, harness, peaks

CONFIGS = [c["file"] for c in harness.load_json("BENCHMARK.json")["configs"]]


@pytest.mark.parametrize("file", CONFIGS)
def test_param_count_matches_the_programs_model(file):
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    sizes = harness.load_json(file)["model"]
    model = GPTForPretraining(GPTConfig(**sizes, fuse_attn_qkv=True))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    counted = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert flops.gpt_param_count(sizes) == counted


def test_model_flops_per_token_is_bench_pys_arithmetic():
    import bench

    sizes = harness.load_json(CONFIGS[0])["model"]
    n = flops.gpt_param_count(sizes)
    assert 3.5e8 < n < 3.6e8
    want = bench.model_flops_per_token(n, sizes["num_layers"], 1024,
                                       sizes["hidden_size"])
    assert flops.train_flops_per_token(sizes, 1024) == want
    assert want == pytest.approx(2.28e9, rel=0.01)


def test_kernel_costs_and_rooflines_by_hand():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e == peaks.peaks_for("TPU v5e")
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    ops, bytes_ = flops.flash_call_cost("fwd", 2, 4, 128, 128, 64, causal=False)
    assert ops == 2 * 2 * 8 * 128 * 128 * 64
    assert bytes_ == 4 * 8 * 128 * 64 * 2       # Q, K, V, O once each, bf16
    causal, _ = flops.flash_call_cost("fwd", 2, 4, 128, 128, 64)
    assert causal == ops / 2
    assert flops.flash_call_cost("dkv", 1, 1, 8, 8, 8)[0] == 2 * flops.flash_call_cost(
        "fwd", 1, 1, 8, 8, 8)[0]
    # paged decode: every live row of K and V read once; memory-bound
    ops, bytes_ = flops.paged_decode_call_cost(4096, 16, 128, 16)
    assert bytes_ == 2 * 4096 * 2048 * 2 + 2 * 16 * 2048 * 2
    seconds, bound = flops.roofline_seconds(ops, bytes_, v5e)
    assert bound == "memory" and seconds == pytest.approx(bytes_ / 819e9)
    assert flops.roofline_seconds(1e15, 1.0, v5e) == (1e15 / 197e12, "compute")
