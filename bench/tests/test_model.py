"""The benchmark's counts, weights and reference, checked against published
totals and against the program's own reference at a small size."""

import numpy as np
import pytest

from bench import model


@pytest.mark.parametrize("name, gmac", [("resnet18-bf16", 1.8), ("vgg16-bf16", 15.5)])
def test_flops_match_published_totals(name, gmac):
    """ResNet-18: 1.8 GMAC (He et al. 2016, Table 1); VGG-16: 15.5 GMAC at
    224x224 (the usual count for configuration D)."""
    config = model.load_config(name)
    assert model.flops_per_image(config) / 2e9 == pytest.approx(gmac, rel=0.02)


@pytest.mark.parametrize("name, mbytes", [("resnet18-bf16", 23.4), ("vgg16-bf16", 276.7)])
def test_weight_bytes_at_bf16(name, mbytes):
    config = model.load_config(name)
    n = sum(int(np.prod(s)) + s[-1] for s, _ in model.weight_shapes(config).values())
    assert 2 * n / 1e6 == pytest.approx(mbytes, rel=0.01)


@pytest.mark.parametrize("name, rows, bound", [
    ("resnet18-bf16", 1, "memory"),  # 22 MB of weights against 3.6 GFLOP
    ("resnet18-bf16", 8, "compute"),
    ("vgg16-bf16", 1, "compute"),
    ("vgg16-bf16", 8, "compute"),
])
def test_which_bound_sets_the_least_time(name, rows, bound):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, which = model.conv_stack_least_seconds(model.load_config(name), rows, peak)
    assert which == bound and seconds > 0


def test_seeds_are_deterministic_and_large_ones_work():
    config = dict(model.load_config("resnet18-bf16"), input_size=32)
    big = 2**31 + 12345
    a = model.init_params(config, big)["conv1"][0]
    b = model.init_params(config, big)["conv1"][0]
    c = model.init_params(config, big + 1)["conv1"][0]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert np.array_equal(model.make_images(config, big, 2), model.make_images(config, big, 2))


@pytest.mark.parametrize("name", ["resnet18-bf16", "vgg16-bf16"])
def test_reference_matches_the_programs_reference(name):
    """The benchmark's reference is its own code; at 32x32 on the CPU it
    agrees with ``repro.net.runner.reference_network`` on the same weights."""
    from repro.net.graph import MODELS
    from repro.net.runner import reference_network

    config = dict(model.load_config(name), input_size=32)
    params = model.init_params(config, 7)
    images = model.make_images(config, 7, 3)
    ours = model.logits_in_blocks(config, params, images, block=2)
    graph = MODELS[config["model"]](input_size=32, num_classes=config["num_classes"])
    theirs = np.asarray(reference_network(images, graph, params))
    scale = np.abs(theirs).max()
    assert np.abs(ours - theirs).max() <= 1e-5 * scale
