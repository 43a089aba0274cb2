"""The ResNet-50 configuration: published totals, the benchmark's reference
against the program's at a small size, and the reader of
``fused_mac_share``."""

import types

import numpy as np
import pytest

from bench import harness, model

NAME = "resnet50-bf16"


def test_flops_match_published_total():
    """4.09 GMAC per 224x224 image (He et al. 2016, Table 1, 50-layer,
    with v1.5's stride on the 3x3)."""
    config = model.load_config(NAME)
    assert model.flops_per_image(config) / 2e9 == pytest.approx(4.09, rel=0.005)


def test_weight_bytes_at_bf16():
    """25.53 M parameters with batch norm folded into the biases."""
    config = model.load_config(NAME)
    n = sum(int(np.prod(s)) + s[-1] for s, _ in model.weight_shapes(config).values())
    assert n / 1e6 == pytest.approx(25.53, abs=0.005)
    assert 2 * n / 1e6 == pytest.approx(51.1, abs=0.05)


def test_reference_matches_the_programs_reference():
    """At 32x32 on the CPU the benchmark's reference agrees with
    ``repro.net.runner.reference_network`` on the same weights."""
    from repro.net.graph import MODELS
    from repro.net.runner import reference_network

    config = dict(model.load_config(NAME), input_size=32)
    params = model.init_params(config, 2**31 + 5)
    images = model.make_images(config, 2**31 + 5, 3)
    ours = model.logits_in_blocks(config, params, images, block=2)
    graph = MODELS[config["model"]](input_size=32, num_classes=config["num_classes"])
    theirs = np.asarray(reference_network(images, graph, params))
    assert np.abs(ours - theirs).max() <= 1e-5 * np.abs(theirs).max()


@pytest.fixture
def read_share(monkeypatch):
    """The reader, with the program's recorder replaced by a stub."""
    import repro.obs.trace

    read = harness.reader("fused_mac_share.offline")

    def with_counters(counters):
        stub = types.SimpleNamespace(counters=counters)
        monkeypatch.setattr(repro.obs.trace, "get_tracer", lambda: stub)
        return read(None)

    return with_counters


def test_fused_mac_share_reads_the_counters(read_share):
    counters = {"fused.conv_macs": 4_087_136_256,
                "fused.chained_conv_macs": 3_609_460_736}
    assert read_share(counters) == pytest.approx(88.31, abs=0.01)
    assert read_share({"fused.conv_macs": 10, "fused.chained_conv_macs": 0}) == 0.0


@pytest.mark.parametrize("counters", [
    {},  # a program that keeps no such counters
    {"fused.patch_levels": 1},
    {"fused.conv_macs": 10},
    {"fused.conv_macs": 0, "fused.chained_conv_macs": 0},
])
def test_fused_mac_share_is_none_without_counters(read_share, counters):
    assert read_share(counters) is None
