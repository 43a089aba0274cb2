"""The ConvNeXt-T configuration: published totals, the benchmark's
reference against the program's at a small size, the int8 control, and
the reader of ``dw_chained_share``."""

import math
import types

import numpy as np
import pytest

from bench import harness, model

NAME = "convnext_t-bf16"
SEED = 2**31 + 5


def test_flops_match_published_total():
    """torchvision's convnext_tiny: 4.46 GFLOPS, counted as multiply-adds,
    per 224x224 image; 105 M of them in the depthwise convs."""
    config = model.load_config(NAME)
    assert model.flops_per_image(config) == 2 * 4_455_531_264
    assert model.flops_per_image(config, ("depthwise",)) == 2 * 105_106_176


def test_parameters_at_bf16():
    """28,589,128 parameters (torchvision: 28.6 M), 57.2 MB at bf16."""
    config = model.load_config(NAME)
    n = sum(math.prod(s) for layer, mod, ins, _ in model.walk(config)
            for s in mod.param_shapes(layer, ins))
    assert n == 28_589_128
    assert 2 * n / 1e6 == pytest.approx(57.2, abs=0.05)


def test_conv_stack_holds_the_depthwise_convs():
    config = model.load_config(NAME)
    ops = {entry[0]["op"] for entry in model.conv_stack(config)}
    assert ops == {"conv", "depthwise"}


@pytest.fixture(scope="module")
def small():
    config = dict(model.load_config(NAME), input_size=32)
    params = model.init_params(config, SEED)
    images = model.make_images(config, SEED, 3)
    reference = model.logits_in_blocks(config, params, images, block=3)
    return config, params, images, reference


def test_reference_matches_the_programs_reference(small):
    """At 32x32 on the CPU the benchmark's reference agrees with
    ``repro.net.runner.reference_network`` on the same weights."""
    from repro.net.graph import MODELS
    from repro.net.runner import reference_network

    config, params, images, ours = small
    graph = MODELS[config["model"]](input_size=32, num_classes=config["num_classes"])
    theirs = np.asarray(reference_network(images, graph, params))
    assert np.abs(ours - theirs).max() <= 1e-5 * np.abs(theirs).max()


def test_int8_control_differs_from_the_reference(small):
    config, params, images, reference = small
    control = model.logits_in_blocks(config, params, images, int8=True, block=3)
    err = model.logit_rms_error(control, reference)
    assert (err > config["correct"]["logit_rms_err"]).all()


@pytest.fixture
def read_share(monkeypatch):
    """The reader, with the program's recorder replaced by a stub."""
    import repro.obs.trace

    read = harness.reader("dw_chained_share.offline")

    def with_counters(counters):
        stub = types.SimpleNamespace(counters=counters)
        monkeypatch.setattr(repro.obs.trace, "get_tracer", lambda: stub)
        return read(None)

    return with_counters


def test_dw_chained_share_reads_the_counters(read_share):
    counters = {"fused.dw_macs": 105_106_176, "fused.chained_dw_macs": 105_106_176}
    assert read_share(counters) == 100.0
    assert read_share({"fused.dw_macs": 4, "fused.chained_dw_macs": 1}) == 25.0
    assert read_share({"fused.dw_macs": 10, "fused.chained_dw_macs": 0}) == 0.0


@pytest.mark.parametrize("counters", [
    {},  # a program that keeps no such counters, as the parent
    {"fused.conv_macs": 10, "fused.chained_conv_macs": 10},
    {"fused.dw_macs": 10},
    {"fused.dw_macs": 0, "fused.chained_dw_macs": 0},
])
def test_dw_chained_share_is_none_without_counters(read_share, counters):
    assert read_share(counters) is None
