"""The reader of ``folded_mac_share``, on stub counters."""

import types

import pytest

from bench import harness


@pytest.fixture
def read_share(monkeypatch):
    """The reader, with the program's recorder replaced by a stub."""
    import repro.obs.trace

    read = harness.reader("folded_mac_share.offline")

    def with_counters(counters):
        stub = types.SimpleNamespace(counters=counters)
        monkeypatch.setattr(repro.obs.trace, "get_tracer", lambda: stub)
        return read(None)

    return with_counters


@pytest.mark.parametrize("counters, share", [
    # VGG-16 at bucket 8: CONV2 and CONV3 run two taps a pass
    ({"fused.conv_macs": 15_346_630_656,
      "fused.folded_conv_macs": 2_774_532_096}, 100 * 2_774_532_096 / 15_346_630_656),
    ({"fused.conv_macs": 10, "fused.folded_conv_macs": 0}, 0.0),
])
def test_folded_mac_share_reads_the_counters(read_share, counters, share):
    assert read_share(counters) == share


@pytest.mark.parametrize("counters", [
    {},  # a program that keeps no such counters
    {"fused.conv_macs": 10},  # a program that folds no taps
    {"fused.conv_macs": 0, "fused.folded_conv_macs": 0},
])
def test_folded_mac_share_is_none_without_counters(read_share, counters):
    assert read_share(counters) is None
