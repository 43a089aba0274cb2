"""The reader of ``dispatch_ahead_share``, on stub counters."""

import types

import pytest

from bench import harness


@pytest.fixture
def read_share(monkeypatch):
    """The reader, with the program's recorder replaced by a stub."""
    import repro.obs.trace

    read = harness.reader("dispatch_ahead_share.offline")

    def with_counters(counters):
        stub = types.SimpleNamespace(counters=counters)
        monkeypatch.setattr(repro.obs.trace, "get_tracer", lambda: stub)
        return read(None)

    return with_counters


@pytest.mark.parametrize("counters, share", [
    ({"serve.batches": 4, "serve.dispatched_ahead": 3}, 75.0),
    ({"serve.batches": 4}, 0.0),  # every batch dispatched at depth 1
])
def test_dispatch_ahead_share_reads_the_counters(read_share, counters, share):
    assert read_share(counters) == share


@pytest.mark.parametrize("counters", [
    {},  # a program that keeps no such counters
    {"fused.conv_macs": 10},
    {"serve.batches": 0, "serve.dispatched_ahead": 0},
])
def test_dispatch_ahead_share_is_none_without_counters(read_share, counters):
    assert read_share(counters) is None
