"""The comparison that decides ``correct`` fails what it has to fail.

The int8 control must read above each configuration's limit, and a run of
the harness whose timed path is broken underneath must come out with
``correct`` false.  Both at 32x32 on the CPU (the Pallas kernels in
interpret mode); the chip readings the limits were set from are in
PERF.md.
"""

import time

import jax.numpy as jnp
import pytest

from bench import harness, model

SEED = 2**31 + 77  # larger than 32 signed bits hold, as the driver's are


def small(name: str) -> dict:
    return dict(model.load_config(name), input_size=32)


@pytest.mark.parametrize("name", ["resnet18-bf16", "vgg16-bf16"])
@pytest.mark.parametrize("seed", [1, SEED])
def test_int8_control_fails_the_limit(name, seed):
    config = small(name)
    params = model.init_params(config, seed)
    pool = model.make_images(config, seed, 16)
    reference = model.logits_in_blocks(config, params, pool)
    control = model.logits_in_blocks(config, params, pool, int8=True)
    err = model.logit_rms_error(control, reference).max()
    assert err > config["correct"]["logit_rms_err"]


TRAFFIC = {"loop": "closed", "in_flight": 16, "rows_per_request": 1,
           "pool_images": 16, "warmup_requests": 16, "engine": {"buckets": [8]}}


def run_small(cell="resnet18-bf16.offline"):
    config = small(cell.split(".")[0])
    return harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                            config=config, traffic=dict(TRAFFIC),
                            require_tpu=False, say=lambda s: None)


def alter_one_answer(logits):
    """The first row's largest and smallest logits trade places."""
    row = logits[0]
    hi, lo = jnp.argmax(row), jnp.argmin(row)
    return logits.at[0, hi].set(row[lo]).at[0, lo].set(row[hi])


def answers_to_other_requests(logits):
    """Each row's answer goes to the next request of the batch."""
    return jnp.roll(logits, 1, axis=0)


class Stale:
    """Every batch returns the previous batch's answers unchanged."""

    def __init__(self):
        self.last = None

    def __call__(self, logits):
        out = logits if self.last is None else self.last
        self.last = logits
        return out


@pytest.fixture
def broken(monkeypatch):
    """Break the forward the serving engine calls, where the answer is made."""
    import repro.net.serve as serve

    def install(fault):
        original = serve.run_network

        def run_network(*args, **kwargs):
            logits, skips = original(*args, **kwargs)
            return fault(logits), skips

        monkeypatch.setattr(serve, "run_network", run_network)

    return install


def test_sound_run_is_correct():
    result = run_small()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result["checks"]) == ["logit_rms_err", "unanswered", "compiles_in_window"]
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [alter_one_answer, answers_to_other_requests, Stale],
                         ids=["answer_altered", "answers_swapped", "state_unchanged"])
def test_broken_forward_is_not_correct(broken, fault):
    broken(fault() if isinstance(fault, type) else fault)
    result = run_small()
    assert not result["correct"]
    assert result["checks"]["logit_rms_err"]["value"] > result["checks"]["logit_rms_err"]["limit"]


def test_failed_requests_are_not_correct(broken):
    """Batches that fail after the warm-up leave requests without answers."""
    from repro.robust.errors import NumericError

    calls = [0]

    def fail_later(logits):
        calls[0] += 1
        if calls[0] > 4:
            raise NumericError("planted failure")
        return logits

    broken(fail_later)
    result = run_small()
    assert not result["correct"]
    assert result["failed"] > 0
    assert result["checks"]["unanswered"]["value"] > 0
