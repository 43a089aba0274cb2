"""What every configuration reads from ``bench/model.py`` equals what it
read at commit cf967b2, before the layer ops moved into ``bench/ops/``:
the counts the roofline and utilization metrics divide by, every weight
drawn from the seed, and the reference and int8-control logits.  All are
held bit for bit but the float32 reference logits: the CPU's float32
convolutions sum in an order that depends on its thread count (the parent
itself reads up to 1.2e-6 of the largest logit apart on 3 and on 8
cores), so they are held to 1e-5 of it.

The golden file holds those readings as the parent computed them:

    PYTHONPATH=<checkout of cf967b2> JAX_PLATFORMS=cpu \\
        python bench/tests/test_parity.py > bench/tests/data/parity_cf967b2.json
"""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

GOLDEN = Path(__file__).resolve().parent / "data" / "parity_cf967b2.json"
CONFIGS = ["resnet18-bf16", "vgg16-bf16", "resnet50-bf16"]
SEEDS = [7, 2**32 + 5]  # one seed above 32 bits, as a run's may be
LOGIT_SEED = 2**31 + 77
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}  # TPU v5 lite


def digest(x) -> str:
    x = np.asarray(x)
    return hashlib.sha256(f"{x.dtype} {x.shape} ".encode() + x.tobytes()).hexdigest()


def readings(name: str) -> dict:
    from bench import model

    config = model.load_config(name)
    out = {
        "flops_per_image": model.flops_per_image(config),
        "conv_flops_per_image": model.flops_per_image(config, ("conv",)),
        "conv_stack_least_bytes": {str(r): model.conv_stack_least_bytes(config, r)
                                   for r in (1, 8)},
        "conv_stack_least_seconds": {str(r): list(model.conv_stack_least_seconds(config, r, PEAK))
                                     for r in (1, 8)},
        "shapes": {k: list(v) for k, v in model.shapes(config).items()},
        "params": {},
    }
    for seed in SEEDS:
        params = model.init_params(config, seed)
        out["params"][str(seed)] = {k: [digest(a) for a in v] for k, v in params.items()}
        del params
    small = dict(config, input_size=32)
    params = model.init_params(small, LOGIT_SEED)
    images = model.make_images(small, LOGIT_SEED, 2)
    logits = model.logits_in_blocks(small, params, images, block=2)
    out["logits"] = logits.ravel().tolist()
    out["int8_logits"] = digest(model.logits_in_blocks(small, params, images, int8=True, block=2))
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_readings_equal_the_parents(name):
    golden = json.loads(GOLDEN.read_text())
    assert sorted(golden) == sorted(CONFIGS)
    have = readings(name)
    assert sorted(have) == sorted(golden[name])
    for key, want in golden[name].items():
        if key == "logits":
            want = np.asarray(want)
            assert np.abs(np.asarray(have[key]) - want).max() <= 1e-5 * np.abs(want).max()
        else:
            assert have[key] == want, f"{name}: {key} differs from the parent's"


if __name__ == "__main__":
    json.dump({name: readings(name) for name in CONFIGS}, sys.stdout, indent=1, sort_keys=True)
    print()
