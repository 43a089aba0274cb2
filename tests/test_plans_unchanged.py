"""The zoo's plans do not move when the planner learns new level kinds.

``data/plans_881fb46.json`` holds, for ResNet-18, VGG-16 and ResNet-50 at
224x224 in bf16 and buckets 1 and 8, the ``auto_partition(...).summary()``
and every launch's ``describe()`` row with its nodes, as the planner of
commit 881fb46 (before depthwise levels and level epilogues) wrote them.
Regenerate it only for a change that means to move these plans.
"""

import json
from pathlib import Path

import pytest

from repro.net.graph import MODELS
from repro.net.partition import auto_partition

GOLDEN = json.loads(
    (Path(__file__).parent / "data" / "plans_881fb46.json").read_text()
)


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_plan_is_unchanged(key):
    model, bucket = key.split("/bucket")
    bucket = int(bucket)
    plan = auto_partition(MODELS[model](), batch=bucket, compute_dtype="bfloat16")
    assert plan.summary() == GOLDEN[key]["summary"]
    rows = [dict(p.launch.describe(bucket, plan.vmem_budget), nodes=list(p.node_names))
            for p in plan.pyramids]
    assert rows == GOLDEN[key]["launches"]
