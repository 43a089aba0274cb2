"""Tap folding (``core/program.taps_per_pass``): a conv level whose input
channel block fills at most half of the MXU's 128-deep contraction reads
``g`` adjacent taps of a kernel row as one operand, against their stacked
weight blocks, from an input tile that holds its channels ``g`` times, copy
``t`` shifted ``t`` columns on (``TileProgram.copies``).

* the rule reads shapes only, and gives 1 to every level of 128 or more
  lanes and every ``K = 1`` level; a launch folds every level but level 0;
* a folded level matches ``ref.py``'s oracle (CONV2's 64->64 shape with a
  pool epilogue, 64->128, the stride-2 staged path, 32 channels at g = 3, a
  linear level, and one fed by a 64-channel level 0, which reads its HBM
  input one tap a pass) at float32 and bfloat16, on a one-cell grid and a
  larger one;
* an all-zero input tile still takes the closed form, bit-identically, with
  the same skip flags; resident, streamed and channel-tiled launches stay
  bitwise equal;
* plan build bumps ``fused.folded_conv_macs`` by the folded levels' MACs,
  ``explain`` prints each level's taps per pass, and no plan moved.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import init_pyramid_params
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import LANES, compile_program, taps_per_pass
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.kernels.fused_conv.ref import fused_pyramid_ref
from repro.net.graph import MODELS
from repro.net.partition import (
    CONV_MACS,
    FOLDED_CONV_MACS,
    auto_partition,
    clear_partition_cache,
)
from repro.obs import tracing

KEY = jax.random.PRNGKey(0)


def _conv(K, S, pad, n_in, n_out, relu=True):
    return FusedLevel("conv", K=K, S=S, pad=pad, n_in=n_in, n_out=n_out,
                      relu=relu)


def _pool(c):
    return FusedLevel("pool", K=2, S=2, pad=0, n_in=c, n_out=c)


# level 0 (8 channels) feeds the folded level 1, so the folded level reads
# an inter-level tile as it does in a served pyramid
SHAPES = {
    "vgg_conv2": (_conv(3, 1, 1, 8, 64), _conv(3, 1, 1, 64, 64), _pool(64)),
    "64_to_128": (_conv(3, 1, 1, 8, 64), _conv(3, 1, 1, 64, 128)),
    "stride2": (_conv(3, 1, 1, 8, 64), _conv(3, 2, 1, 64, 128)),
    "32_ch": (_conv(3, 1, 1, 8, 32), _conv(3, 1, 1, 32, 64)),
    "linear": (_conv(3, 1, 1, 8, 64), _conv(3, 1, 1, 64, 64, relu=False)),
    # a 64-channel level 0 reads the HBM image one tap a pass
    "level0": (_conv(3, 1, 1, 64, 64), _conv(3, 2, 1, 64, 128)),
}
FOLDS = {"vgg_conv2": (1, 2), "64_to_128": (1, 2), "stride2": (1, 2),
         "32_ch": (1, 3), "linear": (1, 2), "level0": (1, 2)}


def _spec(name, size=16):
    return FusionSpec(levels=SHAPES[name], input_size=size)


def _inputs(spec, batch=2, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )


def _regions(spec):
    out = spec.feature_sizes()[-1]
    return {"one": out, "many": out // 2}


@pytest.mark.parametrize("n_in,K,fold", [
    (64, 3, 2), (128, 3, 1), (27, 1, 1), (32, 3, 3), (32, 5, 4), (96, 5, 1),
    (192, 3, 1), (512, 3, 1),
])
def test_taps_per_pass(n_in, K, fold):
    assert taps_per_pass(_conv(K, 1, K // 2, n_in, 64)) == fold
    assert fold * min(n_in, LANES) <= LANES or fold == 1


@pytest.mark.parametrize("name", SHAPES)
def test_the_rule_and_the_copies(name):
    """A folded level's producer computes its channels once per tap of a
    pass; the copies fill lanes the tile pads to anyway, so the modeled
    VMEM bytes are those of the unfolded buffers."""
    prog = compile_program(_spec(name), _regions(_spec(name))["one"])
    assert prog.folds() == FOLDS[name]
    assert prog.folds()[1] == taps_per_pass(prog.levels[1])
    assert prog.copies() == (FOLDS[name][1], 1)
    mid = [s for n, s, _ in prog.vmem_buffers() if n == "mid"]
    assert mid[0][-1] == prog.levels[0].n_out * FOLDS[name][1] <= LANES


CASES = [
    (name, dtype, grid)
    for name in SHAPES
    for dtype in ("float32", "bfloat16")
    for grid in ("one", "many")
]


@pytest.mark.parametrize("name,dtype,grid", CASES)
def test_folded_level_matches_the_oracle(name, dtype, grid):
    spec = _spec(name)
    p = init_pyramid_params(spec, KEY)
    x = _inputs(spec)
    y, skip = fused_pyramid(
        x, p.weights, p.biases, spec=spec, out_region=_regions(spec)[grid],
        streamed=False, compute_dtype=dtype,
    )
    assert skip.shape[-1] == spec.q_convs
    if dtype == "float32":
        ref = fused_pyramid_ref(x, spec, p.weights, p.biases)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
        return
    # at bf16 the oracle takes the kernel's rounded operands; the rest is
    # the rounding of the level-0 tile and of the output
    cast = [jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)
            for a in (x, *p.weights, *p.biases)]
    q = len(p.weights)
    ref = fused_pyramid_ref(cast[0], spec, cast[1 : 1 + q], cast[1 + q :])
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    assert err <= 0.02 * max(scale, 1.0), (err, scale)


@pytest.mark.parametrize("name", SHAPES)
def test_dead_tile_takes_the_closed_form(name):
    """Level 0 with strongly negative biases is all zero after its ReLU:
    the folded level is skipped and emits its closed form, bit-identical
    to computing it."""
    spec = _spec(name)
    p = init_pyramid_params(spec, KEY)
    biases = [p.biases[0] - 10.0, *p.biases[1:]]
    x = _inputs(spec)
    region = _regions(spec)["many"]
    runs = [
        fused_pyramid(x, p.weights, biases, spec=spec, out_region=region,
                      streamed=False, end_skip=skip, compute_dtype="bfloat16")
        for skip in (True, False)
    ]
    (y, flags), (y_live, flags_live) = runs
    assert (np.asarray(flags)[..., 1] == 1).all()
    assert not np.asarray(flags_live).any()
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_live))


@pytest.mark.parametrize("name", SHAPES)
def test_streamed_bitwise_equals_resident(name):
    spec = _spec(name)
    region = _regions(spec)["many"]
    p = init_pyramid_params(spec, KEY)
    x = _inputs(spec)
    runs = [
        fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
            compute_dtype="bfloat16", **knobs,
        )
        for knobs in (
            dict(streamed=False), dict(streamed=True, w_slots=2),
            dict(streamed=True, w_slots=1),
            dict(streamed=True, w_slots=2, c_tiles=2),
        )
    ]
    for y, skip in runs[1:]:
        np.testing.assert_array_equal(np.asarray(y), np.asarray(runs[0][0]))
        np.testing.assert_array_equal(np.asarray(skip), np.asarray(runs[0][1]))


def test_vgg16_plan_bumps_the_folded_macs(capsys):
    """CONV2 (64->64 at 224) and CONV3 (64->128 at 112) run two taps a
    pass; CONV1 runs in patch form, one pass."""
    from repro.obs.explain import main

    clear_partition_cache()
    with tracing() as collector:
        plan = auto_partition(MODELS["vgg16"](), batch=8,
                              compute_dtype="bfloat16")
    assert collector.counters[FOLDED_CONV_MACS] == 1_849_688_064 + 924_844_032
    assert collector.counters[CONV_MACS] == 15_346_630_656
    assert plan.pyramids[0].launch.program.folds() == (1, 2, 2, 1)
    clear_partition_cache()
    assert main(["--model", "vgg16", "--dtype", "bfloat16", "--batch", "8"]) == 0
    text = capsys.readouterr().out
    assert "taps/pass" in text
    assert "CONV1..POOL2" in text and " 1 2 2 1 " in text
    assert f"{FOLDED_CONV_MACS} +2,774,532,096 (18.1% folded)" in text


# every pyramid as "name out_region alpha regime" at buckets 1 and 8, as
# planned before tap folding: folding moves no plan
PLANS = {
    "vgg16.1": (
        "CONV1..POOL2 14 4 resident, CONV5..POOL3 28 1 resident, "
        "CONV8..POOL5 7 1 streamed_w1 "
    ),
    "vgg16.8": (
        "CONV1..POOL2 14 4 resident, CONV5..POOL3 28 1 resident, "
        "CONV8..POOL4 7 2 resident, CONV11..POOL5 7 1 resident "
    ),
    "resnet18.1": (
        "conv1..maxpool 56 1 resident, b0_convA..b0_convB 56 1 resident, "
        "b1_convA..b1_convB 56 1 resident, b2_convA..b2_convB 28 1 "
        "resident, b2_proj 28 1 resident, b3_convA..b3_convB 28 1 resident, "
        "b4_convA..b4_convB 14 1 resident, b4_proj 14 1 resident, "
        "b5_convA..b5_convB 14 1 resident, b6_convA..b6_convB 7 1 resident, "
        "b6_proj 7 1 resident, b7_convA..b7_convB 7 1 resident "
    ),
    "resnet18.8": (
        "conv1..maxpool 56 1 resident, b0_convA..b0_convB 56 1 resident, "
        "b1_convA..b1_convB 56 1 resident, b2_convA..b2_convB 28 1 "
        "resident, b2_proj 28 1 resident, b3_convA..b3_convB 28 1 resident, "
        "b4_convA..b4_convB 14 1 resident, b4_proj 14 1 resident, "
        "b5_convA..b5_convB 14 1 resident, b6_convA..b6_convB 7 1 resident, "
        "b6_proj 7 1 resident, b7_convA..b7_convB 7 1 resident "
    ),
    "resnet50.1": (
        "conv1..maxpool 56 1 resident, b0_convA..b0_convC 56 1 resident, "
        "b0_proj 56 1 resident, b1_convA..b1_convC 56 1 resident, "
        "b2_convA..b2_convC 56 1 resident, b3_convA..b3_convC 28 1 "
        "resident, b3_proj 28 1 resident, b4_convA..b4_convC 28 1 resident, "
        "b5_convA..b5_convC 28 1 resident, b6_convA..b6_convC 28 1 "
        "resident, b7_convA..b7_convC 14 1 resident, b7_proj 14 1 resident, "
        "b8_convA..b8_convC 14 1 resident, b9_convA..b9_convC 14 1 "
        "resident, b10_convA..b10_convC 14 1 resident, b11_convA..b11_convC "
        "14 1 resident, b12_convA..b12_convC 14 1 resident, "
        "b13_convA..b13_convC 7 1 resident, b13_proj 7 1 resident, "
        "b14_convA..b14_convC 7 1 resident, b15_convA..b15_convC 7 1 "
        "resident "
    ),
    "resnet50.8": (
        "conv1..maxpool 56 1 resident, b0_convA..b0_convC 56 1 resident, "
        "b0_proj 56 1 resident, b1_convA..b1_convC 56 1 resident, "
        "b2_convA..b2_convC 56 1 resident, b3_convA..b3_convC 28 1 "
        "resident, b3_proj 28 1 resident, b4_convA..b4_convC 28 1 resident, "
        "b5_convA..b5_convC 28 1 resident, b6_convA..b6_convC 28 1 "
        "resident, b7_convA..b7_convC 14 1 resident, b7_proj 14 1 resident, "
        "b8_convA..b8_convC 14 1 resident, b9_convA..b9_convC 14 1 "
        "resident, b10_convA..b10_convC 14 1 resident, b11_convA..b11_convC "
        "14 1 resident, b12_convA..b12_convC 14 1 resident, "
        "b13_convA..b13_convC 7 1 resident, b13_proj 7 1 resident, "
        "b14_convA..b14_convC 7 1 resident, b15_convA..b15_convC 7 1 "
        "resident "
    ),
}


@pytest.mark.parametrize("model", ["vgg16", "resnet18", "resnet50"])
@pytest.mark.parametrize("bucket", [1, 8])
def test_plans_did_not_move(model, bucket):
    clear_partition_cache()
    plan = auto_partition(MODELS[model](), batch=bucket,
                          compute_dtype="bfloat16")
    got = ", ".join(
        f"{p.name} {p.launch.out_region} {p.launch.program.alpha}"
        f" {p.launch.regime}"
        for p in plan.pyramids
    )
    assert got == PLANS[f"{model}.{bucket}"].strip()
