"""Low-precision fused dataflow (DESIGN.md §11): bf16 end-to-end.

* bf16 parity — every dataflow regime (resident / streamed x1 / x2 /
  channel-tiled) produces **bit-identical**
  bf16 outputs: the f32-accumulate-then-cast contract makes the movement
  schedule invisible at any dtype, exactly as at f32;
* bf16 accuracy — each regime is bit-close to the f32 reference (operand
  rounding only), END skip maps are dtype-invariant, and the END cascade
  fires identically at bf16;
* byte-model scaling — modeled HBM/VMEM/slice bytes of random Q=1-4
  pyramids scale exactly with ``DTYPE_BYTES`` (int32 skip flags excepted),
  as a hypothesis sweep plus a deterministic seeded fallback that runs even
  where hypothesis is stubbed;
* cycle-model scaling — DMA terms scale with bytes, MXU compute cycles
  divide by the dtype's throughput factor, bf16 plans are modeled strictly
  cheaper;
* the plan ladder re-tiers — a pyramid that must stream at f32 goes
  resident at bf16 under the same budget, and the partition DP plans the
  network accordingly;
* end-to-end — ``run_network(..., dtype=jnp.bfloat16)`` runs LeNet within
  the documented logit tolerance (the CI smoke contract), and int8 remains
  model-only (kernels raise).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dtypes import (
    DTYPE_BYTES,
    canonical_dtype,
    dtype_bytes,
    jnp_dtype,
    mxu_throughput,
)
from repro.core.cycle_model import mxu_scaled_cycles
from repro.core.executor import init_pyramid_params
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.intensity import launch_dataflow
from repro.core.program import compile_program, plan_launch
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import MODELS, lenet5
from repro.net.partition import auto_partition
from repro.net.runner import (
    bf16_logit_tol,
    init_network_params,
    prepare_network_params,
    reference_network,
    run_network,
)

KEY = jax.random.PRNGKey(0)

Q2_CHAIN = FusionSpec(
    levels=(
        FusedLevel("conv", K=3, S=1, pad=0, n_in=3, n_out=8),
        FusedLevel("pool", K=2, S=2, pad=0, n_in=8, n_out=8),
        FusedLevel("conv", K=3, S=1, pad=0, n_in=8, n_out=16),
    ),
    input_size=16,
)


def _inputs(spec, batch=1, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )


def _run(spec, x, region, *, biases=None, **kw):
    p = init_pyramid_params(spec, KEY)
    return fused_pyramid(
        x, p.weights, biases if biases is not None else p.biases, spec=spec,
        out_region=region, **kw,
    )


def _random_spec(rng: random.Random) -> FusionSpec:
    """Seeded random Q=1-4 pyramid with positive output sizes."""
    size = rng.randrange(10, 24)
    c = rng.randrange(1, 4)
    cur, levels = size, []
    for _ in range(rng.randrange(1, 5)):
        if levels and levels[-1].kind == "conv" and rng.random() < 0.3:
            if (cur - 2) // 2 + 1 < 2:
                continue
            levels.append(FusedLevel("pool", 2, 2, 0, c, c))
            cur = (cur - 2) // 2 + 1
        else:
            K = rng.randrange(1, 4)
            pad = rng.randrange(0, K // 2 + 1)
            nxt = cur + 2 * pad - K + 1
            if nxt < 2:
                continue
            c2 = rng.randrange(2, 8)
            levels.append(FusedLevel("conv", K, 1, pad, c, c2))
            c, cur = c2, nxt
    if not any(l.kind == "conv" for l in levels):
        levels = [FusedLevel("conv", 3, 1, 1, c, 4)]
    return FusionSpec(levels=tuple(levels), input_size=size)


def _assert_byte_scaling(spec: FusionSpec) -> None:
    """Every HBM byte model scales exactly with bytes_per_val (int32 END
    flags excepted, which stay 4 bytes at any compute dtype); VMEM prices the
    same buffers at their own dtypes."""
    region = spec.feature_sizes()[-1]
    progs = {
        d: compile_program(spec, region, compute_dtype=d)
        for d in ("float32", "bfloat16", "int8")
    }
    base = progs["float32"]
    flags = DTYPE_BYTES["int32"] * base.alpha ** 2 * base.q_convs
    for d, prog in progs.items():
        r = DTYPE_BYTES[d] / DTYPE_BYTES["float32"]
        assert prog.bytes_per_val == DTYPE_BYTES[d]
        assert prog.input_hbm_bytes(1) == base.input_hbm_bytes(1) * r
        # VMEM is priced per buffer as the chip pads it: the same buffers
        # at every dtype, tiles and weights at the compute dtype, the row
        # stage and pooled conv outputs at f32, the skip flags at int32
        for streamed in (False, True):
            bufs = prog.vmem_buffers(2, 1, streamed=streamed, w_slots=2)
            ref = base.vmem_buffers(2, 1, streamed=streamed, w_slots=2)
            assert [(n, s) for n, s, _ in bufs] == [(n, s) for n, s, _ in ref]
            assert all(
                dt == {"stage": "float32", "conv_out": "float32",
                       "skip_block": "int32"}.get(n, d)
                for n, _, dt in bufs
            )
        assert prog.vmem_bytes(2, 1) <= base.vmem_bytes(2, 1)
        for streamed in (False, True):
            assert (
                prog.hbm_bytes(1, streamed=streamed) - flags
                == (base.hbm_bytes(1, streamed=streamed) - flags) * r
            )
            flow = launch_dataflow(prog, streamed=streamed)
            assert flow["skip_bytes"] == DTYPE_BYTES["int32"] * (
                prog.alpha ** 2 * prog.q_convs
            )
            assert (
                flow["input_bytes_halo"] + flow["weight_bytes"]
                + flow["output_bytes"] + flow["skip_bytes"]
                == prog.hbm_bytes(1, streamed=streamed)
            )


class TestBF16KernelParity:
    """All bf16 dataflow regimes are bit-identical to each other and
    bit-close to the f32 reference."""

    def _all_regimes(self, spec, x, region, c_tiles):
        runs = {
            "resident": _run(spec, x, region, compute_dtype="bfloat16"),
            "stream_x1": _run(
                spec, x, region, streamed=True, w_slots=1, x_slots=1,
                compute_dtype="bfloat16",
            ),
            "stream_x2": _run(
                spec, x, region, streamed=True, w_slots=2, x_slots=2,
                compute_dtype="bfloat16",
            ),
            "ktiled": _run(
                spec, x, region, streamed=True, w_slots=2, c_tiles=c_tiles,
                compute_dtype="bfloat16",
            ),
            "stream_w1_x2": _run(
                spec, x, region, streamed=True, w_slots=1, x_slots=2,
                compute_dtype="bfloat16",
            ),
        }
        return runs

    @pytest.mark.parametrize("batch", [1, 2])
    def test_regimes_bitwise_identical(self, batch):
        x = _inputs(Q2_CHAIN, batch=batch)
        runs = self._all_regimes(Q2_CHAIN, x, 5, c_tiles=2)
        y0, s0 = runs.pop("resident")
        assert y0.dtype == jnp.bfloat16
        for name, (y, s) in runs.items():
            np.testing.assert_array_equal(
                np.asarray(y0), np.asarray(y), err_msg=name
            )
            np.testing.assert_array_equal(
                np.asarray(s0), np.asarray(s), err_msg=name
            )

    def test_bit_close_to_f32(self):
        x = _inputs(Q2_CHAIN)
        y32, s32 = _run(Q2_CHAIN, x, 5)
        y16, s16 = _run(Q2_CHAIN, x, 5, compute_dtype="bfloat16")
        # skip maps are dtype-invariant; outputs differ by operand rounding
        np.testing.assert_array_equal(np.asarray(s32), np.asarray(s16))
        err = float(jnp.max(jnp.abs(y32 - y16.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(y32)))
        assert err <= 0.02 * max(scale, 1.0), (err, scale)

    def test_end_cascade_fires_at_bf16(self):
        """A dead input (zero image, biases <= 0, so level 0's post-ReLU
        tile is all zero) must skip levels >= 1 at bf16 exactly as at f32."""
        spec = Q2_CHAIN
        x = jnp.zeros((1, spec.input_size, spec.input_size,
                       spec.levels[0].n_in))
        biases = [-0.1 * jnp.ones((l.n_out,)) for l in spec.levels
                  if l.kind == "conv"]
        for kw in ({}, {"streamed": True, "w_slots": 2},
                   {"streamed": True, "w_slots": 2, "c_tiles": 2}):
            _, skip = _run(
                spec, x, 1, biases=biases, compute_dtype="bfloat16", **kw
            )
            assert np.asarray(skip)[..., 1:].all(), kw

    def test_f32_weights_cast_on_entry(self):
        """f32 master weights and pre-cast bf16 weights give the same bf16
        launch: the wrapper casts every operand once, on entry."""
        p = init_pyramid_params(Q2_CHAIN, KEY)
        x = _inputs(Q2_CHAIN)
        kw = dict(spec=Q2_CHAIN, out_region=5, streamed=True, w_slots=2,
                  compute_dtype="bfloat16")
        y32, _ = fused_pyramid(x, p.weights, p.biases, **kw)
        y16, _ = fused_pyramid(
            x, [w.astype(jnp.bfloat16) for w in p.weights],
            [b.astype(jnp.bfloat16) for b in p.biases], **kw,
        )
        np.testing.assert_array_equal(np.asarray(y32), np.asarray(y16))

    def test_int8_is_model_only(self):
        with pytest.raises(NotImplementedError, match="int8"):
            _run(Q2_CHAIN, _inputs(Q2_CHAIN), 5, compute_dtype="int8")


class TestDtypeTable:
    def test_canonical_accepts_names_and_jnp_dtypes(self):
        assert canonical_dtype("bfloat16") == "bfloat16"
        assert canonical_dtype(jnp.bfloat16) == "bfloat16"
        assert canonical_dtype(np.float32) == "float32"
        assert dtype_bytes(jnp.bfloat16) == 2
        assert jnp_dtype("bfloat16") == jnp.bfloat16

    def test_unknown_dtype_fails_at_plan_time(self):
        with pytest.raises(KeyError, match="float16"):
            canonical_dtype("float16")
        with pytest.raises(KeyError):
            compile_program(Q2_CHAIN, 5, compute_dtype="float64")

    def test_mxu_throughput_factors(self):
        assert mxu_throughput("float32") == 1
        assert mxu_throughput("bfloat16") == 2
        assert mxu_throughput("int8") == 4
        assert mxu_scaled_cycles(101, "bfloat16") == 51  # ceil division
        assert mxu_scaled_cycles(101, "float32") == 101


class TestByteModelScaling:
    """Modeled bytes scale exactly with bytes_per_val — the property that
    keeps the planner's f32/bf16 comparisons honest."""

    def test_seeded_random_pyramids(self):
        rng = random.Random(1234)
        for _ in range(40):
            _assert_byte_scaling(_random_spec(rng))

    @given(st.integers(0, 10_000))
    @settings(max_examples=60, deadline=None)
    def test_hypothesis_random_pyramids(self, seed):
        _assert_byte_scaling(_random_spec(random.Random(seed)))

    def test_slice_bytes_scale(self):
        lp32 = plan_launch(Q2_CHAIN)
        lp16 = plan_launch(Q2_CHAIN, compute_dtype="bfloat16")
        if lp32.c_tiles == lp16.c_tiles:
            assert lp16.slice_bytes() * 2 == lp32.slice_bytes()


class TestCycleModelScaling:
    def test_bf16_strictly_cheaper(self):
        lp32 = plan_launch(Q2_CHAIN)
        lp16 = plan_launch(Q2_CHAIN, compute_dtype="bfloat16")
        assert lp16.modeled_cycles(1) < lp32.modeled_cycles(1)
        assert lp16.hbm_bytes(1) < lp32.hbm_bytes(1)

    def test_input_dma_cycles_halve(self):
        p32 = compile_program(Q2_CHAIN, 5)
        p16 = compile_program(Q2_CHAIN, 5, compute_dtype="bfloat16")
        # ceil-divided, so allow the +-1 rounding of halved byte counts
        assert p16.input_dma_cycles() <= -(-p32.input_dma_cycles() // 2) + 1


class TestPlanReTiering:
    """Halved bytes flip regimes: a pyramid that busts VMEM resident at f32
    fits resident at bf16 under the same budget."""

    # weights ~ 3*3*64*64*2 convs = 294912 floats = 1.15 MiB f32
    FAT = FusionSpec(
        levels=(
            FusedLevel("conv", K=3, S=1, pad=1, n_in=64, n_out=64),
            FusedLevel("conv", K=3, S=1, pad=1, n_in=64, n_out=64),
        ),
        input_size=16,
    )

    def _budget(self):
        # between the bf16 and f32 resident working sets of the best region
        lo = min(
            compile_program(self.FAT, r, compute_dtype="bfloat16").vmem_bytes()
            for r in (1, 2, 4, 8, 16)
        )
        hi = min(
            compile_program(self.FAT, r).vmem_bytes()
            for r in (1, 2, 4, 8, 16)
        )
        assert lo < hi
        return (lo + hi) // 2

    def test_streamed_flips_resident(self):
        budget = self._budget()
        lp32 = plan_launch(self.FAT, vmem_budget=budget)
        lp16 = plan_launch(
            self.FAT, vmem_budget=budget, compute_dtype="bfloat16"
        )
        assert lp32 is None or lp32.streamed
        assert lp16 is not None and not lp16.streamed

    def test_partition_dp_is_dtype_aware(self):
        g = lenet5(input_size=32)
        p32 = auto_partition(g, batch=1)
        p16 = auto_partition(g, batch=1, compute_dtype="bfloat16")
        assert p32.compute_dtype == "float32"
        assert p16.compute_dtype == "bfloat16"
        assert p16 is not p32
        assert p16.hbm_bytes() * 2 <= p32.hbm_bytes() + 4 * 1024  # flag slack
        # a graph built bf16 plans bf16 by default
        g16 = lenet5(input_size=32, compute_dtype="bfloat16")
        assert auto_partition(g16, batch=1).compute_dtype == "bfloat16"


class TestNetworkBF16:
    """The CI smoke contract: LeNet end-to-end at bf16 within the
    documented logit tolerance of the f32 reference."""

    def test_lenet_bf16_within_tolerance(self):
        g = lenet5(input_size=32, num_classes=10)
        x = _inputs_net(g, batch=2)
        params = init_network_params(g, KEY)
        ref = reference_network(x, g, params)
        plan = auto_partition(g, batch=2, compute_dtype="bfloat16")
        prepped = prepare_network_params(plan, params)
        logits, _ = run_network(x, prepped, plan=plan)
        assert logits.dtype == jnp.bfloat16
        err = float(jnp.max(jnp.abs(logits.astype(jnp.float32) - ref)))
        assert err <= bf16_logit_tol(ref), (err, bf16_logit_tol(ref))

    @pytest.mark.slow
    @pytest.mark.parametrize("model", sorted(MODELS))
    def test_zoo_bf16_within_tolerance(self, model):
        # the acceptance sweep: every zoo model end-to-end at bf16 stays
        # within the documented logit tolerance of its f32 reference
        # (reduced spatial scale so interpret mode stays tractable; the
        # partitioner and kernels are the same code as paper scale)
        size = 32 if model != "alexnet" else 67
        g = MODELS[model](input_size=size, num_classes=10)
        x = _inputs_net(g, batch=1)
        params = init_network_params(g, KEY)
        ref = reference_network(x, g, params)
        plan = auto_partition(g, batch=1, compute_dtype="bfloat16")
        prepped = prepare_network_params(plan, params)
        logits, _ = run_network(x, prepped, plan=plan)
        assert logits.dtype == jnp.bfloat16
        err = float(jnp.max(jnp.abs(logits.astype(jnp.float32) - ref)))
        assert err <= bf16_logit_tol(ref), (model, err, bf16_logit_tol(ref))

    def test_dtype_override_accepts_jnp_dtype(self):
        g = lenet5(input_size=32, num_classes=10)
        x = _inputs_net(g, batch=1)
        params = init_network_params(g, KEY)
        plan = auto_partition(g, batch=1, compute_dtype="bfloat16")
        prepped = prepare_network_params(plan, params)
        a, _ = run_network(x, prepped, plan=plan, dtype=jnp.bfloat16)
        b, _ = run_network(x, prepped, plan=plan)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _inputs_net(graph, batch=1, seed=3):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (batch, graph.input_size, graph.input_size, graph.in_channels),
    )
