"""Compile rehearsal for a TPU v5e without one attached.

Interpret mode accepts kernels Mosaic refuses: unaligned DMA windows, value
reshapes and strided value slices, blocks that break the (8, 128) rule, more
VMEM than the scoped limit.  These tests compile for a *described* v5e chip
(``jax.experimental.topologies``), so what the chip's compiler would refuse
fails here, at no chip time:

* one fused-pyramid launch of every distinct kind (weight regime, input
  slots, level strides, pools, taps per pass, compute dtype) the zoo's
  plans use at their published input sizes, buckets 1 and 8, each
  compiled with the VMEM limit its plan's budget sets (budget plus
  Mosaic's headroom);
* the whole ResNet-18 224x224 forward, whose HLO must hold one
  ``tpu_custom_call`` per planned pyramid (no launch left to interpret mode),
  each named after its pyramid (the name a profiler trace shows);
* ConvNeXt-T's launches (the LayerNorm stem in patch form, a block's
  depthwise level with LayerNorm at 96 and at 768 lanes, its GELU level and
  its scaled linear last level) and its whole 224x224 forward at bucket 8,
  one named kernel per pyramid.

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and the workers of a multi-process test run import
every test file.  Keep these tests in this one file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dtypes import jnp_dtype
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import MODELS
from repro.net.partition import auto_partition
from repro.net.runner import (
    _run_network_jit,
    init_network_params,
    prepare_network_params,
)

ZOO = [
    (model, dtype)
    for model in ("lenet", "alexnet", "vgg16", "resnet18")
    for dtype in ("float32", "bfloat16")
] + [("resnet50", "bfloat16"), ("convnext_tiny", "bfloat16")]
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kind(launch, dtype):
    prog = launch.program
    return (
        launch.regime,
        prog.patch,
        launch.x_slots,
        prog.alpha > 1,
        tuple(p.S for p in prog.levels),
        tuple(p.pool for p in prog.levels),
        tuple(p.relu for p in prog.levels),
        tuple((p.kind, p.gelu, p.norm_eps is not None, p.scale)
              for p in prog.levels),
        prog.folds(),
        dtype,
    )


def _compile_launch(pyr, batch, budget, dtype, sharding):
    lp, spec = pyr.launch, pyr.spec
    cdt = jnp_dtype(dtype)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, cdt, sharding=sharding)

    convs = [lvl for lvl in spec.levels if lvl.has_weights]
    x = shape(batch, spec.input_size, spec.input_size, spec.levels[0].n_in)
    weights = [
        shape(c.K, c.K, 1 if c.kind == "depthwise" else c.n_in, c.n_out)
        for c in convs
    ]
    biases = [shape(c.n_out) for c in convs]
    epilogues = [tuple(shape(c.n_out) for _ in range(c.n_vec - 1))
                 for c in convs]
    return fused_pyramid.lower(
        x, weights, biases, epilogues, spec=spec, out_region=lp.out_region,
        streamed=lp.streamed, w_slots=lp.w_slots if lp.streamed else None,
        x_slots=lp.x_slots, c_tiles=lp.c_tiles,
        interpret=False, vmem_budget=budget,
        compute_dtype=dtype,
    ).compile().as_text()


@pytest.mark.parametrize("model,dtype", ZOO)
def test_zoo_launch_kinds_compile(model, dtype, one_chip, no_compile_cache):
    kinds = {}
    for bucket in (1, 8):
        plan = auto_partition(MODELS[model](), batch=bucket, compute_dtype=dtype)
        for pyr in plan.pyramids:
            kinds.setdefault(
                _kind(pyr.launch, dtype), (pyr, bucket, plan.vmem_budget)
            )
    for kind, (pyr, bucket, budget) in kinds.items():
        text = _compile_launch(pyr, bucket, budget, dtype, one_chip)
        assert text.count(CUSTOM_CALL) == 1, (model, pyr.name, kind)


@pytest.mark.parametrize("name", [
    "stem..stem_ln", "b0_dw..b0_scale", "b15_dw..b15_scale",
])
def test_convnext_launches_compile(name, one_chip, no_compile_cache):
    """The stem (patch form, LayerNorm epilogue) and a block at 96 and at
    768 channels: a depthwise level with LayerNorm, a GELU level and a
    scaled linear last level, as the bucket-8 bf16 plan runs them."""
    plan = auto_partition(MODELS["convnext_tiny"](), batch=8,
                          compute_dtype="bfloat16")
    pyr = plan.pyramid_at(name.split("..")[0])
    assert pyr.name == name
    text = _compile_launch(pyr, 8, plan.vmem_budget, "bfloat16", one_chip)
    assert text.count(CUSTOM_CALL) == 1


def _compile_forward(model, dtype, batch, sharding):
    graph = MODELS[model]()
    plan = auto_partition(graph, batch=batch, compute_dtype=dtype)
    params = jax.eval_shape(
        lambda: prepare_network_params(
            plan, init_network_params(graph, jax.random.PRNGKey(0))
        )
    )
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        params,
    )
    x = jax.ShapeDtypeStruct(
        (batch, graph.input_size, graph.input_size, graph.in_channels),
        jnp.float32, sharding=sharding,
    )
    text = _run_network_jit.lower(
        x, params, plan=plan, interpret=False
    ).compile().as_text()
    return plan, text


def test_resnet18_forward_is_all_kernels(one_chip, no_compile_cache):
    plan, text = _compile_forward("resnet18", "float32", 1, one_chip)
    assert text.count(CUSTOM_CALL) == len(plan.pyramids)


def _assert_named_by_pyramid(plan, text):
    names = [
        m.group(1) for line in text.splitlines() if CUSTOM_CALL in line
        for m in [re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=", line)] if m
    ]
    assert len(names) == len(plan.pyramids)
    pyramids = sorted((p.name for p in plan.pyramids), key=len, reverse=True)
    owners = [next((p for p in pyramids if n.startswith(p)), None)
              for n in names]
    assert None not in owners, names
    assert sorted(owners) == sorted(p.name for p in plan.pyramids), names


def test_kernels_are_named_by_pyramid(one_chip, no_compile_cache):
    """Every kernel's HLO instruction name begins with its pyramid's plan
    name, so a device trace names it ``conv1..maxpool.<n>`` and not
    ``fused_pyramid.<n>``, whatever else changes in the graph."""
    _assert_named_by_pyramid(
        *_compile_forward("resnet18", "bfloat16", 1, one_chip)
    )


def test_convnext_forward_is_one_named_kernel_per_pyramid(
    one_chip, no_compile_cache
):
    """ConvNeXt-T at 224x224, bucket 8, bf16: 22 kernels (stem, 18
    blocks, 3 downsamples), each named ``bN_dw..bN_scale`` and so on."""
    plan, text = _compile_forward("convnext_tiny", "bfloat16", 8, one_chip)
    assert len(plan.pyramids) == 22
    assert text.count(CUSTOM_CALL) == len(plan.pyramids)
    _assert_named_by_pyramid(plan, text)
