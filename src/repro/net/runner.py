"""End-to-end batched network execution: ``run_network`` and its oracle.

``run_network`` executes a :class:`~repro.net.partition.PartitionPlan` as a
sequence of fused-pyramid Pallas launches (one per chosen pyramid, weights
resident or streamed per the plan) stitched together with the plain-JAX ops
the plan left outside pyramids: residual adds, standalone activations,
LayerNorms that follow no level, global pooling, flatten, and the dense
classifier head.  The whole forward is jit-compiled with the plan as a
static argument; the per-launch END skip flag maps are returned alongside
the logits.

``reference_network`` is the monolithic oracle: the same graph executed
node-by-node with full intermediate feature maps via
``jax.lax.conv_general_dilated`` / ``reduce_window``.  ``run_network`` must
match it bit-close (float32 ``atol 1e-4`` end-to-end; enforced in
``tests/test_network_runner.py``) — that contract is what makes the
auto-partitioner free to move fusion boundaries without changing results.

Low precision (DESIGN.md §11): ``run_network(..., dtype="bfloat16")`` (or a
bf16-planned partition) moves every activation tile, weight, and dense
operand at bf16 while *all* accumulation — conv MXU passes, dense matmuls,
the global-average-pool mean — runs in f32 via ``preferred_element_type``.
End-to-end logits then differ from the f32 reference only by operand
rounding, bounded by :func:`bf16_logit_tol` across the zoo (enforced in
``tests/test_precision.py`` and the CI smoke job).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cycle_model import DEFAULT_PARAMS
from repro.core.dtypes import canonical_dtype, jnp_dtype
from repro.core.executor import layer_norm
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.obs.trace import LaunchSpan, get_tracer
from repro.robust.guard import get_guard

from .graph import Graph, Node, infer_shapes
from .partition import PartitionPlan, auto_partition

# node name -> its parameters: (w, b) of a conv, depthwise or dense node,
# (gamma, beta) of a layernorm, (gamma,) of a layer_scale
Params = dict[str, tuple[jnp.ndarray, ...]]

# Documented end-to-end bf16 logit tolerance vs the f32 reference.  bf16
# keeps f32's exponent range but only 8 mantissa bits: each layer's
# operands round to ~2^-9 relative error while accumulation stays exact in
# f32, so the end-to-end error is *relative* to logit magnitude — measured
# ~0.5-0.7% across the He-initialized zoo (ResNet-18's logits reach O(100),
# LeNet's O(1); their absolute errors differ 10x, their relative errors
# don't).  The contract is ``max-abs-err <= ATOL + RTOL * max|logit|``:
# RTOL at ~3x the measured worst case, ATOL as a floor for near-zero
# logits.  A precision bug (double rounding, a bf16 accumulator) breaks
# this by an order of magnitude.  Use :func:`bf16_logit_tol`.
BF16_LOGIT_ATOL = 0.05
BF16_LOGIT_RTOL = 0.02


def bf16_logit_tol(reference) -> float:
    """The documented bf16-vs-f32 max-abs-err bound for a given f32
    reference logit tensor (see :data:`BF16_LOGIT_RTOL`)."""
    return BF16_LOGIT_ATOL + BF16_LOGIT_RTOL * float(
        jnp.max(jnp.abs(reference))
    )


def param_shapes(n: Node, c_in: int) -> tuple[tuple[int, ...], ...]:
    """The shapes of node ``n``'s parameters, given its input channels:
    conv ``(K, K, Cin, Cout)`` + bias, depthwise ``(K, K, 1, C)`` + bias,
    dense ``(fan_in, n_out)`` + bias, layernorm gamma and beta, layer_scale
    gamma; ``()`` for a node without."""
    if n.op == "conv":
        return (n.K, n.K, c_in, n.n_out), (n.n_out,)
    if n.op == "depthwise":
        return (n.K, n.K, 1, c_in), (c_in,)
    if n.op == "dense":
        return (c_in, n.n_out), (n.n_out,)
    return {"layernorm": ((c_in,), (c_in,)), "layer_scale": ((c_in,),)}.get(
        n.op, ()
    )


def init_network_params(graph: Graph, key: jax.Array, scale: float = 1.0) -> Params:
    """Parameters for every node that has them, keyed by node name:
    He-initialized weights (fan-in all but the last axis) with biases of
    standard deviation 0.01 for conv, depthwise and dense nodes; LayerNorm
    gamma ``1 + 0.1 N(0, 1)`` and beta ``0.1 N(0, 1)``; layer-scale gamma
    ``1 + 0.1 N(0, 1)`` (an identity-like 1e-6, torchvision's training
    init, would hide the block from every comparison)."""
    shapes = infer_shapes(graph)
    params: Params = {}
    for n in graph.nodes:
        found = param_shapes(n, shapes[n.inputs[0]].channels if n.inputs else 0)
        if not found:
            continue
        key, *keys = jax.random.split(key, 1 + len(found))
        draws = [jax.random.normal(k, s) for k, s in zip(keys, found)]
        if n.op in ("conv", "depthwise", "dense"):
            w_shape = found[0]
            fan_in = int(np.prod(w_shape[:-1]))
            draws = [draws[0] * (scale * (2.0 / fan_in) ** 0.5), draws[1] * 0.01]
        else:  # gamma around 1, then beta around 0
            draws = [(1.0 if i == 0 else 0.0) + 0.1 * d for i, d in enumerate(draws)]
        params[n.name] = tuple(d.astype(jnp.float32) for d in draws)
    return params


def _conv_node(x, n: Node, w, b):
    # f32 accumulation at any operand dtype, cast back to the network's
    # compute dtype — the plain-op mirror of the kernel's §11 contract
    # (identity for f32 inputs, so the reference oracle is unchanged); f32
    # operands take full-precision MXU passes on a TPU, as in the kernel
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(n.S, n.S),
        padding=[(n.pad, n.pad), (n.pad, n.pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1] if n.op == "depthwise" else 1,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ) + b
    out = jax.nn.relu(out) if n.relu else out
    return out.astype(x.dtype)


def _pool_node(x, n: Node):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        window_dimensions=(1, n.K, n.K, 1),
        window_strides=(1, n.S, n.S, 1),
        padding=((0, 0), (n.pad, n.pad), (n.pad, n.pad), (0, 0)),
    )


def _head_op(values, n: Node, params: Params, graph: Graph | None = None):
    if n.op == "relu":
        return jax.nn.relu(values[n.inputs[0]])
    if n.op in ("layernorm", "gelu", "layer_scale"):
        # in f32, cast back to the network dtype once
        x = values[n.inputs[0]]
        if n.op == "layernorm":
            y = layer_norm(x, *params[n.name], n.eps)
        elif n.op == "gelu":
            y = jax.nn.gelu(x.astype(jnp.float32), approximate=False)
        else:
            y = x.astype(jnp.float32) * params[n.name][0]
        return y.astype(x.dtype)
    if n.op == "add":
        return values[n.inputs[0]] + values[n.inputs[1]]
    if n.op == "global_pool":
        # mean in f32: a bf16 running sum over H*W terms would lose low
        # bits of every partial; cast back to the network dtype once
        x = values[n.inputs[0]]
        return jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(x.dtype)
    if n.op == "flatten":
        x = values[n.inputs[0]]
        return x.reshape(x.shape[0], -1)
    if n.op == "dense":
        x = values[n.inputs[0]]
        w, b = params[n.name]
        # operands at the network dtype, accumulation in f32 (§11)
        out = jnp.dot(
            x, w.astype(x.dtype), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ) + b
        out = jax.nn.relu(out) if n.relu else out
        return out.astype(x.dtype)
    from repro.robust.errors import PreflightError

    raise PreflightError(
        f"node {n.name!r} has op {n.op!r}, which the runner cannot execute"
        " (expected one of relu/add/global_pool/flatten/dense/layernorm/"
        "gelu/layer_scale outside pyramids)",
        node=n.name, op=n.op,
        graph=graph.name if graph is not None else None,
    )


def reference_network(x: jnp.ndarray, graph: Graph, params: Params) -> jnp.ndarray:
    """Monolithic node-by-node forward: full intermediate maps, no fusion.
    Ground truth for ``run_network`` and the baseline dataflow whose off-chip
    traffic the partitioner minimizes.  Runs in f32 at ``highest`` matmul
    precision, so on a TPU it is an f32 oracle, not a bf16-pass one."""
    values = {graph.nodes[0].name: x.astype(jnp.float32)}
    with jax.default_matmul_precision("highest"):
        for n in graph.nodes[1:]:
            if n.op in ("conv", "depthwise"):
                w, b = params[n.name]
                values[n.name] = _conv_node(values[n.inputs[0]], n, w, b)
            elif n.op == "pool":
                values[n.name] = _pool_node(values[n.inputs[0]], n)
            else:
                values[n.name] = _head_op(values, n, params, graph)
    return values[graph.output.name]


def prepare_network_params(
    plan: PartitionPlan, params: Params, dtype: str | None = None
) -> Params:
    """Cast params to the plan's compute dtype, once per model.

    ``dtype`` (``None`` = ``plan.compute_dtype``) is the value width the
    launches move: every conv/dense weight and bias is cast once here
    instead of per ``run_network`` call inside the jit graph.  Master params
    stay f32 in the caller's dict — this returns a new dict, so
    re-preparing at another dtype is safe.
    """
    jdt = jnp_dtype(plan.compute_dtype if dtype is None else dtype)
    return {k: tuple(a.astype(jdt) for a in v) for k, v in params.items()}


def pyramid_operands(graph: Graph, node_names, params: Params):
    """``(weights, biases, epilogues)`` of a pyramid covering
    ``node_names``, per level with weights in chain order, as
    :func:`~repro.kernels.fused_conv.ops.fused_pyramid` takes them:
    ``epilogues[l]`` gathers the parameters of the layernorm and
    layer_scale nodes the level absorbed."""
    weights, biases, epilogues = [], [], []
    for m in node_names:
        op = graph.node(m).op
        if op in ("conv", "depthwise"):
            w, b = params[m]
            weights.append(w)
            biases.append(b)
            epilogues.append(())
        elif op in ("layernorm", "layer_scale"):
            epilogues[-1] += tuple(params[m])
    return weights, biases, epilogues


def _forward(
    x: jnp.ndarray,
    params: Params,
    *,
    plan: PartitionPlan,
    end_skip: bool,
    interpret: bool | None,
    cdt: str,
    launch_wrapper=None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """The plan-driven forward loop, shared by the jit fast path, the
    per-launch timed path, and the guarded eager path.
    ``launch_wrapper(pyr, call, x_in)``, when given, wraps each
    fused-pyramid launch — the per-launch path times it there, the guarded path
    (``repro.robust.degrade``) runs its degradation ladder there, using
    ``x_in`` (the launch input) for replans and reference quarantines and
    ``call(interpret=True)``-style keyword overrides for retries.  The jit
    path passes ``None`` so neither adds anything to the compiled graph."""
    jdt = jnp_dtype(cdt)
    graph = plan.graph
    covered = plan.covered()
    values = {graph.nodes[0].name: x.astype(jdt)}
    skips: dict[str, jnp.ndarray] = {}
    for n in graph.nodes[1:]:
        if n.name in covered:
            pyr = plan.pyramid_at(n.name)
            if pyr is None:
                continue  # interior pyramid node: computed with its launch
            operands = pyramid_operands(graph, pyr.node_names, params)
            x_in = values[n.inputs[0]]

            def call(pyr=pyr, x_in=x_in, operands=operands, **overrides):
                kwargs = dict(
                    spec=pyr.spec,
                    out_region=pyr.launch.out_region,
                    streamed=pyr.launch.streamed,
                    w_slots=(
                        pyr.launch.w_slots if pyr.launch.streamed else None
                    ),
                    x_slots=pyr.launch.x_slots,
                    c_tiles=pyr.launch.c_tiles,
                    end_skip=end_skip,
                    interpret=interpret,
                    vmem_budget=plan.vmem_budget,
                    compute_dtype=cdt,
                    name=pyr.name,
                )
                # wrapper retries may override launch knobs, e.g.
                # call(interpret=True) on the degradation ladder
                kwargs.update(overrides)
                return fused_pyramid(x_in, *operands, **kwargs)

            y, skip = call() if launch_wrapper is None else launch_wrapper(
                pyr, call, x_in
            )
            values[pyr.node_names[-1]] = y
            skips[pyr.name] = skip
        elif n.op in ("conv", "depthwise"):
            w, b = params[n.name]
            values[n.name] = _conv_node(
                values[n.inputs[0]], n, w.astype(jdt), b.astype(jdt)
            )
        elif n.op == "pool":
            values[n.name] = _pool_node(values[n.inputs[0]], n)
        else:
            values[n.name] = _head_op(values, n, params, graph)
    return values[graph.output.name], skips


# Python-side retrace accounting of the jit fast path.  ``jax.jit`` keys its
# executable cache on (static args, operand shapes/dtypes), so every distinct
# batch size is a fresh trace + compile even when the plan is identical —
# the cost the serving engine's pad-to-bucket admission amortizes: all
# requests in a bucket share one input shape, so wave 2 of a bucket replays
# the wave-1 executable.  The counter increments inside the traced body
# (which Python only executes at trace time), making "how many compiles did
# this workload pay" a testable quantity (``tests/test_serve.py``).
_JIT_STATS = {"traces": 0}


def jit_trace_count() -> int:
    """Process-lifetime count of ``run_network`` jit fast-path traces."""
    return _JIT_STATS["traces"]


def reset_jit_trace_count() -> None:
    """Zero the retrace counter (the executable cache itself is untouched —
    re-running a known shape after a reset still counts 0 new traces)."""
    _JIT_STATS["traces"] = 0


@partial(jax.jit, static_argnames=("plan", "end_skip", "interpret", "dtype"))
def _run_network_jit(
    x: jnp.ndarray,
    params: Params,
    *,
    plan: PartitionPlan,
    end_skip: bool = True,
    interpret: bool | None = None,
    dtype: str | None = None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    # executes at trace time only: one bump per new (plan, shape, dtype) key
    _JIT_STATS["traces"] += 1
    tracer = get_tracer()
    if tracer.enabled:
        tracer.bump("run_network_jit_trace")
    cdt = canonical_dtype(plan.compute_dtype if dtype is None else dtype)
    return _forward(
        x, params, plan=plan, end_skip=end_skip, interpret=interpret, cdt=cdt
    )


def run_network_per_launch(
    x: jnp.ndarray,
    params: Params,
    *,
    plan: PartitionPlan,
    collector,
    end_skip: bool = True,
    interpret: bool | None = None,
    dtype: str | None = None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """The launch-by-launch timed forward (DESIGN.md §12): the same plan
    executed outside the whole-graph jit (each ``fused_pyramid`` call is
    still jit itself), every launch blocked-until-ready and recorded into
    ``collector`` (a :class:`~repro.obs.trace.TraceCollector`) as a
    :class:`LaunchSpan` whose modeled fields come straight from the plan —
    plus per-launch END-skip count events and one ``run_network`` summary
    event.  Slower than :func:`run_network` by construction (that is what
    it measures), so only callers that want per-launch times call it:
    ``repro.obs.explain --run`` and the example script.  Same arguments and
    results as :func:`run_network`."""
    cdt = canonical_dtype(plan.compute_dtype if dtype is None else dtype)
    model = plan.graph.name
    batch = int(x.shape[0])

    def wrapper(pyr, call, x_in):
        t0 = time.perf_counter()
        y, skip = call()
        jax.block_until_ready((y, skip))
        dur_ms = (time.perf_counter() - t0) * 1e3
        d = pyr.launch.describe(batch, plan.vmem_budget)
        collector.record_span(LaunchSpan(
            name=pyr.name,
            model=model,
            regime=d["regime"],
            out_region=d["out_region"],
            alpha=d["alpha"],
            q_convs=d["q_convs"],
            x_slots=d["x_slots"],
            w_slots=d["w_slots"],
            c_tiles=d["c_tiles"],
            batch=batch,
            compute_dtype=cdt,
            streamed=d["streamed"],
            hbm_bytes=d["hbm_bytes"],
            vmem_bytes=d["vmem_bytes"],
            modeled_cycles=d["modeled_cycles"],
            modeled_us=d["modeled_cycles"] / DEFAULT_PARAMS.freq_mhz,
            start_s=t0,
            duration_ms=dur_ms,
        ))
        return y, skip

    t0 = time.perf_counter()
    logits, skips = _forward(
        x, params, plan=plan, end_skip=end_skip, interpret=interpret,
        cdt=cdt, launch_wrapper=wrapper,
    )
    jax.block_until_ready(logits)
    total_ms = (time.perf_counter() - t0) * 1e3
    for name, skip in skips.items():
        arr = np.asarray(skip)
        # per-level count of grid cells the END cascade skipped, plus the
        # cell total — the runtime twin of the paper's skipped-convolution
        # accounting (level 0 never skips by construction)
        collector.record_event(
            "end_skip_counts",
            model=model,
            launch=name,
            per_level=[int(c) for c in arr.sum(axis=(0, 1, 2))],
            cells=int(arr[..., 0].size),
        )
    collector.record_event(
        "run_network",
        model=model,
        batch=batch,
        compute_dtype=cdt,
        launches=len(skips),
        wallclock_ms=total_ms,
        modeled_cycles=plan.modeled_cycles(),
    )
    return logits, skips


def run_network(
    x: jnp.ndarray,
    params: Params,
    *,
    plan: PartitionPlan,
    end_skip: bool = True,
    interpret: bool | None = None,
    dtype: str | None = None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Execute the partition plan end to end for a batch ``x`` (B, H, W, C).

    ``dtype`` (static; name string or jnp dtype, ``None`` =
    ``plan.compute_dtype``) is the compute dtype of the whole forward:
    every pyramid launch, plain conv/pool, and head op moves operands at
    that width with f32 accumulation, and the logits come back at it.
    Overriding a plan to a *wider* dtype can bust the planned VMEM regimes;
    the supported direction is planning at the dtype you run
    (``auto_partition(..., compute_dtype=...)``) or narrowing.

    ``interpret=None`` resolves per backend (compiled on TPU).  Params may
    come through :func:`prepare_network_params` so the cast to the run
    dtype happens once per model.
    Returns ``(logits, skips)``: ``skips[pyramid.name]`` is that launch's
    ``(B, alpha, alpha, Q)`` int32 END-cascade flag map (level 0 of each
    pyramid never skips).  Aggregate with :func:`skip_fractions`.

    The forward is one jit-compiled program whatever tracer is installed;
    per-launch times come from :func:`run_network_per_launch` (DESIGN.md
    §12).

    Guarded execution (DESIGN.md §13): with a guard installed
    (``repro.robust.guarding()``) the forward instead runs the preflighted,
    sentinel-checked degradation-ladder path of
    :func:`repro.robust.degrade.run_network_guarded`.  The guard is one
    static ``enabled`` check outside jit — guards off leaves the jit fast
    path byte-identical.
    """
    guard = get_guard()
    if guard.enabled:
        from repro.robust.degrade import run_network_guarded

        return run_network_guarded(
            x, params, plan=plan, end_skip=end_skip, interpret=interpret,
            dtype=dtype, guard=guard,
        )
    return _run_network_jit(
        x, params, plan=plan, end_skip=end_skip, interpret=interpret,
        dtype=dtype,
    )


def skip_fractions(skips: dict[str, jnp.ndarray]) -> dict[str, list[float]]:
    """Per-pyramid, per-level fraction of tiles the END cascade skipped."""
    return {
        name: [float(f) for f in np.asarray(s, dtype=np.float64).mean(axis=(0, 1, 2))]
        for name, s in skips.items()
    }


def run_model(
    name: str,
    x: jnp.ndarray,
    params: Params | None = None,
    *,
    input_size: int | None = None,
    num_classes: int | None = None,
    plan: PartitionPlan | None = None,
    seed: int = 0,
    interpret: bool | None = None,
    dtype: str | None = None,
):
    """Convenience one-shot: build the zoo graph, auto-partition, run.

    ``dtype`` selects the compute dtype end to end: the partition is
    *planned* at it (regimes re-tiered under the narrower bytes) and the
    params are cast once before the run; master ``params`` (returned) stay
    f32 so the same dict can be re-run at any dtype.

    Returns ``(logits, skips, plan, params)``.  Used by the example script
    and benchmarks; library code should call :func:`run_network` directly.
    """
    from .graph import MODELS

    kwargs = {}
    if input_size is not None:
        kwargs["input_size"] = input_size
    if num_classes is not None:
        kwargs["num_classes"] = num_classes
    graph = MODELS[name](**kwargs)
    if plan is None:
        plan = auto_partition(graph, batch=x.shape[0], compute_dtype=dtype)
    if params is None:
        params = init_network_params(graph, jax.random.PRNGKey(seed))
    prepped = prepare_network_params(plan, params)
    logits, skips = run_network(x, prepped, plan=plan, interpret=interpret)
    return logits, skips, plan, params
