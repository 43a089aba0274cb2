"""Public wrappers for the variadic fused conv-pyramid Pallas kernel.

All window/offset math comes from the tile-program compiler
(:mod:`repro.core.program`); this module only pads inputs (or builds the
patch tensor of a patch-form level 0), checks the VMEM budget, and
launches:

* :func:`fused_pyramid` — any Q >= 1 conv levels (odd Q and conv-only pairs
  included) as **one** kernel launch; LeNet's Q=2, VGG blocks 1-2's Q=4, and
  every ResNet-18 block each fit a single launch.
* :func:`fused_conv2` — thin compatibility wrapper for the historical 2-conv
  entry point (returns the old ``(B, alpha, alpha)`` skip map).
* :func:`fused_pyramid_chain` — chunks a chain into multiple launches *only*
  when the VMEM budget forces it (or an explicit per-chunk conv cap is given,
  e.g. to reproduce USEFUSE's FPGA deployment granularity of Q=2 per pyramid).

The VMEM-budget check mirrors the paper's "H <= IFM" feasibility bound with
the TPU's real constraint (DESIGN.md §2 assumption change #2).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.dtypes import canonical_dtype, jnp_dtype
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import (
    MOSAIC_HEADROOM_BYTES,
    VMEM_BUDGET_BYTES,
    compile_program,
    pick_out_region,
    plan_launch,
)
from .fused_conv import fused_pyramid_pallas


@partial(
    jax.jit,
    static_argnames=(
        "spec", "out_region", "streamed", "w_slots", "x_slots", "c_tiles",
        "end_skip", "interpret", "vmem_budget", "compute_dtype", "name",
    ),
)
def fused_pyramid(
    x: jnp.ndarray,
    weights: list,
    biases: list,
    epilogues: list | None = None,
    *,
    spec: FusionSpec,
    out_region: int | None = None,
    streamed: bool | None = None,
    w_slots: int | None = None,
    x_slots: int | None = None,
    c_tiles: int | None = None,
    end_skip: bool = True,
    interpret: bool | None = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    compute_dtype: str = "float32",
    name: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Q-conv pyramid forward as a single kernel launch.

    ``x``: (B, H, W, C) NHWC; ``weights[l]``: (K, K, Cin, Cout) — a
    depthwise level's (K, K, 1, C) — and ``biases[l]``: (Cout,) per level
    with weights, in chain order.  Each level applies its own activation
    (``spec.levels[l].relu``, ``.gelu``); ``epilogues[l]`` holds the
    parameters of the rest of its epilogue, in order: a LayerNorm's gamma
    and beta (``norm_eps`` set), then a layer scale's gamma (``scale``),
    each ``(Cout,)`` (``None``: no level has either).  ``out_region``
    must tile the final output exactly; ``None`` picks the largest region
    fitting the VMEM budget.  ``streamed`` / ``w_slots`` / ``x_slots`` /
    ``c_tiles`` pin the weight regime, the input landing-buffer depth, and
    the last level's output-channel tile count (the plan-driven entry used
    by :mod:`repro.net.runner`, whose
    :class:`~repro.core.program.LaunchPlan` already decided them); ``None``
    derives them from the budget along ``plan_launch``'s ladder
    (double-buffered weight streaming preferred over channel-tiled double
    buffering over the blocking single slot; the revolving cross-cell input
    prefetch preferred over the serial fetch whenever the grid has a
    successor cell and the extra landing slot fits).
    ``compute_dtype`` (name string or jnp dtype; static) selects the value
    width of every tile/weight moved by the launch — activations and weights
    are cast on entry, accumulation stays f32 inside the kernel (DESIGN.md
    §11) — and re-tiers the regime ladder, since halved bytes let plans that
    streamed at f32 go resident or double-buffered at bf16.
    ``interpret=None`` resolves to compiled on TPU, interpreted on CPU/GPU.
    ``name`` (static) names the kernel: the runner passes the plan's
    pyramid name (``conv1..maxpool``), which becomes the compiled custom
    call's HLO instruction name and so the kernel's name in a profiler
    trace.
    A level 0 that :func:`~repro.core.program.patch_spec` puts in patch
    form runs as a 1x1 conv over :func:`patch_tensor` of ``x`` with
    :func:`patch_weights`; the caller passes the same ``x`` and
    ``(K, K, Cin, Cout)`` weights either way.
    Returns ``(out, skip)`` with ``skip``: (B, alpha, alpha, Q) int32
    END-cascade flags (level 0 never skips, and skip flags are
    dtype-invariant).
    """
    compute_dtype = canonical_dtype(compute_dtype)
    cdt = jnp_dtype(compute_dtype)
    if out_region is None:
        lp = plan_launch(
            spec, vmem_budget=vmem_budget, compute_dtype=compute_dtype
        )
        if lp is None:
            from repro.robust.errors import BudgetError

            raise BudgetError(
                "no output region fits VMEM; chunk via fused_pyramid_chain",
                vmem_budget=vmem_budget,
            )
        out_region = lp.out_region
        if streamed is None:
            streamed = lp.streamed
            if w_slots is None:
                w_slots = lp.w_slots
                if c_tiles is None:
                    c_tiles = lp.c_tiles
        if x_slots is None:
            x_slots = lp.x_slots
    prog = compile_program(spec, out_region, compute_dtype=compute_dtype)
    # a caller-pinned x_slots=2 charges the extra landing slot to every
    # regime, including the resident-vs-streamed decision itself
    xs_pinned = x_slots if x_slots is not None else 1
    stream = (
        prog.vmem_bytes(xs_pinned) > vmem_budget
        if streamed is None
        else streamed
    )
    if stream and (w_slots is None or c_tiles is None):
        # resolve the open knobs along plan_launch's rung order, accounting
        # for already-pinned x_slots / w_slots / c_tiles so the derived
        # combo is jointly feasible (e.g. a pinned w_slots=2 that busts
        # untiled adopts the smallest feasible channel tiling; w_slots=1 +
        # pipelined input may fit where w_slots=2 + pipelined busts)
        w_slots, c_tiles = prog.resolve_stream_regime(
            vmem_budget, xs_pinned, w_slots, c_tiles
        )
    if not stream:
        w_slots = 1  # unused by the resident kernel; pin for the jit key
    if c_tiles is None:
        c_tiles = 1  # channel tiling is opt-in outside the streamed ladder
    if x_slots is None:
        if prog.alpha == 1:
            x_slots = 1  # no successor cell: nothing to prefetch
        elif stream:
            x_slots = (
                2
                if prog.vmem_stream_bytes(w_slots, 2, c_tiles) <= vmem_budget
                else 1
            )
        else:
            x_slots = 2 if prog.vmem_bytes(2, c_tiles) <= vmem_budget else 1
    vmem = (
        prog.vmem_stream_bytes(w_slots, x_slots, c_tiles)
        if stream
        else prog.vmem_bytes(x_slots, c_tiles)
    )
    if vmem > vmem_budget:
        from repro.robust.errors import BudgetError

        raise BudgetError(
            f"working set {vmem} exceeds VMEM"
            + ("" if stream else "; retry with streamed weights or")
            + " chunk via fused_pyramid_chain",
            vmem_bytes=vmem, vmem_budget=vmem_budget,
        )
    x = x.astype(cdt)
    weights = [w.astype(cdt) for w in weights]
    if prog.patch:
        x = patch_tensor(x, spec.levels[0])
        weights[0] = patch_weights(weights[0], spec.levels[0])
    xp = jnp.pad(
        x,
        ((0, 0), (prog.pad_lo, prog.pad_hi), (prog.pad_lo, prog.pad_hi), (0, 0)),
    )
    if epilogues is None:
        epilogues = [()] * len(biases)
    vecs = [
        jnp.stack([b, *extra]) if extra else b
        for b, extra in zip(biases, epilogues)
    ]
    return fused_pyramid_pallas(
        xp,
        weights,
        [v.astype(cdt) for v in vecs],
        program=prog,
        end_skip=end_skip,
        interpret=interpret,
        stream_weights=stream,
        w_slots=w_slots,
        x_slots=x_slots,
        c_tiles=c_tiles,
        vmem_limit_bytes=vmem_budget + MOSAIC_HEADROOM_BYTES,
        name=name,
    )


def patch_tensor(x: jnp.ndarray, level: FusedLevel) -> jnp.ndarray:
    """Conv ``level``'s patch tensor of ``x``: ``(B, M, M, patch_lanes)``
    for its ``M x M`` output, the input of the level's patch form
    (:func:`~repro.core.program.patch_spec`).  The padded input is split
    into its ``S*S`` stride phases (space to depth: a reshape and a
    transpose, channels in ``(a, b, c)`` order); the ``ceil(K/S)**2``
    taps are unit-stride slices of that phase image, stacked on channels
    in ``(di, dj)`` order.  At stride 1 the phase image is the padded input
    and the channels run ``(ki, kj, c)``.  XLA on a TPU lowers a strided
    slice to a gather, and concatenates narrow pieces one lane-padded copy
    each; this build has neither, and no convolution."""
    K, S, p = level.K, level.S, level.pad
    b, n, c = x.shape[0], x.shape[1], x.shape[3]
    m = level.out_size(n)
    kq = -(-K // S)
    side = S * (m - 1 + kq)  # the rows the taps read; the rest is unread
    hi = max(0, side - n - p)
    xp = jnp.pad(x, ((0, 0), (p, hi), (p, hi), (0, 0)))[:, :side, :side]
    q = side // S
    phases = (
        xp.reshape(b, q, S, q, S, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(b, q, q, S * S * c)
    )
    taps = [
        phases[:, di : di + m, dj : dj + m, :]
        for di in range(kq)
        for dj in range(kq)
    ]
    return jnp.stack(taps, axis=3).reshape(b, m, m, -1)


def patch_weights(w: jnp.ndarray, level: FusedLevel) -> jnp.ndarray:
    """Conv ``level``'s ``(K, K, Cin, Cout)`` weights as the 1x1 weights of
    its patch form, ``(1, 1, patch_lanes, Cout)``, rows in the order of
    :func:`patch_tensor`'s channels: the taps are zero-padded to
    ``S * ceil(K/S)`` a side and each tap index ``k`` split into
    ``(k // S, k % S)``.  A reshape alone at stride 1."""
    K, S = level.K, level.S
    kq = -(-K // S)
    cin, cout = w.shape[2], w.shape[3]
    w = jnp.pad(w, ((0, S * kq - K), (0, S * kq - K), (0, 0), (0, 0)))
    return (
        w.reshape(kq, S, kq, S, cin, cout)
        .transpose(0, 2, 1, 3, 4, 5)
        .reshape(1, 1, -1, cout)
    )


def fused_conv2(
    x: jnp.ndarray,
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    b2: jnp.ndarray,
    *,
    spec: FusionSpec,
    out_region: int,
    end_skip: bool = True,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused 2-conv pyramid forward — compatibility wrapper.

    Returns (output map, skip map) with ``skip``: (B, alpha, alpha) int32 —
    1 where the END cascade skipped the second conv (the historical
    2-level-kernel semantics; new code should call :func:`fused_pyramid`).
    """
    out, skip = fused_pyramid(
        x,
        [w1, w2],
        [b1, b2],
        spec=spec,
        out_region=out_region,
        end_skip=end_skip,
        interpret=interpret,
    )
    return out, skip[..., 1]


def conv_groups(spec: FusionSpec) -> list[list]:
    """Split the level chain into [conv + trailing pools] groups — the
    indivisible units of chunking (a pool executes as its conv's epilogue);
    a depthwise level starts a group as a conv does."""
    if not (spec.levels and spec.levels[0].has_weights):
        from repro.robust.errors import PreflightError

        raise PreflightError(
            "chain must start with a conv level",
            levels=[lvl.kind for lvl in spec.levels],
        )
    groups: list[list] = []
    for lvl in spec.levels:
        if lvl.has_weights:
            groups.append([lvl])
        else:
            groups[-1].append(lvl)
    return groups


def plan_chunks(
    spec: FusionSpec,
    *,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    max_convs_per_chunk: int | None = None,
    compute_dtype: str = "float32",
) -> list[FusionSpec]:
    """Greedy chunking: grow each chunk conv-group by conv-group until the
    VMEM budget (or an explicit conv cap) forces a split.

    A chain that fits the budget returns a single chunk — one kernel launch,
    no intermediate HBM round-trip.  Odd conv counts are fine: a remainder
    simply becomes a final Q=1/Q=3 chunk.  Feasibility is dtype-aware: a
    bf16 chain's halved working set can merge chunks an f32 chain must
    split.  Raises ``ValueError`` when even a lone conv group cannot fit the
    budget (chunking cannot help: a group is the indivisible launch unit).
    """
    groups = conv_groups(spec)
    chunks: list[FusionSpec] = []
    size = spec.input_size

    def fits(levels: list) -> bool:
        sub = FusionSpec(levels=tuple(levels), input_size=size)
        return (
            pick_out_region(
                sub, vmem_budget=vmem_budget, compute_dtype=compute_dtype
            )
            is not None
        )

    cur: list = []
    for g in groups:
        if cur:
            convs = sum(l.has_weights for l in cur)
            capped = max_convs_per_chunk is not None and convs >= max_convs_per_chunk
            if capped or not fits(cur + g):
                chunks.append(FusionSpec(levels=tuple(cur), input_size=size))
                size = chunks[-1].feature_sizes()[-1]
                cur = []
        if not cur and not fits(g):
            name = g[0].name or f"conv K={g[0].K} {g[0].n_in}->{g[0].n_out}"
            from repro.robust.errors import BudgetError

            raise BudgetError(
                f"conv group [{name}] does not fit the {vmem_budget}-byte"
                " VMEM budget even alone (streamed); chunking cannot help",
                node=g[0].name, vmem_budget=vmem_budget,
            )
        cur = cur + g
    chunks.append(FusionSpec(levels=tuple(cur), input_size=size))
    return chunks


def fused_pyramid_chain(
    x: jnp.ndarray,
    weights: list,
    biases: list,
    *,
    spec: FusionSpec,
    out_regions: list[int] | None = None,
    end_skip: bool = True,
    interpret: bool | None = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    max_convs_per_chunk: int | None = None,
    compute_dtype: str = "float32",
):
    """Execute a fusion chain in as few kernel launches as VMEM allows.

    With the variadic kernel a chain that fits the budget runs as **one**
    launch (the paper's §4 VGG Q=4 experiment no longer round-trips the
    level-2 feature map through HBM); larger chains split at conv-group
    boundaries, and only those chunk boundaries touch HBM.  Pass
    ``max_convs_per_chunk=2`` to reproduce the historical 2+2 chained path
    (USEFUSE's own FPGA granularity, §4.4).

    Returns ``(y, skips)`` — ``skips[c]`` is chunk ``c``'s (B, alpha, alpha,
    Q_c) END-cascade flag map.
    """
    chunks = plan_chunks(
        spec,
        vmem_budget=vmem_budget,
        max_convs_per_chunk=max_convs_per_chunk,
        compute_dtype=compute_dtype,
    )
    if out_regions is not None and len(out_regions) != len(chunks):
        from repro.robust.errors import PreflightError

        raise PreflightError(
            f"{len(out_regions)} out_regions for {len(chunks)} chunks",
            out_regions=list(out_regions), chunks=len(chunks),
        )
    y = x
    skips = []
    wi = 0
    for ci, sub in enumerate(chunks):
        q = sub.q_convs
        y, skip = fused_pyramid(
            y,
            list(weights[wi : wi + q]),
            list(biases[wi : wi + q]),
            spec=sub,
            out_region=out_regions[ci] if out_regions is not None else None,
            end_skip=end_skip,
            interpret=interpret,
            vmem_budget=vmem_budget,
            compute_dtype=compute_dtype,
        )
        skips.append(skip)
        wi += q
    return y, skips
