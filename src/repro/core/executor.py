"""Fused-pyramid executor: value-level JAX execution of a fusion plan.

Demonstrates the paper's layer-fusion dataflow at tensor level: every output
tile of the fused chain is computed **only from tile-local buffers** (the
on-chip working set), never from whole intermediate feature maps.  The
monolithic reference (:func:`reference_forward`) materializes every
intermediate map; :func:`fused_forward` must match it exactly — this is the
correctness contract for the fusion-plan math (Eq. (1) windows, lockstep
movement, edge handling).

Hardware note: USEFUSE *reuses* overlapping tile outputs from on-chip buffers
("output pixel reuse instead of recompute", §3.4); value-wise reuse and
recompute are identical, so the executor recomputes halos per tile while the
intensity/cycle models charge the plan's actual buffer traffic.

Layout: NHWC.  Conv weights: (K, K, Cin, Cout) + bias (Cout,); a depthwise
level's (K, K, 1, C) + bias (C,).  Each conv level applies its own
activation (``FusedLevel.relu``): ReLU, as in the paper's conv+ReLU[+pool]
stacks, or none for a linear level; then the rest of its epilogue (exact
GELU, LayerNorm, layer scale; :func:`level_epilogue`).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .fusion import FusionSpec, LockstepPlan, lockstep_plan
from .program import compile_windows


@dataclass
class PyramidParams:
    """Weights for the levels with weights of a fusion spec (index-aligned
    to them); ``epilogues[l]`` holds level ``l``'s LayerNorm gamma and beta
    and layer-scale gamma, those it has, in that order (``None``: no level
    has any)."""

    weights: list[jnp.ndarray]
    biases: list[jnp.ndarray]
    epilogues: list[tuple] | None = None


def init_pyramid_params(
    spec: FusionSpec, key: jax.Array, scale: float = 1.0
) -> PyramidParams:
    ws, bs, eps = [], [], []
    for lvl in spec.levels:
        if not lvl.has_weights:
            continue
        key, k1, k2 = jax.random.split(key, 3)
        cin = 1 if lvl.kind == "depthwise" else lvl.n_in
        fan_in = lvl.K * lvl.K * cin
        w = jax.random.normal(k1, (lvl.K, lvl.K, cin, lvl.n_out)) * (
            scale * (2.0 / fan_in) ** 0.5
        )
        b = jax.random.normal(k2, (lvl.n_out,)) * 0.01
        ws.append(w.astype(jnp.float32))
        bs.append(b.astype(jnp.float32))
        # LayerNorm gamma and beta, layer-scale gamma: about 1, 0 and 1
        centres = ((1.0, 0.0) if lvl.norm_eps is not None else ()) + (
            (1.0,) if lvl.scale else ()
        )
        extra = []
        for centre in centres:
            key, k3 = jax.random.split(key)
            extra.append(centre + 0.1 * jax.random.normal(k3, (lvl.n_out,)))
        eps.append(tuple(extra))
    return PyramidParams(ws, bs, eps if any(eps) else None)


def _conv2d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, stride: int,
            pad: int) -> jnp.ndarray:
    """A conv, or a depthwise one when ``w`` is ``(K, K, 1, C)``."""
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1] if w.shape[2] == 1 else 1,
    )
    return out + b


def layer_norm(x: jnp.ndarray, gamma, beta, eps: float) -> jnp.ndarray:
    """LayerNorm over the last (channel) axis, in float32."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def level_epilogue(x: jnp.ndarray, lvl, extra=()) -> jnp.ndarray:
    """A level's activation and epilogue after its bias, in order: ReLU or
    exact GELU, LayerNorm (``extra``'s gamma and beta), layer scale
    (``extra``'s last)."""
    if lvl.relu:
        x = jax.nn.relu(x)
    elif lvl.gelu:
        x = jax.nn.gelu(x, approximate=False)
    if lvl.norm_eps is not None:
        x = layer_norm(x, extra[0], extra[1], lvl.norm_eps)
    if lvl.scale:
        x = x * extra[-1]
    return x


def _extras(params: PyramidParams, ci: int) -> tuple:
    return () if params.epilogues is None else tuple(params.epilogues[ci])


def _maxpool(x: jnp.ndarray, k: int, s: int) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, k, k, 1),
        window_strides=(1, s, s, 1),
        padding="VALID",
    )


def reference_forward(
    x: jnp.ndarray, spec: FusionSpec, params: PyramidParams
) -> jnp.ndarray:
    """Layer-by-layer execution with full intermediate maps (the baseline
    dataflow whose off-chip traffic fusion eliminates).  Convolutions run at
    ``highest`` matmul precision, so on a TPU the oracle is f32, not a
    bf16-pass approximation of it."""
    ci = 0
    with jax.default_matmul_precision("highest"):
        for lvl in spec.levels:
            if lvl.has_weights:
                x = _conv2d(
                    x, params.weights[ci], params.biases[ci], lvl.S, lvl.pad
                )
                x = level_epilogue(x, lvl, _extras(params, ci))
                ci += 1
            else:
                x = _maxpool(x, lvl.K, lvl.S)
    return x


def fused_forward(
    x: jnp.ndarray,
    spec: FusionSpec,
    params: PyramidParams,
    plan: LockstepPlan | None = None,
    *,
    out_region: int | None = None,
) -> jnp.ndarray:
    """Execute the fused pyramid tile-by-tile per the lockstep plan.

    The alpha x alpha tile grid covers the final output; each tile's chain is
    traced back through the compiled Eq. (1) windows
    (:func:`repro.core.program.compile_windows` — the same tile-program
    lowering the Pallas kernel consumes) and computed from tile-local data.
    """
    if plan is None:
        plan = lockstep_plan(spec, out_region or 1)
    wprog = compile_windows(spec, plan.out_region)
    out = jnp.zeros(
        (x.shape[0], wprog.out_size, wprog.out_size, wprog.n_out), jnp.float32
    )
    for si in plan.starts:
        wins_i = wprog.level_windows(si)
        for sj in plan.starts:
            wins_j = wprog.level_windows(sj)
            # first-level slice (row window from si, col window from sj)
            (lo_i, size_i), (lo_j, size_j) = wins_i[0], wins_j[0]
            p0 = spec.levels[0].pad
            ga_i, ga_j = lo_i - p0, lo_j - p0
            ai, bi = max(ga_i, 0), min(ga_i + size_i, x.shape[1])
            aj, bj = max(ga_j, 0), min(ga_j + size_j, x.shape[2])
            tile = x[:, ai:bi, aj:bj, :]
            tile = jnp.pad(
                tile,
                (
                    (0, 0),
                    (ai - ga_i, ga_i + size_i - bi),
                    (aj - ga_j, ga_j + size_j - bj),
                    (0, 0),
                ),
            )
            tile = _tile_chain_2d(tile, (lo_i, lo_j), spec, params,
                                  (wins_i, wins_j))
            out = out.at[:, si : si + plan.out_region, sj : sj + plan.out_region, :].set(
                tile
            )
    return out


def _tile_chain_2d(tile, g_pad, spec, params, windows):
    """Run one tile through the fused chain using only tile-local buffers.

    ``tile`` holds a window of the level-0 *unpadded* input starting at
    ``g = g_pad - pad_0`` (negative = overlaps the pad border; those rows are
    zero-filled by the caller).  At each level the requested Eq. (1) window is
    cut from the local buffer; any deficit is zero — it is exactly this
    level's padding (interior requests always fit, by construction).  After
    the level executes, rows outside the level's valid output range are
    cropped: a deeper level that asks for them receives zeros (its own pad),
    never values convolved out of thin air.
    """
    wins_i, wins_j = windows
    sizes = spec.feature_sizes()
    gi = g_pad[0] - spec.levels[0].pad
    gj = g_pad[1] - spec.levels[0].pad
    ci = 0
    for l, lvl in enumerate(spec.levels):
        (loi_pad, size_i), (loj_pad, size_j) = wins_i[l], wins_j[l]
        loi, loj = loi_pad - lvl.pad, loj_pad - lvl.pad
        ai, aj = loi - gi, loj - gj
        bi, bj = ai + size_i, aj + size_j
        pli, phi = max(0, -ai), max(0, bi - tile.shape[1])
        plj, phj = max(0, -aj), max(0, bj - tile.shape[2])
        if pli or phi or plj or phj:
            tile = jnp.pad(tile, ((0, 0), (pli, phi), (plj, phj), (0, 0)))
            ai += pli
            bi += pli
            aj += plj
            bj += plj
        tile = tile[:, ai:bi, aj:bj, :]
        if lvl.has_weights:
            tile = _conv2d(tile, params.weights[ci], params.biases[ci], lvl.S, 0)
            tile = level_epilogue(tile, lvl, _extras(params, ci))
            ci += 1
        else:
            tile = _maxpool(tile, lvl.K, lvl.S)
        gi, gj = loi_pad // lvl.S, loj_pad // lvl.S
        # crop to the level's valid output range [0, out_size)
        out_size = sizes[l + 1]
        ci_lo, cj_lo = max(0, -gi), max(0, -gj)
        ci_hi = min(tile.shape[1], out_size - gi)
        cj_hi = min(tile.shape[2], out_size - gj)
        tile = tile[:, ci_lo:ci_hi, cj_lo:cj_hi, :]
        gi += ci_lo
        gj += cj_lo
    return tile


def conv_windows(
    x: jnp.ndarray, spec: FusionSpec, level: int = 0, max_windows: int | None = None
) -> tuple[jnp.ndarray, int]:
    """Extract flattened K*K*N input windows of a conv level (END stats).

    Returns ``(windows, n_windows_per_image)`` with windows shaped
    ``(B, P, K*K*N)`` where P = number of spatial output positions (possibly
    subsampled to ``max_windows``).
    """
    lvl = spec.levels[level]
    assert lvl.kind == "conv"
    xp = jnp.pad(x, ((0, 0), (lvl.pad, lvl.pad), (lvl.pad, lvl.pad), (0, 0)))
    B, H, W, C = xp.shape
    out = (H - lvl.K) // lvl.S + 1
    patches = jax.lax.conv_general_dilated_patches(
        xp,
        (lvl.K, lvl.K),
        (lvl.S, lvl.S),
        "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (B, out, out, K*K*C)
    flat = patches.reshape(B, out * out, -1)
    if max_windows is not None and flat.shape[1] > max_windows:
        idx = np.linspace(0, flat.shape[1] - 1, max_windows).astype(int)
        flat = flat[:, idx, :]
    return flat, out * out
