"""Tile-program compiler: one lowering pass shared by planner, executor, and
the variadic Pallas kernel.

``FusionSpec`` + a chosen output region lower to a static *tile program*:

* **Eq. (1) windows** — the per-level receptive windows of an output tile,
  expressed affinely in the tile's final-output start coordinate
  (:class:`LevelWindow`: ``lo(start) = base + step * start``, constant
  ``size``).  This is the only place window/offset math is derived; the
  executor (:mod:`repro.core.executor`) and the kernel wrapper
  (:mod:`repro.kernels.fused_conv.ops`) both consume it.
* **Uniform-stride grid** — Algorithm 4 realized as an ``alpha x alpha``
  movement grid: every level moves the same number of times, the level-0 tile
  stride is ``stride0`` (:class:`TileProgram`).
* **Validity-mask ranges** — per conv level, the affine global output
  coordinate (``o_base + i * o_step``) and the valid extent used to zero
  rows that fall in a level's padding; ditto for the pool epilogue
  (:class:`ConvLevelProg`).
* **Pool epilogues** — each pool level is folded into the preceding conv
  level's program (the paper's Fig. 4 pooling block is slaved to the conv
  tile; see DESIGN.md §3).
* **Patch form of level 0** — a narrow-input first conv (the image's 3
  channels) runs as a 1x1 conv over its patch tensor, built by XLA ahead
  of the kernel (:func:`patch_spec`, DESIGN.md §8).
* **VMEM-budget accounting** — :meth:`TileProgram.vmem_bytes` models the
  kernel's resident working set; :func:`pick_out_region` scans output regions
  against the budget and :meth:`TileProgram.hbm_bytes` models the per-launch
  off-chip traffic (the quantity fusion minimizes).

The compiler is pure Python over static shapes: programs are frozen,
hashable dataclasses suitable as jit static arguments.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from .dtypes import DTYPE_BYTES, canonical_dtype
from .fusion import FusionSpec, LevelEpilogue, receptive_window

# Planning budget for one launch's VMEM buffers: Mosaic's default scoped
# limit on v5e (the chip has 128 MiB).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# VMEM Mosaic takes beyond the declared buffers: vector spills, and for f32
# operands the bf16 splits of its full-precision matmul.  Compiled for v5e,
# a zoo launch takes up to 10.3 MiB more than its buffers, so every launch is
# compiled with this much on top of its planning budget (the chip has
# 128 MiB of VMEM).
MOSAIC_HEADROOM_BYTES = 16 * 1024 * 1024

# TPU vector layout: the last dim of a VMEM buffer is padded to 128 lanes, the
# second-to-last to one sublane tile (8 rows of 32-bit, 16 of bf16).
LANES = 128
# HBM arrays are tiled the same way, so a DMA window over the sublane (W) dim
# must start and end on a multiple of 8.
DMA_ALIGN = 8

# Level 0 runs in patch form when its patch (patch_lanes) fits this many
# lanes: one MXU dot per output row contracting the whole patch replaces K*K
# dots that each contract Cin lanes.  The image's HBM copy is lane-padded to
# 128 anyway, so up to 128 patch lanes move no more bytes than the padded
# image; four lane blocks take every image-input conv (AlexNet's 11x11
# stride-4 conv1: 432) and no activation-input 3x3 conv (64 channels: 576).
PATCH_MAX_LANES = 4 * LANES


def channel_blocks(c: int) -> tuple[int, int]:
    """``(blocks, lanes)`` of a ``c``-channel VMEM tile buffer.

    The kernel stores every tile as ``(blocks, H, W, lanes)``: channels
    split into 128-lane blocks when they tile exactly, else one block.  A
    strided read on the W dim needs a buffer whose last dim is 128 lanes."""
    lanes = LANES if c % LANES == 0 else c
    return c // lanes, lanes


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def padded_bytes(shape: tuple[int, ...], dtype: str) -> int:
    """Bytes a VMEM buffer of ``shape`` occupies on the chip: the last dim
    padded to 128 lanes, the one before it to a sublane tile."""
    itemsize = DTYPE_BYTES[dtype]
    *lead, rows, lanes = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = _round_up(rows, 8 * 4 // itemsize) * _round_up(lanes, LANES)
    for d in lead:
        n *= d
    return n * itemsize


def padded_lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane vregs.  Weights are stored with
    their output channels padded so: every MXU dot is ``(W, Cin) @ (Cin,
    128)``, one shape for every channel tiling, which keeps tiled and
    untiled launches bitwise equal on every backend."""
    return _round_up(n, LANES)


def weight_slot(levels) -> tuple[int, int, int, int] | None:
    """``(K, K, Cin, Cout)`` of a streamed-weight scratch slot that can hold
    any one of ``levels``' (lane-padded) conv weight tensors; ``None`` when
    none of them is a conv.  A depthwise level's ``K * K * C`` weights stay
    resident when the rest stream: they never take a slot."""
    convs = [p for p in levels if p.kind == "conv"]
    if not convs:
        return None
    return (
        max(p.K for p in convs),
        max(p.K for p in convs),
        max(p.n_in for p in convs),
        max(padded_lanes(p.n_out) for p in convs),
    )


# Modeled HBM service rate of the cycle model's 100 MHz accelerator, in bytes
# per cycle (6.4 GB/s).  Only ratios matter: the constant sets how expensive a
# streamed-weight DMA is relative to the DS-1 compute cycles it overlaps with.
HBM_BYTES_PER_CYCLE = 64


# ---------------------------------------------------------------------------
# Eq. (1) windows, affine in the output start coordinate
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LevelWindow:
    """Eq. (1) window of one spec level, affine in the final-output start.

    A final-output interval ``[s, s + out_region)`` needs this level's padded
    input rows ``[base + step * s, base + step * s + size)``; ``step`` is the
    cumulative stride of this level and everything below it.
    """

    base: int
    step: int
    size: int

    def at(self, start: int) -> tuple[int, int]:
        return (self.base + self.step * start, self.size)


@dataclass(frozen=True)
class WindowProgram:
    """Per-level Eq. (1) windows plus output geometry.

    The contract consumed by the value-level executor: it needs windows for
    *arbitrary* (possibly ragged/clamped) output starts, so offsets stay
    affine in the start coordinate rather than in a grid index.
    """

    spec: FusionSpec
    out_region: int
    windows: tuple[LevelWindow, ...]
    out_size: int
    n_out: int

    def level_windows(self, start: int) -> list[tuple[int, int]]:
        """Per-level ``(lo, size)`` in padded input coords for one start."""
        return [w.at(start) for w in self.windows]


def patch_lanes(level) -> int:
    """Input channels of conv ``level`` in patch form.  A stride-``S``
    level is split into its ``S*S`` phases first (space to depth), so that
    every tap is a unit-stride slice: the patch holds the
    ``ceil(K/S)**2`` taps of the phase image's ``S*S*Cin`` channels, and
    taps past ``K`` carry zero weights.  ``K*K*Cin`` at stride 1."""
    kq = -(-level.K // level.S)
    return kq * kq * level.S * level.S * level.n_in


def taps_per_pass(level) -> int:
    """Adjacent taps of one kernel row that one MXU pass of conv ``level``
    contracts together.  A level whose input channel block
    (:func:`channel_blocks`) fills at most half of the MXU's 128-deep
    contraction reads ``g`` taps' windows side by side as one ``(W, g *
    lanes)`` operand against their stacked weight blocks, so a 64-channel
    3x3 takes 6 passes a row, not 9.  1 for every level of 128 or more
    lanes, every ``K = 1`` level (a patch-form level 0 among them) and
    every depthwise level, which has no contraction.  Reads only shapes."""
    if level.kind == "depthwise":
        return 1
    return max(1, min(level.K, LANES // channel_blocks(level.n_in)[1]))


def patch_spec(spec: FusionSpec) -> FusionSpec:
    """The spec the kernel runs: ``spec`` itself, or, when level 0 is a
    ``K > 1`` conv whose patch (:func:`patch_lanes`) fits
    :data:`PATCH_MAX_LANES` or whose windows do not overlap (``K == S``,
    a patchify stem or a ConvNeXt downsample: its patch tensor is the
    input's own values, reshaped), ``spec`` with level 0 in **patch form** —
    a 1x1, stride-1, pad-0 conv over the patch tensor, an input of level 0's
    output size (``kernels/fused_conv/ops.patch_tensor``).  Later levels,
    pools included, are unchanged.  Reads only shapes."""
    first = spec.levels[0]
    if not (
        first.kind == "conv"
        and first.K > 1
        and (patch_lanes(first) <= PATCH_MAX_LANES or first.K == first.S)
    ):
        return spec
    patch = replace(first, K=1, S=1, pad=0, n_in=patch_lanes(first))
    return FusionSpec(
        levels=(patch, *spec.levels[1:]),
        input_size=first.out_size(spec.input_size),
    )


def chain_channels(spec: FusionSpec) -> int:
    """Channel count leaving the chain (pools are channel-preserving)."""
    c = spec.levels[0].n_in
    for lvl in spec.levels:
        if lvl.has_weights:
            c = lvl.n_out
    return c


def compile_windows(spec: FusionSpec, out_region: int) -> WindowProgram:
    """Lower the Eq. (1) receptive-window chain to affine per-level windows.

    ``receptive_window`` is exact but pointwise; every level's window start is
    affine in the output start (each level applies ``lo -> lo * S`` and a
    constant pad shift), so two evaluations recover ``(base, step)``.
    """
    wins0 = receptive_window(spec, 0, out_region)
    wins1 = receptive_window(spec, 1, out_region)
    windows = tuple(
        LevelWindow(base=w0[0], step=w1[0] - w0[0], size=w0[1])
        for w0, w1 in zip(wins0, wins1)
    )
    return WindowProgram(
        spec=spec,
        out_region=out_region,
        windows=windows,
        out_size=spec.feature_sizes()[-1],
        n_out=chain_channels(spec),
    )


# ---------------------------------------------------------------------------
# Kernel-level program: per-conv-level static offsets + the uniform grid
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConvLevelProg(LevelEpilogue):
    """Static per-conv-level kernel program (offsets affine in tile index).

    ``o_base + i * o_step`` is the global output coordinate of tile row 0 at
    grid index ``i``; rows outside ``[0, valid)`` are this level's padding and
    get masked to zero.  A trailing pool level is folded in as an epilogue
    with its own offset/valid triple.  ``kind`` (``conv`` or ``depthwise``),
    the activation (``relu``, ``gelu``) and the epilogue (``norm_eps``,
    ``scale``) are the :class:`~repro.core.fusion.FusedLevel`'s.
    """

    K: int
    S: int
    n_in: int
    n_out: int
    in_size: int  # tile spatial size entering this level
    out_size: int  # tile spatial size leaving the conv
    o_base: int  # global output coord of tile row 0 at tile index 0
    o_step: int  # global output coord step per tile index
    valid: int  # level's valid output extent (mask range)
    pool: tuple[int, int] | None  # (K, S) of trailing pool, if any
    pool_out: int  # tile spatial size after pool (== out_size if no pool)
    pool_o_base: int = 0
    pool_o_step: int = 0
    pool_valid: int = 0
    relu: bool = True
    kind: str = "conv"
    gelu: bool = False
    norm_eps: float | None = None
    scale: bool = False

    def weight_shape(self, n_out: int | None = None) -> tuple[int, ...]:
        """The lane-padded weight tensor the kernel holds for this level
        (``n_out`` of its output channels, all by default): ``(K, K, Cin,
        Cout)``, or a depthwise level's ``(K * K, C)``, one row per tap."""
        n_out = self.n_out if n_out is None else n_out
        if self.kind == "depthwise":
            return (self.K * self.K, padded_lanes(n_out))
        return (self.K, self.K, self.n_in, padded_lanes(n_out))


@dataclass(frozen=True)
class TileProgram:
    """Complete static program for one variadic fusion-pyramid launch.

    ``levels`` holds one :class:`ConvLevelProg` per conv level (any Q >= 1),
    pools folded in.  ``tile0``/``stride0`` cut level-0 tiles out of the
    pre-padded input; the grid is ``(batch, alpha, alpha)``.  ``spec`` is
    the spec the caller compiled; with ``patch`` set, every geometry and
    byte figure is that of :attr:`kernel_spec`, its patch form, whose input
    is the patch tensor (:func:`patch_spec`).
    """

    spec: FusionSpec
    out_region: int
    alpha: int
    levels: tuple[ConvLevelProg, ...]
    tile0: int
    stride0: int
    pad_lo: int
    pad_hi: int
    out_size: int
    n_out: int
    # canonical dtype name of activations/weights moving through the launch
    # (a string keeps the program hashable for jit); mid-level dot products
    # always accumulate float32 regardless — see DESIGN.md §11
    compute_dtype: str = "float32"
    patch: bool = False

    @property
    def kernel_spec(self) -> FusionSpec:
        """The spec the kernel runs: :attr:`spec`, level 0 in patch form
        when :attr:`patch`."""
        return patch_spec(self.spec) if self.patch else self.spec

    @property
    def q_convs(self) -> int:
        return len(self.levels)

    @property
    def bytes_per_val(self) -> int:
        """Bytes per activation/weight value, from the one DTYPE_BYTES
        table — every byte quantity below scales with this."""
        return DTYPE_BYTES[self.compute_dtype]

    @property
    def padded_input(self) -> int:
        return self.pad_lo + self.kernel_spec.input_size + self.pad_hi

    def weight_floats(self) -> int:
        """Every weight and vector value of the launch."""
        return sum(
            p.n_vec * p.n_out + p.K * p.K * p.n_out
            * (1 if p.kind == "depthwise" else p.n_in)
            for p in self.levels
        )

    def resident_weight_floats(self) -> int:
        """The depthwise weights: resident whichever the regime."""
        return sum(
            p.K * p.K * p.n_out for p in self.levels if p.kind == "depthwise"
        )

    def level_weight_counts(self) -> tuple[int, ...]:
        """Flattened float count of each level's streamed weight tensor
        (bias excluded; 0 for a depthwise level, whose weights never
        stream) — the slice table for streamed-weight launches."""
        return tuple(
            0 if p.kind == "depthwise" else p.K * p.K * p.n_in * p.n_out
            for p in self.levels
        )

    def c_tile_options(self) -> tuple[int, ...]:
        """Legal output-channel tile counts of the last level, ascending and
        excluding the untiled 1: the divisors of the final conv's ``n_out``
        (a ``Cout`` block must tile the channel axis exactly so the per-``k``
        out BlockSpec stays uniform) that keep at least **two** channels per
        slice.  Single-channel slices are excluded on principle (they waste
        the 128-lane MXU) and on contract: XLA lowers the degenerate
        ``(P, Cin) @ (Cin, 1)`` dot through its matrix-vector special case,
        whose contraction order differs from the sliced-out column of the
        full dot — breaking the bitwise-parity guarantee every other slice
        width keeps.  None for a depthwise last level or one with a
        LayerNorm, which needs all its channels at once."""
        last = self.levels[-1]
        if last.kind == "depthwise" or last.norm_eps is not None:
            return ()
        m = last.n_out
        return tuple(c for c in range(2, m // 2 + 1) if m % c == 0)

    def input_window(self) -> int:
        """W extent of the level-0 landing buffer: the columns one cell's
        halo DMA moves.  A 1x1 grid takes the whole (aligned) padded row; a
        larger grid takes a :data:`DMA_ALIGN`-aligned window wide enough to
        hold the ``tile0`` halo at every cell's offset inside it."""
        if self.alpha == 1:
            return _round_up(self.padded_input, DMA_ALIGN)
        slack = max((j * self.stride0) % DMA_ALIGN for j in range(self.alpha))
        return _round_up(self.tile0 + slack, DMA_ALIGN)

    def input_cols(self) -> int:
        """W extent the kernel needs of its input: a multiple of
        :data:`DMA_ALIGN` that holds the last cell's aligned window."""
        if self.alpha == 1:
            return self.input_window()
        last = (self.alpha - 1) * self.stride0 // DMA_ALIGN * DMA_ALIGN
        return max(
            _round_up(self.padded_input, DMA_ALIGN), last + self.input_window()
        )

    def input_lanes(self) -> int:
        """Channel extent the kernel needs of its input: a DMA moves whole
        128-lane tiles, so fewer channels than one block pad to 128."""
        cb, cl = channel_blocks(self.levels[0].n_in)
        return cb * (padded_lanes(cl) if cb == 1 else cl)

    def folds(self) -> tuple[int, ...]:
        """Per level, the taps one MXU pass contracts: :func:`taps_per_pass`
        for a level fed by another level of the launch, 1 for level 0 (its
        input comes from HBM, where the copies would cost an extra pass
        over the image: measured slower, PERF.md §6), and 1 for a level
        fed by a depthwise level, which computes its channels once."""
        return (1,) + tuple(
            1 if prev.kind == "depthwise" else taps_per_pass(p)
            for prev, p in zip(self.levels, self.levels[1:])
        )

    def copies(self) -> tuple[int, ...]:
        """Per level, how many times its output tile holds its channels
        side by side in the lanes, copy ``t`` shifted ``t`` columns on: the
        :meth:`folds` of the level that reads the tile, which then loads
        that many adjacent taps' windows at once (1 for the last level,
        whose output leaves the launch).  The copies fill lanes a
        64-or-fewer-channel tile pads to anyway: no buffer grows."""
        return self.folds()[1:] + (1,)

    def staged_levels(self) -> tuple[bool, ...]:
        """Per level: whether its input rows go through the f32 row stage.
        Mosaic reads a strided W window only from a 32-bit, 128-lane buffer,
        and a packed bf16 buffer only at a static offset; level 0 of a grid
        with ``alpha > 1`` reads at a per-cell (dynamic) column offset."""
        return tuple(
            p.S > 1 or (li == 0 and self.alpha > 1)
            for li, p in enumerate(self.levels)
        )

    def vmem_buffers(
        self,
        x_slots: int = 1,
        c_tiles: int = 1,
        *,
        streamed: bool = False,
        w_slots: int = 1,
    ) -> list[tuple[str, tuple[int, ...], str]]:
        """Every VMEM buffer one launch allocates, as ``(name, shape,
        dtype)`` — the single list the kernel wrapper allocates scratch from
        and :meth:`vmem_bytes` / :meth:`vmem_stream_bytes` price.

        Tiles are stored channel-blocked (:func:`channel_blocks`): the
        ``x_slots`` level-0 landing slots, the f32 row stage of staged
        levels (:meth:`staged_levels`), the f32 conv output of each pooled
        level (the pool reads it strided, so its lanes pad to 128), and each
        non-last level's output tile.  Pallas double-buffers the output and
        skip-flag blocks; weights and biases whose block is the whole array
        are single-buffered.  With ``c_tiles > 1`` the last level works on
        ``Cout / c_tiles`` channels at a time and its weights are laid out
        ``(c_tiles, K, K, Cin, Cout / c_tiles)``.  Streamed launches hold
        ``w_slots`` ring slots sized for the largest level (untiled) or one
        blocking mid-level slot plus ``w_slots`` slice slots (tiled)."""
        cdt = self.compute_dtype
        levels = self.levels
        q = len(levels)
        last = levels[-1]
        ct = last.n_out // c_tiles
        cb0 = channel_blocks(levels[0].n_in)[0]
        ww = self.input_window()
        bufs = [(
            "x_land",
            (x_slots, cb0, self.tile0, ww, self.input_lanes() // cb0),
            cdt,
        )]
        staged = [li for li, s in enumerate(self.staged_levels()) if s]
        if staged:
            width = max(ww if li == 0 else levels[li].in_size for li in staged)
            lanes = max(
                _round_up(channel_blocks(levels[li].n_in)[1], LANES)
                for li in staged
            )
            bufs.append(("stage", (width, lanes), "float32"))
        copies = self.copies()
        for li, p in enumerate(levels):
            cb, cl = channel_blocks(ct if li == q - 1 else p.n_out)
            if p.pool is not None:
                bufs.append((
                    "conv_out",
                    (cb, p.out_size, p.out_size, _round_up(cl, LANES)),
                    "float32",
                ))
            if li < q - 1:
                bufs.append(
                    ("mid", (cb, p.pool_out, p.pool_out, cl * copies[li]), cdt)
                )
        region = self.out_region
        bufs.append(("out_block", (2, region, region, ct), cdt))
        bufs.append(("skip_block", (2, 1, q), "int32"))
        for li, p in enumerate(levels):
            tiled = li == q - 1 and c_tiles > 1
            bufs.append((
                "bias",
                (c_tiles, p.n_vec, ct) if tiled
                else (p.n_vec, p.n_out * copies[li]),
                cdt,
            ))
            if not streamed or p.kind == "depthwise":
                shape = p.weight_shape(ct if tiled else p.n_out)
                bufs.append(("weights", (c_tiles, *shape) if tiled else shape, cdt))
        if streamed:
            if c_tiles > 1:
                mid = weight_slot(levels[:-1])
                if mid is not None:
                    bufs.append(("w_mid", (1, *mid), cdt))
                bufs.append(
                    ("w_slices",
                     (w_slots, last.K, last.K, last.n_in, padded_lanes(ct)), cdt)
                )
            elif weight_slot(levels) is not None:
                bufs.append(("w_ring", (w_slots, *weight_slot(levels)), cdt))
        return bufs

    def vmem_bytes(self, x_slots: int = 1, c_tiles: int = 1) -> int:
        """VMEM one resident-weight launch allocates, in bytes, padded as the
        chip lays it out (:func:`padded_bytes` over :meth:`vmem_buffers`).

        The input stays in HBM; only the level-0 halo rows (DMA'd per grid
        cell into one of ``x_slots`` landing slots) are VMEM-resident, plus
        all weights ("filters are loaded into the kernel buffers only once",
        §3.3.1) and the per-level tile buffers of the pyramid.  ``c_tiles``
        only shrinks the last level's working tile — resident weights stay
        whole, so channel tiling is a streamed-regime tool (the planner never
        picks it resident); the resident kernel still accepts it for parity
        testing.  Tiles, weights and the output move at ``compute_dtype``;
        the row stage and pooled conv outputs are f32.
        """
        return sum(
            padded_bytes(shape, dt)
            for _, shape, dt in self.vmem_buffers(x_slots, c_tiles)
        )

    def vmem_stream_bytes(
        self, slots: int = 1, x_slots: int = 1, c_tiles: int = 1
    ) -> int:
        """VMEM of a launch with per-level weight streaming: only ``slots``
        copies of the largest single level's weights are VMEM-resident at
        once (DMA'd from HBM level by level; ``slots=2`` is the
        double-buffered pipeline that overlaps level ``l+1``'s fetch with
        level ``l``'s compute); biases stay resident.  The fallback when
        :meth:`vmem_bytes` busts the budget — e.g. ResNet-18's last block,
        whose two 512x512 3x3 weight tensors alone exceed 16 MiB.
        ``x_slots`` counts input landing buffers as in :meth:`vmem_bytes`.

        With ``c_tiles > 1`` (the channel-tiled grid) the last level streams
        per-``k`` ``(Cin, Cout / c_tiles)`` slices through ``slots`` scratch
        slots while the mid levels fall back to one blocking slot sized for
        the largest mid level — streamed slices shrink by ``c_tiles``, which
        is what lets ResNet-18 b7 afford the double-buffered ``slots=2``
        regime its untiled weights bust."""
        return sum(
            padded_bytes(shape, dt)
            for _, shape, dt in self.vmem_buffers(
                x_slots, c_tiles, streamed=True, w_slots=slots
            )
        )

    def resolve_stream_regime(
        self,
        vmem_budget: int,
        x_slots: int = 1,
        w_slots: int | None = None,
        c_tiles: int | None = None,
    ) -> tuple[int, int]:
        """Resolve ``(w_slots, c_tiles)`` for a streamed launch along
        :func:`plan_launch`'s rung order — double-buffered untiled >
        channel-tiled double-buffered (smallest feasible ``c_tiles``) >
        blocking single slot — honouring whichever knobs the caller already
        pinned.  The kernel-entry fallback used by
        :func:`repro.kernels.fused_conv.ops.fused_pyramid`, so the single
        rung order lives here and in :func:`plan_launch` only.  Never
        raises: a jointly-infeasible pin surfaces at the caller's VMEM
        assert."""
        if w_slots is None and c_tiles is None:
            if self.vmem_stream_bytes(2, x_slots) <= vmem_budget:
                return 2, 1
            for ct in self.c_tile_options():
                if self.vmem_stream_bytes(2, x_slots, ct) <= vmem_budget:
                    return 2, ct
            return 1, 1
        if w_slots is None:
            fits2 = self.vmem_stream_bytes(2, x_slots, c_tiles) <= vmem_budget
            return (2 if fits2 else 1), c_tiles
        if c_tiles is None:
            if (
                w_slots > 1
                and self.vmem_stream_bytes(w_slots, x_slots) > vmem_budget
            ):
                for ct in self.c_tile_options():
                    if (
                        self.vmem_stream_bytes(w_slots, x_slots, ct)
                        <= vmem_budget
                    ):
                        return w_slots, ct
            return w_slots, 1
        return w_slots, c_tiles

    def input_dma_cycles(self) -> int:
        """Cycles one grid cell's halo-tile DMA occupies the HBM interface
        (``tile0^2 * C`` floats at :data:`HBM_BYTES_PER_CYCLE`) — the
        quantity the cross-cell prefetch pipeline hides behind compute."""
        c0 = self.levels[0].n_in
        return -(
            -self.bytes_per_val * self.tile0 ** 2 * c0 // HBM_BYTES_PER_CYCLE
        )

    def input_hbm_bytes(self, batch: int = 1, *, whole_image: bool = False) -> int:
        """Per-launch input read traffic.  The halo-tile dataflow fetches one
        ``tile0 x tile0`` tile per grid cell — ``alpha^2 * tile0^2 * C`` total,
        overlap bounded by the pyramid halo (the uniform-stride minimum of
        Algorithm 4).  ``whole_image=True`` is the retired whole-image-resident
        model (every grid cell re-reads the padded image: ``alpha^2 * Hp * Wp *
        C``), kept for before/after benchmark comparisons."""
        c0 = self.levels[0].n_in
        tile = self.padded_input ** 2 if whole_image else self.tile0 ** 2
        return self.bytes_per_val * batch * self.alpha ** 2 * tile * c0

    def hbm_bytes(
        self, batch: int = 1, *, streamed: bool = False, c_tiles: int = 1
    ) -> int:
        """Off-chip traffic of one launch: read halo tiles + weights, write
        output map + skip flags.  Chained launches pay this per chunk — the
        intermediate maps crossing HBM are exactly what fusion removes.
        Streamed-weight launches re-read the weights once per grid cell.

        ``c_tiles`` is accepted for symmetry with the VMEM models but leaves
        the total unchanged: the channel-tiled grid reads ``1 / c_tiles`` of
        the last level's weights per ``k`` step across ``c_tiles`` steps
        (same per-cell total), writes each output channel block exactly once,
        and emits one flag vector per cell — channel tiling re-schedules the
        movement, it does not add traffic."""
        del c_tiles  # traffic-invariant; see docstring
        w_reads = batch * self.alpha ** 2 if streamed else 1
        resident = self.resident_weight_floats()
        vals = (
            w_reads * (self.weight_floats() - resident) + resident
            + batch * self.out_size ** 2 * self.n_out
        )
        # skip flags stay int32 whatever the compute dtype
        flag_bytes = (
            DTYPE_BYTES["int32"] * batch * self.alpha ** 2 * self.q_convs
        )
        return (
            self.input_hbm_bytes(batch)
            + self.bytes_per_val * vals
            + flag_bytes
        )


def compile_program(
    spec: FusionSpec, out_region: int, *, compute_dtype="float32"
) -> TileProgram:
    """Lower a fusion spec + output region to the kernel's static program.

    Requires the final output to be exactly tiled by ``out_region`` (the
    uniform-stride grid — every level moves ``alpha`` times per dim).  Every
    pool level must directly follow a conv level: pools execute as epilogues
    of the preceding conv tile (Fig. 4), so a leading or doubled pool has no
    conv program to fold into, and that conv must apply ReLU: the kernel
    pads and masks with zeros, which a max pool ignores only over
    non-negative values.  ``compute_dtype`` (name string or jnp dtype)
    sets the byte width of every activation/weight the program accounts —
    window math is dtype-invariant, the byte and cycle models are not.
    The program is built from :func:`patch_spec` of ``spec``, so a
    narrow-input level 0 is priced and launched in patch form alike.
    """
    from repro.robust.errors import PlanError

    levels = spec.levels
    if not (levels and levels[0].has_weights):
        raise PlanError(
            "chain must start with a conv level",
            levels=[lvl.kind for lvl in levels],
        )
    for l, lvl in enumerate(levels):
        if lvl.kind == "pool" and not levels[l - 1].has_weights:
            raise PlanError(
                "each pool level must directly follow a conv level",
                level=l, node=lvl.name,
            )
        if lvl.kind == "pool" and not levels[l - 1].nonneg:
            raise PlanError(
                "a pool level must follow a ReLU conv level with no"
                " epilogue (its zero padding is neutral only for"
                " non-negative values)",
                level=l, node=lvl.name,
            )
    source = spec
    spec = patch_spec(spec)
    levels = spec.levels
    sizes = spec.feature_sizes()
    out_size = sizes[-1]
    if out_size % out_region != 0:
        raise PlanError(
            f"out_region {out_region} must tile the {out_size} output"
            " exactly",
            out_region=out_region, out_size=out_size,
        )
    alpha = out_size // out_region

    win = compile_windows(spec, out_region).windows
    progs = []
    for l, lvl in enumerate(levels):
        if lvl.kind == "pool":
            continue
        in_size = win[l].size
        out_sz = (in_size - lvl.K) // lvl.S + 1
        pool = None
        pool_out = out_sz
        pool_ob = pool_os = pool_valid = 0
        if l + 1 < len(levels) and levels[l + 1].kind == "pool":
            pk, ps = levels[l + 1].K, levels[l + 1].S
            pool = (pk, ps)
            pool_out = (out_sz - pk) // ps + 1
            pool_ob = win[l + 1].base // ps
            pool_os = (win[l + 1].step * out_region) // ps
            pool_valid = sizes[l + 2]
        progs.append(
            ConvLevelProg(
                K=lvl.K,
                S=lvl.S,
                n_in=lvl.n_in,
                n_out=lvl.n_out,
                in_size=in_size,
                out_size=out_sz,
                o_base=win[l].base // lvl.S,
                o_step=(win[l].step * out_region) // lvl.S,
                valid=sizes[l + 1],
                pool=pool,
                pool_out=pool_out,
                pool_o_base=pool_ob,
                pool_o_step=pool_os,
                pool_valid=pool_valid,
                relu=lvl.relu,
                kind=lvl.kind,
                gelu=lvl.gelu,
                norm_eps=lvl.norm_eps,
                scale=lvl.scale,
            )
        )
    for prev, cur in zip(progs, progs[1:]):
        assert prev.pool_out == cur.in_size, "window chain is inconsistent"

    tile0 = win[0].size
    lo0 = win[0].base - levels[0].pad  # unpadded coords; <= 0 by construction
    assert lo0 <= 0, "level-0 window cannot start inside the image"
    stride0 = win[0].step * out_region
    pad_lo = -lo0
    last_end = lo0 + (alpha - 1) * stride0 + tile0
    pad_hi = max(0, last_end - spec.input_size)
    return TileProgram(
        spec=source,
        out_region=out_region,
        alpha=alpha,
        levels=tuple(progs),
        tile0=tile0,
        stride0=stride0,
        pad_lo=pad_lo,
        pad_hi=pad_hi,
        out_size=out_size,
        n_out=chain_channels(spec),
        compute_dtype=canonical_dtype(compute_dtype),
        patch=spec is not source,
    )


@dataclass(frozen=True)
class LaunchPlan:
    """A costed, VMEM-feasible single-launch configuration of one pyramid.

    The plan-costing hook consumed by the auto-partitioner
    (:mod:`repro.net.partition`) and the kernel wrapper
    (:mod:`repro.kernels.fused_conv.ops`): region choice *and* weight regime
    (resident vs streamed, and with how many stream slots) are decided here,
    once, so planner cost and launched kernel can never disagree.

    ``w_slots`` only matters when ``streamed``: 2 is the double-buffered
    weight pipeline (level ``l+1``'s DMA overlaps level ``l``'s compute), 1
    the blocking start();wait() fallback when two copies of the largest
    level's weights bust VMEM.

    ``x_slots`` is the input landing-buffer count: 2 is the revolving
    cross-cell prefetch pipeline (grid cell ``n`` starts cell ``n+1``'s
    halo-tile DMA before running its own pyramid, so after the per-image
    warm-up fill the input DMA hides behind the MXU cascade), 1 the serial
    start();wait() path.  The chain is confined to one batch element — the
    batch grid axis is declared ``parallel`` and may be partitioned across
    TensorCores, so a prefetch must never cross a batch boundary.

    ``c_tiles > 1`` is the channel-tiled grid: a fourth sequential grid axis
    ``k`` over ``Cout / c_tiles`` output-channel tiles of the *last* level
    (the column-parallel axis of the paper's Fig. 5 WPU array).  Levels
    ``0..Q-2`` are computed once per cell at ``k == 0`` into a persistent
    VMEM scratch and reused for ``k > 0``; level ``Q-1`` runs per ``k`` on a
    ``(Cin, Cout / c_tiles)`` streamed weight slice, so with ``w_slots=2``
    the next slice's DMA overlaps the current slice's MXU pass — the regime
    that restores pipelining to ``alpha == 1`` launches the cross-cell input
    prefetch cannot touch (no successor cell).
    """

    program: TileProgram
    streamed: bool
    w_slots: int = 1
    x_slots: int = 2
    c_tiles: int = 1

    @property
    def spec(self) -> FusionSpec:
        return self.program.spec

    @property
    def out_region(self) -> int:
        return self.program.out_region

    @property
    def regime(self) -> str:
        """Display label: ``resident``, ``streamed_w<slots>``, with a
        ``_c<tiles>`` suffix on channel-tiled launches."""
        if not self.streamed:
            return "resident"
        label = f"streamed_w{self.w_slots}"
        if self.c_tiles > 1:
            label += f"_c{self.c_tiles}"
        return label

    def vmem_bytes(self) -> int:
        if self.streamed:
            return self.program.vmem_stream_bytes(
                self.w_slots, self.x_slots, self.c_tiles
            )
        return self.program.vmem_bytes(self.x_slots, self.c_tiles)

    def hbm_bytes(self, batch: int = 1) -> int:
        return self.program.hbm_bytes(
            batch, streamed=self.streamed, c_tiles=self.c_tiles
        )

    def slice_bytes(self) -> int:
        """Bytes of one per-``k`` streamed weight slice of the last level —
        the DMA granule the channel-tiled pipeline hides behind the MXU
        (0 for resident launches, the whole last level at ``c_tiles == 1``)."""
        if not self.streamed:
            return 0
        cnt = self.program.level_weight_counts()[-1]
        return self.program.bytes_per_val * -(-cnt // self.c_tiles)

    def with_input_pipeline(
        self, vmem_budget: int = VMEM_BUDGET_BYTES
    ) -> LaunchPlan:
        """The ``x_slots=2`` variant of this plan when buildable — the
        planner's ladder rule: the grid has a successor cell (``alpha > 1``)
        and the extra landing slot fits the budget — else this plan
        unchanged.  The single source of the buildability predicate for
        consumers (benchmarks) comparing serial vs pipelined latency."""
        cand = replace(self, x_slots=2)
        if self.program.alpha > 1 and cand.vmem_bytes() <= vmem_budget:
            return cand
        return self

    def body_cycles(self) -> int:
        """Per-grid-cell compute(+weight-DMA) cycles — the ``body`` argument
        of :func:`~repro.core.cycle_model.grid_pipeline_cycles`, shared by
        :meth:`modeled_cycles` and the modeled timelines so cost and
        rendering can never disagree.

        Per movement: DS-1 compute cycles (Eq. 3), plus the streamed-weight
        DMA cost at :data:`HBM_BYTES_PER_CYCLE`.  With a double-buffered
        weight pipeline (``w_slots=2``) only level 0's DMA (the pipeline
        ``fill``) is exposed and the rest hides behind compute —
        ``fill + max(compute, dma - fill)``, never worse than the
        single-slot fallback's serialized ``compute + dma``.  Resident
        weights pay no per-movement DMA.

        With the channel-tiled grid (``c_tiles > 1``, streamed) the body is
        :func:`~repro.core.cycle_model.channel_tiled_body_cycles`: blocking
        mid-level weight DMA + mid compute, then the k-axis pipeline — slice
        0's fetch overlaps the mid pyramid (fill), each later slice's fetch
        overlaps the previous slice's MXU pass (steady), the last slice's
        compute drains exposed.

        Both sides of the overlap are dtype-aware: every weight-DMA term
        scales with the program's ``bytes_per_val``, and the MXU compute
        cycles divide by :func:`~repro.core.dtypes.mxu_throughput` (bf16
        operands double the systolic rate) — so narrowing the dtype shrinks
        the DMA *and* the compute it hides behind."""
        from .cycle_model import channel_tiled_body_cycles

        compute, stream = self._body_terms()
        if stream is None:
            return compute
        kind = stream["kind"]
        if kind == "channel_tiled":
            return channel_tiled_body_cycles(
                stream["compute_mid"],
                stream["compute_last"],
                stream["dma_mid"],
                stream["dma_slice"],
                self.c_tiles,
                pipelined=self.w_slots > 1,
            )
        if kind == "pipelined":
            fill, dma = stream["fill"], stream["dma"]
            return fill + max(compute, dma - fill)
        return compute + stream["dma"]

    def _body_terms(self) -> tuple[int, dict | None]:
        """The raw compute/DMA cycle terms of one grid cell: ``(compute,
        stream)`` with ``stream`` None for resident launches, else a dict
        naming the weight-DMA regime and its terms — consumed by both
        :meth:`body_cycles` and :meth:`body_detail_timeline`."""
        from .cycle_model import (
            ds1_cycles_per_movement,
            ds1_split_cycles_per_movement,
            mxu_scaled_cycles,
            vpu_split_cycles_per_movement,
        )

        bpv = self.program.bytes_per_val
        cdt = self.program.compute_dtype
        spec = self.program.kernel_spec
        # vector-unit work (depthwise levels, epilogues) runs at its own
        # rate whatever the dtype; only the MXU share scales with it
        vpu_mid, vpu_last = vpu_split_cycles_per_movement(spec)
        compute = (
            mxu_scaled_cycles(ds1_cycles_per_movement(spec), cdt)
            + vpu_mid + vpu_last
        )
        if not self.streamed:
            return compute, None
        cnts = self.program.level_weight_counts()
        if self.c_tiles > 1:
            compute_mid, compute_last = ds1_split_cycles_per_movement(spec)
            return compute, {
                "kind": "channel_tiled",
                "compute_mid": mxu_scaled_cycles(compute_mid, cdt) + vpu_mid,
                "compute_last": mxu_scaled_cycles(compute_last, cdt) + vpu_last,
                "dma_mid": -(-bpv * sum(cnts[:-1]) // HBM_BYTES_PER_CYCLE),
                "dma_slice": -(
                    -bpv * -(-cnts[-1] // self.c_tiles) // HBM_BYTES_PER_CYCLE
                ),
            }
        dma = -(-bpv * sum(cnts) // HBM_BYTES_PER_CYCLE)
        if self.w_slots > 1:
            fill = -(-bpv * cnts[0] // HBM_BYTES_PER_CYCLE)
            return compute, {"kind": "pipelined", "dma": dma, "fill": fill}
        return compute, {"kind": "blocking", "dma": dma}

    def body_detail_timeline(self):
        """DMA-vs-MXU bars *inside* one grid cell — weight movement against
        the conv cascade (:class:`~repro.core.cycle_model.TimelineSegment`
        list ending exactly at :meth:`body_cycles`): a single compute bar for
        resident launches, exposed-then-compute for blocking streams, the
        fill-overlap shape for the double-buffered weight pipeline, and the
        k-axis fill/steady/drain for channel-tiled launches."""
        from .cycle_model import TimelineSegment, channel_tiled_body_timeline

        compute, stream = self._body_terms()
        if stream is None:
            return [TimelineSegment("mxu", "pyramid (resident)", 0, compute)]
        kind = stream["kind"]
        if kind == "channel_tiled":
            return channel_tiled_body_timeline(
                stream["compute_mid"],
                stream["compute_last"],
                stream["dma_mid"],
                stream["dma_slice"],
                self.c_tiles,
                pipelined=self.w_slots > 1,
            )
        dma = stream["dma"]
        segs = [TimelineSegment("dma", "weights", 0, dma)]
        if kind == "pipelined":
            # compute starts once level 0's weights (the fill) have landed;
            # later levels' DMA hides behind the cascade
            segs.append(
                TimelineSegment("mxu", "pyramid", stream["fill"], compute)
            )
        else:
            segs.append(TimelineSegment("mxu", "pyramid", dma, compute))
        return segs

    def modeled_timeline(self, *, max_cells: int = 64):
        """The launch's modeled DMA-vs-MXU timeline for one batch element
        (:class:`~repro.core.cycle_model.TimelineSegment` list): the
        uniform-stride grid's input halo-tile stream against the per-cell
        pyramid bodies, serial or software-pipelined per ``x_slots``, ending
        exactly at ``modeled_cycles(batch=1)``.  The Chrome-trace exporter
        (:mod:`repro.obs.timeline`) renders this next to measured spans."""
        from .cycle_model import grid_pipeline_timeline

        return grid_pipeline_timeline(
            self.program.alpha ** 2,
            self.body_cycles(),
            self.program.input_dma_cycles(),
            pipelined=self.x_slots > 1,
            max_cells=max_cells,
        )

    def describe(
        self, batch: int = 1, vmem_budget: int | None = None
    ) -> dict:
        """The launch as one observability row: every plan knob plus the
        modeled byte/cycle quantities the planner optimized, in one flat
        JSON-safe dict (the span schema of DESIGN.md §12 and the row format
        of ``repro.obs.explain``).  ``patch`` says whether level 0 runs in
        patch form (:func:`patch_spec`).  ``vmem_budget`` adds the headroom
        column (budget minus modeled working set)."""
        prog = self.program
        row = {
            "q_convs": prog.q_convs,
            "out_region": self.out_region,
            "alpha": prog.alpha,
            "regime": self.regime,
            "patch": prog.patch,
            "streamed": self.streamed,
            "x_slots": self.x_slots,
            "w_slots": self.w_slots,
            "c_tiles": self.c_tiles,
            "compute_dtype": prog.compute_dtype,
            "batch": batch,
            "hbm_bytes": self.hbm_bytes(batch),
            "vmem_bytes": self.vmem_bytes(),
            "slice_bytes": self.slice_bytes(),
            "modeled_cycles": self.modeled_cycles(batch),
            "body_cycles": self.body_cycles(),
            "input_dma_cycles": prog.input_dma_cycles(),
        }
        if vmem_budget is not None:
            row["vmem_headroom_bytes"] = vmem_budget - row["vmem_bytes"]
        return row

    def modeled_cycles(self, batch: int = 1) -> int:
        """Pipeline-aware cycle cost of the whole launch — the latency
        tiebreaker of the partitioner's dynamic program.

        The per-cell :meth:`body_cycles` is composed per batch element by
        :func:`~repro.core.cycle_model.grid_pipeline_cycles`: serial
        (``x_slots=1``) pays ``(input_dma + body) * cells``; the revolving
        cross-cell prefetch (``x_slots=2``) pays
        ``warmup_fill + body + (cells - 1) * max(body, input_dma)`` — never
        worse than serial, equal at ``alpha == 1`` (no successor cell).

        ``batch`` multiplies the per-image grid (the batch grid axis is
        ``parallel`` across cores but sequential within one, and the
        prefetch chain resets at batch boundaries, so each element pays its
        own warm-up fill).  The byte models scale differently in batch —
        resident weights are read once per launch, streamed weights once per
        cell per element — which is why the partitioner's cut points shift
        with the serving bucket (see :func:`plan_launch` and DESIGN.md §14).
        """
        from .cycle_model import grid_pipeline_cycles

        per_image = grid_pipeline_cycles(
            self.program.alpha ** 2,
            self.body_cycles(),
            self.program.input_dma_cycles(),
            pipelined=self.x_slots > 1,
        )
        return batch * per_image

    def modeled_us(self, batch: int = 1) -> float:
        """:meth:`modeled_cycles` at the cycle model's reference frequency —
        the per-launch share of a serving bucket's latency SLO estimate."""
        from .cycle_model import DEFAULT_PARAMS

        return self.modeled_cycles(batch) / DEFAULT_PARAMS.freq_mhz


def plan_launch(
    spec: FusionSpec,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    *,
    batch: int = 1,
    allow_stream: bool = True,
    prefer_region: str = "largest",
    compute_dtype="float32",
) -> LaunchPlan | None:
    """Pick the launch configuration for one pyramid: an exactly-tiling
    output region whose program fits the VMEM budget, preferring
    fully-resident weights over per-level streaming (which re-reads weights
    once per grid cell), and double-buffered streaming (DMA overlapped with
    compute) over the blocking single-slot fallback.  Between those two
    streamed rungs sits the **channel-tiled** regime: when two whole copies
    of the largest level's weights bust VMEM, tiling the last level's Cout
    across a fourth sequential grid axis shrinks the streamed slice by
    ``c_tiles`` so the double-buffered pipeline fits after all — the ladder
    is resident > streamed x2 > channel-tiled streamed x2 > streamed x1,
    with the smallest (coarsest-slice) feasible ``c_tiles`` preferred.
    Within each weight regime the two-slot input landing buffer (cross-cell
    halo prefetch, ``x_slots=2``) is preferred over the serial single slot;
    a 1x1 grid has no successor cell to prefetch, so ``alpha == 1`` pins
    ``x_slots=1``.  ``prefer_region="largest"`` (default) minimizes grid
    overhead; ``"smallest"`` is the paper's smallest-tile preference —
    maximal tile grids, i.e. END skipping at its finest granularity.
    ``compute_dtype`` re-tiers the whole ladder: the rungs are walked with
    that dtype's byte widths, so a chain that busts VMEM resident at float32
    may climb back to resident (or from channel-tiled to plain streamed x2)
    at bfloat16 — the launched kernel then moves that dtype end to end.

    ``batch`` is the costing scale: within a rung the plan knobs are chosen
    by ``modeled_cycles(batch)`` at the batch the launch will actually run
    (the serving engine plans per bucket).  The rung *order* needs no batch
    argument — resident weights are read once per launch while streamed
    re-reads scale with ``batch * alpha^2``, so the ladder is cost-monotone
    at every batch — but the batch still decides plans globally through the
    partitioner, which compares whole cut points at the bucket batch and
    shifts toward fewer, weight-resident launches as batch grows (weight
    loads amortize across the batch; activation traffic does not).
    Returns ``None`` when no single launch fits."""
    if prefer_region not in ("largest", "smallest"):
        from repro.robust.errors import PreflightError

        raise PreflightError(
            f"prefer_region must be 'largest' or 'smallest',"
            f" got {prefer_region!r}"
        )
    compute_dtype = canonical_dtype(compute_dtype)
    out_size = spec.feature_sizes()[-1]
    regions = [r for r in range(out_size, 0, -1) if out_size % r == 0]
    if prefer_region == "smallest":
        regions.reverse()

    def x_options(prog: TileProgram) -> tuple[int, ...]:
        return (1,) if prog.alpha == 1 else (2, 1)

    def pick_x(prog: TileProgram, build) -> LaunchPlan | None:
        """Cheapest feasible input-buffer knob of one rung, costed at
        ``batch``: ``build(xs)`` returns the rung's plan at ``x_slots=xs``
        or None when it busts VMEM.  The prefetch pipeline is never modeled
        slower than serial at any batch; on a tie keep the extra landing
        slot (the historical ladder's preference)."""
        cands = [p for p in (build(xs) for xs in x_options(prog)) if p]
        if not cands:
            return None
        return min(cands, key=lambda p: (p.modeled_cycles(batch), -p.x_slots))

    def feasible(plan: LaunchPlan) -> LaunchPlan | None:
        return plan if plan.vmem_bytes() <= vmem_budget else None

    for r in regions:
        prog = compile_program(spec, r, compute_dtype=compute_dtype)
        plan = pick_x(
            prog,
            lambda xs, prog=prog: feasible(
                LaunchPlan(program=prog, streamed=False, x_slots=xs)
            ),
        )
        if plan is not None:
            return plan
    if allow_stream:
        # region preference stays primary (a smaller region multiplies the
        # alpha^2 streamed weight re-reads); within a region prefer the
        # double-buffered two-slot weight pipeline over channel-tiled
        # double buffering over the blocking single slot, and within a
        # weight regime the cheapest feasible input buffer at ``batch``
        for r in regions:
            prog = compile_program(spec, r, compute_dtype=compute_dtype)
            rungs = [dict(w_slots=2)]
            rungs += [dict(w_slots=2, c_tiles=ct) for ct in prog.c_tile_options()]
            rungs += [dict(w_slots=1)]
            for knobs in rungs:
                plan = pick_x(
                    prog,
                    lambda xs, prog=prog, knobs=knobs: feasible(
                        LaunchPlan(
                            program=prog, streamed=True, x_slots=xs, **knobs
                        )
                    ),
                )
                if plan is not None:
                    return plan
    return None


def pick_out_region(
    spec: FusionSpec,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    *,
    allow_stream: bool = True,
    compute_dtype="float32",
) -> int | None:
    """Largest output region that tiles the output exactly and whose program
    fits the VMEM budget — the TPU analogue of the paper's ``H <= IFM``
    feasibility bound (DESIGN.md §2 assumption change #2).

    Fully-resident weights are preferred; when no region fits that way and
    ``allow_stream``, regions feasible under per-level weight streaming are
    considered.  Returns ``None`` when nothing fits (the chain must then be
    chunked).
    """
    plan = plan_launch(
        spec, vmem_budget, allow_stream=allow_stream,
        compute_dtype=compute_dtype,
    )
    return None if plan is None else plan.out_region
