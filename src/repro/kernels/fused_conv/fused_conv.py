"""Pallas TPU kernel: variadic USEFUSE fusion pyramid (conv[+ReLU][+pool] x Q).

The paper's fused-layer dataflow, adapted to the TPU memory hierarchy
(DESIGN.md §2, §8): one grid cell computes one fusion-pyramid tile end to
end — every intermediate level stays in VMEM (the TPU analogue of "no
off-chip intermediate traffic") for *any* pyramid depth Q >= 1, including odd
Q and ResNet-style conv-only pairs.  The grid is the uniform-stride tile
plan: the ``alpha x alpha`` movement grid with identical movement counts at
every level is exactly Algorithm 4's uniform stride, realized as a Pallas
grid.

The kernel is compiled from a :class:`~repro.core.program.TileProgram` — the
single tile-program lowering shared with the value-level executor — and
receives one ``ConvLevelProg`` per conv level (pool epilogues folded in).
Every VMEM buffer it allocates comes from
:meth:`~repro.core.program.TileProgram.vmem_buffers`, the same list the
planner prices, and it is compiled with ``vmem_limit_bytes`` set from the
budget the plan was made under.

Per grid cell (b, i, j):
  * the input stays in HBM (memory space ANY); the level-0 halo rows
    (``tile0`` rows starting at ``i*stride0``; the whole padded row on a 1x1
    grid, else an 8-aligned column window holding the ``tile0`` halo at
    column ``j*stride0``) are DMA'd into a VMEM landing buffer with
    ``make_async_copy`` — per-cell input traffic is about ``tile0^2 * C``
    (Algorithm 4's uniform minimal movement), not the whole padded image;
  * with ``x_slots=2`` the landing buffer is a *revolving two-slot pipeline
    across grid cells*: before running its own pyramid, cell ``n`` (row-major
    within its batch element) starts the halo DMA for cell ``n+1`` — next
    ``j``, wrapping to the next ``i`` — into the idle slot, so after the
    per-image warm-up fill the input stream hides behind the Q-level MXU
    cascade (§3.3's tile movement).  The chain deliberately resets at every
    batch boundary: the batch grid axis is declared ``parallel`` in
    ``dimension_semantics`` and may be partitioned across TensorCores, and a
    prefetch crossing a batch boundary would land in another core's scratch.
    END-skipped cells still issue their successor's prefetch (the input
    prefetch precedes the cascade, outside every liveness branch), so a dead
    region never stalls the pipeline.  ``x_slots=1`` is the serial
    start();wait() path — bit-identical, only the movement schedule differs;
  * conv levels run one output row at a time from VMEM refs: K*K
    ``(W, Cin) @ (Cin, Cout)`` MXU dots per row, f32-accumulated, each
    reading its input window with a (possibly strided) ref read (a level of
    64 or fewer input lanes fed by another level reads
    :func:`~repro.core.program.taps_per_pass` adjacent taps' windows as one
    operand, from a tile that holds its channels that many times, each copy
    shifted a column on) — the WPU
    array of Fig. 5 maps onto MXU tiles (a narrow-input level 0 arrives in
    patch form, :func:`~repro.core.program.patch_spec`: K = 1 over its patch
    tensor, one dot per row).  Tiles are channel-blocked
    ``(blocks, H, W, lanes)`` (:func:`~repro.core.program.channel_blocks`);
    strided and per-cell-offset reads go through an f32 row stage, the only
    form Mosaic reads them from;
  * inner-layer padding is realized by *validity masking*: rows whose global
    coordinate falls outside a level's valid output range are zeroed — zeros
    are exactly the next level's pad value, and post-ReLU zeros are neutral
    for maxpool (the executor's crop logic, branch-free for SIMD);
  * each conv level applies its own activation (``ConvLevelProg.relu``):
    ReLU, or none for a linear level such as a ResNet bottleneck's last
    conv;
  * a depthwise level (``kind == "depthwise"``, a ConvNeXt block's 7x7)
    has no contraction over channels: it runs on the vector unit, ``K*K``
    multiply-adds of each tap's shifted window by that tap's per-channel
    weight row, in f32, then emits through the same path as a conv level;
  * a level's epilogue runs in f32 on each output row, in a fixed order
    after the bias: the activation (ReLU, or the exact GELU of
    :func:`gelu_exact` through the rational :func:`erf_f32`), a LayerNorm
    over the level's real channels (the lanes a tile pads to are not part of
    the row, so they count in neither the mean nor the variance), and a
    per-channel scale.  The parameters ride as extra rows of the bias
    operand;
  * END tile-skip (the paper's §3.2 insight at TPU-feasible granularity)
    generalizes to a **cascade**: at every level l >= 1 whose input comes
    from a ReLU level, if that post-ReLU tile is all zero the level's K^2
    MXU pass is skipped and its output collapses to the closed form
    ``epilogue(act(b_l))``; the constant tile feeds the next level, which
    applies the same test — so a dead tile with non-positive downstream
    biases short-circuits the remaining ReLU levels.  A level after a linear
    level always computes: its input may be negative, so a zero max proves
    nothing.  Each level tracks the max of what it stored, which is the next
    level's test.  A per-level skip flag is emitted for statistics.

Weight regimes ("filters are loaded into the kernel buffers only once",
§3.3.1, vs the VMEM-busting fallback):
  * resident — all weights live whole in VMEM for the launch;
  * streamed, double-buffered (``w_slots=2``) — each level's weights stay in
    HBM; level ``l+1``'s tensor is DMA'd into the idle scratch slot before
    level ``l``'s MXU pass so the transfer hides behind compute
    (START-wait-flip).  The prefetch for level ``l+1`` is issued inside level
    ``l``'s *live* branch, so a cascade of END-skipped levels issues no
    weight DMAs at all; the one speculative case (level ``l`` live but its
    output all zero) drains the already-started DMA in the skip branch to
    keep semaphores balanced;
  * streamed, single-slot (``w_slots=1``) — blocking start();wait() per live
    level, when even two copies of the largest level's weights bust VMEM
    (e.g. ResNet-18's 512-channel block).

The regime itself is chosen once by :func:`~repro.core.program.plan_launch`
so planner cost and launched kernel can never disagree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import resolve_interpret
from repro.core.dtypes import EXEC_DTYPES, jnp_dtype
from repro.core.program import (  # noqa: F401 (ConvLevelProg re-export)
    DMA_ALIGN,
    LANES,
    ConvLevelProg,
    TileProgram,
    channel_blocks,
    padded_lanes,
    weight_slot,
)

# vmem_buffers entries the kernel allocates as scratch (the rest are the
# BlockSpec buffers Pallas allocates itself)
_SCRATCH = ("x_land", "stage", "conv_out", "mid", "w_ring", "w_mid", "w_slices")


def _window(n: int, full: int):
    """Index of the first ``n`` of ``full`` elements: the whole dim when it
    covers it (Mosaic refuses an explicit full-extent slice of a tiled dim)."""
    return slice(None) if n == full else pl.ds(0, n)


def _lanes(c: int, cl: int, full: int):
    """Index of channel block ``c`` (``cl`` lanes) of a ``full``-lane dim."""
    return slice(None) if cl == full else pl.ds(c * cl, cl)


class _Copies(list):
    """DMA descriptors started and waited on together."""

    def start(self):
        for cp in self:
            cp.start()

    def wait(self):
        for cp in self:
            cp.wait()


class _Tile:
    """A channel-blocked ``(blocks, H, W, lanes)`` VMEM tile (``lead``
    indexes a slot of a larger buffer), read from column ``off`` on."""

    def __init__(self, ref, lead=(), off=0):
        self.ref, self.lead, self.off = ref, lead, off

    def row(self, c, r):
        return self.ref[(*self.lead, c, r)]

    def window(self, c, r, start, n):
        return self.ref[(*self.lead, c, r, pl.ds(start, n), slice(None))]


def _in_range(g, valid: int):
    return (g >= 0) & (g < valid)


# erf(x) = x * P(x^2) / Q(x^2) on [-4, 4], the rational float32
# approximation of XLA's and Eigen's erf: Mosaic has no lowering for
# ``lax.erf_p``.  Beyond +-4, erf is +-1 in float32.  Largest absolute
# error against ``jax.scipy.special.erf`` over the float32 range: 4.2e-7
# (tests/test_convnext.py holds it under ERF_MAX_ERR).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
          -7.37332916720468e-03, -1.42647390514189e-02)
ERF_MAX_ERR = 5e-7


def erf_f32(x):
    """erf of a float32 array by :data:`_ERF_P` over :data:`_ERF_Q`."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.float32(_ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + jnp.float32(c)
    q = jnp.float32(_ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + jnp.float32(c)
    return x * p / q


def gelu_exact(x):
    """The exact GELU, ``x * Phi(x) = x / 2 * (1 + erf(x / sqrt 2))``, in
    float32 (not the tanh approximation)."""
    return 0.5 * x * (1.0 + erf_f32(x * jnp.float32(0.7071067811865476)))


def _epilogue(acc, vec, prog: ConvLevelProg, n_out: int):
    """A level's epilogue on one f32 output row ``(W, width)``: bias, the
    activation, LayerNorm, scale.  ``vec`` is the level's f32 vector
    operand, rows ``bias, [gamma, beta], [scale]`` (``prog.n_vec``).  The
    LayerNorm's mean and variance are over the ``n_out`` real channels: a
    row holds exactly those (copies repeat them), never lane padding."""
    acc = acc + vec[0:1]
    if prog.relu:
        acc = jnp.maximum(acc, 0.0)
    elif prog.gelu:
        acc = gelu_exact(acc)
    if prog.norm_eps is not None:
        inv = jnp.float32(1.0 / n_out)
        mean = jnp.sum(acc[:, :n_out], axis=1, keepdims=True) * inv
        d = acc - mean
        var = jnp.sum(jnp.square(d[:, :n_out]), axis=1, keepdims=True) * inv
        acc = d * jax.lax.rsqrt(var + jnp.float32(prog.norm_eps))
        acc = acc * vec[1:2] + vec[2:3]
    if prog.scale:
        acc = acc * vec[prog.n_vec - 1 : prog.n_vec]
    return acc


def _emit(emit, r, c, v, cdt, m):
    """Cast channel block ``c`` of output row ``r`` (f32) to the compute
    dtype once, store it, and fold its max into ``m``."""
    v = v.astype(cdt)
    emit(r, c, v)
    return jnp.maximum(m, jnp.max(v.astype(jnp.float32)))


def _conv_level(
    src: _Tile,
    w_at,
    vec,
    prog: ConvLevelProg,
    idx,
    *,
    n_out: int,
    dots: bool,
    stage,
    conv_out,
    emit,
    cdt,
    fold: int = 1,
    copies: int = 1,
):
    """One conv or depthwise level, its epilogue (:func:`_epilogue`) and
    its pool, one output row at a time.

    ``w_at(ki, kj, c, o)`` gives tap ``(ki, kj)``'s ``(lanes, 128)`` weights
    for input channel block ``c`` and 128-lane output block ``o`` (a
    depthwise level's ``w_at(ki, kj, c)``: the tap's f32 ``(1, lanes)``
    weight row of block ``c``); ``vec`` is ``(n_vec, copies * n_out)``
    f32, the bias first.  With ``copies > 1`` the level's weights
    and bias repeat its ``n_out`` channels that many times across the
    output lanes, and every row it emits holds them so.
    ``dots=False`` is the closed form of an all-zero input (the conv output
    is the bias everywhere) — bit-identical to the live path, which adds the
    bias to an exact-zero accumulator.  Rows are masked to the level's valid
    range, pooled from the f32 ``conv_out`` buffer, masked again, cast once,
    and handed to ``emit(r, c, value)``.  Returns the max of the emitted
    values: the END predicate of the next level.

    ``fold`` (:meth:`~repro.core.program.TileProgram.folds`) adjacent
    taps of a kernel row share one MXU pass.  The input tile holds its channels
    ``g`` times side by side, copy ``t`` shifted ``t`` columns on (the
    producer's ``copies``), so one window load is the group's operand:
    lanes ``[t * Cin, (t + 1) * Cin)`` hold tap ``(ki, kj + t)``'s
    window, with no lane moves."""
    cb_in, cl_in = channel_blocks(prog.n_in)
    width = copies * n_out
    cb, cl = (1, width) if copies > 1 else channel_blocks(n_out)
    n_blocks = padded_lanes(n_out) // LANES
    # Mosaic multiplies f32 operands in one bf16 pass unless asked for full
    # f32 passes (bf16 operands are exact in one pass, and refuse HIGHEST)
    precision = jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None
    K, S, W = prog.K, prog.S, prog.out_size
    g0 = prog.o_base + idx[0] * prog.o_step
    g1 = prog.o_base + idx[1] * prog.o_step
    col_ok = _in_range(
        jax.lax.broadcasted_iota(jnp.int32, (W, width), 0) + g1, prog.valid
    )

    def patch(c, r, ki, kj, lanes=cl_in):
        if stage is None:
            return src.window(c, r + ki, src.off + kj, W)[:, :lanes]
        rows = pl.ds(src.off + kj, W, stride=S) if S > 1 else pl.ds(src.off + kj, W)
        return stage[rows, :][:, :lanes]

    def one_pass(accs, c, r, taps):
        """Add one MXU pass over ``taps``, adjacent in one kernel row: their
        windows, one load from the input's copies, against their weight
        blocks stacked on the sublane axis."""
        lhs = patch(c, r, *taps[0], lanes=len(taps) * cl_in).astype(cdt)

        def rhs(o):
            ws = [w_at(ki, kj, c, o) for ki, kj in taps]
            return ws[0] if len(ws) == 1 else jnp.concatenate(ws, axis=0)

        return [
            a + jnp.dot(lhs, rhs(o), precision=precision,
                        preferred_element_type=jnp.float32)
            for o, a in enumerate(accs)
        ]

    def dw_row(r):
        """The depthwise sum of one output row, channel block by block."""
        blocks = []
        for c in range(cb_in):
            a = jnp.zeros((W, cl_in), jnp.float32)
            for ki in range(K):
                if stage is not None:
                    v = src.row(c, r * S + ki)[:, :cl_in]
                    stage[: v.shape[0], :cl_in] = v.astype(jnp.float32)
                for kj in range(K):
                    a = a + patch(c, r, ki, kj).astype(jnp.float32) * w_at(ki, kj, c)
            blocks.append(a)
        return blocks[0] if cb_in == 1 else jnp.concatenate(blocks, axis=1)

    def conv_row(r, m):
        acc = jnp.zeros((W, width), jnp.float32)
        if dots and prog.kind == "depthwise":
            acc = dw_row(r)
        elif dots:
            accs = [jnp.zeros((W, LANES), jnp.float32)] * n_blocks
            for ki in range(K):
                for c in range(cb_in):
                    if stage is not None:
                        v = src.row(c, r * S + ki)[:, : fold * cl_in]
                        stage[: v.shape[0], : fold * cl_in] = v.astype(jnp.float32)
                    for kj in range(0, K, fold):
                        taps = [(ki, u) for u in range(kj, min(kj + fold, K))]
                        accs = one_pass(accs, c, r, taps)
            acc = accs[0] if n_blocks == 1 else jnp.concatenate(accs, axis=1)
            acc = acc[:, :width]
        acc = _epilogue(acc, vec, prog, n_out)
        acc = acc * (col_ok & _in_range(r + g0, prog.valid))
        for c in range(cb):
            block = acc[:, c * cl : (c + 1) * cl]
            if prog.pool is None:
                m = _emit(emit, r, c, block, cdt, m)
            else:
                conv_out[c, r, :, :cl] = block
        return m

    m = jax.lax.fori_loop(0, W, conv_row, jnp.float32(0.0))
    if prog.pool is None:
        return m

    pk, ps = prog.pool
    P = prog.pool_out
    pg0 = prog.pool_o_base + idx[0] * prog.pool_o_step
    pg1 = prog.pool_o_base + idx[1] * prog.pool_o_step
    pcol_ok = _in_range(
        jax.lax.broadcasted_iota(jnp.int32, (P, cl), 0) + pg1, prog.pool_valid
    )

    def pool_row(r, m):
        ok = pcol_ok & _in_range(r + pg0, prog.pool_valid)
        for c in range(cb):
            mx = None
            for pi in range(pk):
                for pj in range(pk):
                    cols = pl.ds(pj, P, stride=ps) if ps > 1 else pl.ds(pj, P)
                    v = conv_out[c, r * ps + pi, cols, :][:, :cl]
                    mx = v if mx is None else jnp.maximum(mx, v)
            m = _emit(emit, r, c, mx * ok, cdt, m)
        return m

    return jax.lax.fori_loop(0, P, pool_row, jnp.float32(0.0))


def _flag_vector(flags):
    """The per-level int32 skip flags as one ``(1, Q)`` row."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, len(flags)), 1)
    row = jnp.zeros((1, len(flags)), jnp.int32)
    for l, f in enumerate(flags):
        row = jnp.where(iota == l, f, row)
    return row


class _Launch:
    """Static per-launch context shared by both kernel bodies: the program,
    the named scratch buffers and the halo DMA."""

    def __init__(self, program: TileProgram, scratch, names, x_hbm, x_sem):
        self.prog = program
        self.cdt = jnp_dtype(program.compute_dtype)
        bufs: dict[str, list] = {}
        for name, ref in zip(names, scratch):
            bufs.setdefault(name, []).append(ref)
        self.bufs = bufs
        self.x_land = bufs["x_land"][0]
        self.stage = bufs.get("stage", [None])[0]
        pooled = iter(bufs.get("conv_out", []))
        self.conv_out = [
            next(pooled) if p.pool is not None else None for p in program.levels
        ]
        self.mid = bufs.get("mid", [])
        self.staged = program.staged_levels()
        self.folds = program.folds()
        self.copies = program.copies()
        self.x_hbm, self.x_sem = x_hbm, x_sem

    def x_dma(self, bi, ii, jj, slot) -> _Copies:
        """Halo DMA of cell (bi, ii, jj) into landing slot ``slot``.  All
        cells of a prefetch chain share ``bi``: the batch axis is
        ``parallel`` (possibly core-partitioned), so the chain must never
        cross a batch boundary."""
        p = self.prog
        cb = channel_blocks(p.levels[0].n_in)[0]
        cl = p.input_lanes() // cb
        rows = pl.ds(ii * p.stride0, p.tile0)
        if p.alpha == 1:
            cols = slice(None)
        else:
            start = jj * p.stride0 // DMA_ALIGN * DMA_ALIGN
            cols = pl.ds(pl.multiple_of(start, DMA_ALIGN), p.input_window())
        return _Copies(
            pltpu.make_async_copy(
                self.x_hbm.at[bi, rows, cols, _lanes(c, cl, cb * cl)],
                self.x_land.at[slot, c],
                self.x_sem.at[slot],
            )
            for c in range(cb)
        )

    def x_tile(self, j, slot) -> _Tile:
        """The level-0 input: landing slot ``slot``, read from cell column
        ``j``'s offset inside the aligned window."""
        p = self.prog
        off = 0
        if p.alpha > 1:
            off = j * p.stride0 - j * p.stride0 // DMA_ALIGN * DMA_ALIGN
        return _Tile(self.x_land, (slot,), off)

    def fetch_halo(self, bi, i, j, x_slots, during=None):
        """Land cell (i, j)'s halo and return its slot.  With ``x_slots=2``
        the first cell of each batch element self-fetches and every cell
        starts its successor's fetch into the idle slot before waiting on its
        own.  ``during()`` runs between the starts and the wait (the weight
        pipeline's warm-up)."""
        alpha = self.prog.alpha
        if x_slots > 1:
            cell = i * alpha + j
            slot = jax.lax.rem(cell, x_slots)

            @pl.when(cell == 0)
            def _():
                self.x_dma(bi, i, j, slot).start()

            ni = jnp.where(j == alpha - 1, i + 1, i)
            nj = jnp.where(j == alpha - 1, 0, j + 1)

            @pl.when(cell + 1 < alpha * alpha)
            def _():  # issued unconditionally w.r.t. the END cascade
                self.x_dma(bi, ni, nj, 1 - slot).start()
        else:
            slot = 0
            self.x_dma(bi, i, j, slot).start()
        if during is not None:
            during()
        self.x_dma(bi, i, j, slot).wait()
        return slot

    def level(self, l, src, w_at, vec, idx, emit, *, n_out, dots):
        stage = self.stage if self.staged[l] else None
        return _conv_level(
            src, w_at, vec, self.prog.levels[l], idx, n_out=n_out,
            dots=dots, stage=stage, conv_out=self.conv_out[l], emit=emit,
            cdt=self.cdt, fold=self.folds[l], copies=self.copies[l],
        )

    def skippable(self, l, end_skip: bool) -> bool:
        """Whether level ``l`` may be END-skipped: its input is the output
        of a ReLU level with no epilogue after it, so a zero max proves the
        input all zero."""
        return end_skip and l > 0 and self.prog.levels[l - 1].nonneg

    def mid_emit(self, l):
        copies, ref = self.copies[l], self.mid[l]

        def emit(r, c, v):
            ref[c, r] = v
            # copy t of the channels holds the row t columns on, so that the
            # next level reads t + 1 adjacent taps' windows in one load
            n, lanes = v.shape[0], v.shape[1] // copies
            for t in range(1, copies):
                ref[c, r, pl.ds(0, n - t), pl.ds(t * lanes, lanes)] = (
                    v[t:, t * lanes : (t + 1) * lanes]
                )
        return emit


def _out_emit(out_ref, n_out: int):
    """Store rows of the launch's output block, channel block by block."""
    cb, cl = channel_blocks(n_out)

    def emit(r, c, v):
        out_ref[0, 0, 0, 0, r, :, _lanes(c, cl, n_out)] = v
    return emit


def _slot_reader(ref, slot, prog: ConvLevelProg, full):
    """``w_at`` over the ``(K, K, Cin, Cout)`` weights held in ``ref[slot]``
    (a scratch slot of shape ``full``, possibly larger than this level)."""
    _, cl = channel_blocks(prog.n_in)

    def w_at(ki, kj, c, o):
        return ref[slot, ki, kj, pl.ds(c * cl, cl), _lanes(o, LANES, full[3])]
    return w_at


def _ref_reader(ref, lead=()):
    """``w_at`` over a whole (lane-padded) weight tensor ``ref[lead]``."""
    cin, cout = ref.shape[-2:]
    _, cl = channel_blocks(cin)

    def w_at(ki, kj, c, o):
        return ref[(*lead, ki, kj, _lanes(c, cl, cin), _lanes(o, LANES, cout))]
    return w_at


def _dw_reader(ref, prog: ConvLevelProg):
    """``w_at`` over a depthwise level's ``(K * K, C)`` weights: tap
    ``(ki, kj)``'s f32 weight row of channel block ``c``."""
    _, cl = channel_blocks(prog.n_out)
    full = ref.shape[-1]

    def w_at(ki, kj, c):
        row = pl.ds(ki * prog.K + kj, 1)
        return ref[row, _lanes(c, cl, full)].astype(jnp.float32)
    return w_at


def _resident_reader(ref, prog: ConvLevelProg):
    """``w_at`` over a level's weights held whole in VMEM."""
    return _dw_reader(ref, prog) if prog.kind == "depthwise" else _ref_reader(ref)


def _slot_dma(w_hbm, ring, slot, prog: ConvLevelProg, full, sem):
    """DMA one level's whole lane-padded ``(K, K, Cin, Cout)`` tensor into
    a slot."""
    shape = (prog.K, prog.K, prog.n_in, padded_lanes(prog.n_out))
    dst = ring.at[(slot,) + tuple(_window(n, f) for n, f in zip(shape, full))]
    return pltpu.make_async_copy(w_hbm, dst, sem)


def _pyramid_kernel(
    *refs,
    program: TileProgram,
    names: tuple[str, ...],
    end_skip: bool,
    stream: bool,
    w_slots: int,
    x_slots: int,
):
    progs = program.levels
    q = len(progs)
    x_hbm = refs[0]
    w_refs = refs[1 : 1 + 2 * q : 2]
    b_refs = refs[2 : 2 + 2 * q : 2]
    out_ref, skip_ref = refs[1 + 2 * q], refs[2 + 2 * q]
    scratch = refs[3 + 2 * q :]
    sems = scratch[len(names) :]
    ctx = _Launch(program, scratch, names, x_hbm, sems[0])
    bi = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    idx = (i, j)

    if stream:
        ring, w_sem = ctx.bufs.get("w_ring", [None])[0], sems[1]
        full = weight_slot(progs)

        def w_dma(l):
            """Level l's weights into its ring slot; none for a depthwise
            level, whose weights stay resident."""
            if progs[l].kind == "depthwise":
                return _Copies()
            return _slot_dma(
                w_refs[l], ring, l % w_slots, progs[l], full, w_sem.at[l % w_slots]
            )

    warm = None
    if stream and w_slots > 1:
        def warm():  # pipeline warm-up: level 0 always computes
            w_dma(0).start()
    slot = ctx.fetch_halo(bi, i, j, x_slots, during=warm)
    src = ctx.x_tile(j, slot)

    flags = []
    # per level: None = statically live (always computed), else the traced
    # liveness predicate — the prefetch-bookkeeping contract: level l+1's
    # weight DMA was issued iff level l ran its live branch.
    live_flags: list = []
    m = None
    for l, prog in enumerate(progs):
        prev_live = live_flags[l - 1] if l else None
        emit = ctx.mid_emit(l) if l < q - 1 else _out_emit(out_ref, prog.n_out)
        bias = b_refs[l][...].astype(jnp.float32)

        def fetch_w(l=l, prev_live=prev_live):
            # called inside level l's live branch only
            if not stream or progs[l].kind == "depthwise":
                return _resident_reader(w_refs[l], progs[l])
            if w_slots > 1:
                if l > 0 and prev_live is not None:
                    # predecessor skipped => no prefetch: fetch on demand
                    @pl.when(jnp.logical_not(prev_live))
                    def _():
                        w_dma(l).start()
            else:
                w_dma(l).start()
            w_dma(l).wait()
            return _slot_reader(ring, l % w_slots, progs[l], full)

        def run_level(l=l, src=src, emit=emit, bias=bias, fetch_w=fetch_w):
            w_at = fetch_w()
            if stream and w_slots > 1 and l + 1 < q:
                # double-buffer flip: start the next level's weight DMA into
                # the idle slot before this level's K^2 MXU pass
                w_dma(l + 1).start()
            return ctx.level(l, src, w_at, bias, idx, emit,
                             n_out=progs[l].n_out, dots=True)

        def skip_level(l=l, src=src, emit=emit, bias=bias, prev_live=prev_live):
            if stream and w_slots > 1:
                # drain the speculative prefetch (issued iff the previous
                # level ran live) so the semaphore stays balanced
                if prev_live is None:
                    w_dma(l).wait()
                else:
                    @pl.when(prev_live)
                    def _():
                        w_dma(l).wait()
            return ctx.level(l, src, None, bias, idx, emit,
                             n_out=progs[l].n_out, dots=False)

        if not ctx.skippable(l, end_skip):
            # level 0 always computes; after a linear level the all-zero
            # test is not a sound skip predicate (negatives would survive).
            live_flags.append(None)
            flags.append(jnp.int32(0))
            m = run_level()
        else:
            # END cascade: post-ReLU tiles are >= 0, so max == 0 proves the
            # whole tile (masked halo included) is zero and the conv input is
            # literally the zero tensor — the cond skips the K^2 MXU pass and
            # emits the closed form instead, bit-exactly.
            live = m > 0.0
            live_flags.append(live)
            flags.append(jnp.where(live, 0, 1).astype(jnp.int32))
            m = jax.lax.cond(live, run_level, skip_level)
        if l < q - 1:
            src = _Tile(ctx.mid[l])

    skip_ref[0, 0, 0] = _flag_vector(flags)


def _ktiled_kernel(
    *refs,
    program: TileProgram,
    names: tuple[str, ...],
    end_skip: bool,
    stream: bool,
    w_slots: int,
    x_slots: int,
    c_tiles: int,
):
    """Channel-tiled variant over the (B, alpha, alpha, c_tiles) grid.

    The fourth grid axis ``k`` walks ``Cout / c_tiles`` output-channel tiles
    of the *last* level (the column-parallel axis of the paper's Fig. 5 WPU
    array).  Levels ``0..Q-2`` run once per cell, at ``k == 0``, into their
    VMEM tile buffers (Pallas TPU scratch survives sequential grid
    iterations — the same property the revolving landing buffer relies on);
    every ``k`` re-reads the last level's input tile and computes only its
    k-th channel block into output block ``k``.  The last level's weights
    arrive as ``(c_tiles, K, K, Cin, Cout/c_tiles)`` and its bias as
    ``(c_tiles, 1, Cout/c_tiles)``.

    Streamed weights split in two: mid levels fetch their whole tensor
    through one *blocking* scratch slot inside their live branch (the
    double-buffer budget belongs to the slices), while the last level DMAs
    per-``k`` slices through ``w_slots`` revolving slots — slice 0 starts at
    the top of the ``k == 0`` body so it fills behind the mid pyramid, slice
    ``k+1`` starts before slice ``k``'s MXU pass.  Slice DMAs are issued and
    drained *unconditionally* with respect to the END cascade (only the MXU
    pass is gated), so the semaphores stay balanced with no speculative
    drain paths.  The END flag vector is written once, at ``k == 0``; the
    mid tile's max, the last level's liveness test, is kept in SMEM for
    ``k > 0`` (it is k-invariant: every k reads the same mid tile)."""
    progs = program.levels
    q = len(progs)
    last = progs[-1]
    ct = last.n_out // c_tiles
    x_hbm = refs[0]
    w_refs = refs[1 : 1 + 2 * q : 2]
    b_refs = refs[2 : 2 + 2 * q : 2]
    out_ref, skip_ref = refs[1 + 2 * q], refs[2 + 2 * q]
    scratch = refs[3 + 2 * q :]
    sems = list(scratch[len(names) :])
    live_max = sems.pop()  # SMEM (1,) f32
    ctx = _Launch(program, scratch, names, x_hbm, sems.pop(0))
    if stream:
        mid_full = weight_slot(progs[:-1])
        if mid_full is not None:
            w_mid, wm_sem = ctx.bufs["w_mid"][0], sems.pop(0)
        w_slices, wk_sem = ctx.bufs["w_slices"][0], sems.pop(0)

        def wm_dma(l):
            """Blocking mid-level fetch into the single mid slot."""
            return _slot_dma(w_refs[l], w_mid, 0, progs[l], mid_full, wm_sem)

        def wk_dma(kk):
            """Per-k slice fetch: the last level's kk-th Cout block."""
            return pltpu.make_async_copy(
                w_refs[q - 1].at[kk],
                w_slices.at[kk % w_slots],
                wk_sem.at[kk % w_slots],
            )

    bi = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    idx = (i, j)
    slot = jax.lax.rem(i * program.alpha + j, x_slots) if x_slots > 1 else 0
    last_live = not ctx.skippable(q - 1, end_skip)

    # ---- k == 0: input halo fetch (+ cross-cell prefetch chain) and the
    # mid pyramid, kept in the level tile buffers for k > 0 ----
    @pl.when(k == 0)
    def _():
        warm = None
        if stream and w_slots > 1:
            def warm():  # slice 0 fills behind the mid pyramid
                wk_dma(0).start()
        ctx.fetch_halo(bi, i, j, x_slots, during=warm)
        src = ctx.x_tile(j, slot)
        flags = []
        m = None
        for l, prog in enumerate(progs[:-1]):
            bias = b_refs[l][...].astype(jnp.float32)
            emit = ctx.mid_emit(l)

            def run_level(l=l, src=src, bias=bias, emit=emit):
                if stream and progs[l].kind == "conv":
                    wm_dma(l).start()
                    wm_dma(l).wait()
                    w_at = _slot_reader(w_mid, 0, progs[l], mid_full)
                else:
                    w_at = _resident_reader(w_refs[l], progs[l])
                return ctx.level(l, src, w_at, bias, idx, emit,
                                 n_out=progs[l].n_out, dots=True)

            def skip_level(l=l, src=src, bias=bias, emit=emit):
                return ctx.level(l, src, None, bias, idx, emit,
                                 n_out=progs[l].n_out, dots=False)

            if not ctx.skippable(l, end_skip):
                flags.append(jnp.int32(0))
                m = run_level()
            else:
                live = m > 0.0
                flags.append(jnp.where(live, 0, 1).astype(jnp.int32))
                m = jax.lax.cond(live, run_level, skip_level)
            src = _Tile(ctx.mid[l])
        if last_live:
            flags.append(jnp.int32(0))
        else:
            live_max[0] = m
            flags.append(jnp.where(m > 0.0, 0, 1).astype(jnp.int32))
        skip_ref[0, 0, 0] = _flag_vector(flags)

    # ---- every k: the last level's k-th output-channel block ----
    src = _Tile(ctx.mid[q - 2]) if q > 1 else ctx.x_tile(j, slot)
    bias = b_refs[q - 1][k].astype(jnp.float32)
    if stream:
        if w_slots > 1:
            @pl.when(k + 1 < c_tiles)
            def _():  # revolving flip: next slice behind this MXU pass
                wk_dma(k + 1).start()
        else:
            wk_dma(k).start()  # blocking single-slot fallback
        wk_dma(k).wait()  # unconditional: doubles as the END drain
        w_at = _ref_reader(w_slices, (k % w_slots,))
    else:
        w_at = _ref_reader(w_refs[q - 1], (k,))
    emit = _out_emit(out_ref, ct)

    def run_last(dots):
        return ctx.level(q - 1, src, w_at, bias, idx, emit,
                         n_out=ct, dots=dots)

    if last_live:
        run_last(True)
    else:
        jax.lax.cond(
            live_max[0] > 0.0, lambda: run_last(True), lambda: run_last(False)
        )


def fused_pyramid_pallas(
    x_padded: jnp.ndarray,  # (B, Hp, Wp, C) pre-padded input
    weights: list[jnp.ndarray],
    biases: list[jnp.ndarray],
    *,
    program: TileProgram,
    end_skip: bool = True,
    interpret: bool | None = None,
    stream_weights: bool = False,
    w_slots: int = 2,
    x_slots: int = 2,
    c_tiles: int = 1,
    vmem_limit_bytes: int | None = None,
    name: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Launch the variadic fused pyramid over the (B, alpha, alpha) grid.

    The input stays in HBM; each grid cell DMAs its halo rows into VMEM.
    With ``x_slots=2`` (default) the landing buffer revolves across grid
    cells: each cell prefetches its successor's halo into the idle slot
    before running its own pyramid, hiding the input stream behind compute
    after the per-image warm-up; ``x_slots=1`` is the serial
    fetch-then-compute path (bit-identical output).  The grid is launched
    with ``dimension_semantics=("parallel", "arbitrary", "arbitrary")`` so
    the compiler may partition the batch axis across TensorCores — the
    prefetch chain never crosses a batch boundary, so the partitioning is
    safe.

    Weights/biases are flat per-conv-level lists, index-aligned with
    ``program.levels``; a depthwise level's weights are ``(K, K, 1, C)``.
    A level's bias may be its whole ``(n_vec, n_out)`` vector operand: the
    bias, then its LayerNorm's gamma and beta, then its layer scale.  With
    ``stream_weights`` the conv weights stay in HBM
    (memory space ANY) and each level's tensor is DMA'd into one of
    ``w_slots`` shared VMEM scratch slots — double-buffered (prefetch
    overlapping compute) when ``w_slots == 2`` — the fallback when the
    fully-resident working set busts the VMEM budget (see
    ``TileProgram.vmem_stream_bytes``).
    ``interpret=None`` auto-resolves to compiled on TPU, interpreted
    elsewhere.  ``vmem_limit_bytes`` is the scoped-VMEM limit the compiled
    kernel gets: the budget its plan was made under plus
    :data:`~repro.core.program.MOSAIC_HEADROOM_BYTES`.  ``name`` is the
    kernel's name (the ``pallas_call``'s, hence the HLO custom call's).

    With ``c_tiles > 1`` the launch runs the channel-tiled grid
    ``(B, alpha, alpha, c_tiles)``: a fourth sequential axis over
    ``Cout / c_tiles`` output-channel tiles of the last level, the mid
    pyramid computed once per cell at ``k == 0`` and kept in VMEM, and (when
    streamed) per-``k`` weight-slice DMAs revolving through ``w_slots``
    scratch slots — the regime that restores DMA/MXU overlap to
    ``alpha == 1`` launches (see ``_ktiled_kernel``).  ``c_tiles`` must
    divide the last level's ``Cout``; output and skip shapes are unchanged,
    and the result is bit-identical to ``c_tiles=1``.

    All operands must arrive in ``program.compute_dtype`` (DESIGN.md §11):
    halo tiles, weight slices, inter-level tiles, and the output all move at
    that width, while every conv accumulates in f32
    (``preferred_element_type``) and casts once after the level epilogue.
    The int32 skip map is dtype-invariant.

    Returns ``(out, skip)`` with ``skip`` shaped ``(B, alpha, alpha, Q)`` —
    ``skip[..., l] == 1`` where level ``l``'s conv was short-circuited by the
    END cascade (level 0 never skips).
    """
    B = x_padded.shape[0]
    q = program.q_convs
    if program.compute_dtype not in EXEC_DTYPES:
        raise NotImplementedError(
            f"compute dtype {program.compute_dtype!r} is modeled but not"
            f" executable; the kernels run {EXEC_DTYPES}"
        )
    cdt = jnp_dtype(program.compute_dtype)
    assert x_padded.dtype == cdt, (
        f"x_padded dtype {x_padded.dtype} != program compute dtype {cdt}"
    )
    assert all(b.dtype == cdt for b in biases), (
        f"bias dtypes must match the program compute dtype {cdt}"
    )
    assert all(w.dtype == cdt for w in weights), (
        f"weight dtypes must match the program compute dtype {cdt}"
    )
    assert x_slots in (1, 2), "x_slots: 1 (serial) or 2 (revolving pipeline)"
    assert len(weights) == q, "one weight tensor per conv level"
    assert len(biases) == q, "one bias per conv level"
    # each level's vector operand: the bias, then its epilogue's rows
    biases = [b.reshape(-1, b.shape[-1]) for b in biases]
    assert all(
        b.shape == (p.n_vec, p.n_out) for b, p in zip(biases, program.levels)
    ), "one (n_vec, n_out) bias-and-epilogue operand per level"
    last = program.levels[-1]
    assert c_tiles >= 1 and last.n_out % c_tiles == 0, (
        f"c_tiles {c_tiles} must divide the last level's Cout {last.n_out}"
    )
    assert c_tiles == 1 or last.n_out // c_tiles >= 2, (
        "channel slices must keep >= 2 channels: the degenerate one-column"
        " dot reassociates the Cin contraction (see"
        " TileProgram.c_tile_options) and would break bitwise parity"
    )
    assert x_padded.shape[1] == x_padded.shape[2] == program.padded_input, (
        "x_padded spatial dims must equal the program's padded input"
    )
    # a depthwise level's (K, K, 1, C) weights as (K * K, C), a tap a row
    weights = [
        w.reshape(p.K * p.K, p.n_out) if p.kind == "depthwise" else w
        for w, p in zip(weights, program.levels)
    ]
    ct = last.n_out // c_tiles
    if c_tiles > 1:
        weights[-1] = weights[-1].reshape(
            last.K, last.K, last.n_in, c_tiles, ct
        ).transpose(3, 0, 1, 2, 4)
        biases[-1] = biases[-1].reshape(-1, c_tiles, ct).transpose(1, 0, 2)
    # a level whose tile feeds a folded level computes its channels
    # `copies` times over, in the lanes it pads to anyway
    copies = program.copies()
    weights = [
        jnp.concatenate([w] * k, axis=-1) if k > 1 else w
        for w, k in zip(weights, copies)
    ]
    biases = [jnp.tile(b, (1, k)) if k > 1 else b for b, k in zip(biases, copies)]
    weights = [
        jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, padded_lanes(n) - n)])
        for w, n in ((w, w.shape[-1]) for w in weights)
    ]
    extra_cols = program.input_cols() - program.padded_input
    extra_lanes = program.input_lanes() - x_padded.shape[3]
    if extra_cols or extra_lanes:
        # the halo DMA moves whole (8, 128) tiles: align W and the lanes
        x_padded = jnp.pad(
            x_padded, ((0, 0), (0, 0), (0, extra_cols), (0, extra_lanes))
        )

    alpha, region = program.alpha, program.out_region
    tiled = c_tiles > 1
    grid = (B, alpha, alpha, c_tiles) if tiled else (B, alpha, alpha)
    bufs = program.vmem_buffers(
        x_slots, c_tiles, streamed=stream_weights, w_slots=w_slots
    )
    scratch = [(n, s, d) for n, s, d in bufs if n in _SCRATCH]
    scratch_shapes = [pltpu.VMEM(s, jnp_dtype(d)) for _, s, d in scratch]
    scratch_shapes.append(pltpu.SemaphoreType.DMA((x_slots,)))
    if stream_weights:
        if tiled and weight_slot(program.levels[:-1]) is not None:
            scratch_shapes.append(pltpu.SemaphoreType.DMA(()))
        scratch_shapes.append(pltpu.SemaphoreType.DMA((w_slots,)))
    if tiled:
        scratch_shapes.append(pltpu.SMEM((1,), jnp.float32))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda *g, n=a.ndim: (0,) * n)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands: list[jnp.ndarray] = [x_padded]
    for w, b, p in zip(weights, biases, program.levels):
        streams = stream_weights and p.kind == "conv"
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY) if streams else whole(w),
            whole(b),
        ]
        operands += [w, b]
    common = dict(
        program=program,
        names=tuple(n for n, _, _ in scratch),
        end_skip=end_skip,
        stream=stream_weights,
        w_slots=w_slots,
        x_slots=x_slots,
    )
    if tiled:
        kernel = functools.partial(_ktiled_kernel, c_tiles=c_tiles, **common)
        out_index = lambda b, i, j, k: (b, i, j, k, 0, 0, 0)  # noqa: E731
    else:
        kernel = functools.partial(_pyramid_kernel, **common)
        out_index = lambda b, i, j: (b, i, j, 0, 0, 0, 0)  # noqa: E731
    out, skip = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            # one whole (region, region, ct) block per cell and channel tile:
            # its last two dims are the array's, as Mosaic's (8, 128) block
            # rule requires for any region and channel width
            pl.BlockSpec((1, 1, 1, 1, region, region, ct), out_index),
            pl.BlockSpec(
                (1, 1, 1, 1, q), lambda b, i, j, *k: (b, i, j, 0, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (B, alpha, alpha, c_tiles, region, region, ct), cdt
            ),
            jax.ShapeDtypeStruct((B, alpha, alpha, 1, q), jnp.int32),
        ],
        scratch_shapes=scratch_shapes,
        # the batch axis is embarrassingly parallel: every cross-cell chain
        # (input prefetch) is confined to one batch element, so the compiler
        # may partition dim 0 across cores; the movement grid dims stay
        # sequential (the revolving landing buffer is carried cell to cell,
        # the mid tiles k to k)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (len(grid) - 1),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=resolve_interpret(interpret),
        name=name,
    )(*operands)
    side = alpha * region
    out = out.transpose(0, 1, 4, 2, 5, 3, 6).reshape(B, side, side, last.n_out)
    return out, skip.reshape(B, alpha, alpha, q)
