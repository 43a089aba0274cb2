"""CNN graph IR: whole networks as explicit dataflow graphs.

A :class:`Graph` is a topologically-ordered tuple of :class:`Node` — conv /
depthwise conv / pool / relu / residual-add / global-pool / flatten / dense /
layernorm / gelu / layer scale — each naming its producer nodes.
Construction validates the whole graph (unique names, forward references
only, shape/channel chaining) so malformed networks fail here with a named
node, not deep inside a kernel.

The IR is the single source of the model zoo: :func:`lenet5`,
:func:`alexnet`, :func:`vgg16`, :func:`resnet18`, :func:`resnet50` and
:func:`convnext_tiny` replace the raw tuple tables that used to live in
``core/cnn_models.py`` (which now *derives* its paper fusion specs from
these graphs).  All builders take ``input_size`` so tests and
interpret-mode demos can run reduced-scale variants of the same topology.

:func:`fusable_segments` extracts the maximal linear conv/pool chains the
auto-partitioner (:mod:`repro.net.partition`) is allowed to cut into fusion
pyramids.  Chain boundaries — residual joins, multi-consumer forks (the
block input feeding both body and shortcut), standalone activations, the
classifier head — are exactly the IR nodes that force a feature map to
materialize, i.e. the partitioner's legal cut points.

Activation convention: conv and dense nodes carry a fused ``relu`` flag (the
paper's pyramids are conv+ReLU stacks), while standalone ``relu`` nodes
express post-residual-add activations.  The flag lowers to the pyramid
level (:class:`~repro.core.fusion.FusedLevel`), so a chain may mix ReLU and
linear convs — a ResNet bottleneck's ``1x1 → 3x3 → 1x1 (linear)`` is one
chain.  Only a pool must follow a ReLU conv: the kernel pads and masks with
zeros, which a max pool ignores only over non-negative values.

A ``layernorm``, ``gelu`` or ``layer_scale`` node that follows a chain's
conv or depthwise level lowers into that level as its epilogue (in the
fixed order bias → activation → LayerNorm → scale), so a ConvNeXt block's
``dw → ln → pw1 → gelu → pw2 → scale`` is one chain of three levels; one
that follows no level (a LayerNorm after a residual add, or on the pooled
vector) runs as a plain op.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from repro.core.dtypes import canonical_dtype
from repro.core.fusion import FusedLevel, FusionSpec

# ops a chain's conv or depthwise level absorbs as its epilogue
EPILOGUE_OPS = ("layernorm", "gelu", "layer_scale")
_OPS = ("input", "conv", "depthwise", "pool", "relu", "add", "global_pool",
        "flatten", "dense") + EPILOGUE_OPS


@dataclass(frozen=True)
class Node:
    """One IR node.  ``K``/``S``/``pad`` apply to conv, depthwise and pool
    nodes, ``n_out`` to conv and dense nodes, ``relu`` to conv, depthwise and
    dense nodes (fused activation), ``eps`` to layernorm nodes.  A depthwise
    (one ``K x K`` filter per channel) keeps its channels; a layernorm
    normalizes over the channels of each pixel (or of a flat vector), then
    applies its per-channel gamma and beta; a layer_scale multiplies each
    channel by its own gamma; a gelu is the exact (erf) GELU."""

    op: str
    name: str
    inputs: tuple[str, ...] = ()
    K: int = 0
    S: int = 1
    pad: int = 0
    n_out: int = 0
    relu: bool = True
    eps: float = 0.0


@dataclass(frozen=True)
class Shape:
    """Feature shape leaving a node: a square ``size x size x channels`` map,
    or a flat vector (``size == 0``, ``channels`` = feature count)."""

    size: int
    channels: int

    @property
    def is_map(self) -> bool:
        return self.size > 0


@dataclass(frozen=True)
class Graph:
    """A whole CNN as a topologically-ordered node tuple.

    ``nodes[0]`` must be the single ``input`` node; ``nodes[-1]`` is the
    network output (the logits for the zoo models).  Hashable — usable as a
    jit static argument.

    ``compute_dtype`` (canonical name string, DESIGN.md §11) is the value
    width the network's tiles and weights move at — the default the
    partitioner and runner inherit when no explicit dtype override is given.
    Accumulation is always f32 regardless.
    """

    name: str
    input_size: int
    in_channels: int
    nodes: tuple[Node, ...]
    compute_dtype: str = "float32"

    def __post_init__(self) -> None:
        if not self.nodes or self.nodes[0].op != "input":
            raise ValueError(f"graph {self.name}: nodes[0] must be the input node")
        object.__setattr__(
            self, "compute_dtype", canonical_dtype(self.compute_dtype)
        )
        infer_shapes(self)  # raises on any structural error

    def node(self, name: str) -> Node:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(f"graph {self.name} has no node {name!r}")

    @property
    def output(self) -> Node:
        return self.nodes[-1]

    def consumers(self) -> dict[str, tuple[str, ...]]:
        out: dict[str, tuple[str, ...]] = {n.name: () for n in self.nodes}
        for n in self.nodes:
            for src in n.inputs:
                out[src] = out[src] + (n.name,)
        return out


def infer_shapes(graph: Graph) -> dict[str, Shape]:
    """Shape/channel inference over the whole graph; the single validation
    pass every other net/ component builds on.  Raises ``ValueError`` naming
    the offending node."""
    shapes: dict[str, Shape] = {}
    for n in graph.nodes:
        if n.op not in _OPS:
            raise ValueError(f"node {n.name}: unknown op {n.op!r}")
        if n.name in shapes:
            raise ValueError(f"node {n.name}: duplicate name")
        ins = []
        for src in n.inputs:
            if src not in shapes:
                raise ValueError(
                    f"node {n.name}: input {src!r} is not an earlier node"
                )
            ins.append(shapes[src])
        n_in = {"input": 0, "add": 2}.get(n.op, 1)
        if len(ins) != n_in:
            raise ValueError(
                f"node {n.name}: op {n.op} takes {n_in} inputs, got {len(ins)}"
            )
        if n.op == "input":
            shapes[n.name] = Shape(graph.input_size, graph.in_channels)
            continue
        if n.op in ("conv", "depthwise", "pool"):
            s = ins[0]
            if not s.is_map:
                raise ValueError(f"node {n.name}: {n.op} needs a feature map")
            out = (s.size + 2 * n.pad - n.K) // n.S + 1
            if out < 1:
                raise ValueError(
                    f"node {n.name}: K={n.K} S={n.S} pad={n.pad} leaves no "
                    f"output from a {s.size}x{s.size} input"
                )
            ch = n.n_out if n.op == "conv" else s.channels
            shapes[n.name] = Shape(out, ch)
        elif n.op in ("relu",) + EPILOGUE_OPS:
            shapes[n.name] = ins[0]
        elif n.op == "add":
            if ins[0] != ins[1]:
                raise ValueError(
                    f"node {n.name}: add operands disagree: {ins[0]} vs {ins[1]}"
                )
            shapes[n.name] = ins[0]
        elif n.op == "global_pool":
            if not ins[0].is_map:
                raise ValueError(f"node {n.name}: global_pool needs a feature map")
            shapes[n.name] = Shape(0, ins[0].channels)
        elif n.op == "flatten":
            s = ins[0]
            feats = s.size * s.size * s.channels if s.is_map else s.channels
            shapes[n.name] = Shape(0, feats)
        elif n.op == "dense":
            if ins[0].is_map:
                raise ValueError(
                    f"node {n.name}: dense needs a flat vector (flatten or "
                    "global_pool first)"
                )
            shapes[n.name] = Shape(0, n.n_out)
    return shapes


# ---------------------------------------------------------------------------
# Fusable segments — the partitioner's search domains
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Segment:
    """A maximal linear conv/pool chain: the domain one dynamic program cuts.

    Every interior node has exactly one consumer (its successor), so no map
    inside the segment is needed elsewhere — fusing across any interior edge
    is legal.  Segment ends are the graph's materialization points.
    """

    nodes: tuple[Node, ...]
    input_size: int
    in_channels: int

    @property
    def node_names(self) -> tuple[str, ...]:
        return tuple(n.name for n in self.nodes)

    @property
    def level_nodes(self) -> tuple[tuple[str, ...], ...]:
        """Per level of :meth:`spec`, the names of the nodes it lowers: a
        conv, depthwise or pool node, then the epilogue nodes it absorbed."""
        return level_nodes(self.nodes)

    def spec(self) -> FusionSpec:
        """Lower the chain to the fusion planner's :class:`FusionSpec`."""
        return FusionSpec(
            levels=_levels(self.nodes, self.in_channels),
            input_size=self.input_size,
        )


def level_nodes(nodes) -> tuple[tuple[str, ...], ...]:
    """Group a chain's nodes by the level each lowers into: a conv, depthwise
    or pool node starts a level, an epilogue node joins the one before."""
    out: list[list[str]] = []
    for n in nodes:
        if n.op in EPILOGUE_OPS:
            out[-1].append(n.name)
        else:
            out.append([n.name])
    return tuple(tuple(g) for g in out)


def _absorb(level: FusedLevel, n: Node) -> FusedLevel | None:
    """``level`` with epilogue node ``n`` lowered into it, or ``None`` when
    the fixed order bias → activation → LayerNorm → scale leaves it no
    place (a pool, an activation after ReLU or after a LayerNorm, ...)."""
    if level.kind == "pool" or level.scale:
        return None
    if n.op == "layer_scale":
        return replace(level, scale=True)
    if level.norm_eps is not None:
        return None
    if n.op == "layernorm":
        return replace(level, norm_eps=n.eps)
    if level.relu or level.gelu:
        return None
    return replace(level, gelu=True)


def _levels(nodes: tuple[Node, ...], in_channels: int) -> tuple[FusedLevel, ...]:
    levels, c = [], in_channels
    for n in nodes:
        if n.op in EPILOGUE_OPS:
            levels[-1] = _absorb(levels[-1], n)
        elif n.op in ("conv", "depthwise"):
            n_out = n.n_out if n.op == "conv" else c
            levels.append(
                FusedLevel(n.op, K=n.K, S=n.S, pad=n.pad, n_in=c,
                           n_out=n_out, name=n.name, relu=n.relu)
            )
            c = n_out
        else:
            levels.append(
                FusedLevel("pool", K=n.K, S=n.S, pad=n.pad, n_in=c, n_out=c,
                           name=n.name)
            )
    return tuple(levels)


def fusable_segments(graph: Graph) -> tuple[Segment, ...]:
    """Maximal fusable chains, in topological order.

    A conv or depthwise conv starts or extends a chain; a pool extends one.
    A node extends the current chain only when it consumes the chain tail
    and the tail has no other consumer; a pool also needs a ReLU conv before
    it (see the module docstring).  Convs of either activation chain
    freely.  An epilogue node (layernorm, gelu, layer_scale) extends the
    chain when the level before it has a free place for it
    (:func:`_absorb`), and otherwise ends it and runs as a plain op.
    Everything else (residual add, fork, head op) terminates the chain:
    these are the cut points.
    """
    shapes = infer_shapes(graph)
    n_consumers = {k: len(v) for k, v in graph.consumers().items()}
    segments: list[Segment] = []
    cur: list[Node] = []

    def flush() -> None:
        if cur:
            src = graph.node(cur[0].inputs[0])
            s_in = shapes[src.name]
            segments.append(
                Segment(
                    nodes=tuple(cur),
                    input_size=s_in.size,
                    in_channels=s_in.channels,
                )
            )
            cur.clear()

    def follows_tail(n: Node) -> bool:
        return bool(cur) and n.inputs[0] == cur[-1].name and (
            n_consumers[cur[-1].name] == 1
        )

    for n in graph.nodes:
        if n.op in ("conv", "depthwise", "pool"):
            extends = follows_tail(n) and (
                n.op != "pool"
                or (cur[-1].op in ("conv", "depthwise", "pool") and cur[-1].relu)
            )
            if extends:
                cur.append(n)
                continue
            flush()
            if n.op != "pool":
                cur.append(n)
            # an orphan pool (no conv head) cannot start a pyramid; the
            # runner executes it as a plain op
        elif (
            n.op in EPILOGUE_OPS
            and follows_tail(n)
            and _absorb(_levels(tuple(cur), 0)[-1], n) is not None
        ):
            cur.append(n)
        else:
            flush()
    flush()
    return tuple(segments)


# ---------------------------------------------------------------------------
# Model zoo
# ---------------------------------------------------------------------------


class _Builder:
    """Tiny fluent helper: tracks the running tail so linear stretches read
    like layer lists; returns node names for explicit wiring."""

    def __init__(self, in_name: str = "image"):
        self.nodes: list[Node] = [Node("input", in_name)]
        self.tail = in_name

    def _add(self, node: Node) -> str:
        self.nodes.append(node)
        self.tail = node.name
        return node.name

    def conv(self, name, K, S, pad, n_out, *, src=None, relu=True) -> str:
        return self._add(
            Node("conv", name, (src or self.tail,), K=K, S=S, pad=pad,
                 n_out=n_out, relu=relu)
        )

    def pool(self, name, K, S, pad=0, *, src=None) -> str:
        return self._add(Node("pool", name, (src or self.tail,), K=K, S=S, pad=pad))

    def depthwise(self, name, K, S, pad, *, src=None) -> str:
        return self._add(
            Node("depthwise", name, (src or self.tail,), K=K, S=S, pad=pad,
                 relu=False)
        )

    def layernorm(self, name, eps, *, src=None) -> str:
        return self._add(
            Node("layernorm", name, (src or self.tail,), relu=False, eps=eps)
        )

    def op(self, op, name, *srcs, n_out=0, relu=True) -> str:
        return self._add(
            Node(op, name, srcs or (self.tail,), n_out=n_out, relu=relu)
        )

    def graph(self, name, input_size, in_channels,
              compute_dtype="float32") -> Graph:
        return Graph(name, input_size, in_channels, tuple(self.nodes),
                     compute_dtype)


def lenet5(input_size: int = 32, num_classes: int = 10, *,
           compute_dtype: str = "float32") -> Graph:
    """LeNet-5 (paper §3.3.1): two conv+pool stages, three dense layers."""
    b = _Builder()
    b.conv("CL1", 5, 1, 0, 6)
    b.pool("MPL1", 2, 2)
    b.conv("CL2", 5, 1, 0, 16)
    b.pool("MPL2", 2, 2)
    b.op("flatten", "flatten")
    b.op("dense", "FC1", n_out=120)
    b.op("dense", "FC2", n_out=84)
    b.op("dense", "FC3", n_out=num_classes, relu=False)
    return b.graph("lenet", input_size, 1, compute_dtype)


def alexnet(input_size: int = 227, num_classes: int = 1000, *,
            compute_dtype: str = "float32") -> Graph:
    """AlexNet conv stack (no LRN) + the three dense layers."""
    b = _Builder()
    b.conv("CONV1", 11, 4, 0, 96)
    b.pool("POOL1", 3, 2)
    b.conv("CONV2", 5, 1, 2, 256)
    b.pool("POOL2", 3, 2)
    b.conv("CONV3", 3, 1, 1, 384)
    b.conv("CONV4", 3, 1, 1, 384)
    b.conv("CONV5", 3, 1, 1, 256)
    b.pool("POOL5", 3, 2)
    b.op("flatten", "flatten")
    b.op("dense", "FC6", n_out=4096)
    b.op("dense", "FC7", n_out=4096)
    b.op("dense", "FC8", n_out=num_classes, relu=False)
    return b.graph("alexnet", input_size, 3, compute_dtype)


_VGG16_PLAN = ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512))


def vgg16(input_size: int = 224, num_classes: int = 1000, *,
          compute_dtype: str = "float32") -> Graph:
    """VGG-16: five conv blocks with trailing 2x2 pools, three dense layers."""
    b = _Builder()
    ci = 0
    for bi, (n_convs, ch) in enumerate(_VGG16_PLAN):
        for _ in range(n_convs):
            ci += 1
            b.conv(f"CONV{ci}", 3, 1, 1, ch)
        b.pool(f"POOL{bi + 1}", 2, 2)
    b.op("flatten", "flatten")
    b.op("dense", "FC1", n_out=4096)
    b.op("dense", "FC2", n_out=4096)
    b.op("dense", "FC3", n_out=num_classes, relu=False)
    return b.graph("vgg16", input_size, 3, compute_dtype)


# (n_out, stride of convA) per residual block
_RESNET18_PLAN = ((64, 1), (64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                  (512, 2), (512, 1))


def resnet18(input_size: int = 224, num_classes: int = 1000, *,
             compute_dtype: str = "float32") -> Graph:
    """ResNet-18: 7x7/2 stem + 3x3/2 maxpool, eight 2-conv residual blocks
    (1x1 projection shortcuts at the stride-2 / channel-change blocks),
    global average pool and the classifier.

    Per the repro's block variant, every body conv applies fused ReLU —
    including convB before the add (the published block leaves convB
    linear; the benchmark's ``resnet18-bf16`` configuration states this
    variant); the residual join is a standalone ``add`` + ``relu`` pair.
    Projection shortcuts are relu-free 1x1 convs, forked from the block
    input, which makes them their own Q=1 pyramids.
    """
    b = _Builder()
    b.conv("conv1", 7, 2, 3, 64)
    b.pool("maxpool", 3, 2, pad=1)
    c_in = 64
    for i, (ch, s1) in enumerate(_RESNET18_PLAN):
        blk, block_in = f"b{i}", b.tail
        b.conv(f"{blk}_convA", 3, s1, 1, ch, src=block_in)
        body = b.conv(f"{blk}_convB", 3, 1, 1, ch)
        if s1 != 1 or c_in != ch:
            shortcut = b.conv(f"{blk}_proj", 1, s1, 0, ch, src=block_in,
                              relu=False)
        else:
            shortcut = block_in
        b.op("add", f"{blk}_add", body, shortcut)
        b.op("relu", f"{blk}_relu")
        c_in = ch
    b.op("global_pool", "gap")
    b.op("dense", "FC", n_out=num_classes, relu=False)
    return b.graph("resnet18", input_size, 3, compute_dtype)


# (bottleneck width, blocks) per stage, conv2_x..conv5_x (He et al. 2016,
# Table 1, 50-layer column); each block widens to 4x its width
_RESNET50_PLAN = ((64, 3), (128, 4), (256, 6), (512, 3))


def resnet50(input_size: int = 224, num_classes: int = 1000, *,
             compute_dtype: str = "float32") -> Graph:
    """ResNet-50 v1.5: the ResNet-18 stem, sixteen bottleneck blocks
    ``1x1 (ReLU) → 3x3/s (ReLU) → 1x1 to 4x the width (linear)``, the
    residual ``add`` and then ``relu``, global average pool and the
    classifier.  v1.5 puts a stage's stride 2 on the 3x3 conv of its first
    block (conv3_x..conv5_x), not on the 1x1.  The first block of every
    stage, conv2_x's included (64 → 256), has a 1x1 projection shortcut at
    that stride.  Batch norm is folded into the conv biases.  The body
    ``convA..convC`` is one fusable chain with a linear last level."""
    b = _Builder()
    b.conv("conv1", 7, 2, 3, 64)
    b.pool("maxpool", 3, 2, pad=1)
    i = 0
    for stage, (width, blocks) in enumerate(_RESNET50_PLAN):
        for k in range(blocks):
            blk, block_in = f"b{i}", b.tail
            s = 2 if stage > 0 and k == 0 else 1
            b.conv(f"{blk}_convA", 1, 1, 0, width, src=block_in)
            b.conv(f"{blk}_convB", 3, s, 1, width)
            body = b.conv(f"{blk}_convC", 1, 1, 0, 4 * width, relu=False)
            if k == 0:
                shortcut = b.conv(f"{blk}_proj", 1, s, 0, 4 * width,
                                  src=block_in, relu=False)
            else:
                shortcut = block_in
            b.op("add", f"{blk}_add", body, shortcut)
            b.op("relu", f"{blk}_relu")
            i += 1
    b.op("global_pool", "gap")
    b.op("dense", "FC", n_out=num_classes, relu=False)
    return b.graph("resnet50", input_size, 3, compute_dtype)


# ConvNeXt-T (Liu et al. 2022, arXiv:2201.03545; torchvision convnext_tiny):
# blocks and widths per stage, and the LayerNorm epsilon
_CONVNEXT_T_DEPTHS = (3, 3, 9, 3)
_CONVNEXT_T_WIDTHS = (96, 192, 384, 768)
_CONVNEXT_LN_EPS = 1e-6


def convnext_tiny(input_size: int = 224, num_classes: int = 1000, *,
                  compute_dtype: str = "float32",
                  depths: tuple[int, ...] = _CONVNEXT_T_DEPTHS) -> Graph:
    """ConvNeXt-T: a 4x4/4 patchify stem and a LayerNorm, four stages of
    blocks ``7x7 depthwise (bias) → LayerNorm → 1x1 to 4C, GELU → 1x1
    back to C (linear) → layer scale → residual add`` at widths 96, 192,
    384, 768, a LayerNorm and a 2x2/2 conv between stages, then global
    average pool, LayerNorm and the classifier.  Every LayerNorm is over
    channels with epsilon 1e-6; stochastic depth is the identity at
    inference.  ``depths`` (published: 3, 3, 9, 3) lets tests run fewer
    blocks at the published widths.  Each block's ``dw..scale`` is one
    fusable chain of three levels; the downsample and head LayerNorms
    follow no level and run as plain ops."""
    b = _Builder()
    b.conv("stem", 4, 4, 0, _CONVNEXT_T_WIDTHS[0], relu=False)
    b.layernorm("stem_ln", _CONVNEXT_LN_EPS)
    i = 0
    for stage, (depth, width) in enumerate(zip(depths, _CONVNEXT_T_WIDTHS)):
        if stage:
            b.layernorm(f"s{stage}_ln", _CONVNEXT_LN_EPS)
            b.conv(f"s{stage}_down", 2, 2, 0, width, relu=False)
        for _ in range(depth):
            blk, block_in = f"b{i}", b.tail
            b.depthwise(f"{blk}_dw", 7, 1, 3, src=block_in)
            b.layernorm(f"{blk}_ln", _CONVNEXT_LN_EPS)
            b.conv(f"{blk}_pw1", 1, 1, 0, 4 * width, relu=False)
            b.op("gelu", f"{blk}_gelu", relu=False)
            b.conv(f"{blk}_pw2", 1, 1, 0, width, relu=False)
            b.op("layer_scale", f"{blk}_scale", relu=False)
            b.op("add", f"{blk}_add", b.tail, block_in)
            i += 1
    b.op("global_pool", "gap")
    b.layernorm("head_ln", _CONVNEXT_LN_EPS)
    b.op("dense", "FC", n_out=num_classes, relu=False)
    return b.graph("convnext_tiny", input_size, 3, compute_dtype)


MODELS = {
    "lenet": lenet5,
    "alexnet": alexnet,
    "vgg16": vgg16,
    "resnet18": resnet18,
    "resnet50": resnet50,
    "convnext_tiny": convnext_tiny,
}


def backbone_prefix(graph: Graph, n_convs: int) -> FusionSpec:
    """FusionSpec of the first ``n_convs`` convs (+ interleaved/trailing
    pools) of the graph's leading fusable segment — how ``core/cnn_models``
    derives the paper's hand-picked fusion groups from the zoo graphs."""
    seg = fusable_segments(graph)[0]
    taken, convs = [], 0
    for n in seg.nodes:
        if n.op == "conv":
            if convs == n_convs:
                break
            convs += 1
        taken.append(n)
    if convs < n_convs:
        raise ValueError(
            f"graph {graph.name}: leading segment has only {convs} convs"
        )
    sub = replace(seg, nodes=tuple(taken))
    return sub.spec()
