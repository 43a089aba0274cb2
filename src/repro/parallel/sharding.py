"""Logical-axis sharding rules: DP (fsdp) x TP (tensor) x EP (expert) x pods.

Every parameter/cache leaf carries a tuple of logical axis names.  Rules
map logical names to mesh axes; the resolver turns (shape, axes, mesh) into
a PartitionSpec with two safety valves needed by real architectures:

* divisibility fallback — a dim that does not divide by its mesh-axis extent
  drops that mapping (replicates) rather than relying on GSPMD padding.
* one-mesh-axis-once — if two logical dims of one tensor resolve to the same
  mesh axis, the later one is dropped (a mesh axis can shard one dim only).

Default rule set (production mesh (pod, data, model)):

  batch/fsdp      -> ('pod', 'data')   # DP + FSDP parameter sharding
  tensor-ish dims -> ('model',)        # TP: heads / mlp / vocab / experts
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np
from jax.sharding import Mesh, PartitionSpec


DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    # data-parallel / fsdp family
    "batch": ("pod", "data"),
    "embed": ("pod", "data"),  # fsdp shard of the non-TP weight dim
    "layers": (),
    # tensor-parallel family
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "lora": ("model",),
    # serving
    "cache_seq": (),
    # activations
    "seq": (),
    "act_embed": (),
}


@dataclass(frozen=True)
class ShardingRules:
    rules: dict[str, tuple[str, ...]] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def override(self, **kw: tuple[str, ...]) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kw)
        return ShardingRules(d)

    def mesh_axes_for(self, logical: str | None, mesh: Mesh) -> tuple[str, ...]:
        if logical is None:
            return ()
        axes = self.rules.get(logical, ())
        return tuple(a for a in axes if a in mesh.axis_names)


def _axis_size(mesh: Mesh, names: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[n] for n in names])) if names else 1


def partition_spec(
    shape: tuple[int, ...],
    logical_axes: tuple[str | None, ...],
    mesh: Mesh,
    rules: ShardingRules,
) -> PartitionSpec:
    """Resolve one tensor's logical axes to a PartitionSpec."""
    used: set[str] = set()
    entries: list[Any] = []
    for dim, logical in zip(shape, logical_axes):
        names = rules.mesh_axes_for(logical, mesh)
        names = tuple(n for n in names if n not in used)
        size = _axis_size(mesh, names)
        if not names or size <= 1 or dim % size != 0:
            entries.append(None)  # divisibility fallback: replicate
            continue
        used.update(names)
        entries.append(names if len(names) > 1 else names[0])
    while entries and entries[-1] is None:
        entries.pop()  # trailing Nones are implicit
    return PartitionSpec(*entries)
