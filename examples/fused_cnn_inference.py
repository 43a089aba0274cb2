"""End-to-end fused CNN inference with machine-chosen fusion boundaries.

Builds a zoo model as a graph (`repro.net.graph`), lets the memory-aware
auto-partitioner pick the pyramid cuts (`repro.net.partition`), executes the
whole network through the fused Pallas kernels (`repro.net.runner`) and
verifies the logits against the monolithic JAX reference.  Also demonstrates
the END tile-skip cascade firing on spatially sparse input.

Run:  PYTHONPATH=src python examples/fused_cnn_inference.py --model lenet
      PYTHONPATH=src python examples/fused_cnn_inference.py --model resnet18

On a TPU every model runs at its published input size with compiled
kernels; elsewhere big models default to a reduced spatial scale so
interpret mode stays quick.  Pass --input-size to override either default.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.core import resolve_interpret
from repro.net.graph import MODELS, infer_shapes
from repro.net.partition import auto_partition, layerwise_partition
from repro.net.runner import (
    bf16_logit_tol,
    init_network_params,
    reference_network,
    run_network,
    run_network_per_launch,
    skip_fractions,
)
from repro.obs import TraceCollector

# interpret-friendly default scales off the chip (paper scale for LeNet only)
INTERPRET_SIZE = {"lenet": 32, "alexnet": 67, "vgg16": 32, "resnet18": 32,
                  "resnet50": 32, "convnext_tiny": 32}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=sorted(MODELS), default="lenet")
    ap.add_argument("--input-size", type=int, default=None)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="compute dtype for activations/weights; "
                         "accumulation stays f32 either way (DESIGN.md #11)")
    args = ap.parse_args()
    use_compile_cache()

    interpret = resolve_interpret(None)
    size = args.input_size or (
        INTERPRET_SIZE[args.model] if interpret
        else MODELS[args.model]().input_size
    )
    graph = MODELS[args.model](input_size=size, num_classes=10,
                               compute_dtype=args.dtype)
    shapes = infer_shapes(graph)
    print(f"{graph.name}: {len(graph.nodes)} nodes, input {size}x{size}, "
          f"logits {shapes[graph.output.name].channels}, "
          f"compute dtype {graph.compute_dtype}")

    plan = auto_partition(graph, batch=args.batch)
    layer = layerwise_partition(graph, batch=args.batch)
    print(plan.summary())
    print(f"layer-by-layer baseline: {layer.hbm_bytes():,}B over "
          f"{layer.n_launches()} launches -> auto saves "
          f"{1 - plan.hbm_bytes() / layer.hbm_bytes():.1%} modeled HBM traffic")

    params = init_network_params(graph, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (args.batch, size, size,
                                                  graph.in_channels))
    t0 = time.time()
    logits, skips = run_network(x, params, plan=plan)
    jax.block_until_ready(logits)
    mode = "interpret mode" if interpret else "compiled kernels"
    print(f"run_network: logits {logits.shape} in {time.time() - t0:.1f}s "
          f"({jax.default_backend()} backend, {mode}, includes compile)")
    ref = reference_network(x, graph, params)
    err = float(jnp.abs(logits.astype(jnp.float32) - ref).max())
    print("max |err| vs monolithic f32 reference:", err)
    if args.dtype == "bfloat16":
        # the documented low-precision contract (DESIGN.md #11): bf16
        # operands, f32 accumulation, error relative to logit magnitude
        tol = bf16_logit_tol(ref)
        print(f"bf16 logit tolerance: {tol:.4f}")
        assert err <= tol, f"bf16 error {err} exceeds tolerance {tol}"

    # sparse input: most tiles die after level 0, the END cascade skips the
    # deeper convs of each pyramid.  Re-partition with the paper's
    # smallest-region preference: maximal tile grids even at reduced scale,
    # so the per-tile skips become visible.
    tight = auto_partition(graph, batch=args.batch, prefer_region="smallest")
    blob = max(4, size // 4)
    xs = jnp.zeros_like(x).at[:, :blob, :blob, :].set(
        jax.random.normal(jax.random.PRNGKey(2),
                          (args.batch, blob, blob, graph.in_channels)) * 3
    )
    sparse_params = {
        k: (v[0], v[1] - 0.3) if graph.node(k).op == "conv" else v
        for k, v in params.items()
    }
    # run the sparse forward launch by launch (DESIGN.md #12): one
    # measured+modeled span per fused launch
    collector = TraceCollector()
    logits_s, skips_s = run_network_per_launch(
        xs, sparse_params, plan=tight, collector=collector
    )
    ref_s = reference_network(xs, graph, sparse_params)
    print("sparse input: max |err|", float(jnp.abs(logits_s - ref_s).max()))
    for name, frac in skip_fractions(skips_s).items():
        if any(f > 0 for f in frac):
            print(f"  END skips {name}: "
                  + ", ".join(f"L{i}={f:.0%}" for i, f in enumerate(frac)))
    print("traced launches (modeled cycle-model time vs measured wall clock):")
    for s in collector.spans:
        print(f"  {s.name:<24} {s.regime:<16} modeled {s.modeled_us:>9,.1f}us"
              f"   measured {s.duration_ms:>9,.1f}ms")
    print(f"  (python -m repro.obs.explain --model {args.model} "
          "--trace t.json renders the full plan table + Perfetto timeline)")


if __name__ == "__main__":
    main()
