"""Smoke run of the fused-pyramid serving path on one TPU chip.

Serves ResNet-18 at its published 224x224 input through
``ServingEngine(resnet18(), params, ServeConfig())`` — compiled Mosaic
kernels, no guard, no fallback rung — in float32 and bfloat16, with requests
that form bucket 1 and bucket 8.  Weights and images are drawn from
``--seed``.  It checks that

* every request completes and every resilience counter stays zero;
* the compiled forward holds one ``tpu_custom_call`` per planned pyramid;
* the logits match the f32 ``highest``-precision reference: float32 within
  ``F32_LOGIT_RTOL`` of the largest reference logit, bfloat16 within
  ``bf16_logit_tol``.

It prints the device first, then per dtype and bucket the compile time and
the first and steady request times (chip wall clock), and as its last line
``{"ok": true, "device": {...}}``.  Without a TPU it exits non-zero before
doing any work.  Run it from the repository root: ``python chip_smoke.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

# float32 logits vs the highest-precision reference, as a share of the
# largest reference logit (measured on a TPU v5e; see PERF.md)
F32_LOGIT_RTOL = 1e-4
BUCKETS = (1, 8)


def _device() -> dict:
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def _require(ok: bool, message: str) -> None:
    """A failed check ends the run (an ``assert`` would vanish under -O)."""
    if not ok:
        raise SystemExit(f"chip smoke failed: {message}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def smoke_dtype(graph, params, images, reference, dtype: str) -> None:
    """Serve bucket 1 and bucket 8 at ``dtype`` and check every result."""
    from repro.net.partition import auto_partition
    from repro.net.runner import (
        _run_network_jit,
        bf16_logit_tol,
        prepare_network_params,
    )
    from repro.net.serve import ServeConfig, ServingEngine

    config = ServeConfig() if dtype == "float32" else ServeConfig(
        compute_dtype=dtype
    )
    engine = ServingEngine(graph, params, config)
    for bucket in BUCKETS:
        # the plan the engine makes for this bucket, compiled ahead of the
        # first request: its HLO must hold one Mosaic kernel per pyramid
        plan = auto_partition(
            graph, vmem_budget=config.vmem_budget, batch=bucket,
            prefer_region=config.prefer_region, compute_dtype=dtype,
        )
        prepared = prepare_network_params(plan, params)
        x = jax.ShapeDtypeStruct(
            (bucket, graph.input_size, graph.input_size, graph.in_channels),
            np.float32,
        )
        compiled, compile_s = _timed(
            lambda: _run_network_jit.lower(
                x, prepared, plan=plan, end_skip=config.end_skip,
                interpret=config.interpret, dtype=None,
            ).compile()
        )
        kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
        _require(
            kernels == len(plan.pyramids),
            f"{dtype} bucket {bucket}: {kernels} tpu_custom_call ops for"
            f" {len(plan.pyramids)} planned pyramids",
        )

        requests = [images[i : i + 1] for i in range(bucket)]
        first, first_s = _timed(lambda: engine.serve(requests))
        steady, steady_s = _timed(lambda: engine.serve(requests))
        for results in (first, steady):
            bad = [r for r in results if not r.ok]
            _require(not bad, f"{dtype} bucket {bucket}: {bad[:1]}")
            _require(
                {r.bucket for r in results} == {bucket},
                f"{dtype}: requests ran in buckets"
                f" {sorted({r.bucket for r in results})}, expected {bucket}",
            )
        logits = np.concatenate(
            [np.asarray(r.logits, np.float32) for r in steady]
        )
        ref = reference[:bucket]
        err = float(np.max(np.abs(logits - ref)))
        scale = float(np.max(np.abs(ref)))
        if dtype == "float32":
            tol = F32_LOGIT_RTOL * scale
        else:
            tol = bf16_logit_tol(ref)
        print(
            f"{dtype} bucket {bucket}: {len(plan.pyramids)} kernels,"
            f" compile {compile_s:.2f} s, first request {first_s * 1e3:.1f} ms,"
            f" steady {steady_s * 1e3:.1f} ms (chip wall clock);"
            f" max |logit err| {err:.3e} (tol {tol:.3e}, max |logit|"
            f" {scale:.3e})",
            flush=True,
        )
        _require(err <= tol, f"{dtype} bucket {bucket}: logit error {err} > {tol}")

    summary = engine.summary()
    resilience = {
        k: v for k, v in summary["resilience"].items() if k != "breakers"
    }
    _require(not any(resilience.values()), f"{dtype}: resilience {resilience}")
    _require(not summary["resilience"]["breakers"], "a breaker was armed")
    _require(summary["rejected"] == 0, f"{dtype}: rejected requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = _device()
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        print("no TPU: the smoke run needs one", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import use_compile_cache
    from repro.net.graph import resnet18
    from repro.net.runner import init_network_params, reference_network

    print(f"compile cache: {use_compile_cache()}", flush=True)
    graph = resnet18()
    params = init_network_params(graph, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    images = rng.standard_normal(
        (max(BUCKETS), graph.input_size, graph.input_size, graph.in_channels)
    ).astype(np.float32)
    reference = np.asarray(reference_network(images, graph, params))
    for dtype in ("float32", "bfloat16"):
        smoke_dtype(graph, params, images, reference, dtype)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
