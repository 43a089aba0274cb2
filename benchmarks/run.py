"""Benchmark harness: one section per paper table/figure, plus the
whole-network partition comparison, with machine-readable output.

``PYTHONPATH=src python -m benchmarks.run``  prints ``name,...`` CSV rows and
writes ``BENCH_pyramid.json`` (``--out`` to relocate) holding the per-workload
HBM bytes, wall-clock numbers (each recorded as its median plus a
``{p50_ms, p95_ms, reps}`` stats dict over :data:`WALLCLOCK_REPS` timed reps
after one warm-up), END skip fractions, and the auto-partition vs
paper-fusion vs layer-by-layer comparison for every zoo model — the rows the
perf trajectory tracks.  Wall clocks are never gated; the analytic rows are
(see ``check_regression``).

Sections:

* Tables 1-4 — DS-1/DS-2 cycle-model durations vs the paper (paper_tables)
* Figs 10-11 — performance vs operational intensity (intensity)
* Figs 12-14 — END detection / energy / ResNet-18 cycle savings (end_savings)
* Whole-network partitions — modeled HBM/latency of auto vs baselines
* Kernel wall-time sanity (interpret mode; TPU timing is the dry-run's job)

``--dry-run`` keeps only the analytic sections (no kernel launches, no
digit-level simulation) so the CI smoke job finishes in seconds on CPU.
"""

from __future__ import annotations

import argparse
import json

FREQ_MHZ = 100.0

# every wall-clock number in the JSON is the median of this many timed reps
# (after one untimed warm-up call); the rep count rides along in the output
# so check_regression-style consumers compare like with like
WALLCLOCK_REPS = 5


def _percentile_ms(times: list[float], q: float) -> float:
    """Linear-interpolated q-th percentile (times already in ms) — the
    shared :func:`repro.obs.stats.percentile`, kept under its historical
    name for callers and tests."""
    from repro.obs.stats import percentile

    return percentile(times, q)


def _timed_stats_ms(fn, reps: int = WALLCLOCK_REPS) -> dict:
    """Wall-clock stats over ``reps`` timed calls of ``fn`` — the shared
    :func:`repro.obs.stats.timed_stats_ms` (warm-up + reps, returns
    ``{"p50_ms", "p95_ms", "reps"}``).  Every wall-clock metric in
    BENCH_pyramid.json records this dict alongside its median scalar so the
    trajectory carries tail latency too.  Wall clocks are never gated by
    check_regression, so the extra keys do not widen the gate."""
    from repro.obs.stats import timed_stats_ms

    return timed_stats_ms(fn, reps)


def _timed_median_ms(fn, reps: int = WALLCLOCK_REPS) -> float:
    """Median-only convenience wrapper around :func:`_timed_stats_ms`."""
    return _timed_stats_ms(fn, reps)["p50_ms"]


def _partition_comparison(csv=print) -> dict:
    """Auto vs paper vs layer-by-layer for every zoo model: modeled HBM
    traffic and DS-1 latency of all pyramid launches (batch 1).  The
    ``auto_bf16`` strategy re-runs the DP with 2-byte operands so the JSON
    records how the halved working set re-tiers the plan ladder (regime
    flips and cut-point moves) alongside the ~2x HBM reduction."""
    from repro.net.graph import MODELS
    from repro.net.partition import (
        auto_partition,
        layerwise_partition,
        paper_partition,
    )

    out: dict = {}
    csv("partition,model,strategy,hbm_bytes,launches,modeled_latency_us")
    for model in MODELS:
        graph = MODELS[model]()
        rows = {}
        for strategy, plan in (
            ("auto", auto_partition(graph)),
            ("auto_bf16", auto_partition(graph, compute_dtype="bfloat16")),
            ("paper", paper_partition(graph)),
            ("layerwise", layerwise_partition(graph)),
        ):
            lat_us = plan.modeled_cycles() / FREQ_MHZ
            rows[strategy] = {
                "hbm_bytes": plan.hbm_bytes(),
                "launches": plan.n_launches(),
                "modeled_latency_us": lat_us,
                "pyramids": [
                    {
                        "nodes": list(p.node_names),
                        "q_convs": p.q_convs,
                        "out_region": p.launch.out_region,
                        "streamed": p.launch.streamed,
                        "regime": p.launch.regime,
                        "c_tiles": p.launch.c_tiles,
                        "hbm_bytes": p.launch.hbm_bytes(),
                    }
                    for p in plan.pyramids
                ],
            }
            csv(
                f"partition,{model},{strategy},{rows[strategy]['hbm_bytes']},"
                f"{rows[strategy]['launches']},{lat_us:.1f}"
            )
        auto, layer = rows["auto"]["hbm_bytes"], rows["layerwise"]["hbm_bytes"]
        paper = rows["paper"]["hbm_bytes"]
        csv(
            f"partition_savings,{model},auto_vs_layerwise,"
            f"{(layer - auto) / layer:.1%},auto_vs_paper,"
            f"{(paper - auto) / paper:.1%}"
        )
        bf16 = rows["auto_bf16"]
        flips = sum(
            1
            for p32, p16 in zip(rows["auto"]["pyramids"], bf16["pyramids"])
            if p32["regime"] != p16["regime"]
        ) if rows["auto"]["launches"] == bf16["launches"] else None
        csv(
            f"partition_dtype,{model},bf16_hbm_ratio,"
            f"{auto / bf16['hbm_bytes']:.2f}x,launches,"
            f"{rows['auto']['launches']}->{bf16['launches']},regime_flips,"
            f"{'resegmented' if flips is None else flips}"
        )
        out[model] = rows
    return out


def _kernel_dataflow(csv=print, dry_run: bool = True) -> dict:
    """Per-launch HBM dataflow of the fused-pyramid kernel: the retired
    whole-image-resident input model vs the halo-tile model (what the kernel
    now actually moves), per regime, the fully-blocking vs software-pipelined
    modeled latency delta (cross-cell input prefetch *and* the k-axis weight
    slice pipeline of channel-tiled launches), plus compiled-vs-interpret
    wall clock when kernels may run.  The analytic rows are emitted even
    under ``--dry-run`` so the CI smoke job can assert the section exists
    and the bench trajectory has comparable numbers."""
    import dataclasses

    import jax

    from repro.core.cnn_models import (
        LENET5_FUSION,
        VGG_FUSION,
        resnet18_fusions,
    )
    from repro.core.intensity import launch_dataflow
    from repro.core.program import plan_launch

    out: dict = {"launches": {}}
    csv(
        "kernel_dataflow,workload,input_model,input_bytes,weight_bytes,"
        "output_bytes,regime"
    )
    specs = {
        "lenet_q2": LENET5_FUSION,
        "vgg_blocks12_q4_224": VGG_FUSION,
        "resnet18_b7_streamed": resnet18_fusions()[7],
    }
    # every workload is planned twice: at f32 and at bf16.  The bf16 twin
    # rides as ``<name>_bf16`` so the regression gate tracks both ladders;
    # the dtype row below reports the HBM ratio and any plan-tier flip the
    # halved bytes buy (e.g. streamed -> resident, fewer c_tiles).
    for name, spec in specs.items():
        for dtype in ("float32", "bfloat16"):
            lp = plan_launch(spec, compute_dtype=dtype)
            flow = launch_dataflow(lp.program, streamed=lp.streamed)
            # the fully-blocking schedule: serial input fetch AND blocking
            # weight DMA, at the launched c_tiles — what every DMA/MXU
            # overlap (cross-cell x pipeline + k-axis slice pipeline) is
            # measured against
            cycles_serial = dataclasses.replace(
                lp, x_slots=1, w_slots=1
            ).modeled_cycles()
            # only advertise the pipelined latency when the x_slots=2 kernel
            # is actually buildable (the planner's own ladder rule) —
            # otherwise the row reports the launched regime
            cycles_pipe = lp.with_input_pipeline().modeled_cycles()
            # the k-axis share alone: the launched plan vs its blocking-slice
            # (w_slots=1) twin — 0 for resident launches, > 0 exactly when
            # the weight pipeline (channel-tiled or whole-level) overlaps
            cycles_w1 = dataclasses.replace(lp, w_slots=1).modeled_cycles()
            row = {
                **flow,
                "compute_dtype": dtype,
                "regime": lp.regime,
                "alpha": lp.program.alpha,
                "out_region": lp.out_region,
                "tile0": lp.program.tile0,
                "streamed": lp.streamed,
                "w_slots": lp.w_slots,
                "x_slots": lp.x_slots,
                "c_tiles": lp.c_tiles,
                "slice_bytes": lp.slice_bytes(),
                "hbm_bytes_total": lp.hbm_bytes(),
                "input_reduction": (
                    flow["input_bytes_whole_image"] / flow["input_bytes_halo"]
                ),
                "modeled_cycles": lp.modeled_cycles(),
                "modeled_cycles_serial": cycles_serial,
                "modeled_cycles_pipelined": cycles_pipe,
                "pipeline_cycles_saved": cycles_serial - cycles_pipe,
                "k_pipeline_cycles_saved": cycles_w1 - lp.modeled_cycles(),
            }
            key = name if dtype == "float32" else f"{name}_bf16"
            out["launches"][key] = row
            for model in ("whole_image", "halo"):
                csv(
                    f"kernel_dataflow,{key},{model},"
                    f"{flow[f'input_bytes_{model}']},{flow['weight_bytes']},"
                    f"{flow['output_bytes']},{lp.regime}"
                )
            csv(
                f"kernel_dataflow_reduction,{key},input,"
                f"{row['input_reduction']:.1f}x,alpha,{row['alpha']}"
            )
            csv(
                f"kernel_dataflow_pipeline,{key},serial,{cycles_serial},"
                f"pipelined,{cycles_pipe},saved,{row['pipeline_cycles_saved']},"
                f"x_slots,{lp.x_slots},c_tiles,{lp.c_tiles},"
                f"slice_bytes,{row['slice_bytes']},"
                f"k_saved,{row['k_pipeline_cycles_saved']}"
            )
        f32, b16 = out["launches"][name], out["launches"][f"{name}_bf16"]
        csv(
            f"kernel_dataflow_dtype,{name},bf16_hbm_ratio,"
            f"{f32['hbm_bytes_total'] / b16['hbm_bytes_total']:.2f}x,"
            f"cycles_ratio,"
            f"{f32['modeled_cycles'] / b16['modeled_cycles']:.2f}x,"
            f"regime,{f32['regime']}->{b16['regime']},"
            f"c_tiles,{f32['c_tiles']}->{b16['c_tiles']}"
        )

    if not dry_run:
        from repro.core import resolve_interpret
        from repro.core.executor import init_pyramid_params
        from repro.kernels.fused_conv.ops import fused_pyramid

        spec = LENET5_FUSION
        params = init_pyramid_params(spec, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 1))
        wall: dict = {
            "backend": jax.default_backend(),
            "reps": WALLCLOCK_REPS,
        }
        modes = [("interpret", True)]
        if not resolve_interpret(None):  # compiled mode available (TPU)
            modes.append(("compiled", False))
        for label, interp in modes:
            def call(interp=interp):
                y, _ = fused_pyramid(
                    x, params.weights, params.biases, spec=spec,
                    out_region=1, interpret=interp,
                )
                jax.block_until_ready(y)

            stats = _timed_stats_ms(call)
            wall[f"{label}_ms"] = stats["p50_ms"]
            wall[f"{label}_stats"] = stats
            csv(
                f"kernel_dataflow_wallclock,lenet_q2,{label},"
                f"{stats['p50_ms']:.1f},ms_per_call_median{WALLCLOCK_REPS},"
                f"p95,{stats['p95_ms']:.1f}"
            )
        if "compiled_ms" not in wall:
            wall["compiled_ms"] = None  # no TPU on this host
        out["wallclock"] = wall
    return out


def _serving(csv=print, dry_run: bool = True) -> dict:
    """Serving engine (DESIGN.md §14): per-bucket modeled rows — launches,
    HBM bytes, batch-aware modeled cycles, and the published SLO (cold:
    host staging + compute; steady: the double-buffered ``max`` bound) —
    for LeNet and ResNet-18 at paper scale.  The analytic rows are emitted
    under ``--dry-run`` too and are regression-gated (``modeled_cycles``
    and ``slo_us`` per bucket), so a plan ladder change that slows a
    serving bucket fails CI even though no kernel ran.

    When kernels may run (``not dry_run``), a measured sweep drives 8
    single-image requests through a :class:`~repro.net.serve.ServingEngine`
    at each bucket size and through a sequential batch-1 ``run_network``
    baseline (ResNet-18 at the reduced interpret scale).  The acceptance
    row is ``bucket8_beats_sequential``: continuous batching at bucket 8
    must out-throughput one-at-a-time calls."""
    from repro.core.cycle_model import host_staging_cycles, serve_stream_cycles
    from repro.core.dtypes import DTYPE_BYTES
    from repro.net.graph import MODELS
    from repro.net.partition import auto_partition

    buckets = (1, 2, 4, 8)
    out: dict = {}
    csv(
        "serving,model,bucket,launches,hbm_bytes,modeled_cycles,"
        "slo_us,steady_us,us_per_img"
    )
    for model in ("lenet", "resnet18"):
        graph = MODELS[model]()
        rows: dict = {}
        for bucket in buckets:
            plan = auto_partition(graph, batch=bucket)
            compute = plan.modeled_cycles()
            in_bytes = DTYPE_BYTES[plan.compute_dtype] * bucket * (
                graph.input_size ** 2 * graph.in_channels
            )
            staging = host_staging_cycles(in_bytes)
            slo_us = serve_stream_cycles(
                1, compute, staging, double_buffered=False
            ) / FREQ_MHZ
            steady_us = max(compute, staging) / FREQ_MHZ
            rows[f"bucket{bucket}"] = {
                "bucket": bucket,
                "launches": plan.n_launches(),
                "hbm_bytes": plan.hbm_bytes(),
                "modeled_cycles": compute,
                "staging_cycles": staging,
                "slo_us": slo_us,
                "steady_us": steady_us,
                "us_per_img": slo_us / bucket,
            }
            csv(
                f"serving,{model},{bucket},{plan.n_launches()},"
                f"{plan.hbm_bytes()},{compute},{slo_us:.1f},"
                f"{steady_us:.1f},{slo_us / bucket:.1f}"
            )
        b1, b8 = rows["bucket1"], rows["bucket8"]
        efficiency = 8 * b1["slo_us"] / b8["slo_us"]
        csv(
            f"serving_batch_efficiency,{model},bucket8_vs_1x8,"
            f"{efficiency:.2f}x_modeled,launches,"
            f"{b1['launches']}->{b8['launches']}"
        )
        # the serving acceptance for big models: modeled batch efficiency
        # (8 cold batch-1 SLOs vs one cold bucket-8 SLO).  The measured
        # interpret-mode wall clock is NOT the acceptance — CPU kernel
        # emulation scales with rows, so batching shows ~1x there (0.87x
        # for resnet18) while the TPU-model claim is >3x; the floor gate
        # lives in check_regression.EFFICIENCY_FLOORS.
        out[model] = {
            "buckets": rows,
            "modeled_batch_efficiency_b8": efficiency,
        }

    if not dry_run:
        measured = _serving_measured(csv)
        for model, rows in measured.items():
            out[model]["measured"] = rows
    return out


def _serving_measured(csv=print) -> dict:
    """Measured half of the serving section: 8 single-image requests per
    bucket through the engine vs sequential batch-1 calls, interpret mode.
    The sequential baseline blocks per call — request-response semantics:
    a one-at-a-time server must return each result before dispatching the
    next forward, which is exactly the sync overhead continuous batching
    amortizes.  Wall clocks are never gated; ``bucket8_beats_sequential``
    records the acceptance row for LeNet (the only zoo model whose
    interpret-mode wall clock is not dominated by per-image kernel
    emulation — for ResNet-18 the rows ride as ungated context next to
    its modeled batch efficiency, which is the TPU-model claim)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.net.graph import MODELS
    from repro.net.partition import auto_partition
    from repro.net.runner import (
        init_network_params,
        prepare_network_params,
        run_network,
    )
    from repro.net.serve import ServeConfig, ServingEngine

    n_imgs = 8
    sizes = {"lenet": None, "resnet18": 32}  # interpret-friendly scales
    out: dict = {}
    for model, size in sizes.items():
        kwargs = {"input_size": size} if size else {}
        graph = MODELS[model](**kwargs)
        params = init_network_params(graph, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        imgs = [
            rng.standard_normal(
                (1, graph.input_size, graph.input_size, graph.in_channels)
            ).astype(np.float32)
            for _ in range(n_imgs)
        ]

        # sequential baseline: one batch-1 run_network call per image,
        # host->device copy included and blocking per call (a one-request-
        # at-a-time server returns each result before the next dispatch)
        plan1 = auto_partition(graph, batch=1)
        prep1 = prepare_network_params(plan1, params)

        def sequential():
            for x in imgs:
                logits, _ = run_network(
                    jax.device_put(jnp.asarray(x)), prep1, plan=plan1
                )
                jax.block_until_ready(logits)

        seq_stats = _timed_stats_ms(sequential)
        seq_imgs_per_s = n_imgs / (seq_stats["p50_ms"] / 1e3)
        csv(
            f"serving_measured,{model},sequential_b1,"
            f"{seq_stats['p50_ms']:.1f},ms_per_{n_imgs}imgs,imgs_per_s,"
            f"{seq_imgs_per_s:.1f}"
        )
        rows: dict = {
            "input_size": graph.input_size,
            "n_imgs": n_imgs,
            "wallclock_reps": WALLCLOCK_REPS,
            "sequential_b1": {
                "wallclock_ms": seq_stats["p50_ms"],
                "wallclock_stats": seq_stats,
                "imgs_per_s": seq_imgs_per_s,
            },
        }

        # engine sweep: a single-bucket engine per size so every batch pads
        # to exactly that bucket (the warm-up rep absorbs plan + jit trace)
        for bucket in (1, 2, 4, 8):
            eng = ServingEngine(
                graph, params, ServeConfig(buckets=(bucket,))
            )

            def call(eng=eng):
                eng.serve(imgs)

            stats = _timed_stats_ms(call)
            entry = eng._entry(bucket)  # cached by the warm-up
            imgs_per_s = n_imgs / (stats["p50_ms"] / 1e3)
            rows[f"bucket{bucket}"] = {
                "wallclock_ms": stats["p50_ms"],
                "wallclock_stats": stats,
                "imgs_per_s": imgs_per_s,
                "slo_us": entry.slo_us,
                "steady_us": entry.steady_us,
            }
            csv(
                f"serving_measured,{model},bucket{bucket},"
                f"{stats['p50_ms']:.1f},ms_per_{n_imgs}imgs,imgs_per_s,"
                f"{imgs_per_s:.1f},slo_us,{entry.slo_us:.1f}"
            )
        speedup = rows["bucket8"]["imgs_per_s"] / seq_imgs_per_s
        rows["bucket8_speedup_vs_sequential"] = speedup
        # the acceptance row: only meaningful where interpret-mode wall
        # clock reflects batching (LeNet); big-model rows are context
        rows["bucket8_beats_sequential"] = bool(speedup > 1.0)
        csv(
            f"serving_measured_speedup,{model},bucket8_vs_sequential,"
            f"{speedup:.2f}x,beats_sequential,"
            f"{rows['bucket8_beats_sequential']}"
        )
        out[model] = rows
    return out


def _lenet_e2e(csv=print) -> dict:
    """End-to-end LeNet-5 through run_network: wall clock + skip fractions
    (the only zoo model cheap enough to execute at paper scale in interpret
    mode), then the same network at bf16 — wall clock, modeled HBM, and the
    max-abs logit error against the f32 run, alongside the documented
    tolerance (``bf16_logit_tol``) the CI smoke job enforces."""
    import jax
    import jax.numpy as jnp

    from repro.net.graph import lenet5
    from repro.net.partition import auto_partition
    from repro.net.runner import (
        bf16_logit_tol,
        init_network_params,
        prepare_network_params,
        run_network,
        skip_fractions,
    )

    graph = lenet5()
    raw = init_network_params(graph, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 1))

    plan = auto_partition(graph, batch=4)
    params = prepare_network_params(plan, raw)
    logits_f32, skips = run_network(x, params, plan=plan)  # + jit warm

    def call():
        logits, _ = run_network(x, params, plan=plan)
        jax.block_until_ready(logits)

    stats = _timed_stats_ms(call)
    dt_ms = stats["p50_ms"]
    frac = skip_fractions(skips)
    csv(f"lenet_e2e,auto_plan,interpret,{dt_ms:.1f},ms_per_batch4,"
        f"p95,{stats['p95_ms']:.1f}")

    plan16 = auto_partition(graph, batch=4, compute_dtype="bfloat16")
    params16 = prepare_network_params(plan16, raw)
    logits_b16, _ = run_network(x, params16, plan=plan16)  # jit warm

    def call16():
        logits, _ = run_network(x, params16, plan=plan16)
        jax.block_until_ready(logits)

    stats16 = _timed_stats_ms(call16)
    dt16_ms = stats16["p50_ms"]
    err = float(jnp.max(jnp.abs(
        logits_b16.astype(jnp.float32) - logits_f32
    )))
    tol = bf16_logit_tol(logits_f32)
    csv(f"lenet_e2e_bf16,auto_plan,interpret,{dt16_ms:.1f},ms_per_batch4,"
        f"max_abs_err,{err:.4f},tol,{tol:.4f}")
    # modeled_cycles rides alongside the wall clock so obs.report can join
    # this workload into the model-vs-measured drift table
    return {
        "hbm_bytes": plan.hbm_bytes(),
        "modeled_cycles": plan.modeled_cycles(),
        "wallclock_ms": dt_ms,
        "wallclock_stats": stats,
        "wallclock_reps": WALLCLOCK_REPS,
        "batch": 4,
        "skip_fractions": frac,
        "bf16": {
            "hbm_bytes": plan16.hbm_bytes(),
            "modeled_cycles": plan16.modeled_cycles(),
            "wallclock_ms": dt16_ms,
            "wallclock_stats": stats16,
            "max_abs_err": err,
            "logit_tol": tol,
        },
    }


def _guard_overhead(csv=print) -> dict:
    """Guarded-runtime cost (DESIGN.md §13): the LeNet e2e workload run
    unguarded (jit fast path) vs under ``guarding()`` — the wall-clock
    delta is ``guard_overhead_pct`` — plus the fallback counts of the
    clean guarded run (all-clean expected) and of a squeezed run that
    forces the replan rung.  All rows are ungated stats context: wall
    clocks are never part of the regression gate."""
    import jax

    from repro.net.graph import lenet5
    from repro.net.partition import auto_partition
    from repro.net.runner import (
        init_network_params,
        prepare_network_params,
        run_network,
    )
    from repro.robust import GuardConfig, guarding, inject

    graph = lenet5()
    master = init_network_params(graph, jax.random.PRNGKey(0))
    plan = auto_partition(graph, batch=4)
    params = prepare_network_params(plan, master)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 1))

    def plain():
        logits, _ = run_network(x, params, plan=plan)
        jax.block_until_ready(logits)

    def guarded():
        with guarding(GuardConfig(), source_params=master):
            logits, _ = run_network(x, params, plan=plan)
        jax.block_until_ready(logits)

    stats_plain = _timed_stats_ms(plain)
    stats_guard = _timed_stats_ms(guarded)
    overhead_pct = (
        (stats_guard["p50_ms"] - stats_plain["p50_ms"])
        / stats_plain["p50_ms"] * 100.0
    )

    # clean-run fallback counts (expected empty) ...
    with guarding(GuardConfig(), source_params=master) as guard:
        logits, _ = run_network(x, params, plan=plan)
        jax.block_until_ready(logits)
    clean_counts = guard.last_report.fallback_counts()
    clean = guard.last_report.clean_launches
    launches = guard.last_report.launches

    # ... and a squeezed run demonstrating the replan rung end to end
    with guarding(GuardConfig(), source_params=master) as guard:
        with inject(seed=0) as inj:
            inj.squeeze_budget(0.002)
            logits, _ = run_network(x, params, plan=plan)
            jax.block_until_ready(logits)
    squeezed_counts = guard.last_report.fallback_counts()

    csv(
        f"guard_overhead,lenet_e2e,plain,{stats_plain['p50_ms']:.1f},"
        f"guarded,{stats_guard['p50_ms']:.1f},ms_per_batch4,"
        f"overhead_pct,{overhead_pct:.1f}"
    )
    csv(
        f"guard_fallbacks,lenet_e2e,clean,{clean}/{launches},"
        f"counts,{clean_counts},squeezed_counts,{squeezed_counts}"
    )
    return {
        "guard_overhead_pct": overhead_pct,
        "plain_ms": stats_plain["p50_ms"],
        "plain_stats": stats_plain,
        "guarded_ms": stats_guard["p50_ms"],
        "guarded_stats": stats_guard,
        "wallclock_reps": WALLCLOCK_REPS,
        "batch": 4,
        "clean_launches": clean,
        "launches": launches,
        "fallback_counts": clean_counts,
        "squeezed": {"factor": 0.002, "fallback_counts": squeezed_counts},
    }


def _kernel_micro(csv=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cnn_models import LENET5_FUSION
    from repro.core.executor import init_pyramid_params
    from repro.kernels.fused_conv.ops import fused_conv2
    from repro.kernels.online_sop.ops import online_sop_end

    out = {"wallclock_reps": WALLCLOCK_REPS}
    params = init_pyramid_params(LENET5_FUSION, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 1))
    args = (x, params.weights[0], params.biases[0], params.weights[1],
            params.biases[1])

    def call_conv():
        res, _ = fused_conv2(*args, spec=LENET5_FUSION, out_region=1)
        jax.block_until_ready(res)

    stats = _timed_stats_ms(call_conv)
    us = stats["p50_ms"] * 1e3
    csv(f"kernel_fused_conv_lenet,interpret,{us:.0f},us_per_call,"
        f"p95,{stats['p95_ms'] * 1e3:.0f}")
    out["fused_conv_lenet_us"] = us
    out["fused_conv_lenet_stats"] = stats

    xs = jnp.asarray(np.random.default_rng(0).uniform(-0.03, 0.03, (512, 25)),
                     jnp.float32)
    y = jnp.asarray(np.random.default_rng(1).uniform(-0.5, 0.5, (25,)),
                    jnp.float32) / 4

    def call_sop():
        s, _, _ = online_sop_end(xs, y, 16)
        jax.block_until_ready(s)

    stats = _timed_stats_ms(call_sop)
    us = stats["p50_ms"] * 1e3
    csv(f"kernel_online_sop_512x25,interpret,{us:.0f},us_per_call,"
        f"p95,{stats['p95_ms'] * 1e3:.0f}")
    out["online_sop_512x25_us"] = us
    out["online_sop_512x25_stats"] = stats
    return out


def _vgg_q4_fusion_delta(csv=print) -> dict:
    """Single-kernel VGG Q=4 (the variadic pyramid) vs the historical 2+2
    chained path: analytic HBM traffic at paper scale (224^2) and interpret-
    mode wall clock at reduced scale.  The chained path round-trips the
    block-1 output feature map through HBM; the single launch does not."""
    import dataclasses
    import jax

    from repro.core.cnn_models import VGG_FUSION
    from repro.core.executor import init_pyramid_params
    from repro.core.program import compile_program, pick_out_region
    from repro.kernels.fused_conv.ops import fused_pyramid_chain, plan_chunks

    out: dict = {}
    modes = [("single", {}), ("chained2", {"max_convs_per_chunk": 2})]
    traffic = {}
    for label, kwargs in modes:
        chunks = plan_chunks(VGG_FUSION, **kwargs)
        total = 0
        for ch in chunks:
            prog = compile_program(ch, pick_out_region(ch))
            total += prog.hbm_bytes(1)
        traffic[label] = total
        out[f"hbm_bytes_{label}"] = total
        csv(
            f"vgg_q4_hbm_traffic,{label},{len(chunks)}_launches,"
            f"{total},bytes"
        )
    saved = traffic["chained2"] - traffic["single"]
    csv(
        f"vgg_q4_hbm_traffic_delta,single_vs_chained2,{saved},bytes_saved,"
        f"{saved / traffic['chained2']:.1%},of_chained"
    )

    spec = dataclasses.replace(VGG_FUSION, input_size=32)
    params = init_pyramid_params(spec, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
    wall = {}
    out["wallclock_reps"] = WALLCLOCK_REPS
    for label, kwargs in modes:
        def call(kwargs=kwargs):
            y, _ = fused_pyramid_chain(
                x, params.weights, params.biases, spec=spec, **kwargs
            )
            jax.block_until_ready(y)

        stats = _timed_stats_ms(call)
        wall[label] = stats["p50_ms"]
        out[f"wallclock_ms_{label}"] = wall[label]
        out[f"wallclock_stats_{label}"] = stats
        csv(f"vgg_q4_wallclock,{label},interpret,{wall[label]:.1f},ms_per_call,"
            f"p95,{stats['p95_ms']:.1f}")
    csv(
        f"vgg_q4_wallclock_delta,single_vs_chained2,"
        f"{wall['chained2'] - wall['single']:.1f},ms_saved_per_call"
    )
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="analytic sections only: no kernel launches, no "
                         "digit-level simulation (CI smoke mode)")
    ap.add_argument("--out", default="BENCH_pyramid.json",
                    help="where to write the machine-readable results")
    args = ap.parse_args(argv)

    from benchmarks import intensity, paper_tables
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    bench: dict = {"dry_run": args.dry_run, "workloads": {}}

    print("== Tables 1-4: cycle models vs paper ==")
    paper_tables.run()
    print("== Figs 10-11: operational intensity ==")
    intensity.run()
    print("== whole-network partitions: auto vs paper vs layer-by-layer ==")
    bench["partition"] = _partition_comparison()
    print("== kernel dataflow: whole-image vs halo-tile HBM traffic ==")
    bench["kernel_dataflow"] = _kernel_dataflow(dry_run=args.dry_run)
    print("== serving: bucketed batching SLOs"
          + ("" if args.dry_run else " + measured throughput sweep") + " ==")
    bench["serving"] = _serving(dry_run=args.dry_run)

    if not args.dry_run:
        from benchmarks import end_savings

        print("== Figs 12-14: END savings ==")
        end_savings.run()
        print("== LeNet-5 end-to-end (run_network, interpret mode) ==")
        bench["workloads"]["lenet_e2e"] = _lenet_e2e()
        print("== guarded runtime: overhead + fallback counts ==")
        bench["workloads"]["guard_overhead"] = _guard_overhead()
        print("== kernels (interpret-mode wall time; TPU perf comes from the"
              " dry-run roofline) ==")
        bench["workloads"]["kernel_micro"] = _kernel_micro()
        print("== VGG Q=4: single-kernel fusion vs 2+2 chained (HBM traffic +"
              " latency) ==")
        bench["workloads"]["vgg_q4"] = _vgg_q4_fusion_delta()

    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
