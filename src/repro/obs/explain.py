"""``python -m repro.obs.explain`` — show what the planner decided and why.

Prints the auto-partition of a zoo model as a per-launch table (covered
nodes, Q, grid, level-0 form, regime, plan knobs, modeled HBM/VMEM bytes
with budget headroom, modeled cycles, each level's taps per MXU pass and
its kind, activation and epilogue: ``relu``, ``linear``, ``gelu``, with
``dw:`` before a depthwise level and ``+ln`` / ``+scale`` after a
LayerNorm / layer scale), the pyramids whose level 0 runs in patch form,
and the plan build's conv-MAC counters (all conv MACs, those in pyramids of
two or more levels, and those of levels run two or more taps a pass; for a
model with depthwise levels, their MACs and those chained to the next
level in their launch), and optionally:

* ``--trace out.json`` — export a Chrome-trace / Perfetto JSON of every
  launch's modeled fill/steady/drain DMA-vs-MXU timeline
  (:mod:`repro.obs.timeline`); with ``--run`` the measured launch spans
  ride alongside.
* ``--run`` — execute the plan launch by launch
  (:func:`~repro.net.runner.run_network_per_launch`: one warm-up then
  ``--reps`` timed forwards) and print the model-vs-measured drift table
  (:mod:`repro.obs.report`).
* ``--guard`` — execute the plan under the guarded runtime
  (:mod:`repro.robust`, DESIGN.md §13) and print the fallback table: which
  launches ran clean and which rung of the degradation ladder each
  degraded launch took.  ``--squeeze F`` simulates VMEM pressure (budget
  scaled by F) so the replan rung is demonstrable from the CLI.

Examples::

    PYTHONPATH=src python -m repro.obs.explain --model vgg16
    PYTHONPATH=src python -m repro.obs.explain --model lenet --trace t.json
    PYTHONPATH=src python -m repro.obs.explain --model resnet18 \\
        --dtype bfloat16 --run --trace t.json
    PYTHONPATH=src python -m repro.obs.explain --model lenet \\
        --guard --squeeze 0.08

Big models default to the same reduced interpret-friendly input sizes as
``examples/fused_cnn_inference.py`` when run; the *plan table* is always
computed at the requested (default paper) scale.
"""

from __future__ import annotations

import argparse
import sys

from repro.core.cycle_model import DEFAULT_PARAMS

# interpret-friendly --run scales (paper scale for LeNet only); the table
# itself defaults to paper scale via the graph builders
RUN_SIZE = {"lenet": 32, "alexnet": 67, "vgg16": 32, "resnet18": 32,
            "resnet50": 32, "convnext_tiny": 32}


def _fmt_bytes(n: int) -> str:
    return f"{n / 1024:,.0f}K" if n < 32 * 1024 * 1024 else f"{n / 2**20:,.1f}M"


def level_label(lvl) -> str:
    """A level's kind, activation and epilogue as the plan table prints
    it: ``relu``, ``linear`` or ``gelu``, ``dw:`` before a depthwise
    level's, ``+ln`` and ``+scale`` after."""
    parts = ["relu" if lvl.relu else "gelu" if lvl.gelu else "linear"]
    parts += ["ln"] * (lvl.norm_eps is not None) + ["scale"] * lvl.scale
    return ("dw:" if lvl.kind == "depthwise" else "") + "+".join(parts)


def plan_table(plan, vmem_budget: int, out=print) -> None:
    """Render a PartitionPlan as one row per launch (the tabular twin of the
    trace's span schema)."""
    out(
        f"{'launch':<26} {'nodes':>5} {'Q':>2} {'grid':>6} {'region':>6} "
        f"{'L0':<6} {'regime':<16} {'x/w/c':>6} {'hbm':>9} {'vmem':>9} "
        f"{'headroom':>9} {'cycles':>10} {'us':>9}  {'taps/pass':<12} "
        "activations"
    )
    for p in plan.pyramids:
        d = p.launch.describe(plan.batch, vmem_budget)
        out(
            f"{p.name:<26} {len(p.node_names):>5} {d['q_convs']:>2} "
            f"{d['alpha']}x{d['alpha']:<4} {d['out_region']:>6} "
            f"{'patch' if d['patch'] else 'direct':<6} {d['regime']:<16} "
            f"{d['x_slots']}/{d['w_slots']}/{d['c_tiles']:<2} "
            f"{_fmt_bytes(d['hbm_bytes']):>9} "
            f"{_fmt_bytes(d['vmem_bytes']):>9} "
            f"{_fmt_bytes(d['vmem_headroom_bytes']):>9} "
            f"{d['modeled_cycles']:>10,} "
            f"{d['modeled_cycles'] / DEFAULT_PARAMS.freq_mhz:>9,.1f}  "
            + f"{' '.join(map(str, p.launch.program.folds())):<12} "
            + " ".join(level_label(lvl)
                       for lvl in p.spec.levels if lvl.has_weights)
        )
    out(
        f"total: {plan.n_launches()} launches, "
        f"{plan.hbm_bytes():,} modeled HBM bytes, "
        f"{plan.modeled_cycles():,} modeled cycles "
        f"({plan.modeled_cycles() / DEFAULT_PARAMS.freq_mhz:,.1f} us at "
        f"{DEFAULT_PARAMS.freq_mhz:g} MHz)"
    )


def serve_table(summary: dict, out=print) -> None:
    """Render a serving engine's :meth:`~repro.net.serve.ServingEngine.summary`
    as the bucket/SLO/throughput table: one row per bucket, modeled columns
    (launches, SLO, steady-state) next to measured (p50/p95, imgs/s), then
    the cache lines and — when the summary carries CLI wave deltas — the
    per-wave plan/jit reuse proof."""
    out(
        f"serving {summary['model']} dtype={summary['compute_dtype']}"
        + (" [guarded]" if summary.get("guarded") else "")
        + f": {summary['completed']} completed, {summary['rejected']}"
        f" rejected, {summary['imgs_per_s']:,.1f} imgs/s overall"
    )
    out(
        f"{'bucket':>6} {'batches':>7} {'reqs':>5} {'imgs':>5} "
        f"{'launches':>8} {'slo_us':>10} {'steady_us':>10} "
        f"{'p50_ms':>9} {'p95_ms':>9} {'imgs/s':>9}"
    )
    for row in summary["buckets"]:
        out(
            f"{row['bucket']:>6} {row['batches']:>7} {row['requests']:>5} "
            f"{row['images']:>5} "
            f"{row.get('launches', '-'):>8} "
            + (f"{row['slo_us']:>10,.1f} " if "slo_us" in row
               else f"{'-':>10} ")
            + (f"{row['steady_us']:>10,.1f} " if "steady_us" in row
               else f"{'-':>10} ")
            + f"{row['p50_ms']:>9,.2f} {row['p95_ms']:>9,.2f} "
            f"{row['imgs_per_s']:>9,.1f}"
        )
    cache = summary["cache"]
    out(
        f"plan cache: serve {cache['serve']['hits']}h/"
        f"{cache['serve']['misses']}m/{cache['serve']['evictions']}e "
        f"({cache['serve']['currsize']}/{cache['serve']['maxsize']}), "
        f"partition {cache['partition']['hits']}h/"
        f"{cache['partition']['misses']}m/"
        f"{cache['partition']['evictions']}e, "
        f"jit traces {cache['jit_traces']}"
    )
    res = summary.get("resilience")
    if res is not None:
        counters = {
            k: v for k, v in res.items()
            if k != "breakers" and v
        }
        breakers = res.get("breakers") or {}
        active = {
            b: s for b, s in breakers.items()
            if s["transitions"] or s["state"] != "closed"
        }
        if counters or active:
            out(
                "resilience: "
                + ", ".join(f"{k}={v}" for k, v in counters.items())
                if counters else "resilience:"
            )
            for b, s in sorted(active.items(), key=lambda kv: int(kv[0])):
                pin = f" pinned={s['pinned_rung']}" if s["pinned_rung"] else ""
                out(
                    f"  breaker bucket {b}: {s['state']}"
                    f" ({s['opens']} opens, {s['transitions']} transitions,"
                    f" {s['failures']}/{s['threshold']} failures){pin}"
                )
    for i, wave in enumerate(summary.get("waves", []), start=1):
        out(
            f"wave {i}: +{wave['serve_misses']} plans, "
            f"+{wave['jit_traces']} jit traces, "
            f"{wave['serve_hits']} serve cache hits, "
            f"{wave['partition_misses']} partition misses "
            f"({wave['wall_s']:.2f}s)"
        )


def fallback_table(report, out=print) -> None:
    """Render a guarded run's :class:`~repro.robust.degrade.RunReport`:
    one row per fallback event, plus the degraded-plan detail (the chained
    sub-launches a replan substituted for the planned launch)."""
    out(
        f"guarded: {report.clean_launches}/{report.launches} launches clean"
        + (
            f", fallbacks {report.fallback_counts()}"
            if report.degraded else ", no fallbacks"
        )
    )
    if not report.degraded:
        return
    out(f"{'launch':<26} {'rung':<12} reason")
    for e in report.events:
        out(f"{e.launch:<26} {e.rung:<12} {e.reason}")
        subs = e.detail.get("sub_launches")
        if subs:
            out(
                f"{'':<26} {'':<12} degraded plan: "
                + " -> ".join(subs)
                + f" (budget {_fmt_bytes(e.detail['budget'])})"
            )


def main(argv: list[str] | None = None) -> int:
    from repro.core.program import VMEM_BUDGET_BYTES
    from repro.net.graph import MODELS
    from repro.net.partition import (
        CHAINED_CONV_MACS,
        CHAINED_DW_MACS,
        CONV_MACS,
        DW_MACS,
        FOLDED_CONV_MACS,
        PATCH_LEVELS,
        auto_partition,
        partition_cache_info,
    )
    from repro.obs.trace import get_tracer

    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--model", choices=sorted(MODELS), default="lenet")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32")
    ap.add_argument("--input-size", type=int, default=None,
                    help="spatial input size (default: the model's paper "
                         "scale; --run defaults to a reduced scale instead)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--vmem-budget", type=int, default=VMEM_BUDGET_BYTES)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Perfetto/chrome://tracing JSON of the "
                         "modeled (and, with --run, measured) timelines")
    ap.add_argument("--run", action="store_true",
                    help="execute the plan launch by launch, timing each "
                         "launch, and report model-vs-measured drift")
    ap.add_argument("--reps", type=int, default=3,
                    help="traced forwards after the warm-up (with --run)")
    ap.add_argument("--guard", action="store_true",
                    help="execute the plan under the guarded runtime and "
                         "print the fallback table (DESIGN.md §13)")
    ap.add_argument("--squeeze", type=float, default=None, metavar="F",
                    help="with --guard: simulate VMEM pressure by scaling "
                         "the budget by F (0 < F <= 1) via the fault "
                         "injector, demonstrating the replan rung")
    args = ap.parse_args(argv)

    size = args.input_size
    if size is None and (args.run or args.guard):
        size = RUN_SIZE[args.model]
    kwargs = {"compute_dtype": args.dtype}
    if size is not None:
        kwargs["input_size"] = size
    graph = MODELS[args.model](**kwargs)

    tracer = get_tracer()
    counters = (PATCH_LEVELS, CONV_MACS, CHAINED_CONV_MACS, FOLDED_CONV_MACS,
                DW_MACS, CHAINED_DW_MACS)
    before = [tracer.counters.get(c, 0) for c in counters]
    plan = auto_partition(
        graph, batch=args.batch, vmem_budget=args.vmem_budget
    )
    built, macs, chained, folded, dw, dw_chained = (
        tracer.counters.get(c, 0) - b for c, b in zip(counters, before)
    )
    print(
        f"{graph.name}: input {graph.input_size}x{graph.input_size}, "
        f"batch {args.batch}, dtype {plan.compute_dtype}, "
        f"VMEM budget {_fmt_bytes(args.vmem_budget)}"
    )
    plan_table(plan, args.vmem_budget)
    patch = [p.name for p in plan.pyramids if p.launch.program.patch]
    print(
        f"level 0 in patch form: {', '.join(patch) or 'none'} "
        f"({PATCH_LEVELS} +{built} for this plan's build)"
    )
    share = f" ({100 * chained / macs:.1f}% chained)" if macs else ""
    fshare = f" ({100 * folded / macs:.1f}% folded)" if macs else ""
    print(
        f"conv MACs per image: {CONV_MACS} +{macs:,}, "
        f"{CHAINED_CONV_MACS} +{chained:,}{share}, "
        f"{FOLDED_CONV_MACS} +{folded:,}{fshare}"
    )
    if dw:
        print(
            f"depthwise MACs per image: {DW_MACS} +{dw:,}, "
            f"{CHAINED_DW_MACS} +{dw_chained:,} "
            f"({100 * dw_chained / dw:.1f}% chained)"
        )
    info = partition_cache_info()
    print(
        f"partition cache: {info.hits} hits / {info.misses} misses "
        f"({info.currsize} plans cached)"
    )

    if args.guard:
        import contextlib

        import jax

        from repro.net.runner import (
            init_network_params,
            prepare_network_params,
            run_network,
        )
        from repro.robust import GuardConfig, guarding, inject

        master = init_network_params(graph, jax.random.PRNGKey(0))
        params = prepare_network_params(plan, master)
        x = jax.random.normal(
            jax.random.PRNGKey(1),
            (args.batch, graph.input_size, graph.input_size,
             graph.in_channels),
        )
        squeeze = contextlib.nullcontext()
        if args.squeeze is not None:
            squeeze = inject(seed=0)
        print("\nguarded run"
              + (f" (VMEM squeezed x{args.squeeze})" if args.squeeze
                 is not None else ""))
        with guarding(GuardConfig(), source_params=master) as guard:
            with squeeze as inj:
                if inj is not None:
                    inj.squeeze_budget(args.squeeze)
                logits, _ = run_network(x, params, plan=plan)
        jax.block_until_ready(logits)
        fallback_table(guard.last_report)

    collector = None
    if args.run:
        import jax

        from repro.net.runner import (
            init_network_params,
            prepare_network_params,
            run_network_per_launch,
            skip_fractions,
        )
        from repro.obs.report import (
            drift_report,
            drift_rows_from_spans,
            format_report,
        )
        from repro.obs.trace import TraceCollector

        params = prepare_network_params(
            plan, init_network_params(graph, jax.random.PRNGKey(0))
        )
        x = jax.random.normal(
            jax.random.PRNGKey(1),
            (args.batch, graph.input_size, graph.input_size,
             graph.in_channels),
        )
        # warm-up: compiles every launch the timed forwards will run
        logits, _ = run_network_per_launch(
            x, params, plan=plan, collector=TraceCollector()
        )
        jax.block_until_ready(logits)
        print(f"\nrunning {args.reps} launch-by-launch forwards "
              f"(interpret={jax.default_backend() != 'tpu'}) ...")
        collector = TraceCollector()
        for _ in range(args.reps):
            _, skips = run_network_per_launch(
                x, params, plan=plan, collector=collector
            )
        frac = skip_fractions(skips)
        for name, f in frac.items():
            if any(v > 0 for v in f):
                print(f"END skips {name}: "
                      + ", ".join(f"L{i}={v:.0%}" for i, v in enumerate(f)))
        print()
        format_report(drift_report(drift_rows_from_spans(collector.spans)))

    if args.trace:
        from repro.obs.timeline import chrome_trace, write_chrome_trace

        trace = chrome_trace(
            collector,
            launches=[(p.name, p.launch) for p in plan.pyramids],
        )
        write_chrome_trace(args.trace, trace)
        print(f"\nwrote {args.trace} "
              f"({len(trace['traceEvents'])} events — load in "
              "ui.perfetto.dev or chrome://tracing)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
