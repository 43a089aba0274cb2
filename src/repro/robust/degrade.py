"""The graceful-degradation ladder: guarded ``run_network`` execution.

:func:`run_network_guarded` runs the same plan-driven forward loop as the
jit fast path (``repro.net.runner._forward``), eagerly, with each fused
launch wrapped in a bounded ladder of fallbacks.  Every rung trades
performance for the guarantee that the forward *finishes with correct
logits*; the bottom rung is the node-by-node reference path, which is
always available because its only requirements are the graph and finite
params.  The rungs, top to bottom:

1. **fused launch** — the planned Pallas launch, unchanged.
2. **interpret retry** — a compile/lowering/runtime failure retries the
   same launch once with ``interpret=True`` (the Mosaic-free Pallas
   interpreter; slow but immune to lowering bugs).
3. **replan** — a :class:`BudgetError` (the planned working set no longer
   fits, e.g. under simulated VMEM pressure) re-cuts the failing pyramid
   under a shrunken budget via
   :func:`repro.net.partition.replan_pyramid` — tighter cuts, a chain of
   smaller launches — up to ``GuardConfig.max_replans`` times, each retry
   shrinking the budget by ``budget_shrink``.
4. **reference quarantine** — a numeric-sentinel trip (NaN/Inf or
   magnitude blow-up in a launch output) or exhaustion of the rungs above
   quarantines the launch: the covered nodes are recomputed with the
   plain-op reference path, and the sentinel walk localizes the first
   offending level when the fault reproduces there.

A quarantined or replanned launch reports a neutral all-zeros END-skip map
for its pyramid key (shape ``(B, 1, 1, Q)``) so downstream skip accounting
stays well-formed; the real per-sub-launch skip fractions ride in the
:class:`RunReport` event detail.

Every fallback is recorded twice: as a :class:`FallbackEvent` in the
returned report (stored on ``guard.last_report``) and — when a tracer is
installed — as an ``obs`` ``"degrade"`` trace event, so the drift report
and Perfetto timeline show *where* the run left the fast path.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from .errors import BudgetError, NumericError
from .guard import sentinel_stats, sentinel_trips


@dataclass(frozen=True)
class FallbackEvent:
    """One rung taken: which launch degraded, to what, and why."""

    launch: str
    rung: str  # "heal" | "interpret" | "replan" | "reference" | "reference_full"
    reason: str
    detail: dict = field(default_factory=dict)

    def describe(self) -> str:
        extra = ", ".join(f"{k}={v}" for k, v in self.detail.items())
        return (
            f"{self.launch}: -> {self.rung} ({self.reason})"
            + (f" [{extra}]" if extra else "")
        )


@dataclass
class RunReport:
    """What one guarded forward did: rungs taken, launches run clean."""

    model: str = ""
    batch: int = 0
    compute_dtype: str = ""
    launches: int = 0
    clean_launches: int = 0
    events: list = field(default_factory=list)

    @property
    def degraded(self) -> bool:
        return bool(self.events)

    def fallback_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for e in self.events:
            counts[e.rung] = counts.get(e.rung, 0) + 1
        return counts

    def summary(self) -> str:
        head = (
            f"guarded run[{self.model}] batch={self.batch}"
            f" dtype={self.compute_dtype}: {self.clean_launches}/"
            f"{self.launches} launches clean"
        )
        if not self.events:
            return head + ", no fallbacks"
        lines = [head] + [f"  {e.describe()}" for e in self.events]
        return "\n".join(lines)


def _zero_skip(batch: int, q_convs: int) -> jnp.ndarray:
    # neutral END-skip map for a launch that did not run fused: nothing
    # skipped, one grid cell per level slot
    return jnp.zeros((batch, 1, 1, q_convs), dtype=jnp.int32)


def _reference_walk(x_in, pyr, graph, params, jdt, magnitude_limit=None):
    """Recompute a pyramid's covered nodes with the plain-op reference path.

    Returns ``(y, first_bad_level)`` where ``first_bad_level`` is the index
    (within the pyramid's conv levels) whose output first trips the
    sentinel, or ``None`` when the recompute is clean — i.e. the original
    fault did not reproduce and was the kernel execution itself.
    """
    from repro.net.runner import _conv_node, _head_op, _pool_node

    y = x_in
    level = -1
    first_bad = None
    for nm in pyr.node_names:
        n = graph.node(nm)
        if n.op in ("conv", "depthwise"):
            level += 1
            w, b = params[nm]
            y = _conv_node(y, n, w.astype(jdt), b.astype(jdt))
        elif n.op == "pool":
            y = _pool_node(y, n)
        else:  # an epilogue node the level absorbed
            y = _head_op({n.inputs[0]: y}, n, params, graph)
        if first_bad is None:
            if sentinel_trips(sentinel_stats(y), magnitude_limit) is not None:
                first_bad = level
    return y, first_bad


def _run_subplan(x_in, subs, params, graph, cdt, *, end_skip, interpret,
                 vmem_budget):
    """Execute a replanned pyramid chain: each sub-pyramid as its own fused
    launch."""
    from repro.kernels.fused_conv.ops import fused_pyramid
    from repro.net.runner import pyramid_operands

    y = x_in
    sub_skips = {}
    for sp in subs:
        y, sk = fused_pyramid(
            y,
            *pyramid_operands(graph, sp.node_names, params),
            spec=sp.spec,
            out_region=sp.launch.out_region,
            streamed=sp.launch.streamed,
            w_slots=sp.launch.w_slots if sp.launch.streamed else None,
            x_slots=sp.launch.x_slots,
            c_tiles=sp.launch.c_tiles,
            end_skip=end_skip,
            interpret=interpret,
            vmem_budget=vmem_budget,
            compute_dtype=cdt,
            name=sp.name,
        )
        sub_skips[sp.name] = sk
    return y, sub_skips


def _skip_fracs(sub_skips: dict) -> dict[str, list[float]]:
    return {
        name: [float(f) for f in
               np.asarray(s, dtype=np.float64).mean(axis=(0, 1, 2))]
        for name, s in sub_skips.items()
    }


def run_network_guarded(
    x,
    params,
    *,
    plan,
    end_skip: bool = True,
    interpret: bool | None = None,
    dtype: str | None = None,
    guard=None,
):
    """Guarded twin of :func:`repro.net.runner.run_network`.

    Same signature and return contract ``(logits, skips)``; runs eagerly
    (launch by launch, like the traced path) with preflight validation up
    front, the fault injector consulted at each stage boundary, numeric
    sentinels on every launch output, and the degradation ladder answering
    failures.  The :class:`RunReport` lands on ``guard.last_report``.
    """
    from repro.net.runner import _forward, prepare_network_params
    from repro.obs.trace import get_tracer

    from .faults import get_injector
    from .guard import get_guard
    from .validate import nonfinite_param_nodes, preflight

    guard = get_guard() if guard is None else guard
    cfg = guard.config
    injector = get_injector()
    tracer = get_tracer()
    graph = plan.graph
    batch = int(x.shape[0])
    report = RunReport(model=graph.name, batch=batch,
                       launches=plan.n_launches())

    def record(event: FallbackEvent) -> None:
        report.events.append(event)
        if tracer.enabled:
            tracer.record_event(
                "degrade", model=graph.name, launch=event.launch,
                rung=event.rung, reason=event.reason, **event.detail,
            )

    # -- preflight (with one bounded healing attempt) -----------------------
    if cfg.preflight:
        try:
            cdt = preflight(x, params, plan=plan, dtype=dtype)
        except NumericError as e:
            if not (cfg.heal_params and guard.source_params is not None):
                raise
            healed = prepare_network_params(plan, guard.source_params, dtype)
            still_bad = nonfinite_param_nodes(healed)
            if still_bad:
                raise NumericError(
                    "params still non-finite after reloading from source;"
                    " the master copy is corrupt too",
                    nodes=still_bad,
                ) from e
            record(FallbackEvent(
                launch="<preflight>", rung="heal",
                reason="non-finite params reloaded from source",
                detail={"nodes": e.context.get("nodes", [])},
            ))
            params = healed
            cdt = preflight(x, params, plan=plan, dtype=dtype)
    else:
        from repro.core.dtypes import canonical_dtype

        cdt = canonical_dtype(plan.compute_dtype if dtype is None else dtype)
    from repro.core.dtypes import jnp_dtype

    jdt = jnp_dtype(cdt)
    report.compute_dtype = cdt

    # the effective budget a launch must fit at run time: the plan's own
    # budget scaled by any injected VMEM squeeze
    effective_budget = int(plan.vmem_budget * injector.vmem_factor)

    def reference_rung(pyr, x_in, reason, detail=None):
        y, bad_level = _reference_walk(
            x_in, pyr, graph, params, jdt, cfg.magnitude_limit
        )
        d = dict(detail or {})
        d["level"] = bad_level if bad_level is not None else "kernel-only"
        record(FallbackEvent(
            launch=pyr.name, rung="reference", reason=reason, detail=d,
        ))
        if bad_level is not None:
            # the fault reproduces in the reference math: the data/params
            # themselves blow up at that level — not recoverable by any
            # execution path
            raise NumericError(
                f"launch {pyr.name}: level {bad_level} output is non-finite"
                " (or over the magnitude limit) even on the reference path",
                launch=pyr.name, level=bad_level,
            )
        return y, _zero_skip(batch, pyr.q_convs)

    def replan_rung(pyr, call, x_in, reason):
        from repro.net.partition import replan_pyramid

        budget = effective_budget
        for attempt in range(cfg.max_replans):
            try:
                subs = replan_pyramid(
                    graph, pyr, vmem_budget=budget, batch=batch,
                    compute_dtype=cdt,
                )
                bad = [sp.name for sp in subs
                       if sp.launch.vmem_bytes() > budget]
                if bad:
                    raise BudgetError(
                        f"replan of {pyr.name} still exceeds"
                        f" {budget} bytes", launch=bad[0],
                    )
                y, sub_skips = _run_subplan(
                    x_in, subs, params, graph, cdt, end_skip=end_skip,
                    interpret=interpret, vmem_budget=budget,
                )
                record(FallbackEvent(
                    launch=pyr.name, rung="replan", reason=reason,
                    detail={
                        "attempt": attempt + 1,
                        "budget": budget,
                        "sub_launches": [sp.name for sp in subs],
                        "sub_skip_fractions": _skip_fracs(sub_skips),
                    },
                ))
                return y, _zero_skip(batch, pyr.q_convs)
            except (BudgetError, ValueError):
                budget = int(budget * cfg.budget_shrink)
        return reference_rung(
            pyr, x_in, f"replan exhausted after {cfg.max_replans} attempts",
            detail={"original_reason": reason},
        )

    def guarded_wrapper(pyr, call, x_in):
        # -- plan stage: injected faults + the run-time budget check -------
        try:
            injector.fire("plan", pyr.name)
            vmem = pyr.launch.vmem_bytes()
            if vmem > effective_budget:
                raise BudgetError(
                    f"launch {pyr.name} needs {vmem} bytes,"
                    f" {effective_budget} available",
                    launch=pyr.name, vmem_bytes=vmem,
                    vmem_budget=effective_budget,
                )
        except BudgetError as e:
            return replan_rung(pyr, call, x_in, str(e))
        except Exception as e:  # injected plan fault
            return reference_rung(pyr, x_in, f"plan stage failed: {e}")

        # -- compile/run stages: fused launch, one interpret retry ---------
        try:
            injector.fire("compile", pyr.name)
            injector.fire("run", pyr.name)
            y, skip = call()
        except BudgetError as e:
            return replan_rung(pyr, call, x_in, str(e))
        except Exception as first:
            try:
                injector.fire("compile", pyr.name)
                injector.fire("run", pyr.name)
                y, skip = call(interpret=True)
                record(FallbackEvent(
                    launch=pyr.name, rung="interpret",
                    reason=f"launch failed: {first}",
                ))
            except Exception as second:
                return reference_rung(
                    pyr, x_in,
                    f"interpret retry failed too: {second}",
                    detail={"first_error": str(first)},
                )
            else:
                y = injector.corrupt_output(pyr.name, y)
                if cfg.sentinel:
                    trip = sentinel_trips(
                        sentinel_stats(y), cfg.magnitude_limit
                    )
                    if trip is not None:
                        return reference_rung(
                            pyr, x_in, f"sentinel tripped: {trip}"
                        )
                return y, skip

        # -- numeric sentinel on the clean fused output --------------------
        y = injector.corrupt_output(pyr.name, y)
        if cfg.sentinel:
            trip = sentinel_trips(sentinel_stats(y), cfg.magnitude_limit)
            if trip is not None:
                return reference_rung(
                    pyr, x_in, f"sentinel tripped: {trip}"
                )
        report.clean_launches += 1
        return y, skip

    logits, skips = _forward(
        x, params, plan=plan, end_skip=end_skip, interpret=interpret,
        cdt=cdt, launch_wrapper=guarded_wrapper,
    )

    # -- final logits sentinel: faults in the plain-op head ----------------
    if cfg.sentinel:
        trip = sentinel_trips(sentinel_stats(logits), None)
        if trip is not None:
            from repro.net.runner import reference_network

            logits = reference_network(x.astype(jdt), graph, params)
            record(FallbackEvent(
                launch="<head>", rung="reference_full",
                reason=f"logits sentinel tripped: {trip}",
            ))
            if sentinel_trips(sentinel_stats(logits), None) is not None:
                raise NumericError(
                    "logits are non-finite even on the full reference path",
                    launch="<head>",
                )

    if tracer.enabled:
        tracer.record_event(
            "guarded_run", model=graph.name, batch=batch, compute_dtype=cdt,
            launches=report.launches, clean_launches=report.clean_launches,
            fallbacks=report.fallback_counts(),
        )
    guard.last_report = report
    return logits, skips
