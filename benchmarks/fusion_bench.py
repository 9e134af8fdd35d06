"""Paper Fig. 13 (WSP/NWR/RADIUS) + Fig. 14/Table 3 (DRR/Trust/RDS):
fused vs unfused edge-work ratio and wall time, weighted and unweighted
graphs — including the direction-optimized pallas engine with kernel-launch
counting and push/pull direction accounting.

For the pallas engine extra columns track the execution layer (DESIGN.md
§2/§7): ``launches`` is the number of ``pallas_call``s appearing in the
traced program per engine iteration (a direction-optimized round traces one
pull and one push sweep; exactly one executes per iteration), ``push/pull``
the runtime per-direction iteration counts, and ``seed_sweeps`` the
per-iteration sweep count of the pre-fusion execution model (one launch per
lex level per plan, plus one has-pred probe per component on pull− rounds).

``--engines pallas`` additionally benchmarks the direction switch itself on
the frontier workloads (BFS/SSSP): total edge work and sweep executions of
the adaptive engine vs the pull-only engine — the quantity the
direction-optimized engine must keep ≤ pull — and writes machine-readable
``BENCH_pallas.json`` so the perf trajectory is tracked across PRs.

``--engines pallas`` also runs the push-resolution section (DESIGN.md §10):
the adaptive engine with the dst-sorted segment resolution vs the
reference full-rectangle scatter on the frontier workloads — resolution
edge work (Σ nnz of the resolution tiles actually processed vs
push_iters·rectangle), traced launches per class, and wall time.  The
gated property is frontier-proportionality: sorted resolution work must
stay strictly under the scatter rectangle whenever push iterations ran,
the sorted/scatter work ratio must not regress vs the baseline, and the
permutation gather must read only real slots — ``gather_work`` (one slot
list of the dst-major rectangle per push iteration) strictly under
``push_iters · n_pad · width`` with the scatter path reporting exactly 0
(it performs no permutation gather).

``--engines pallas`` also runs the batched-throughput section (DESIGN.md
§9): a B-source sweep of one query shape served sequentially (the source
is a traced executor argument, so the sweep must hold ONE executor-cache
entry and re-trace nothing after the first query) against one
``run_program_batch`` vmapped launch (B queries per launch).  Gated
quantities: executor-cache entries of the sequential sweep (the
retrace-per-source regression this section exists for) and traced launch
counts, never wall time.

``--engines pallas`` also runs the sharded section (DESIGN.md §11) when the
process has ≥ 2 devices (CI forces host devices via XLA_FLAGS): the
``pallas_sharded`` engine on a 2-shard mesh vs the single-device engine on
BFS/SSSP/PageRank — per-shard edge work and traced launches, cross-shard
combine counts, and the compositional invariant that the global direction
switch keeps the sharded fixpoint on the single-device iteration sequence
(values bitwise-equal for the idempotent workloads, asserted in-bench).
The section also compares the sharded engine's default per-shard sorted
resolution against the per-shard scatter oracle: both must agree on
values, and sorted resolve work must stay strictly under the scatter
rectangle whenever push iterations ran.

``--engines pallas`` also runs the guard-overhead section (DESIGN.md §12):
default guarded execution (validation, termination precondition, divergence
sentinel, convergence check) vs guards-off on BFS/SSSP/PageRank.  The
guards are free at the fixpoint level, so the gated quantities are
deterministic: bitwise values, identical iterations/edge work, and traced
launches guarded ≤ guards-off.

``--engines pallas`` also runs the serving section (DESIGN.md §13): the
continuous-batching analytics service (``repro.launch.service``) driven by
a seeded open-loop arrival trace — mixed BFS/SSSP sweep queries through
the fixed-slot chunked batch lanes plus scalar radius/drr queries paired
via ``fusion.fuse_many``.  The scheduler runs on a virtual clock, so the
serving metrics (queries-per-launch, batch occupancy, launch/fused-round
counts, executor-cache entries, virtual p50/p99 latency and queries/sec)
are a deterministic function of the seed; every served answer is asserted
bitwise-equal to a solo ``run_program`` in-bench.  Wall-clock latency is
reported, never gated.

``--engines pallas`` also runs the planner section (DESIGN.md §14): the
query planner's default ``ExecutionPlan`` vs the same knobs pinned
explicitly (the historical kwarg surface) on BFS/SSSP/PageRank.  The gated
properties are deterministic: planned and pinned runs must produce
bitwise-identical values with identical iteration counts and edge work
(default plans reproduce the documented heuristics exactly), the planner
must add ZERO traced launches and zero executor-cache entries (planning is
a host-side cache lookup, invisible to the compiled program), and the
recorded-stats feedback cache must hold an entry per benched query shape.

``--engines pallas`` also runs the incremental section (DESIGN.md §15):
a small seeded insert-only perturbation (~0.5% of |E|) of the R-MAT graph,
then the delta-seeded warm-started fixpoint vs a cold full recompute on
the mutated graph, on the idempotent workloads (BFS/SSSP/CC).  Everything
gated is deterministic on the seeded trace: the answers must be
bitwise-equal (asserted in-bench — GraFS Def. 2 makes warm+delta exact for
idempotent insert-only batches), delta edge work must stay strictly under
the full recompute's, the planner must resolve ``incremental="delta"`` for
the small batch, and the patch-vs-rebuild layout counts are recorded so
the baseline gates the in-place ELL patch staying engaged.  Wall time is
reported, never gated.

``--baseline PATH`` reads a committed ``BENCH_pallas.json`` (before the
fresh run, which is never written over it) and fails (exit 1) if the fresh
run regresses on traced launches, the fused/unfused edge-work ratio, the
push-vs-pull work advantage, the resolution section's gather/resolve-work
bounds, the batched executor/retrace counts, the sharded engine's
iteration parity / launch / combine / resolution-work counts, the guard
section's launch parity, the serving section's queries-per-launch /
launch / fused-round / cache-entry counts, or the incremental section's
delta-vs-full edge-work ratio and patch-vs-rebuild layout counts — the one
comparison path shared by the CI bench-smoke gate and local runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):           # `python benchmarks/fusion_bench.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    try:
        import repro                    # noqa: F401  (pip install -e .)
    except ImportError:                 # fall back to the source tree
        sys.path.insert(0, os.path.join(_root, "src"))

from benchmarks.common import BENCH_GRAPHS, emit, timed
from repro.core import engine, fusion
from repro.core import usecases as U
from repro.core.iterate import plan_idempotent
from repro.core.plan import _plan_levels

SIMPLE = ["WSP", "NWR", "RADIUS"]
MULTI = ["DRR", "Trust", "RDS"]
DIRECTION = ["BFS", "SSSP"]             # sparse-frontier direction workloads
RESOLUTION = ["BFS", "SSSP"]            # push-resolution (sorted vs scatter)
BATCHED = ["BFS", "SSSP"]               # single-source batched-query sweeps
SHARDED = ["BFS", "SSSP", "PR"]         # shard_map composition (PR = direct
                                        # PageRank, the epilogue pull− round)
GUARDED = ["BFS", "SSSP", "PR"]         # guarded vs guards-off execution
                                        # (validation + divergence sentinel)
SERVING = ["MIX"]                       # open-loop serving traces (the MIX
                                        # trace: BFS/SSSP sweeps + fused
                                        # radius/drr scalars)
PLANNER = ["BFS", "SSSP", "PR"]         # planned vs pinned-knob execution
                                        # (the ExecutionPlan default-parity
                                        # and zero-overhead contract)
INCREMENTAL = ["BFS", "SSSP", "CC"]     # delta-vs-full over a mutating
                                        # graph (idempotent rounds only:
                                        # bitwise parity is the contract)
_BATCHED_SPECS = {"BFS": U.bfs, "SSSP": U.sssp}
_BATCH_B = 8                            # sources per batched sweep
_SERVE_B = 6                            # continuous-batch slots per lane
_SERVE_CHUNK = 4                        # fixpoint iterations per launch
_SERVE_REQUESTS = 16                    # open-loop trace length
_SERVE_SEED = 0
_SHARD_K = 2                            # shards of the sharded section's mesh
_INCR_SEED = 7                          # perturbation RNG seed of the
                                        # incremental section (deterministic)
_INCR_FRAC = 0.005                      # inserted edges as a fraction of |E|
                                        # — well under the planner's
                                        # INCREMENTAL_DELTA threshold

_JSON_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_pallas.json")

# tolerance for ratio comparisons against the baseline: iteration counts and
# edge work are deterministic on the seeded graphs, but leave headroom for
# jax-version differences in while_loop/cond accounting
_BASELINE_RTOL = 0.05


def seed_sweeps_per_iter(prog) -> int:
    """Per-iteration edge-sweep count of the one-launch-per-level execution
    model the fused sweep replaced (summed over the program's iteration
    rounds)."""
    total = 0
    for _name, round_ in prog.rounds:
        if not round_.leaves:
            continue
        plans = [leaf.plan for leaf in round_.leaves]
        idempotent = all(plan_idempotent(p) for p in plans)
        for p in plans:
            levels = _plan_levels(p)
            total += len(levels)
            if not idempotent:
                total += len(levels)        # one has-pred probe per component
    return total


def pallas_run_stats(g, prog, model=None):
    """Cold-build the pallas executors, run once, and return (result, sweep
    stats): the trace-time launch counts.  The runtime direction counts are
    the result's ``stats.push_iters`` / ``stats.pull_iters``."""
    from repro.kernels import edge_reduce as er
    engine.clear_program_caches()
    er.reset_sweep_stats()
    res = engine.run_program(g, prog, engine="pallas", model=model)
    return res, dict(er.SWEEP_STATS)


def bench_direction(g, gname: str, weighted: bool, name: str) -> dict:
    """Adaptive (direction-optimized) vs pull-only pallas on one frontier
    workload: the acceptance quantity is edge work and sweep executions of
    adaptive ≤ pull-only (DESIGN.md §2/§7)."""
    prog = fusion.fuse(U.ALL_SPECS[name]())
    res_auto, s_auto = pallas_run_stats(g, prog, model=None)
    res_pull, s_pull = pallas_run_stats(g, prog, model="pull")
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "iterations": res_auto.stats.iterations,
        "edge_work_auto": float(res_auto.stats.edge_work),
        "edge_work_pull": float(res_pull.stats.edge_work),
        "sweeps_auto": res_auto.stats.pull_iters + res_auto.stats.push_iters,
        "sweeps_pull": res_pull.stats.pull_iters + res_pull.stats.push_iters,
        "push_iters": res_auto.stats.push_iters,
        "pull_iters": res_auto.stats.pull_iters,
        "launches_traced_auto": s_auto["launches"],
        "launches_traced_pull": s_pull["launches"],
    }


def bench_resolution(g, gname: str, weighted: bool, name: str) -> dict:
    """Push-resolution section (DESIGN.md §10): the adaptive engine with the
    dst-sorted segment resolution vs the reference full-rectangle scatter on
    one sparse-frontier workload.  The acceptance quantities are RESOLUTION
    edge work — sorted must stay frontier-proportional (Σ nnz of the
    resolution tiles actually processed), strictly under the scatter path's
    `push_iters · n_pad · width` rectangle cost, with bit-identical values —
    and GATHER work: the candidate slots the permutation gather reads (the
    real slots of the dst-major rectangle), strictly under the full
    rectangle per push iteration and 0 under scatter (no permutation
    gather).
    Wall time is reported, never gated (interpret-mode CPU noise)."""
    from repro.graph.structure import push_resolution_cached
    from repro.kernels import edge_reduce as er
    prog = fusion.fuse(U.ALL_SPECS[name]())
    pres = push_resolution_cached(g)
    rectangle = float(pres.n_pad * pres.width)

    def one(resolution):
        engine.clear_program_caches()
        er.reset_sweep_stats()
        t, res = timed(lambda: engine.run_program(
            g, prog, engine="pallas", push_resolution=resolution), repeats=1)
        return t, res, dict(er.SWEEP_STATS)

    t_sorted, res_sorted, s_sorted = one("sorted")
    t_scatter, res_scatter, s_scatter = one("scatter")
    import numpy as np
    assert np.array_equal(np.asarray(res_sorted.value),
                          np.asarray(res_scatter.value)), \
        f"{name}: sorted resolution diverged from scatter"
    assert res_sorted.stats.push_iters == res_scatter.stats.push_iters
    # the section must actually exercise push resolution — if a heuristic
    # change stops these workloads pushing, fail loud instead of silently
    # gating nothing
    assert res_sorted.stats.push_iters >= 1, \
        f"{name}: no push iterations — resolution section is vacuous"
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "push_iters": res_sorted.stats.push_iters,
        "num_edges": g.num_edges,
        "edge_work": float(res_sorted.stats.edge_work),
        "resolve_work_sorted": float(res_sorted.stats.resolve_work),
        "resolve_work_scatter": float(res_scatter.stats.resolve_work),
        "gather_work_sorted": float(res_sorted.stats.gather_work),
        "gather_work_scatter": float(res_scatter.stats.gather_work),
        "rectangle": rectangle,
        "resolve_launches": s_sorted["resolve_launches"],
        "launches_traced_sorted": s_sorted["launches"],
        "launches_traced_scatter": s_scatter["launches"],
        "t_sorted_ms": t_sorted * 1e3, "t_scatter_ms": t_scatter * 1e3,
    }


def bench_batched(g, gname: str, weighted: bool, name: str,
                  batch: int = _BATCH_B) -> dict:
    """Batched-throughput section (DESIGN.md §9): B single-source queries of
    one shape, sequential (source as traced executor argument) vs one
    vmapped launch.  The gated quantities are the executor-cache entry count
    and traced launches of the sequential sweep — the per-source-retrace
    regression this PR class exists to prevent — plus the batched launch
    count (B queries : 1 executor)."""
    from repro.kernels import edge_reduce as er
    from repro.kernels import ops as kops
    spec_fn = _BATCHED_SPECS[name]
    srcs = list(range(min(batch, g.n)))
    prog = fusion.fuse(spec_fn(srcs[0]))

    def seq():
        # fresh spec per source: the exact shape that used to retrace
        return [engine.run_program(g, fusion.fuse(spec_fn(s)),
                                   engine="pallas") for s in srcs]

    engine.clear_program_caches()
    er.reset_sweep_stats()
    res_seq = seq()
    exec_seq = kops.executor_cache_size()
    launches_seq = er.SWEEP_STATS["launches"]       # trace-time = retraces
    t_seq, _ = timed(seq, repeats=1)

    def bat():
        return engine.run_program_batch(g, prog, sources=srcs,
                                        engine="pallas")

    engine.clear_program_caches()
    er.reset_sweep_stats()
    res_bat = bat()
    exec_bat = kops.executor_cache_size()
    launches_bat = er.SWEEP_STATS["launches"]
    t_bat, _ = timed(bat, repeats=1)
    assert all(int(a.stats.iterations) == int(b.stats.iterations)
               for a, b in zip(res_seq, res_bat))
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "batch": len(srcs),
        "exec_entries_seq": exec_seq,
        "exec_entries_batched": exec_bat,
        "launches_traced_seq": launches_seq,
        "launches_traced_batched": launches_bat,
        "t_seq_ms": t_seq * 1e3, "t_batched_ms": t_bat * 1e3,
        "queries_per_launch": len(srcs) / max(launches_bat, 1),
    }


def bench_sharded(g, gname: str, weighted: bool, name: str,
                  k: int = _SHARD_K):
    """Sharded section (DESIGN.md §11): ``pallas_sharded`` on a k-shard mesh
    vs the single-device pallas engine on one workload.  The acceptance
    quantities are compositional: the sharded run must take the SAME
    iteration sequence (the global direction switch — gated via iteration +
    push-iteration parity for the idempotent frontier workloads), its values
    must match (bitwise when idempotent, allclose for the float-sum PR round
    — asserted here, in-bench), and per-shard traced launches / cross-shard
    combine counts must not grow vs the baseline.  The sharded push sweep
    resolves through its per-shard sorted stack by default — the section
    also runs the per-shard scatter oracle and records both resolve/gather
    works so the baseline gates the sharded sorted resolve strictly under
    the per-shard scatter rectangle.  Wall time is reported, never gated.
    Returns None (section skipped) when the process has fewer than k
    devices — CI forces host devices via XLA_FLAGS."""
    import jax
    import numpy as np
    if len(jax.devices()) < k:
        return None
    from jax.sharding import Mesh

    from repro.kernels import edge_reduce as er
    mesh = Mesh(np.asarray(jax.devices()[:k]), ("data",))
    idempotent = name != "PR"

    def one(eng, **kw):
        engine.clear_program_caches()
        er.reset_sweep_stats()
        if name == "PR":
            dk = U.handwritten_pagerank(g.n)
            t, res = timed(lambda: engine.run_direct(
                g, dk, engine=eng, mesh=mesh, **kw), repeats=1)
        else:
            prog = fusion.fuse(U.ALL_SPECS[name]())
            t, res = timed(lambda: engine.run_program(
                g, prog, engine=eng, mesh=mesh, **kw), repeats=1)
        return t, res, dict(er.SWEEP_STATS)

    t_s, res_s, stats_s = one("pallas_sharded")
    _, res_sc, _ = one("pallas_sharded", push_resolution="scatter")
    t_1, res_1, stats_1 = one("pallas")
    res_match = (np.array_equal if idempotent else
                 lambda a, b: np.allclose(a, b, atol=1e-5))
    assert res_match(np.asarray(res_s.value), np.asarray(res_sc.value)), \
        f"{name}: sharded sorted resolution diverged from sharded scatter"
    v_s, v_1 = np.asarray(res_s.value), np.asarray(res_1.value)
    if idempotent:
        assert np.array_equal(v_1, v_s), \
            f"{name}: sharded diverged from single-device (bitwise)"
        assert res_s.stats.iterations == res_1.stats.iterations and \
            res_s.stats.push_iters == res_1.stats.push_iters, \
            f"{name}: sharded iteration sequence diverged " \
            f"({res_s.stats.iterations}/{res_s.stats.push_iters} vs " \
            f"{res_1.stats.iterations}/{res_1.stats.push_iters})"
    else:
        assert np.allclose(v_1, v_s, atol=1e-5), \
            f"{name}: sharded PR diverged beyond allclose"
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "shards": k, "idempotent": idempotent,
        "iterations_sharded": res_s.stats.iterations,
        "iterations_single": res_1.stats.iterations,
        "push_iters_sharded": res_s.stats.push_iters,
        "edge_work_sharded": float(res_s.stats.edge_work),
        "edge_work_single": float(res_1.stats.edge_work),
        # per-shard resolution stack vs the per-shard scatter oracle
        "resolve_work_sharded_sorted": float(res_s.stats.resolve_work),
        "resolve_work_sharded_scatter": float(res_sc.stats.resolve_work),
        "gather_work_sharded": float(res_s.stats.gather_work),
        "shard_work": list(res_s.stats.shard_work),
        # SPMD traces the shard body once, so trace-time sweep counts ARE
        # per-shard launches (one per direction branch per round)
        "shard_launches_traced": stats_s["launches"],
        "launches_traced_single": stats_1["launches"],
        "cross_combines": res_s.stats.cross_combines,
        "t_sharded_ms": t_s * 1e3, "t_single_ms": t_1 * 1e3,
    }


def bench_guard(g, gname: str, weighted: bool, name: str) -> dict:
    """Guard-overhead section (DESIGN.md §12): the default guarded execution
    (graph validation + termination precondition + divergence sentinel +
    convergence check) vs guards-off on one workload.  The guards are
    designed to be free at the fixpoint level — the sentinel folds into the
    existing convergence reduction, validation is a cached host-side pass —
    so the acceptance quantities are DETERMINISTIC equalities: bitwise
    values, identical iteration counts and edge work, and traced launches
    guarded ≤ guards-off (asserted in-bench; launches also gated vs the
    committed baseline).  Wall time is reported, never gated."""
    import numpy as np

    from repro.kernels import edge_reduce as er

    def one(guarded):
        engine.clear_program_caches()
        er.reset_sweep_stats()
        off = dict(validate=False, divergence_sentinel=False,
                   on_nonconverge="ignore")
        kw = {} if guarded else off
        if name == "PR":
            dk = U.handwritten_pagerank(g.n)
            t, res = timed(lambda: engine.run_direct(
                g, dk, engine="pallas", **kw), repeats=1)
        else:
            prog = fusion.fuse(U.ALL_SPECS[name]())
            t, res = timed(lambda: engine.run_program(
                g, prog, engine="pallas", **kw), repeats=1)
        return t, res, dict(er.SWEEP_STATS)

    t_on, res_on, s_on = one(True)
    t_off, res_off, s_off = one(False)
    assert np.array_equal(np.asarray(res_on.value),
                          np.asarray(res_off.value)), \
        f"{name}: guarded execution changed the computed values"
    assert res_on.stats.iterations == res_off.stats.iterations, \
        f"{name}: guards changed the iteration count " \
        f"({res_on.stats.iterations} vs {res_off.stats.iterations})"
    assert float(res_on.stats.edge_work) == float(res_off.stats.edge_work), \
        f"{name}: guards changed the edge work"
    assert s_on["launches"] <= s_off["launches"], \
        f"{name}: guards added traced launches " \
        f"({s_on['launches']} vs {s_off['launches']})"
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "iterations": res_on.stats.iterations,
        "edge_work": float(res_on.stats.edge_work),
        "launches_traced_guarded": s_on["launches"],
        "launches_traced_off": s_off["launches"],
        "t_guarded_ms": t_on * 1e3, "t_off_ms": t_off * 1e3,
    }


def bench_serving(g, gname: str, weighted: bool, name: str) -> dict:
    """Serving section (DESIGN.md §13): the continuous-batching analytics
    service under a seeded open-loop arrival trace.  The scheduler's virtual
    clock makes every serving metric deterministic — queries-per-launch
    (the continuous-batching win: answers per compiled launch), batch
    occupancy, launch and fused-round counts, executor-cache entries, and
    the virtual p50/p99 latencies — and every served answer is asserted
    bitwise-equal to a solo ``run_program`` here, in-bench.  Only wall time
    is machine-dependent, and only wall time goes ungated."""
    from repro.kernels import edge_reduce as er
    from repro.kernels import ops as kops
    from repro.launch import service as S

    engine.clear_program_caches()
    er.reset_sweep_stats()
    cfg = S.ServiceConfig(engine="pallas", max_batch=_SERVE_B,
                          chunk_iters=_SERVE_CHUNK)
    svc = S.AnalyticsService(cfg)
    svc.add_graph(gname, g)
    svc.register("BFS", U.bfs)
    svc.register("SSSP", U.sssp)
    # arrival rate ~16× the per-chunk virtual service time: the whole trace
    # lands within the first launches, so batches fill and scalar requests
    # queue up to be paired (the bench measures batching under pressure,
    # not an idle service)
    rate = 16.0 / (cfg.launch_overhead_s + cfg.chunk_iters * cfg.iter_cost_s)
    arrivals = S.open_loop_arrivals(
        _SERVE_REQUESTS, rate=rate, seed=_SERVE_SEED,
        make_request=S.standard_mix(gname, g.n))
    m = svc.run_open_loop(arrivals)
    # capture the gated execution-layer counters BEFORE verification runs
    # its own solo programs
    launches = er.SWEEP_STATS["launches"]
    exec_entries = kops.executor_cache_size()
    assert m["completed"] == _SERVE_REQUESTS, \
        f"serving trace lost requests: {m['completed']}/{_SERVE_REQUESTS}"
    checked = S.verify_sequential(svc)
    assert checked == _SERVE_REQUESTS, \
        f"serving answers not bitwise-equal to solo runs ({checked} checked)"
    assert m["queries_per_launch"] > 1.0, \
        f"continuous batching did not batch: queries_per_launch = " \
        f"{m['queries_per_launch']}"
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "requests": _SERVE_REQUESTS,
        "completed": m["completed"],
        "batch_launches": m["batch_launches"],
        "queries_per_launch": m["queries_per_launch"],
        "occupancy": m["occupancy"],
        "scalar_rounds": m["scalar_rounds"],
        "scalar_fused": m["scalar_fused"],
        "solo_runs": m["solo_runs"],
        "total_iterations": m["total_iterations"],
        "launches_traced": launches,
        "exec_entries": exec_entries,
        "v_p50_ms": m["v_p50_ms"], "v_p99_ms": m["v_p99_ms"],
        "v_qps": m["v_qps"],
        "t_wall_ms": m["wall_s"] * 1e3,
    }


def bench_planner(g, gname: str, weighted: bool, name: str) -> dict:
    """Planner section (DESIGN.md §14): the default ``ExecutionPlan`` vs the
    same decisions pinned through the explicit kwarg surface on one
    workload.  Default plans must reproduce the documented heuristics
    BITWISE (asserted here, in-bench: values, iterations, edge work), and
    planning must be invisible to the compiled program — zero extra traced
    launches, zero extra executor-cache entries (a plan is a host-side LRU
    lookup).  Wall time is reported, never gated."""
    import numpy as np

    from repro.core import plan as P
    from repro.kernels import edge_reduce as er
    from repro.kernels import ops as kops

    pinned_kw = dict(model=None, switch_k=20.0, push_resolution="sorted")

    def one(kw):
        engine.clear_program_caches()
        er.reset_sweep_stats()
        if name == "PR":
            dk = U.handwritten_pagerank(g.n)
            t, res = timed(lambda: engine.run_direct(
                g, dk, engine="pallas", **kw), repeats=1)
        else:
            prog = fusion.fuse(U.ALL_SPECS[name]())
            t, res = timed(lambda: engine.run_program(
                g, prog, engine="pallas", **kw), repeats=1)
        return t, res, dict(er.SWEEP_STATS), kops.executor_cache_size()

    t_plan, res_plan, s_plan, exec_plan = one({})
    t_pin, res_pin, s_pin, exec_pin = one(pinned_kw)
    assert np.array_equal(np.asarray(res_plan.value),
                          np.asarray(res_pin.value)), \
        f"{name}: planned execution diverged from pinned knobs"
    assert res_plan.stats.iterations == res_pin.stats.iterations, \
        f"{name}: planner changed the iteration count " \
        f"({res_plan.stats.iterations} vs {res_pin.stats.iterations})"
    assert float(res_plan.stats.edge_work) == \
        float(res_pin.stats.edge_work), \
        f"{name}: planner changed the edge work"
    assert res_plan.stats.plan is not None and \
        res_plan.stats.plan.engine == "pallas", \
        f"{name}: resolved plan missing from ExecStats"
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "iterations": res_plan.stats.iterations,
        "edge_work": float(res_plan.stats.edge_work),
        "launches_traced_planned": s_plan["launches"],
        "launches_traced_pinned": s_pin["launches"],
        "exec_entries_planned": exec_plan,
        "exec_entries_pinned": exec_pin,
        "plan_entries": P.plan_cache_size(),
        "feedback_entries": P.feedback_cache_size(),
        "t_planned_ms": t_plan * 1e3, "t_pinned_ms": t_pin * 1e3,
    }


def bench_incremental(g, gname: str, weighted: bool, name: str) -> dict:
    """Incremental section (DESIGN.md §15): converge once cold on ``g``
    with ``return_state=True``, apply a small seeded insert-only
    perturbation (~0.5% of |E|) through ``mutate_edges``, then run the
    delta-seeded warm-started fixpoint vs a cold full recompute on the
    mutated graph.  The acceptance quantities are deterministic on the
    seeded trace: BITWISE value parity (asserted here, in-bench — the
    workloads are idempotent rounds, so warm+delta is exact for insert-only
    batches), delta edge work strictly under the full recompute's, the
    planner resolving ``incremental="delta"`` for the small batch, and the
    patch-vs-rebuild layout counts (the in-place ELL patch must keep
    absorbing the batch).  Wall time is reported, never gated."""
    import numpy as np

    from repro.graph import mutate as M

    prog = fusion.fuse(U.ALL_SPECS[name]())
    engine.clear_program_caches()
    _res_prev, state = engine.run_program(g, prog, engine="pallas",
                                          return_state=True)
    rng = np.random.default_rng(_INCR_SEED)
    k = max(2, int(g.num_edges * _INCR_FRAC))
    src = rng.integers(0, g.n, size=k)
    dst = rng.integers(0, g.n, size=k)
    ins = (src, dst, (0.1 + rng.random(k)).astype(np.float32)) if weighted \
        else (src, dst)
    g2, md = M.mutate_edges(g, insert=ins)
    t_delta, res_delta = timed(lambda: engine.run_program(
        g2, prog, engine="pallas", init_state=state, delta=md), repeats=1)
    t_full, res_full = timed(lambda: engine.run_program(
        g2, prog, engine="pallas"), repeats=1)
    assert np.array_equal(np.asarray(res_delta.value),
                          np.asarray(res_full.value)), \
        f"{name}: delta-mode answer diverged from the cold recompute"
    assert res_delta.stats.plan is not None and \
        res_delta.stats.plan.incremental == "delta", \
        f"{name}: planner did not choose delta propagation for a " \
        f"{k}-edge insert batch"
    assert float(res_delta.stats.edge_work) < \
        float(res_full.stats.edge_work), \
        f"{name}: delta edge work {float(res_delta.stats.edge_work):.0f} " \
        f"not under the full recompute's " \
        f"{float(res_full.stats.edge_work):.0f}"
    return {
        "graph": gname, "weighted": weighted, "usecase": name,
        "num_edges": g.num_edges, "inserted": int(md.inserted),
        "touched": int(md.touched.size),
        "plan_incremental": res_delta.stats.plan.incremental,
        "iterations_delta": res_delta.stats.iterations,
        "iterations_full": res_full.stats.iterations,
        "edge_work_delta": float(res_delta.stats.edge_work),
        "edge_work_full": float(res_full.stats.edge_work),
        "patched_layouts": int(md.patched_layouts),
        "rebuilt_layouts": int(md.rebuilt_layouts),
        "t_delta_ms": t_delta * 1e3, "t_full_ms": t_full * 1e3,
    }


def run(graph_names=("RM-S",), usecases=SIMPLE + MULTI,
        engines=("pull", "push"), json_out=None, direction_usecases=None,
        batched_usecases=None, resolution_usecases=None,
        sharded_usecases=None, guard_usecases=None, serving_usecases=None,
        planner_usecases=None, incremental_usecases=None):
    rows = []
    json_rows = []
    direction_rows = []
    batched_rows = []
    resolution_rows = []
    sharded_rows = []
    guard_rows = []
    serving_rows = []
    planner_rows = []
    incremental_rows = []
    if direction_usecases and "pallas" not in engines:
        raise ValueError("direction_usecases bench the pallas engine's "
                         "push/pull switch; add 'pallas' to engines")
    if batched_usecases and "pallas" not in engines:
        raise ValueError("batched_usecases bench the pallas engine's "
                         "vmapped executors; add 'pallas' to engines")
    if resolution_usecases and "pallas" not in engines:
        raise ValueError("resolution_usecases bench the pallas engine's "
                         "push resolution; add 'pallas' to engines")
    if sharded_usecases and "pallas" not in engines:
        raise ValueError("sharded_usecases bench the pallas_sharded "
                         "engine; add 'pallas' to engines")
    if guard_usecases and "pallas" not in engines:
        raise ValueError("guard_usecases bench the pallas engine's guarded "
                         "execution; add 'pallas' to engines")
    if serving_usecases and "pallas" not in engines:
        raise ValueError("serving_usecases bench the continuous-batching "
                         "service on the pallas engine; add 'pallas' to "
                         "engines")
    if planner_usecases and "pallas" not in engines:
        raise ValueError("planner_usecases bench the query planner on the "
                         "pallas engine; add 'pallas' to engines")
    if incremental_usecases and "pallas" not in engines:
        raise ValueError("incremental_usecases bench the pallas engine's "
                         "delta-seeded warm starts; add 'pallas' to engines")
    if direction_usecases is None:
        direction_usecases = DIRECTION if "pallas" in engines else []
    if batched_usecases is None:
        batched_usecases = BATCHED if "pallas" in engines else []
    if resolution_usecases is None:
        resolution_usecases = RESOLUTION if "pallas" in engines else []
    if sharded_usecases is None:
        sharded_usecases = SHARDED if "pallas" in engines else []
    if guard_usecases is None:
        guard_usecases = GUARDED if "pallas" in engines else []
    if serving_usecases is None:
        serving_usecases = SERVING if "pallas" in engines else []
    if planner_usecases is None:
        planner_usecases = PLANNER if "pallas" in engines else []
    if incremental_usecases is None:
        incremental_usecases = INCREMENTAL if "pallas" in engines else []
    for gname in graph_names:
        for weighted in (False, True):
            g = BENCH_GRAPHS[gname](weighted)
            for eng in engines:
                for name in usecases:
                    spec = U.ALL_SPECS[name]()
                    fprog = fusion.fuse(spec)
                    uprog = fusion.lower_unfused(spec)
                    launches = ""
                    if eng == "pallas":
                        res_p, sweep = pallas_run_stats(g, fprog)
                        launches = sweep["launches"]
                    t_f, rf = timed(lambda: engine.run_program(
                        g, fprog, engine=eng), repeats=3)
                    t_u, ru = timed(lambda: engine.run_program(
                        g, uprog, engine=eng), repeats=3)
                    ratio = rf.stats.edge_work / max(ru.stats.edge_work, 1.0)
                    row = [gname, "w" if weighted else "unw", eng, name,
                           round(ratio, 4),
                           round(t_u / max(t_f, 1e-9), 3),
                           rf.stats.rounds, ru.stats.rounds,
                           round(t_f * 1e3, 1), round(t_u * 1e3, 1),
                           launches, seed_sweeps_per_iter(fprog)]
                    rows.append(row)
                    if eng == "pallas":
                        json_rows.append({
                            "graph": gname, "weighted": weighted,
                            "usecase": name,
                            "edge_work_ratio": float(ratio),
                            "t_fused_ms": t_f * 1e3,
                            "t_unfused_ms": t_u * 1e3,
                            "rounds_fused": rf.stats.rounds,
                            "iterations_fused": rf.stats.iterations,
                            # pallas_calls in the traced program, summed
                            # over the program's rounds (≤ 2 per round:
                            # one per lax.cond direction branch)
                            "launches_traced": launches,
                            "push_iters": res_p.stats.push_iters,
                            "pull_iters": res_p.stats.pull_iters,
                            "seed_sweeps_per_iter":
                                seed_sweeps_per_iter(fprog)})
            if "pallas" in engines:
                for name in direction_usecases:
                    direction_rows.append(
                        bench_direction(g, gname, weighted, name))
                for name in resolution_usecases:
                    resolution_rows.append(
                        bench_resolution(g, gname, weighted, name))
                for name in batched_usecases:
                    batched_rows.append(
                        bench_batched(g, gname, weighted, name))
                for name in sharded_usecases:
                    row = bench_sharded(g, gname, weighted, name)
                    if row is None:
                        print(f"sharded section skipped ({name}): fewer "
                              f"than {_SHARD_K} devices — set XLA_FLAGS="
                              "--xla_force_host_platform_device_count")
                    else:
                        sharded_rows.append(row)
                for name in guard_usecases:
                    guard_rows.append(bench_guard(g, gname, weighted, name))
                for name in serving_usecases:
                    serving_rows.append(
                        bench_serving(g, gname, weighted, name))
                for name in planner_usecases:
                    planner_rows.append(
                        bench_planner(g, gname, weighted, name))
                for name in incremental_usecases:
                    incremental_rows.append(
                        bench_incremental(g, gname, weighted, name))
    header = ["graph", "weights", "engine", "usecase", "edge_work_ratio",
              "speedup", "rounds_fused", "rounds_unfused", "t_fused_ms",
              "t_unfused_ms", "launches", "seed_sweeps"]
    out = emit(rows, header)
    if direction_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["iterations"], round(r["edge_work_auto"], 1),
               round(r["edge_work_pull"], 1), r["push_iters"],
               r["pull_iters"], r["sweeps_auto"], r["sweeps_pull"]]
              for r in direction_rows],
             ["graph", "weights", "usecase", "iters", "work_auto",
              "work_pull", "push_iters", "pull_iters", "sweeps_auto",
              "sweeps_pull"])
    if resolution_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["push_iters"], round(r["resolve_work_sorted"], 1),
               round(r["resolve_work_scatter"], 1),
               round(r["resolve_work_sorted"]
                     / max(r["resolve_work_scatter"], 1.0), 4),
               round(r["gather_work_sorted"], 1),
               round(r["gather_work_sorted"]
                     / max(r["push_iters"] * r["rectangle"], 1.0), 4),
               r["resolve_launches"],
               round(r["t_sorted_ms"], 1), round(r["t_scatter_ms"], 1)]
              for r in resolution_rows],
             ["graph", "weights", "usecase", "push_iters", "res_work_sorted",
              "res_work_scatter", "res_ratio", "gather_work",
              "gather_vs_rect", "resolve_launches",
              "t_sorted_ms", "t_scatter_ms"])
    if batched_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["batch"], r["exec_entries_seq"], r["exec_entries_batched"],
               r["launches_traced_seq"], r["launches_traced_batched"],
               round(r["queries_per_launch"], 2),
               round(r["t_seq_ms"], 1), round(r["t_batched_ms"], 1)]
              for r in batched_rows],
             ["graph", "weights", "usecase", "batch", "exec_seq",
              "exec_batched", "traced_seq", "traced_batched",
              "queries_per_launch", "t_seq_ms", "t_batched_ms"])
    if sharded_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["shards"], r["iterations_sharded"], r["iterations_single"],
               round(r["edge_work_sharded"], 1),
               round(r["edge_work_single"], 1),
               round(r["resolve_work_sharded_sorted"], 1),
               round(r["resolve_work_sharded_scatter"], 1),
               round(r["gather_work_sharded"], 1),
               r["shard_launches_traced"], r["cross_combines"],
               round(r["t_sharded_ms"], 1), round(r["t_single_ms"], 1)]
              for r in sharded_rows],
             ["graph", "weights", "usecase", "shards", "iters_sharded",
              "iters_single", "work_sharded", "work_single",
              "res_sorted", "res_scatter", "gather_work",
              "shard_launches", "cross_combines", "t_sharded_ms",
              "t_single_ms"])
    if guard_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["iterations"], round(r["edge_work"], 1),
               r["launches_traced_guarded"], r["launches_traced_off"],
               round(r["t_guarded_ms"], 1), round(r["t_off_ms"], 1)]
              for r in guard_rows],
             ["graph", "weights", "usecase", "iters", "edge_work",
              "traced_guarded", "traced_off", "t_guarded_ms", "t_off_ms"])
    if serving_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["requests"], r["batch_launches"],
               round(r["queries_per_launch"], 2), round(r["occupancy"], 2),
               r["scalar_rounds"], r["scalar_fused"],
               r["launches_traced"], r["exec_entries"],
               round(r["v_p50_ms"], 2), round(r["v_p99_ms"], 2),
               r["v_qps"], round(r["t_wall_ms"], 1)]
              for r in serving_rows],
             ["graph", "weights", "trace", "requests", "batch_launches",
              "q_per_launch", "occupancy", "scalar_rounds", "scalar_fused",
              "traced", "exec_entries", "v_p50_ms", "v_p99_ms", "v_qps",
              "t_wall_ms"])
    if planner_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["iterations"], round(r["edge_work"], 1),
               r["launches_traced_planned"], r["launches_traced_pinned"],
               r["exec_entries_planned"], r["exec_entries_pinned"],
               r["plan_entries"], r["feedback_entries"],
               round(r["t_planned_ms"], 1), round(r["t_pinned_ms"], 1)]
              for r in planner_rows],
             ["graph", "weights", "usecase", "iters", "edge_work",
              "traced_planned", "traced_pinned", "exec_planned",
              "exec_pinned", "plan_entries", "feedback", "t_planned_ms",
              "t_pinned_ms"])
    if incremental_rows:
        emit([[r["graph"], "w" if r["weighted"] else "unw", r["usecase"],
               r["inserted"], r["touched"],
               r["iterations_delta"], r["iterations_full"],
               round(r["edge_work_delta"], 1), round(r["edge_work_full"], 1),
               round(r["edge_work_delta"]
                     / max(r["edge_work_full"], 1.0), 4),
               r["patched_layouts"], r["rebuilt_layouts"],
               round(r["t_delta_ms"], 1), round(r["t_full_ms"], 1)]
              for r in incremental_rows],
             ["graph", "weights", "usecase", "inserted", "touched",
              "iters_delta", "iters_full", "work_delta", "work_full",
              "work_ratio", "patched", "rebuilt", "t_delta_ms",
              "t_full_ms"])
    doc = {"bench": "fusion_bench", "engine": "pallas",
           "rows": json_rows, "direction_rows": direction_rows,
           "resolution_rows": resolution_rows,
           "batched_rows": batched_rows,
           "sharded_rows": sharded_rows,
           "guard_rows": guard_rows,
           "serving_rows": serving_rows,
           "planner_rows": planner_rows,
           "incremental_rows": incremental_rows,
           "table": out}
    if json_rows or direction_rows or batched_rows or resolution_rows \
            or sharded_rows or guard_rows or serving_rows or planner_rows \
            or incremental_rows:
        path = json_out or _JSON_PATH
        with open(path, "w") as f:
            json.dump({k: v for k, v in doc.items() if k != "table"},
                      f, indent=1)
        print(f"wrote {path}")
    return doc


# ---------------------------------------------------------------------------
# Baseline regression gate (shared by CI bench-smoke and local runs).
# ---------------------------------------------------------------------------

def _row_key(r):
    return (r["graph"], r["weighted"], r["usecase"])


def compare_baseline(current: dict, baseline: dict,
                     rtol: float = _BASELINE_RTOL) -> list:
    """Regressions of ``current`` against ``baseline``; empty list = pass.

    Gated quantities are the deterministic execution-layer metrics —
    launches per iteration, fused/unfused edge-work ratio, and the
    direction engine's work advantage — never wall time (machine noise).
    Comparison is over the intersection of rows: a smoke run may bench a
    subset of the baseline's usecases (the workflow controls coverage)."""
    errors = []
    cur_rows = {_row_key(r): r for r in current.get("rows", [])}
    base_rows = {_row_key(r): r for r in baseline.get("rows", [])}
    for key, b in base_rows.items():
        r = cur_rows.get(key)
        if r is None:
            continue
        # strict on purpose: a +1 here is exactly the "extra kernel launch
        # snuck in" regression this gate exists for.  Trace-time counts are
        # jax-version-sensitive in principle; if a jax upgrade changes how
        # often bodies trace, regenerate the baseline deliberately.
        if r["launches_traced"] > b["launches_traced"]:
            errors.append(
                f"{key}: traced launches {r['launches_traced']} > baseline "
                f"{b['launches_traced']}")
        if r["edge_work_ratio"] > b["edge_work_ratio"] * (1 + rtol):
            errors.append(
                f"{key}: edge_work_ratio {r['edge_work_ratio']:.4f} > "
                f"baseline {b['edge_work_ratio']:.4f} (+{rtol:.0%})")
    base_dir = {_row_key(r): r for r in baseline.get("direction_rows", [])}
    for r in current.get("direction_rows", []):
        key = _row_key(r)
        # The acceptance property on the committed direction workloads:
        # adaptive must not do more (tile-counted) work or more sweep
        # executions than pull-only.  NOT a theorem of the heuristic —
        # tile granularity can overcount a push block whose sparse
        # frontier is co-blocked with hubs — so the work check carries
        # the shared tolerance; treat a trip on a new workload as "tune
        # the threshold or drop the workload", not as noise.
        if r["edge_work_auto"] > r["edge_work_pull"] * (1 + rtol):
            errors.append(
                f"{key}: adaptive work {r['edge_work_auto']:.0f} > pull-only "
                f"{r['edge_work_pull']:.0f} (+{rtol:.0%})")
        if r["sweeps_auto"] > r["sweeps_pull"]:
            errors.append(
                f"{key}: adaptive sweeps {r['sweeps_auto']} > pull-only "
                f"{r['sweeps_pull']}")
        b = base_dir.get(key)
        if b is None:
            continue
        if b["edge_work_pull"] and r["edge_work_pull"]:
            adv_now = r["edge_work_auto"] / r["edge_work_pull"]
            adv_base = b["edge_work_auto"] / b["edge_work_pull"]
            if adv_now > adv_base * (1 + rtol):
                errors.append(
                    f"{key}: push/pull work advantage regressed "
                    f"{adv_now:.3f} > baseline {adv_base:.3f} (+{rtol:.0%})")
    base_res = {_row_key(r): r for r in baseline.get("resolution_rows", [])}
    for r in current.get("resolution_rows", []):
        key = _row_key(r)
        # Standing frontier-proportionality bounds, not just a diff.
        # (bench_resolution itself asserts push_iters >= 1, so the section
        # can never silently gate nothing.)  Two bounds: under the padded
        # scatter rectangle (the cost the sorted path replaces), and —
        # the sharper one — strictly under push_iters·|E|, which is
        # exactly what fully-disengaged tile compaction would cost (every
        # real slot reduced every push iteration).  A trip on the second
        # means the compaction stopped engaging.
        if r["push_iters"] > 0:
            if not (r["resolve_work_sorted"] < r["resolve_work_scatter"]):
                errors.append(
                    f"{key}: sorted resolution work "
                    f"{r['resolve_work_sorted']:.0f} not under scatter "
                    f"{r['resolve_work_scatter']:.0f}")
            full_nnz = r["push_iters"] * r.get("num_edges", 0)
            if full_nnz and not (r["resolve_work_sorted"] < full_nnz):
                errors.append(
                    f"{key}: sorted resolution work "
                    f"{r['resolve_work_sorted']:.0f} ≥ push_iters·|E| = "
                    f"{full_nnz:.0f} — tile compaction disengaged")
            # gather bounds (DESIGN.md §10): the permutation gather reads
            # the real slots only — strictly under the padded
            # `push_iters · n_pad · width` rectangle — and the scatter path
            # performs no permutation gather at all.
            full_rect = r["push_iters"] * r.get("rectangle", 0)
            if full_rect and not (r["gather_work_sorted"] < full_rect):
                errors.append(
                    f"{key}: gather work {r['gather_work_sorted']:.0f} ≥ "
                    f"push_iters·rectangle = {full_rect:.0f} — the "
                    "gather reads padding slots")
            if "gather_work_scatter" in r and r["gather_work_scatter"] != 0:
                errors.append(
                    f"{key}: scatter path reports gather work "
                    f"{r['gather_work_scatter']:.0f} (must be 0 — it "
                    "performs no permutation gather)")
        b = base_res.get(key)
        if b is None:
            continue
        if b["resolve_work_scatter"] and r["resolve_work_scatter"]:
            ratio_now = r["resolve_work_sorted"] / r["resolve_work_scatter"]
            ratio_base = b["resolve_work_sorted"] / b["resolve_work_scatter"]
            if ratio_now > ratio_base * (1 + rtol):
                errors.append(
                    f"{key}: resolution-work ratio regressed "
                    f"{ratio_now:.4f} > baseline {ratio_base:.4f} "
                    f"(+{rtol:.0%})")
        if r["launches_traced_sorted"] > b["launches_traced_sorted"]:
            errors.append(
                f"{key}: sorted traced sweep launches "
                f"{r['launches_traced_sorted']} > baseline "
                f"{b['launches_traced_sorted']}")
    base_sharded = {_row_key(r): r for r in baseline.get("sharded_rows", [])}
    for r in current.get("sharded_rows", []):
        key = _row_key(r)
        # Standing compositional properties (DESIGN.md §11), not just diffs:
        # the global direction switch must keep the sharded fixpoint on the
        # single-device iteration sequence for the idempotent frontier
        # workloads (value bitwise-equality is asserted inside
        # bench_sharded itself).
        if r.get("idempotent") and \
                r["iterations_sharded"] != r["iterations_single"]:
            errors.append(
                f"{key}: sharded iterations {r['iterations_sharded']} != "
                f"single-device {r['iterations_single']} — global direction "
                "switch diverged")
        # Standing bound for the per-shard resolution stack (DESIGN.md
        # §11): whenever push iterations ran, the sharded sorted resolve
        # must stay strictly under the per-shard scatter rectangle.
        if r.get("push_iters_sharded", 0) > 0 and \
                "resolve_work_sharded_sorted" in r:
            if not (r["resolve_work_sharded_sorted"]
                    < r["resolve_work_sharded_scatter"]):
                errors.append(
                    f"{key}: sharded sorted resolution work "
                    f"{r['resolve_work_sharded_sorted']:.0f} not under the "
                    f"per-shard scatter rectangle "
                    f"{r['resolve_work_sharded_scatter']:.0f}")
        b = base_sharded.get(key)
        if b is None:
            continue
        # per-shard traced launches and cross-shard combine counts are the
        # sharded engine's launch-contract analogues: strict, like
        # launches_traced
        if r["shard_launches_traced"] > b["shard_launches_traced"]:
            errors.append(
                f"{key}: per-shard traced launches "
                f"{r['shard_launches_traced']} > baseline "
                f"{b['shard_launches_traced']}")
        if r["cross_combines"] > b["cross_combines"]:
            errors.append(
                f"{key}: cross-shard combines {r['cross_combines']} > "
                f"baseline {b['cross_combines']}")
        if b["edge_work_single"] and r["edge_work_single"]:
            ovh_now = r["edge_work_sharded"] / r["edge_work_single"]
            ovh_base = b["edge_work_sharded"] / b["edge_work_single"]
            if ovh_now > ovh_base * (1 + rtol):
                errors.append(
                    f"{key}: sharded/single edge-work overhead regressed "
                    f"{ovh_now:.3f} > baseline {ovh_base:.3f} (+{rtol:.0%})")
    base_batched = {_row_key(r): r for r in baseline.get("batched_rows", [])}
    for r in current.get("batched_rows", []):
        key = _row_key(r)
        # Standing properties of the source-parameterized executors
        # (DESIGN.md §8/§9), not just diffs: a B-source sequential sweep
        # holds ONE executor entry, and the batched run ONE vmapped entry.
        # A 2 here is exactly the retrace-per-source regression.
        if r["exec_entries_seq"] > 1:
            errors.append(
                f"{key}: sequential {r['batch']}-source sweep holds "
                f"{r['exec_entries_seq']} executor entries (want 1 — "
                "the source is being baked into the trace again)")
        if r["exec_entries_batched"] > 1:
            errors.append(
                f"{key}: batched sweep holds {r['exec_entries_batched']} "
                "executor entries (want 1)")
        b = base_batched.get(key)
        if b is None:
            continue
        for field in ("launches_traced_seq", "launches_traced_batched"):
            if r[field] > b[field]:
                errors.append(f"{key}: {field} {r[field]} > baseline "
                              f"{b[field]} (a retrace snuck in)")
    base_guard = {_row_key(r): r for r in baseline.get("guard_rows", [])}
    for r in current.get("guard_rows", []):
        key = _row_key(r)
        # Standing property (DESIGN.md §12): the divergence sentinel and
        # convergence bookkeeping fold into the existing fixpoint cond —
        # guarded execution must never add a traced launch over guards-off
        # (bench_guard additionally asserts bitwise values and identical
        # iterations/edge work in-bench).
        if r["launches_traced_guarded"] > r["launches_traced_off"]:
            errors.append(
                f"{key}: guarded traced launches "
                f"{r['launches_traced_guarded']} > guards-off "
                f"{r['launches_traced_off']} — the sentinel grew the "
                "traced program")
        b = base_guard.get(key)
        if b is None:
            continue
        # strict vs the committed baseline, like launches_traced: a +1 is
        # exactly the "guard launch snuck in" regression this row gates
        if r["launches_traced_guarded"] > b["launches_traced_guarded"]:
            errors.append(
                f"{key}: guarded traced launches "
                f"{r['launches_traced_guarded']} > baseline "
                f"{b['launches_traced_guarded']}")
    base_serving = {_row_key(r): r for r in baseline.get("serving_rows", [])}
    for r in current.get("serving_rows", []):
        key = _row_key(r)
        # Standing property (DESIGN.md §13): continuous batching must
        # actually batch — more than one answer per compiled launch on the
        # seeded trace (bench_serving additionally asserts every answer
        # bitwise-equal to its solo run, in-bench).
        if r["queries_per_launch"] <= 1.0:
            errors.append(
                f"{key}: serving queries_per_launch "
                f"{r['queries_per_launch']:.3f} <= 1 — continuous batching "
                "disengaged")
        b = base_serving.get(key)
        if b is None:
            continue
        # every gated quantity here is a deterministic function of the
        # seeded trace and the virtual clock — wall time is never compared
        if r["queries_per_launch"] < b["queries_per_launch"] * (1 - rtol):
            errors.append(
                f"{key}: queries_per_launch {r['queries_per_launch']:.3f} < "
                f"baseline {b['queries_per_launch']:.3f} (-{rtol:.0%})")
        for field in ("batch_launches", "scalar_rounds", "launches_traced",
                      "exec_entries"):
            if r[field] > b[field]:
                errors.append(
                    f"{key}: serving {field} {r[field]} > baseline "
                    f"{b[field]}")
        if r["scalar_fused"] < b["scalar_fused"]:
            errors.append(
                f"{key}: serving scalar_fused {r['scalar_fused']} < "
                f"baseline {b['scalar_fused']} — fuse_many pairing "
                "stopped absorbing scalar requests")
    base_planner = {_row_key(r): r for r in baseline.get("planner_rows", [])}
    for r in current.get("planner_rows", []):
        key = _row_key(r)
        # Standing properties (DESIGN.md §14): planning is a host-side cache
        # lookup, so the planned run must trace exactly what the pinned run
        # traces and hold the same executor entries (bitwise value /
        # iteration / edge-work parity is asserted inside bench_planner
        # itself); and executed queries must leave recorded-stats feedback
        # for the adaptive loop to consume.
        if r["launches_traced_planned"] != r["launches_traced_pinned"]:
            errors.append(
                f"{key}: planner changed traced launches "
                f"({r['launches_traced_planned']} vs pinned "
                f"{r['launches_traced_pinned']}) — planning must be "
                "invisible to the compiled program")
        if r["exec_entries_planned"] != r["exec_entries_pinned"]:
            errors.append(
                f"{key}: planner changed executor-cache entries "
                f"({r['exec_entries_planned']} vs pinned "
                f"{r['exec_entries_pinned']})")
        if r["feedback_entries"] < 1:
            errors.append(
                f"{key}: no recorded-stats feedback after an executed "
                "query — the planner's feedback loop is disconnected")
        b = base_planner.get(key)
        if b is None:
            continue
        # strict vs the committed baseline, like launches_traced
        if r["launches_traced_planned"] > b["launches_traced_planned"]:
            errors.append(
                f"{key}: planned traced launches "
                f"{r['launches_traced_planned']} > baseline "
                f"{b['launches_traced_planned']}")
        if r["exec_entries_planned"] > b["exec_entries_planned"]:
            errors.append(
                f"{key}: planned executor entries "
                f"{r['exec_entries_planned']} > baseline "
                f"{b['exec_entries_planned']}")
    base_incr = {_row_key(r): r for r in baseline.get("incremental_rows", [])}
    for r in current.get("incremental_rows", []):
        key = _row_key(r)
        # Standing properties (DESIGN.md §15), not just diffs: delta
        # propagation must do strictly less edge work than the cold full
        # recompute it replaces (bench_incremental additionally asserts the
        # answers bitwise-equal in-bench), and the planner must actually
        # resolve delta propagation for the small seeded insert batch — a
        # "full" here means the mutation-size heuristic disengaged and the
        # whole section gates nothing.
        if not (r["edge_work_delta"] < r["edge_work_full"]):
            errors.append(
                f"{key}: delta edge work {r['edge_work_delta']:.0f} not "
                f"under the full recompute's {r['edge_work_full']:.0f} — "
                "delta propagation disengaged")
        if r.get("plan_incremental") != "delta":
            errors.append(
                f"{key}: planner resolved incremental="
                f"{r.get('plan_incremental')!r} for the small seeded "
                "insert batch (want 'delta')")
        b = base_incr.get(key)
        if b is None:
            continue
        if b["edge_work_full"] and r["edge_work_full"]:
            ratio_now = r["edge_work_delta"] / r["edge_work_full"]
            ratio_base = b["edge_work_delta"] / b["edge_work_full"]
            if ratio_now > ratio_base * (1 + rtol):
                errors.append(
                    f"{key}: delta/full work ratio regressed "
                    f"{ratio_now:.4f} > baseline {ratio_base:.4f} "
                    f"(+{rtol:.0%})")
        # strict, like launches_traced: a rebuild where the baseline
        # patched means the in-place ELL patch stopped absorbing the batch
        if r["rebuilt_layouts"] > b["rebuilt_layouts"]:
            errors.append(
                f"{key}: rebuilt layouts {r['rebuilt_layouts']} > baseline "
                f"{b['rebuilt_layouts']} — the in-place layout patch "
                "stopped absorbing the insert batch")
    return errors


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--engines", default="pull,push",
                    help="comma list: pull,push,dense,adaptive,pallas")
    ap.add_argument("--graphs", default=None,
                    help=f"comma list from {sorted(BENCH_GRAPHS)}; defaults "
                         "to RM-S, or RM-XS when pallas is benchmarked "
                         "(interpret-mode grids step in Python on CPU)")
    ap.add_argument("--usecases", default=",".join(SIMPLE + MULTI))
    ap.add_argument("--batched", default=None, metavar="NAMES",
                    help="comma list of batched-sweep workloads "
                         f"(default {','.join(BATCHED)} when pallas is "
                         "benchmarked; pass '' to skip)")
    ap.add_argument("--resolution", default=None, metavar="NAMES",
                    help="comma list of push-resolution workloads "
                         f"(default {','.join(RESOLUTION)} when pallas is "
                         "benchmarked; pass '' to skip)")
    ap.add_argument("--sharded", default=None, metavar="NAMES",
                    help="comma list of sharded-engine workloads "
                         f"(default {','.join(SHARDED)} when pallas is "
                         "benchmarked and >= 2 devices exist; pass '' to "
                         "skip)")
    ap.add_argument("--guard", default=None, metavar="NAMES",
                    help="comma list of guard-overhead workloads "
                         f"(default {','.join(GUARDED)} when pallas is "
                         "benchmarked; pass '' to skip)")
    ap.add_argument("--serving", default=None, metavar="NAMES",
                    help="comma list of open-loop serving traces "
                         f"(default {','.join(SERVING)} when pallas is "
                         "benchmarked; pass '' to skip)")
    ap.add_argument("--planner", default=None, metavar="NAMES",
                    help="comma list of planner-parity workloads "
                         f"(default {','.join(PLANNER)} when pallas is "
                         "benchmarked; pass '' to skip)")
    ap.add_argument("--incremental", default=None, metavar="NAMES",
                    help="comma list of delta-vs-full mutation workloads "
                         f"(default {','.join(INCREMENTAL)} when pallas is "
                         "benchmarked; pass '' to skip)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="where to write the machine-readable results "
                         f"(default {_JSON_PATH})")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed BENCH_pallas.json to diff against; "
                         "regressions exit 1 (the CI perf gate)")
    args = ap.parse_args()
    engines = tuple(args.engines.split(","))
    graphs = args.graphs or ("RM-XS" if "pallas" in engines else "RM-S")
    baseline = None
    json_out = args.json_out
    if args.baseline:
        # read the baseline BEFORE running, and never write the fresh run
        # over it: `--baseline BENCH_pallas.json` without --json-out must
        # compare fresh-vs-committed, not fresh-vs-itself
        with open(args.baseline) as f:
            baseline = json.load(f)
        if json_out is None and os.path.realpath(args.baseline) == \
                os.path.realpath(_JSON_PATH):
            json_out = _JSON_PATH.replace(".json", ".fresh.json")
            print(f"baseline is the default output path; writing fresh "
                  f"results to {json_out}")
    batched = None if args.batched is None else \
        tuple(u for u in args.batched.split(",") if u)
    resolution = None if args.resolution is None else \
        tuple(u for u in args.resolution.split(",") if u)
    sharded = None if args.sharded is None else \
        tuple(u for u in args.sharded.split(",") if u)
    guard = None if args.guard is None else \
        tuple(u for u in args.guard.split(",") if u)
    serving = None if args.serving is None else \
        tuple(u for u in args.serving.split(",") if u)
    planner = None if args.planner is None else \
        tuple(u for u in args.planner.split(",") if u)
    incremental = None if args.incremental is None else \
        tuple(u for u in args.incremental.split(",") if u)
    result = run(graph_names=tuple(graphs.split(",")),
                 usecases=tuple(u for u in args.usecases.split(",") if u),
                 engines=engines, json_out=json_out,
                 batched_usecases=batched, resolution_usecases=resolution,
                 sharded_usecases=sharded, guard_usecases=guard,
                 serving_usecases=serving, planner_usecases=planner,
                 incremental_usecases=incremental)
    if baseline is not None:
        if not (result["rows"] or result["direction_rows"]
                or result["batched_rows"] or result["resolution_rows"]
                or result["sharded_rows"] or result["guard_rows"]
                or result["serving_rows"] or result["planner_rows"]
                or result["incremental_rows"]):
            print("--baseline requires the pallas engine in --engines "
                  "(no gated rows were produced)")
            sys.exit(2)
        errors = compare_baseline(result, baseline)
        if errors:
            print("PERF REGRESSION vs baseline:")
            for e in errors:
                print("  -", e)
            sys.exit(1)
        print(f"baseline check OK ({args.baseline}: "
              f"{len(baseline.get('rows', []))} rows, "
              f"{len(baseline.get('direction_rows', []))} direction rows, "
              f"{len(baseline.get('resolution_rows', []))} resolution rows, "
              f"{len(baseline.get('batched_rows', []))} batched rows, "
              f"{len(baseline.get('sharded_rows', []))} sharded rows, "
              f"{len(baseline.get('guard_rows', []))} guard rows, "
              f"{len(baseline.get('serving_rows', []))} serving rows, "
              f"{len(baseline.get('planner_rows', []))} planner rows, "
              f"{len(baseline.get('incremental_rows', []))} incremental "
              "rows)")
