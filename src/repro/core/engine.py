"""Triple-let executor: iteration → map → reduce (paper §5).

Runs a ``FusedProgram`` (from fusion.fuse or fusion.lower_unfused) on a
graph under one of the engines:

  pull | push     sparse frontier engines (iterate.iterate_graph)
  adaptive        Gemini-style per-iteration push/pull switch (segment ops)
  dense           dense edge-matrix reference engine
  pallas          direction-optimized blocked-ELL TPU kernel engine
                  (repro.kernels; ``model`` forces "pull"/"push", default
                  picks per iteration by frontier density)
  distributed     shard_map vertex-cut engine, plain segment-reduce per
                  shard (needs a mesh)
  pallas_sharded  shard_map vertex-cut engine running the fused blocked-ELL
                  Pallas sweeps SHARD-LOCALLY with monoid cross-shard
                  combines and a global direction switch (needs a mesh;
                  DESIGN.md §11)

The three primitives map exactly as §5 prescribes: the fused ilet runs as an
iterative path reduction, the mlet as a vectorized per-vertex map, the rlet
as (masked) reductions over the vertex dimension, and the final expression
evaluates on the results.  ⊥ values (reduction identities / ±inf) are
excluded from vertex reductions per C6 (R(n, ⊥) = n).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import conditions as _conditions
from repro.core import guard, iterate
from repro.core import plan as _plan
from repro.core.fusion import FusedProgram, FusedRound, plan_output
from repro.core.kernel_lang import eval_expr
from repro.core.plan import ExecutionPlan, plan_execution  # noqa: F401
from repro.core.synthesis import DirectKernels, synthesize_round

_BOT_CUTOFF = 1e8

# Engine-invocation retry knobs used when the caller enables ``fallback``
# without providing an ``ft_config``: one retry of the same engine before
# degrading (lowering failures are deterministic — long budgets just delay
# the fallback), minimal backoff.
_FALLBACK_RETRIES = 1
_FALLBACK_BACKOFF_S = 0.01


def clear_program_caches():
    """Drop every layer of the compiled-program cache: synthesized round
    kernels, blocked-ELL layouts (single-device and sharded), and jitted
    pallas executors.  Mostly for tests and benchmarks that need cold-start
    numbers; normal callers keep the caches warm across rounds, repeated
    queries and repeats."""
    from repro.core import synthesis
    from repro.graph import structure
    synthesis._ROUND_CACHE.clear()
    structure._ELL_CACHE.clear()
    structure._RES_CACHE.clear()
    structure._WDEG_CACHE.clear()
    structure._SHARDED_ELL_CACHE.clear()
    structure._SHARDED_RES_CACHE.clear()
    structure._VALID_CACHE.clear()
    structure._STATS_CACHE.clear()
    structure._SLOT_CACHE.clear()
    _plan.clear_plan_caches()
    from repro.graph import mutate as _mutate
    _mutate.reset_mutation_stats()
    try:
        from repro.kernels import ops as kops
        kops.clear_executor_cache()
    except ImportError:                 # pallas backend unavailable
        pass


def clear_graph_caches(g) -> int:
    """Selective per-graph eviction (DESIGN.md §13): drop ONE graph's
    derived layouts / degrees / validation summary from the structure
    caches, leaving other resident graphs and the graph-shape-generic
    compiled executors alone.  The serving layer's bounded graph LRU calls
    this when a graph loses residency; ``program_cache_stats`` verifies the
    bound.  Also evicts the graph's cached plans and recorded-stats feedback
    (core.plan) so an evicted graph's adaptation history dies with it.
    Returns the number of cache entries dropped."""
    from repro.graph import structure
    return structure.clear_graph_caches(g) + _plan.clear_graph_plans(g)


def program_cache_stats() -> dict:
    from repro.core import synthesis
    from repro.graph import structure
    out = {"synth_rounds": len(synthesis._ROUND_CACHE),
           "ell_layouts": len(structure._ELL_CACHE),
           "sharded_layouts": len(structure._SHARDED_ELL_CACHE),
           "push_resolutions": len(structure._RES_CACHE),
           "sharded_resolutions": len(structure._SHARDED_RES_CACHE),
           "graph_stats": len(structure._STATS_CACHE),
           "slot_maps": len(structure._SLOT_CACHE),
           "plans": _plan.plan_cache_size(),
           "feedback": _plan.feedback_cache_size()}
    try:
        from repro.kernels import ops as kops
        out["pallas_executors"] = kops.executor_cache_size()
    except ImportError:
        out["pallas_executors"] = 0
    return out


@dataclasses.dataclass
class ExecStats:
    rounds: int = 0
    iterations: int = 0
    edge_work: float = 0.0
    push_iters: int = 0             # runtime per-direction iteration counts
    pull_iters: int = 0             # (direction-aware engines; 0 elsewhere)
    resolve_work: float = 0.0       # push-resolution edge work (pallas
                                    # engines; Σ resolution-tile nnz under
                                    # "sorted", full rectangle under
                                    # "scatter", 0 on pull iterations;
                                    # summed over shards when sharded)
    gather_work: float = 0.0        # candidate slots read through the
                                    # permutation gather (pallas engines;
                                    # the dst-major rectangle's real slots
                                    # per push iteration under "sorted", 0
                                    # under "scatter", which performs no
                                    # permutation gather)
    activity_reads: int = 0         # contributing-tile table entries the
                                    # resolution activity test read
                                    # (contrib_entries × push iterations
                                    # under "sorted"; pallas engines)
    tiles_swept: int = 0            # live (block_v × block_e) tiles the
                                    # pull/push sweep kernels ran, summed
                                    # over iterations (pallas engines;
                                    # summed over shards when sharded)
    grid_steps: int = 0             # grid steps the pull, push and resolve
                                    # kernels launched, summed over
                                    # launches: the live-tile list's length
                                    # or the full grid's (pallas engines;
                                    # summed over shards when sharded)
    tile_shape: tuple = ()          # (block_v, block_e) of those tiles
    exit_change: float = 0.0        # the last iteration's change in the
                                    # stopping norm of the last round: the
                                    # L1 change Σ|Δ| of a component under
                                    # an L1 rule, else max |Δ| (float
                                    # components; 0 without the sentinel)
    shards: int = 0                 # shard count of the sharded engines
                                    # (distributed / pallas_sharded)
    shard_launches: int = 0         # traced pallas launches PER SHARD
                                    # summed over rounds (pallas_sharded:
                                    # one per direction branch per round)
    cross_combines: int = 0         # cross-shard state-combine collectives
                                    # executed (iterations × per-iteration
                                    # lex-level psums; pallas_sharded)
    shard_work: tuple = ()          # per-shard edge work ([k]; its sum is
                                    # edge_work's sharded contribution)
    engine_used: str = ""           # engine that actually produced the
                                    # result (differs from the request only
                                    # after a fallback)
    converged: bool = True          # False when a round exhausted max_iter
                                    # with live vertices (only observable
                                    # under on_nonconverge="ignore"/"warn" —
                                    # the continuous-batching scheduler's
                                    # retire-or-carry signal)
    fallbacks: tuple = ()           # (from_engine, to_engine, error) per
                                    # degradation step (guard.FallbackEvent)
    exec_retries: int = 0           # same-engine retries spent before each
                                    # success/fallback (ft.bounded_retry)
    plan: object = None             # the resolved core.plan.ExecutionPlan
                                    # this query lowered through — every
                                    # knob decision, inspectable after the
                                    # fact (None only on hand-built stats)


@dataclasses.dataclass
class ExecResult:
    value: object                  # final result (array for vertex queries)
    named: dict                    # bound intermediate results
    stats: ExecStats


def _valid_mask(x):
    xf = x.astype(jnp.float32)
    return jnp.isfinite(xf) & (jnp.abs(xf) < _BOT_CUTOFF)


def _vertex_reduce(op: str, vals, mask):
    vals = vals.astype(jnp.float32)
    if op == "collect":
        return mask
    ident = {"min": jnp.inf, "max": -jnp.inf, "sum": 0.0, "prod": 1.0}[op]
    masked = jnp.where(mask, vals, ident)
    fn = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum, "prod": jnp.prod}[op]
    return fn(masked)


def _source_overrides(round_, source) -> Optional[dict]:
    """{comp idx: source} re-sourcing every SOURCED component of a round to
    one query source (single-source programs: BFS/SSSP/WP/…).  Sourceless
    components (Paths(v)) are untouched — sourced-ness is structural."""
    if source is None:
        return None
    return {comp.idx: int(source) for comp in round_.components
            if comp.source is not None}


def _round_runtime(round_, synth):
    comps = iterate.comp_runtimes(round_, {k: v for k, v in synth.items()
                                           if not isinstance(k, tuple)})
    plans = [leaf.plan for leaf in round_.leaves]
    return comps, plans


@obs.span("grafs.validate")
def _validate_inputs(g, source=None, sources=None):
    """Graph structural validation + query-source range check (guarded
    execution, DESIGN.md §12).  Returns the cached ``GraphCheck`` so the
    termination-precondition probe can reuse the edge-value ranges."""
    from repro.graph import structure
    chk = structure.validate_graph(g)
    probe = []
    if source is not None:
        probe.append(source)
    if sources is not None:
        probe.extend(np.asarray(sources).ravel().tolist())
    for s in probe:
        s = int(s)
        if not 0 <= s < g.n:
            raise guard.GraphValidationError(
                f"query source {s} out of range [0, {g.n})")
    return chk


def _check_preconditions(chk, comps, plans):
    """Raise ``TerminationPreconditionError`` when the graph's actual
    edge-value ranges void the spec's synthesis-time termination proof
    (strengthened C10, §5.2) — min-plus on negative weights being the
    canonical never-terminating case.  In-contract graphs (w ≥ 0, c > 0)
    return immediately without probing."""
    if chk is None:
        return
    bad = _conditions.violated_preconditions(
        comps, plans, (chk.w_min, chk.w_max), (chk.c_min, chk.c_max))
    if bad:
        v = bad[0]
        raise guard.TerminationPreconditionError(
            f"termination precondition {v['condition']} violated for "
            f"component {v['component']} (op {v['op']}) on this graph "
            f"(w ∈ [{chk.w_min}, {chk.w_max}], c ∈ [{chk.c_min}, "
            f"{chk.c_max}]): {v['detail']} — the fixpoint may not "
            "terminate; fix the graph or run with validate=False",
            condition=v["condition"], component=v["component"],
            detail=v["detail"])


def _check_outcome(res, max_iter_eff, on_nonconverge):
    """Surface the structured convergence outcome of one finished round:
    a fired divergence sentinel raises ``DivergenceError``; exhausting
    ``max_iter`` with live vertices raises (or warns, per
    ``on_nonconverge``) ``NonConvergenceError`` with the exit diagnostics
    instead of returning a silent partial state.  Tracer-valued outcomes
    (batched results) are the callers' responsibility."""
    if on_nonconverge == "ignore":
        return
    divg = getattr(res, "diverged", False)
    conv = getattr(res, "converged", True)
    if isinstance(divg, (bool, np.bool_)) and divg:
        raise guard.DivergenceError(
            f"fixpoint diverged after {res.iterations} iterations: the "
            "NaN/Inf sentinel fired (values left the monoid's meaningful "
            "domain)", iterations=int(res.iterations))
    if isinstance(conv, (bool, np.bool_)) and not conv:
        active = int(getattr(res, "active_count", 0))
        resid = float(getattr(res, "residual", float("nan")))
        msg = (f"fixpoint exhausted max_iter={max_iter_eff} without "
               f"converging: {active} vertices still active after "
               f"{res.iterations} iterations, last-iteration residual "
               f"{resid:.3e}")
        if on_nonconverge == "warn":
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            return
        raise guard.NonConvergenceError(
            msg, iterations=int(res.iterations), max_iter=int(max_iter_eff),
            active_count=active, residual=resid)


def _check_batch_outcomes(res, src_list, max_iter_eff, on_nonconverge):
    """Per-query convergence outcomes of one batched round ([B]-valued
    ``converged``/``diverged``), naming the offending query sources."""
    if on_nonconverge == "ignore":
        return
    divg = np.asarray(res.diverged)
    conv = np.asarray(res.converged)
    if divg.any():
        bad = [src_list[i] for i in np.flatnonzero(divg)]
        raise guard.DivergenceError(
            f"batched fixpoint diverged for query sources {bad}: the "
            "NaN/Inf sentinel fired",
            iterations=int(np.asarray(res.iterations).max()))
    if not conv.all():
        bad = np.flatnonzero(~conv)
        acts = np.asarray(res.active_count)
        iters = np.asarray(res.iterations)
        msg = (f"batched fixpoint exhausted max_iter={max_iter_eff} for "
               f"query sources {[src_list[i] for i in bad]} "
               f"(active counts {[int(acts[i]) for i in bad]})")
        if on_nonconverge == "warn":
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            return
        raise guard.NonConvergenceError(
            msg, iterations=int(iters.max()), max_iter=int(max_iter_eff),
            active_count=int(acts[bad].sum()))


def _dispatch_guarded(call, engine, fallback, ft_config):
    """Run ``call(engine)``; on infrastructure-shaped failure
    (``guard.recoverable``) retry the SAME engine with a bounded budget,
    then degrade one step down ``guard.FALLBACK_CHAIN`` and repeat.  Guard
    verdicts and programming errors propagate unchanged.  Returns
    ``(result, engine_used, fallback_events, retries_used)``."""
    if not fallback:
        return call(engine), engine, (), 0
    from repro.runtime import ft as _ft
    retries = _FALLBACK_RETRIES if ft_config is None else ft_config.max_retries
    backoff = _FALLBACK_BACKOFF_S if ft_config is None else ft_config.backoff_s
    eng = engine
    events = []
    retries_used = 0
    while True:
        try:
            out, r = _ft.bounded_retry(lambda: call(eng), retries, backoff,
                                       retryable=guard.recoverable)
            return out, eng, tuple(events), retries_used + r
        except Exception as exc:
            retries_used += retries
            if not guard.recoverable(exc):
                raise
            nxt = guard.FALLBACK_CHAIN.get(eng)
            if nxt is None:
                raise
            events.append(guard.FallbackEvent(eng, nxt,
                                              f"{type(exc).__name__}: {exc}"))
            eng = nxt


def _rescale_warm_state(init_state, comps, n):
    """Guarded warm start of a NON-idempotent round from a previous solution
    (DESIGN.md §15): a (−) recompute round re-derives every vertex from its
    neighborhood each sweep and contracts to its unique attractive fixpoint
    from ANY finite state, so the warm state needs sanitizing, not
    re-deriving.  For mass-conserving "sum" components (PR-style) non-finite
    entries (values a structural edit invalidated) are replaced by the
    finite mean and the result rescaled to keep the retired answer's total
    mass — the fixpoint mass is graph-dependent (dangling-vertex leakage),
    so the previous converged mass, not an a-priori invariant, is the best
    unbiased seed after a small edit.  All-finite states pass bitwise
    untouched."""
    out = []
    for a, cr in zip(init_state, comps):
        arr = np.array(a)
        if cr.op == "sum":
            finite = np.isfinite(arr)
            if not finite.all():
                mass = float(arr[finite].sum()) if finite.any() else 0.0
                fill = mass / max(1, int(finite.sum()))
                arr = np.where(finite, arr, fill).astype(arr.dtype)
                tot = float(arr.sum())
                if np.isfinite(tot) and tot != 0.0 and mass != 0.0:
                    arr = (arr * (mass / tot)).astype(arr.dtype)
        out.append(jnp.asarray(arr))
    return tuple(out)


def _run_iteration(g, round_: FusedRound, engine: str, plan: ExecutionPlan,
                   mesh, axes, max_iter, tol, source=None, graph_check=None,
                   checkpoint_every=None, ckpt_dir=None, resume=False,
                   init_state=None, delta=None):
    """One iteration round under ``plan`` on ``engine`` — which differs from
    ``plan.engine`` only while walking the guard fallback chain, in which
    case the engine-dependent plan fields re-resolve (``degrade_plan``)."""
    eff = _plan.degrade_plan(plan, engine)
    model = eff.model
    comps, plans = _round_runtime(round_, synthesize_round(round_))
    _check_preconditions(graph_check, comps, plans)
    sources = _source_overrides(round_, source)
    if engine in ("pull", "push"):
        m = model or ("pull+" if engine == "pull" else "push+")
        res = iterate.iterate_graph(g, comps, plans, model=m,
                                    max_iter=max_iter, tol=tol,
                                    sources=sources)
    elif engine == "adaptive":
        res = iterate.iterate_adaptive(g, comps, plans, max_iter=max_iter,
                                       tol=tol, sources=sources)
    elif engine == "dense":
        res = iterate.iterate_dense(g, comps, plans, max_iter=max_iter,
                                    tol=tol, sources=sources)
    elif engine == "distributed":
        assert mesh is not None, "distributed engine needs a mesh"
        res = iterate.iterate_distributed(g, comps, plans, mesh, axes=axes,
                                          model=model or "pull+",
                                          max_iter=max_iter, tol=tol,
                                          sources=sources)
    elif engine == "pallas":
        from repro.kernels import ops as kops
        ist = init_state
        if (delta is not None and ist is not None
                and not all(iterate.plan_idempotent(p) for p in plans)):
            ist = _rescale_warm_state(ist, comps, g.n)
        res = kops.iterate_pallas(g, comps, plans, max_iter=max_iter, tol=tol,
                                  sources=sources, plan=eff,
                                  checkpoint_every=checkpoint_every,
                                  ckpt_dir=ckpt_dir, resume=resume,
                                  init_state=ist, delta=delta)
    elif engine == "pallas_sharded":
        assert mesh is not None, "pallas_sharded engine needs a mesh"
        from repro.kernels import ops as kops
        res = kops.iterate_pallas_sharded(
            g, comps, plans, mesh, axes=axes,
            max_iter=max_iter, tol=tol, sources=sources, plan=eff)
    else:
        raise ValueError(f"unknown engine {engine}")
    return res, comps


@obs.span("grafs.finish")
def _finish_round(g, round_: FusedRound, env: dict):
    """mlet (vectorized per-vertex maps) + rlet (masked vertex reductions) +
    the round's output expression, over an env already holding the leaf
    results.  Shared by the sequential and batched program runners."""
    for name, expr in round_.maps:
        env[name] = eval_expr(expr, env, jnp)
    for name, op, m_name, cond_name in round_.vreduces:
        vals = jnp.asarray(env[m_name])
        vals = jnp.broadcast_to(vals, (g.n,)) if vals.ndim == 0 else vals
        mask = _valid_mask(vals)
        if cond_name is not None:
            cond = jnp.asarray(env[cond_name])
            mask = mask & jnp.broadcast_to(cond.astype(bool), (g.n,))
        env[name] = _vertex_reduce(op, vals, mask)
    if getattr(round_, "multi_out", None):
        # fuse_many round: every paired request's own answer from the ONE
        # shared execution — {key: scalar}, no re-execution.
        return {key: eval_expr(e, env, jnp) for key, e in round_.multi_out}
    return eval_expr(round_.out, env, jnp)


def _accumulate(stats: ExecStats, res) -> None:
    stats.rounds += 1
    stats.iterations += res.iterations
    stats.edge_work += res.edge_work
    conv = getattr(res, "converged", True)
    if isinstance(conv, (bool, np.bool_)):      # tracer-valued on vmapped runs
        stats.converged = stats.converged and bool(conv)
    pi = getattr(res, "push_iters", 0)
    li = getattr(res, "pull_iters", 0)
    rw = getattr(res, "resolve_work", 0.0)
    gw = getattr(res, "gather_work", 0.0)
    ar = getattr(res, "activity_reads", 0)
    if isinstance(pi, int):
        stats.push_iters += pi
    if isinstance(li, int):
        stats.pull_iters += li
    if isinstance(rw, (int, float)):
        stats.resolve_work += float(rw)
    if isinstance(gw, (int, float)):
        stats.gather_work += float(gw)
    if isinstance(ar, int):
        stats.activity_reads += ar
    ts = getattr(res, "tiles_swept", 0)
    if isinstance(ts, int):
        stats.tiles_swept += ts
    gs = getattr(res, "grid_steps", 0)
    if isinstance(gs, int):
        stats.grid_steps += gs
    stats.tile_shape = getattr(res, "tile_shape", stats.tile_shape)
    rs = getattr(res, "residual", 0.0)
    if isinstance(rs, (int, float)):
        stats.exit_change = float(rs)
    stats.shards = max(stats.shards, getattr(res, "shards", 0))
    stats.shard_launches += getattr(res, "shard_launches", 0)
    stats.cross_combines += getattr(res, "cross_combines", 0)
    sw = tuple(getattr(res, "shard_work", ()))
    if sw:
        if len(stats.shard_work) == len(sw):
            stats.shard_work = tuple(a + b
                                     for a, b in zip(stats.shard_work, sw))
        elif not stats.shard_work:
            stats.shard_work = sw
        else:                       # shard count changed between rounds
            stats.shard_work = stats.shard_work + sw


@obs.span("grafs.run_program")
def run_program(g, prog: FusedProgram, engine: Optional[str] = None,
                model: Optional[str] = None, mesh=None, axes=("data",),
                max_iter: Optional[int] = None, tol: float = 0.0,
                source: Optional[int] = None,
                push_resolution: Optional[str] = None,
                switch_k="auto",
                shard_strategy: Optional[str] = None,
                validate: bool = True,
                on_nonconverge: str = "raise",
                fallback: bool = False, ft_config=None,
                divergence_sentinel: bool = True,
                checkpoint_every: Optional[int] = None,
                ckpt_dir=None, resume: bool = False,
                init_state=None, delta=None, return_state: bool = False,
                adaptive: bool = False,
                plan: Optional[ExecutionPlan] = None,
                explain: bool = False):
    """Execute a fused program.  ``source`` optionally re-sources every
    sourced component to one query source — the program (and with it every
    compiled-executor cache entry) is source-generic, so querying another
    source never re-fuses, re-synthesizes or retraces (DESIGN.md §8).

    Every knob kwarg is a HINT to the query planner (``core.plan``,
    DESIGN.md §14): ``plan_execution`` resolves engine (None → "pull",
    "auto" → statistics-driven), direction, ``switch_k`` (the Gemini rule;
    None falls back to the frontier-fraction threshold), ``push_resolution``
    ("sorted"/"scatter", pallas engine only) and ``shard_strategy``
    ("contiguous" | "dst_hash", ``pallas_sharded``) into one frozen
    ``ExecutionPlan``, normalized exactly once; explicit hints always win,
    and default plans reproduce the documented heuristics bitwise.  The
    resolved plan is recorded in ``ExecResult.stats.plan``; ``explain=True``
    skips execution and returns the ``PlanExplanation`` (plan + the graph
    statistics and per-field reasons behind it).  ``adaptive=True`` lets
    unpinned knobs consult the recorded-stats feedback of this
    (graph, kind).  A pre-resolved ``plan=`` bypasses planning entirely.

    Guarded execution (DESIGN.md §12): ``validate`` (default on) checks the
    graph's structural contract, the query source's range, and the per-round
    termination preconditions (C10 against the actual edge-value ranges)
    before any kernel launches.  ``on_nonconverge`` ("raise"/"warn"/
    "ignore") governs what a round that exhausts ``max_iter`` — or trips
    the divergence sentinel — does.  ``fallback=True`` degrades
    infrastructure failures down ``guard.FALLBACK_CHAIN``
    (pallas_sharded → pallas → adaptive) with bounded retry (``ft_config``
    tunes the budget), recording every event in the stats.
    ``checkpoint_every``/``ckpt_dir``/``resume`` thread the chunked
    checkpointed fixpoint (pallas engine only).

    Incremental execution (DESIGN.md §15; pallas engine, single-round
    programs): ``init_state=prev`` warm-starts the fixpoint from a previous
    solution and ``delta=`` seeds the frontier with only the vertices whose
    values may have changed — pass a ``graph.mutate.MutationDelta`` (its
    ``touched`` set becomes the frontier seed AND its mutation-size
    statistics feed the planner's ``incremental`` knob: small touched sets
    resolve to ``"delta"``, large ones — or idempotent rounds after
    deletions, whose stale values cannot retract — to ``"full"``, which
    runs the planned cold recompute ignoring the warm hints) or a raw
    vertex-id array (always honored verbatim).  Idempotent rounds converge
    bitwise-equal to a cold recompute on the mutated graph; non-idempotent
    (PR-style) rounds take the guarded rescaled-warm-start path and need
    ``tol > 0``.  ``return_state=True`` returns ``(result, state)`` with the
    round's final per-component ``[n]`` state — feed it back as the next
    edit's ``init_state``."""
    mutation = None
    delta_ids = delta
    if delta is not None and hasattr(delta, "touched"):
        mutation = delta
        delta_ids = np.asarray(mutation.touched)
    if plan is None or explain:
        planned = plan_execution(
            g, prog, engine=engine, model=model, mesh=mesh, axes=axes,
            switch_k=switch_k, push_resolution=push_resolution,
            shard_strategy=shard_strategy, validate=validate,
            on_nonconverge=on_nonconverge, fallback=fallback,
            divergence_sentinel=divergence_sentinel, adaptive=adaptive,
            mutation=mutation,
            default_engine="pallas" if (init_state is not None
                                        or delta is not None or return_state)
            else "pull", explain=explain)
        if explain:
            return planned
        plan = planned
    if mutation is not None and plan.incremental == "full":
        # The planner judged the warm+delta path unsound or unprofitable
        # (touched set too large, or an idempotent round after deletions —
        # stale monotone values cannot retract): planned full recompute,
        # warm hints dropped.  The decision is visible in stats.plan.
        init_state = None
        delta_ids = None
    if (checkpoint_every is not None or resume) and plan.engine != "pallas":
        raise ValueError("checkpointed fixpoints are a pallas-engine "
                         f"feature; got engine={plan.engine!r}")
    if init_state is not None or delta_ids is not None or return_state:
        if plan.engine != "pallas":
            raise ValueError(
                "init_state/delta/return_state warm-start hooks are a "
                f"pallas-engine feature; got engine={plan.engine!r}")
        iter_rounds = [r for _, r in prog.rounds if r.leaves]
        if len(prog.rounds) != 1 or len(iter_rounds) != 1:
            raise ValueError(
                "init_state/delta/return_state need a single-round program "
                f"(one iteration round, no LetRound chain); got "
                f"{len(prog.rounds)} rounds")
    chk = _validate_inputs(g, source=source) if plan.validate else None
    max_iter_eff = max_iter if max_iter is not None else 2 * g.n + 4
    stats = ExecStats(engine_used=plan.engine, plan=plan)
    named: dict = {}
    final = None
    state_out = None
    for bind_name, round_ in prog.rounds:
        env: dict = dict(named)
        if round_.leaves:
            def call(eng, round_=round_):
                return _run_iteration(
                    g, round_, eng, plan, mesh, axes, max_iter, tol,
                    source=source, graph_check=chk,
                    checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir,
                    resume=resume, init_state=init_state, delta=delta_ids)
            (res, comps), eng_used, events, retries = \
                _dispatch_guarded(call, plan.engine, plan.fallback, ft_config)
            stats.engine_used = eng_used
            stats.fallbacks += tuple(ev.as_tuple() for ev in events)
            stats.exec_retries += retries
            _accumulate(stats, res)
            _check_outcome(res, max_iter_eff, plan.on_nonconverge)
            if return_state:
                state_out = tuple(np.asarray(s) for s in res.state)
            for leaf in round_.leaves:
                env[leaf.name] = res.state[plan_output(leaf.plan)]
        out = _finish_round(g, round_, env)
        if bind_name is not None:
            prefix = "$vec:" if round_.out_kind == "vertex" else "$scalar:"
            named[prefix + bind_name] = out
        final = out
    with obs.span("grafs.finish"):
        _plan.record_feedback(g, plan.kind, stats)
    result = ExecResult(value=final, named=named, stats=stats)
    if return_state:
        return result, state_out
    return result


def run_program_batch(g, prog: FusedProgram, sources: Sequence,
                      engine: Optional[str] = None, model: Optional[str] = None,
                      mesh=None, axes=("data",),
                      max_iter: Optional[int] = None, tol: float = 0.0,
                      push_resolution: Optional[str] = None,
                      switch_k="auto",
                      validate: bool = True,
                      on_nonconverge: str = "raise",
                      fallback: bool = False, ft_config=None,
                      init_state=None, return_state=False,
                      adaptive: bool = False,
                      plan: Optional[ExecutionPlan] = None,
                      explain: bool = False):
    """Serve B concurrent single-source queries of one program in ONE
    compiled launch per round (DESIGN.md §9).

    ``sources`` is a [B] sequence of query sources; every sourced component
    of every round is re-sourced per batch element (single-source programs —
    BFS/SSSP/WP sweeps and friends).  On the pallas engine the iteration
    rounds run as ``jax.vmap``-batched fixpoints over the shared blocked-ELL
    layout — per-query convergence via the active mask, results bit-identical
    to B sequential ``run_program(..., source=s)`` calls, and ONE executor
    cache entry regardless of B.  Other engines fall back to the sequential
    loop (the reference semantics this path is tested against).

    Returns a list of B ``ExecResult``s, each with its own per-query stats
    (iterations, edge work, push/pull split).

    Guarded execution mirrors ``run_program``: upfront validation (graph +
    every batch source), per-round termination preconditions, per-QUERY
    convergence outcomes, and — with ``fallback=True`` — degradation of a
    recoverably-failing batched pallas launch to the sequential reference
    loop (recorded in each query's stats).

    Continuous-batching hooks (DESIGN.md §13; pallas engine, single-round
    programs only): ``init_state`` warm-starts every batch slot from one
    per-component ``[B, n]`` array (an earlier chunk's carried state, with
    fresh ``batch_init_state`` rows spliced in where new queries joined);
    ``return_state=True`` returns ``(results, state)`` where ``state`` is
    the round's final per-component ``[B, n]`` state — feed it back as the
    next chunk's ``init_state``.  Bound ``max_iter`` to the scheduler's
    chunk quantum and read each query's ``stats.converged`` (under
    ``on_nonconverge="ignore"``) to decide retire-vs-carry per slot.

    Knob kwargs are planner HINTS (``core.plan``, DESIGN.md §14), resolved
    through ``plan_execution(default_engine="pallas", batch=B)`` exactly as
    in ``run_program``; the resolved plan — including the explicit
    ``batch_lane`` decision ("vmapped" one-launch batch vs. the recorded
    "sequential" degradation of non-pallas engines) — lands in every
    query's ``stats.plan``."""
    src_arr = np.asarray(sources)
    if src_arr.ndim != 1:
        raise ValueError(
            f"run_program_batch sources must be a [B] vector of query "
            f"sources, got shape {src_arr.shape}; per-component [B, n_comps] "
            "batching is the kernels-layer iterate_pallas_batch API")
    if plan is None or explain:
        planned = plan_execution(
            g, prog, engine=engine, model=model, mesh=mesh, axes=axes,
            switch_k=switch_k, push_resolution=push_resolution,
            batch=len(src_arr), validate=validate,
            on_nonconverge=on_nonconverge, fallback=fallback,
            adaptive=adaptive, default_engine="pallas", explain=explain)
        if explain:
            return planned
        plan = planned
    if init_state is not None or return_state:
        if plan.engine != "pallas":
            raise ValueError("init_state/return_state are pallas-engine "
                             f"continuous-batching hooks; got {plan.engine!r}")
        if plan.fallback:
            raise ValueError("init_state/return_state cannot degrade to the "
                             "sequential fallback loop (a warm-started batch "
                             "has no per-query equivalent there); run with "
                             "fallback=False")
        iter_rounds = [r for _, r in prog.rounds if r.leaves]
        if len(prog.rounds) != 1 or len(iter_rounds) != 1:
            raise ValueError(
                "init_state/return_state need a single-round program (one "
                f"iteration round, no LetRound chain); got "
                f"{len(prog.rounds)} rounds")
    chk = _validate_inputs(g, sources=src_arr) if plan.validate else None
    max_iter_eff = max_iter if max_iter is not None else 2 * g.n + 4
    src_list = [int(s) for s in src_arr]
    B = len(src_list)
    if plan.engine != "pallas":
        # The planner already recorded this as an explicit decision
        # (batch_lane="sequential"); the guard event makes it visible in the
        # same place every other degradation lands (satellite 3).
        ev = guard.batch_degradation(plan.engine, B).as_tuple()
        outs = [run_program(g, prog, mesh=mesh, axes=axes, max_iter=max_iter,
                            tol=tol, source=s, ft_config=ft_config, plan=plan)
                for s in src_list]
        for o in outs:
            o.stats.fallbacks = (ev,) + o.stats.fallbacks
        return outs
    from repro.kernels import ops as kops
    stats = [ExecStats(engine_used="pallas", plan=plan) for _ in range(B)]
    named: list = [{} for _ in range(B)]
    finals: list = [None] * B
    state_out = None
    for bind_name, round_ in prog.rounds:
        envs = [dict(nm) for nm in named]
        if round_.leaves:
            comps, plans = _round_runtime(round_, synthesize_round(round_))
            _check_preconditions(chk, comps, plans)
            try:
                res = kops.iterate_pallas_batch(
                    g, comps, plans, src_list, max_iter=max_iter, tol=tol,
                    init_state=init_state, plan=plan)
            except Exception as exc:
                if not plan.fallback or not guard.recoverable(exc):
                    raise
                # batched launch degraded: the whole batch re-runs through
                # the sequential reference loop, the event recorded on
                # every query's stats.
                ev = guard.FallbackEvent(
                    "pallas", "adaptive",
                    f"{type(exc).__name__}: {exc}").as_tuple()
                outs = [run_program(g, prog, engine="adaptive", model=None,
                                    max_iter=max_iter, tol=tol, source=s,
                                    validate=plan.validate,
                                    on_nonconverge=plan.on_nonconverge,
                                    fallback=plan.fallback,
                                    ft_config=ft_config) for s in src_list]
                for o in outs:
                    o.stats.fallbacks = (ev,) + o.stats.fallbacks
                    o.stats.engine_used = "adaptive"
                return outs
            _check_batch_outcomes(res, src_list, max_iter_eff,
                                  plan.on_nonconverge)
            iters = np.asarray(res.iterations)
            works = np.asarray(res.edge_work)
            pushes = np.asarray(res.push_iters)
            res_ws = np.asarray(res.resolve_work)
            gat_ws = np.asarray(res.gather_work)
            acts = np.asarray(res.activity_reads)
            tiles = np.asarray(res.tiles_swept)
            steps = np.asarray(res.grid_steps)
            resids = np.asarray(res.residual)
            convs = np.asarray(res.converged)
            for b in range(B):
                st = stats[b]
                st.rounds += 1
                st.iterations += int(iters[b])
                st.edge_work += float(works[b])
                st.push_iters += int(pushes[b])
                st.pull_iters += int(iters[b]) - int(pushes[b])
                st.resolve_work += float(res_ws[b])
                st.gather_work += float(gat_ws[b])
                st.activity_reads += int(acts[b])
                st.tiles_swept += int(tiles[b])
                st.grid_steps += int(steps[b])
                st.tile_shape = res.tile_shape
                st.exit_change = float(resids[b])
                st.converged = st.converged and bool(convs[b])
                for leaf in round_.leaves:
                    envs[b][leaf.name] = res.state[plan_output(leaf.plan)][b]
            if return_state:
                state_out = res.state
        for b in range(B):
            out = _finish_round(g, round_, envs[b])
            if bind_name is not None:
                prefix = "$vec:" if round_.out_kind == "vertex" else "$scalar:"
                named[b][prefix + bind_name] = out
            finals[b] = out
    for st in stats:
        _plan.record_feedback(g, plan.kind, st)
    results = [ExecResult(value=finals[b], named=named[b], stats=stats[b])
               for b in range(B)]
    if return_state:
        return results, state_out
    return results


def batchable_program(prog: FusedProgram) -> bool:
    """True when a fused program fits the continuous-batching contract
    (DESIGN.md §13): exactly one round, with an iteration (leaves), every
    plan idempotent (monotone (+) rounds — the unique-fixpoint argument that
    makes chunked warm-resume bitwise-safe; (−) recompute rounds depend on
    the iteration count and must run monolithically), and every component
    sourced (so a per-slot source re-sources the whole round).  Programs
    that fail this run solo or through the scalar fuse_many lane."""
    if len(prog.rounds) != 1:
        return False
    _, round_ = prog.rounds[0]
    if not round_.leaves:
        return False
    if not all(iterate.plan_idempotent(leaf.plan) for leaf in round_.leaves):
        return False
    return all(c.source is not None for c in round_.components)


def batch_init_state(g, prog: FusedProgram, sources: Sequence) -> tuple:
    """Fresh per-component ``[B, n]`` initial state blocks for a batch of
    query sources of a single-round program — the rows a continuous-batching
    scheduler splices into its carried state when new queries take over
    retired slots (``run_program_batch(init_state=...)``).  Row b is exactly
    the C1/C2 initial state of a solo ``source=sources[b]`` run."""
    iter_rounds = [r for _, r in prog.rounds if r.leaves]
    if len(iter_rounds) != 1:
        raise ValueError("batch_init_state needs a single-round program; "
                         f"got {len(iter_rounds)} iteration rounds")
    round_ = iter_rounds[0]
    comps, _plans = _round_runtime(round_, synthesize_round(round_))
    rows = [iterate._init_state(comps, g.n,
                                _source_overrides(round_, int(s)))
            for s in sources]
    return tuple(jnp.stack([r[i] for r in rows])
                 for i in range(len(comps)))


# ---------------------------------------------------------------------------
# Direct-kernel execution (PageRank and other Fig. 4b style kernel sets).
# ---------------------------------------------------------------------------

def run_direct(g, dk: DirectKernels, engine: Optional[str] = None,
               mesh=None, axes=("data",),
               model: Optional[str] = None,
               source: Optional[int] = None,
               sources: Optional[Sequence] = None,
               push_resolution: Optional[str] = None,
               switch_k="auto",
               shard_strategy: Optional[str] = None,
               validate: bool = True,
               on_nonconverge: str = "raise",
               fallback: bool = False, ft_config=None,
               divergence_sentinel: bool = True,
               checkpoint_every: Optional[int] = None,
               ckpt_dir=None, resume: bool = False,
               init_state=None, delta=None,
               adaptive: bool = False,
               plan: Optional[ExecutionPlan] = None,
               explain: bool = False):
    """Execute a direct kernel set on one engine.

    ``model`` optionally pins the pallas sweep direction ("pull"/"push");
    the default is the engine's documented behaviour — the per-iteration
    frontier-density heuristic for idempotent kernels, full-recompute for
    the rest — NOT a forced direction.  ``source`` overrides ``dk.source``
    for one query; ``sources`` runs a [B] batch of queries (one vmapped
    launch on the pallas engine, a sequential loop elsewhere) and returns a
    list of per-query ``ExecResult``s.  Both need a source-generic kernel
    set (``dk.source`` not None).

    As in ``run_program``, every knob kwarg is a hint resolved by the query
    planner into one frozen ``ExecutionPlan`` (recorded in ``stats.plan``;
    ``explain=True`` returns the ``PlanExplanation`` without executing;
    ``plan=`` supplies a pre-resolved plan; ``adaptive=True`` opts into the
    recorded-stats feedback for unpinned knobs).

    Guarded execution matches ``run_program``: ``validate`` /
    ``on_nonconverge`` / ``fallback`` + ``ft_config`` /
    ``divergence_sentinel``, plus the chunked-checkpoint knobs
    (``checkpoint_every``/``ckpt_dir``/``resume``/``init_state``, pallas
    engine only; ``init_state`` warm-starts the fixpoint from per-component
    [n] arrays).  ``delta=`` (a ``mutate.MutationDelta`` or raw vertex-id
    array, with ``init_state``) takes the incremental path exactly as in
    ``run_program`` — for the non-idempotent kernels this engine mostly
    serves (PR-style), that is the guarded rescaled warm start, converging
    to the same tolerance-fixed answer as a cold run (DESIGN.md §15)."""
    from repro.core.fusion import Prim

    mutation = None
    delta_ids = delta
    if delta is not None and hasattr(delta, "touched"):
        mutation = delta
        delta_ids = np.asarray(mutation.touched)
    if plan is None or explain:
        planned = plan_execution(
            g, dk, engine=engine, model=model, mesh=mesh, axes=axes,
            switch_k=switch_k, push_resolution=push_resolution,
            shard_strategy=shard_strategy,
            batch=None if sources is None else len(sources),
            validate=validate, on_nonconverge=on_nonconverge,
            fallback=fallback, divergence_sentinel=divergence_sentinel,
            adaptive=adaptive, mutation=mutation,
            default_engine="pallas" if (init_state is not None
                                        or delta is not None) else "pull",
            explain=explain)
        if explain:
            return planned
        plan = planned
    if mutation is not None and plan.incremental == "full":
        init_state = None
        delta_ids = None
    if delta_ids is not None and sources is not None:
        raise ValueError("delta warm starts are a solo-query path; "
                         "batched sources cannot share one touched set")
    if (checkpoint_every is not None or resume or init_state is not None
            or delta_ids is not None) and plan.engine != "pallas":
        raise ValueError("checkpointed/warm-started fixpoints are a "
                         f"pallas-engine feature; got engine={plan.engine!r}")
    if (source is not None or sources is not None) and dk.source is None:
        raise ValueError(
            "run_direct source overrides need a source-generic DirectKernels "
            "(init_fn(v, s) with source=...); this kernel set is sourceless "
            "or bakes its source into the init closure")
    if dk.source is not None and iterate._init_arity(dk.init_fn) < 2:
        raise ValueError(
            "DirectKernels.source requires a source-generic init_fn(v, s); "
            "a single-argument closure bakes its own source, so re-sourcing "
            "would move the ⊥-mask without moving the init value")
    chk = _validate_inputs(g, source=source, sources=sources) \
        if plan.validate else None
    max_iter_eff = dk.max_iter if dk.max_iter is not None else 2 * g.n + 4
    comp = iterate.CompRuntime(
        idx=0, op=dk.rop, dtype=iterate.DTYPES[dk.dtype],
        p_fn=dk.p_fn, init_fn=dk.init_fn, source=dk.source, e_fn=dk.e_fn)
    plans = [Prim(dk.rop, 0)]
    _check_preconditions(chk, [comp], plans)
    if sources is not None:
        if plan.engine == "pallas":
            from repro.kernels import ops as kops
            try:
                res = kops.iterate_pallas_batch(
                    g, [comp], plans, sources,
                    max_iter=dk.max_iter, tol=dk.tol, plan=plan)
            except Exception as exc:
                if not plan.fallback or not guard.recoverable(exc):
                    raise
                ev = guard.FallbackEvent(
                    "pallas", "adaptive",
                    f"{type(exc).__name__}: {exc}").as_tuple()
                outs = [run_direct(g, dk, engine="adaptive", model=None,
                                   source=int(s), validate=plan.validate,
                                   on_nonconverge=plan.on_nonconverge,
                                   fallback=plan.fallback,
                                   ft_config=ft_config)
                        for s in sources]
                for o in outs:
                    o.stats.fallbacks = (ev,) + o.stats.fallbacks
                    o.stats.engine_used = "adaptive"
                return outs
            _check_batch_outcomes(res, [int(s) for s in sources],
                                  max_iter_eff, plan.on_nonconverge)
            iters = np.asarray(res.iterations)
            works = np.asarray(res.edge_work)
            pushes = np.asarray(res.push_iters)
            res_ws = np.asarray(res.resolve_work)
            gat_ws = np.asarray(res.gather_work)
            acts = np.asarray(res.activity_reads)
            tiles = np.asarray(res.tiles_swept)
            steps = np.asarray(res.grid_steps)
            resids = np.asarray(res.residual)
            outs = [ExecResult(
                value=res.state[0][b], named={},
                stats=ExecStats(rounds=1, iterations=int(iters[b]),
                                edge_work=float(works[b]),
                                push_iters=int(pushes[b]),
                                pull_iters=int(iters[b]) - int(pushes[b]),
                                resolve_work=float(res_ws[b]),
                                gather_work=float(gat_ws[b]),
                                activity_reads=int(acts[b]),
                                tiles_swept=int(tiles[b]),
                                grid_steps=int(steps[b]),
                                tile_shape=res.tile_shape,
                                exit_change=float(resids[b]),
                                engine_used="pallas", plan=plan))
                for b in range(len(iters))]
            for o in outs:
                _plan.record_feedback(g, plan.kind, o.stats)
            return outs
        # Non-pallas engines have no batched fixpoint: the planner resolved
        # batch_lane="sequential" and the guard event records the
        # degradation on every query (satellite 3).
        ev = guard.batch_degradation(plan.engine, len(sources)).as_tuple()
        outs = [run_direct(g, dk, mesh=mesh, axes=axes, source=int(s),
                           ft_config=ft_config, plan=plan)
                for s in sources]
        for o in outs:
            o.stats.fallbacks = (ev,) + o.stats.fallbacks
        return outs

    src_over = None if source is None else {0: int(source)}
    # frontier-masked (+) models for idempotent kernels (BFS/CC/SSSP/WP);
    # full-recompute (−) for non-idempotent / epilogue kernels (PageRank)
    idempotent = dk.rop in iterate._IDEMPOTENT_OPS and dk.e_fn is None
    if delta_ids is not None and init_state is not None and not idempotent:
        init_state = _rescale_warm_state(init_state, [comp], g.n)

    def call(engine):
        eff = _plan.degrade_plan(plan, engine)
        pull_like = engine in ("pull", "dense", "distributed")
        eng_model = ("pull+" if pull_like else "push+") if idempotent else \
            ("pull-" if pull_like else "push-")
        if engine in ("pull", "push"):
            return iterate.iterate_graph(g, [comp], plans, model=eng_model,
                                         max_iter=dk.max_iter, tol=dk.tol,
                                         sources=src_over)
        if engine == "adaptive":
            return iterate.iterate_adaptive(g, [comp], plans,
                                            max_iter=dk.max_iter, tol=dk.tol,
                                            sources=src_over)
        if engine == "dense":
            return iterate.iterate_dense(g, [comp], plans,
                                         max_iter=dk.max_iter,
                                         tol=dk.tol, sources=src_over)
        if engine == "distributed":
            assert mesh is not None, "distributed engine needs a mesh"
            return iterate.iterate_distributed(
                g, [comp], plans, mesh, axes=axes, model="pull-",
                max_iter=dk.max_iter, tol=dk.tol, sources=src_over)
        if engine == "pallas":
            # The engine's documented default: per-iteration direction
            # heuristic for idempotent kernels (pull− recompute otherwise),
            # forced only by an explicit model — NOT derived from pull_like,
            # which omits pallas and used to pin push for every direct
            # kernel.
            from repro.kernels import ops as kops
            return kops.iterate_pallas(
                g, [comp], plans, max_iter=dk.max_iter, tol=dk.tol,
                sources=src_over,
                checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir,
                resume=resume, init_state=init_state, delta=delta_ids,
                plan=eff)
        if engine == "pallas_sharded":
            assert mesh is not None, "pallas_sharded engine needs a mesh"
            from repro.kernels import ops as kops
            return kops.iterate_pallas_sharded(
                g, [comp], plans, mesh, axes=axes,
                max_iter=dk.max_iter, tol=dk.tol,
                sources=src_over, plan=eff)
        raise ValueError(engine)

    res, eng_used, events, retries = _dispatch_guarded(call, plan.engine,
                                                       plan.fallback,
                                                       ft_config)
    stats = ExecStats(engine_used=eng_used,
                      fallbacks=tuple(ev.as_tuple() for ev in events),
                      exec_retries=retries, plan=plan)
    _accumulate(stats, res)
    _check_outcome(res, max_iter_eff, plan.on_nonconverge)
    _plan.record_feedback(g, plan.kind, stats)
    return ExecResult(value=res.state[0], named={}, stats=stats)
