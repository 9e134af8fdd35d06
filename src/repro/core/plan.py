"""ExecutionPlan: the query planner of the GraFS executor.

Grafs synthesizes *kernels* from specs; this module extends the same idea
to *execution strategy* (GraphIt's schedule/algorithm decoupling, GraphMat's
one-tuned-backend mapping): every knob the engines used to thread by hand —
engine choice, sweep direction, the Gemini ``switch_k``, push resolution,
shard strategy, batching, validation and fallback policy — is resolved in
ONE place, ``plan_execution``, from cached per-graph statistics
(``structure.graph_stats``) with caller kwargs acting as hints/overrides
that are normalized exactly once.  The resolved ``ExecutionPlan`` is frozen
and hashable: the engine entry points lower through it, ``ops.iterate_pallas*``
*asserts* (not re-parses) its fields, and the compiled-executor cache keys
derive from it, so identical decisions hit identical cache entries.

Default plans reproduce the documented heuristics bitwise — Gemini
``SWITCH_K``, ``"sorted"`` resolution, ``"auto"`` direction — so planned
execution is bit-identical to the historical explicit-kwarg paths.

A recorded-stats feedback cache closes the loop (DESIGN.md §14): each
executed query records its observed push/pull split, resolve work and
convergence per (graph, query kind); subsequent queries that opt in
(``adaptive=True``) get a ``switch_k``/resolution adjusted within bounded
factors of the defaults.  Adaptation is restricted to idempotent rounds —
where push and pull sweeps are bitwise-interchangeable per iteration, so a
different direction sequence can never change the fixpoint value — and the
cache is LRU-bounded and evicted per graph via ``clear_graph_plans`` /
``engine.clear_graph_caches``.
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from typing import Optional

from repro import obs
from repro.core import iterate
from repro.core.fusion import FusedProgram, Lex
from repro.core.synthesis import DirectKernels

# ---------------------------------------------------------------------------
# Documented knob defaults (moved here from kernels/ops.py, which re-exports
# them — the planner is the single owner of knob semantics).
# ---------------------------------------------------------------------------

DENSE_FRONTIER = 0.05      # documented FALLBACK switch point (switch_k=None):
                           # frontier fraction above which the pull sweep
                           # wins (dense reads beat frontier-proportional
                           # row skipping)

SWITCH_K = 20.0            # the default Gemini rule: push while the
                           # frontier's outgoing edge count |E_frontier|
                           # (Σ out_deg over active vertices — degree data
                           # already in the layout) stays ≤ |E| / k.  This
                           # is Gemini's actual criterion (edge mass, not
                           # vertex fraction): a few active hubs can carry
                           # pull-worthy edge volume, and many active leaves
                           # can still be push-cheap.  Override per query
                           # with switch_k=<float>; switch_k=None falls back
                           # to the DENSE_FRONTIER vertex-fraction rule.

PUSH_RESOLUTION = "sorted"  # default dst-keyed resolution of the push
                            # sweep: "sorted" = dst-sorted segment-reduce
                            # tile pass (frontier-proportional, DESIGN.md
                            # §10); "scatter" = full-rectangle XLA scatter
                            # (the reference/fallback path)

# Feedback-adaptation bounds: an adapted switch_k never leaves
# [SWITCH_K / ADAPT_SPAN, SWITCH_K * ADAPT_SPAN], and the push-fraction
# thresholds that move it are deliberately coarse (a 2× step per signal).
ADAPT_SPAN = 4.0
ADAPT_PUSH_HI = 0.75        # ≥ this push fraction → the switch under-pushes
                            # never mattered; probe pull earlier (k / 2)
ADAPT_PUSH_LO = 0.25        # ≤ this push fraction (with pushes observed) →
                            # push rarely won; raise the bar (k * 2)

ENGINES = ("pull", "push", "adaptive", "dense", "pallas", "distributed",
           "pallas_sharded")

INCREMENTAL_DELTA = 0.05    # incremental-execution threshold (DESIGN.md §15):
                            # a mutation batch editing ≤ this fraction of |E|
                            # plans the warm+delta propagation; a larger edit
                            # plans a full recompute (the touched frontier
                            # would sweep most of the graph anyway, and the
                            # warm state buys nothing over the identity init)


# ---------------------------------------------------------------------------
# Knob normalizers — THE single copy (engine.py and ops.py used to each run
# their own).  Error texts are load-bearing: existing tests match them.
# ---------------------------------------------------------------------------

def _normalize_switch_k(switch_k, dense_threshold=DENSE_FRONTIER):
    """"auto" → the default Gemini k; None → the DENSE_FRONTIER fallback;
    a positive number → that k.  Returned value is part of the executor
    cache key.  A non-default ``dense_threshold`` combined with an active
    Gemini rule is rejected rather than silently ignored — the fraction
    threshold only governs the ``switch_k=None`` fallback."""
    if isinstance(switch_k, str):
        if switch_k != "auto":
            raise ValueError(f"switch_k must be 'auto', None or a number, "
                             f"got {switch_k!r}")
        switch_k = SWITCH_K
    elif switch_k is not None:
        switch_k = float(switch_k)
        if not switch_k > 0:
            raise ValueError(f"switch_k must be > 0 (push while |E_frontier|"
                             f" <= |E|/k), got {switch_k}")
    if switch_k is not None and dense_threshold != DENSE_FRONTIER:
        raise ValueError(
            "dense_threshold only governs the switch_k=None fallback; pass "
            "switch_k=None to use a custom frontier-fraction threshold, or "
            "tune the Gemini rule via switch_k")
    return switch_k


def _check_resolution(push_resolution) -> str:
    """None → the engine default, so callers (engine.py) can forward their
    own optional knob unconditionally."""
    if push_resolution is None:
        return PUSH_RESOLUTION
    if push_resolution not in ("scatter", "sorted"):
        raise ValueError(f"push_resolution must be 'scatter' or 'sorted', "
                         f"got {push_resolution!r}")
    return push_resolution


def _pallas_direction(model) -> str:
    """Map the engine-level ``model`` to the pallas sweep-direction policy:
    None/"auto" → per-iteration heuristic, "pull"/"pull+"/"pull−" → pull
    sweeps only, "push"/… → push sweeps only."""
    if model in (None, "auto"):
        return "auto"
    base = str(model).rstrip("+-")
    if base in ("pull", "push"):
        return base
    raise ValueError(f"pallas engine: unknown model {model!r}")


def _check_on_nonconverge(on_nonconverge: str) -> str:
    if on_nonconverge not in ("raise", "warn", "ignore"):
        raise ValueError(f"on_nonconverge must be 'raise', 'warn' or "
                         f"'ignore', got {on_nonconverge!r}")
    return on_nonconverge


def assert_normalized(plan: "ExecutionPlan") -> None:
    """The kernels-layer contract: a plan that reaches ``ops`` is already
    normalized — fields are asserted, never re-parsed (satellite 1)."""
    assert plan.direction in ("auto", "pull", "push"), plan.direction
    assert plan.switch_k is None or (isinstance(plan.switch_k, float)
                                     and plan.switch_k > 0), plan.switch_k
    assert plan.push_resolution in ("sorted", "scatter"), plan.push_resolution
    assert plan.on_nonconverge in ("raise", "warn", "ignore"), \
        plan.on_nonconverge
    assert plan.shard_strategy in ("contiguous", "dst_hash"), \
        plan.shard_strategy
    assert plan.incremental in (None, "delta", "full"), plan.incremental


# ---------------------------------------------------------------------------
# The plan itself.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Every resolved execution decision of one query, in one frozen value.

    ``engine``/``model`` select the executor; ``direction`` is the pallas
    sweep-direction policy derived from ``model``; ``switch_k`` /
    ``dense_threshold`` / ``push_resolution`` are the normalized
    direction-switch and push-resolution knobs (exactly the values the
    executor cache keys carry); ``shard_strategy``/``axes`` shape the
    vertex-cut engines; ``batch_size``/``batch_lane`` describe source
    batching ("vmapped" = one fused launch, "sequential" = the per-source
    degradation recorded as an explicit decision); the remaining fields are
    the guarded-execution policy.  ``resolution_hint`` keeps the RAW caller
    hint so a fallback re-plan for a different engine re-resolves it (a
    sharded plan's "scatter" must not leak into a single-device retry that
    would default to "sorted")."""
    engine: str
    model: Optional[str] = None
    direction: str = "auto"
    switch_k: Optional[float] = SWITCH_K
    dense_threshold: float = DENSE_FRONTIER
    push_resolution: str = PUSH_RESOLUTION
    resolution_hint: Optional[str] = None
    shard_strategy: str = "contiguous"
    axes: tuple = ("data",)
    batch_size: Optional[int] = None
    batch_lane: Optional[str] = None
    validate: bool = True
    on_nonconverge: str = "raise"
    fallback: bool = False
    divergence_sentinel: bool = True
    adaptive: bool = False
    incremental: Optional[str] = None  # mutation-aware execution mode: None
                                       # (no mutation hint), "delta" (warm
                                       # start + touched-set frontier seed) or
                                       # "full" (planned cold recompute — the
                                       # warm hints are dropped; DESIGN.md §15)
    kind: tuple = ()                 # structural query-shape key (plan cache
                                     # + feedback identity; source-free)

    def knobs(self) -> dict:
        """Every resolved knob, by name — the explain/ExecStats surface."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


@dataclasses.dataclass
class PlanExplanation:
    """``explain=True`` payload: the plan, the graph statistics that drove
    it, the feedback snapshot (if any), and one human-readable reason per
    resolved field."""
    plan: ExecutionPlan
    stats: object                   # structure.GraphStats
    feedback: Optional[dict]
    decisions: dict                 # field -> reason string


# ---------------------------------------------------------------------------
# Query-shape ("kind") keys: structural, source-free — exactly the identity
# the executor cache uses for plan levels + sourced-ness.
# ---------------------------------------------------------------------------

def _plan_levels(plan):
    levels = []
    p = plan
    while isinstance(p, Lex):
        levels.append((p.comp, p.op))
        p = p.secondary
    levels.append((p.comp, p.op))
    return levels


def program_kind(prog) -> tuple:
    """Structural identity of a query shape: per-round plan levels and
    sourced-ness for fused programs, (rop, dtype, epilogue?) for direct
    kernel sets.  Source VALUES are deliberately absent — every query source
    of one shape shares a plan-cache/feedback entry, mirroring the
    source-free executor cache (DESIGN.md §8)."""
    if isinstance(prog, FusedProgram):
        rounds = []
        for _name, round_ in prog.rounds:
            rounds.append((
                tuple(tuple(_plan_levels(leaf.plan)) for leaf in round_.leaves),
                tuple(c.source is not None for c in round_.components)))
        return ("program", tuple(rounds))
    if isinstance(prog, DirectKernels):
        return ("direct", prog.rop, str(prog.dtype),
                prog.e_fn is not None, prog.source is not None)
    return ("adhoc",)


def _prog_idempotent(prog) -> bool:
    """True when every iteration round of the query is idempotent (+model):
    the regime where push and pull sweeps are bitwise-interchangeable per
    iteration, so feedback adaptation of the direction switch is value-safe."""
    if isinstance(prog, FusedProgram):
        leaves = [leaf for _n, r in prog.rounds for leaf in r.leaves]
        return bool(leaves) and all(iterate.plan_idempotent(leaf.plan)
                                    for leaf in leaves)
    if isinstance(prog, DirectKernels):
        return prog.rop in iterate._IDEMPOTENT_OPS and prog.e_fn is None
    return False


# ---------------------------------------------------------------------------
# Plan cache + recorded-stats feedback cache (both LRU-bounded, identity
# keyed on the graph with weakref guards like the structure caches).
# ---------------------------------------------------------------------------

_PLAN_CACHE: OrderedDict = OrderedDict()
_PLAN_CACHE_MAX = 256

_FEEDBACK: OrderedDict = OrderedDict()
_FEEDBACK_MAX = 256


@dataclasses.dataclass
class FeedbackRecord:
    """Per-(graph, kind) observed execution statistics — the planner's
    recorded-stats feedback loop (tentpole).  Updated by the engine entry
    points after every executed query from ``ExecStats`` (which aggregates
    the kernels' SWEEP_STATS-visible counters)."""
    queries: int = 0
    iterations: int = 0
    push_iters: int = 0
    pull_iters: int = 0
    edge_work: float = 0.0
    resolve_work: float = 0.0
    nonconverged: int = 0
    epoch: int = 0                  # bumps on every record: plan-cache keys
                                    # carry it so adaptive plans refresh

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _lru_put(cache: OrderedDict, maxlen: int, key, value) -> None:
    cache[key] = value
    cache.move_to_end(key)
    while len(cache) > maxlen:
        cache.popitem(last=False)


def plan_cache_size() -> int:
    return len(_PLAN_CACHE)


def feedback_cache_size() -> int:
    return len(_FEEDBACK)


def clear_plan_caches() -> None:
    _PLAN_CACHE.clear()
    _FEEDBACK.clear()


def clear_graph_plans(g) -> int:
    """Drop ONE graph's plan-cache and feedback entries (the planner's share
    of ``engine.clear_graph_caches`` — the serving LRU's eviction hook).
    Returns the number of entries dropped."""
    dropped = 0
    for cache in (_PLAN_CACHE, _FEEDBACK):
        stale = [k for k, (ref, _) in list(cache.items()) if ref() is g]
        for k in stale:
            if cache.pop(k, None) is not None:
                dropped += 1
    return dropped


def record_feedback(g, kind: tuple, stats) -> None:
    """Fold one executed query's ``ExecStats`` into the (graph, kind)
    feedback record.  Tracer-valued stats (vmapped batches report per-query
    host ints, so this only guards exotic callers) are skipped."""
    iters = getattr(stats, "iterations", 0)
    if not isinstance(iters, (int, float)):
        return
    key = (id(g), kind)
    hit = _FEEDBACK.get(key)
    rec = None
    if hit is not None:
        ref, rec = hit
        if ref() is not g:          # id reuse after GC: start fresh
            rec = None
    if rec is None:
        rec = FeedbackRecord()
        _lru_put(_FEEDBACK, _FEEDBACK_MAX, key, (weakref.ref(g), rec))
        weakref.finalize(g, _FEEDBACK.pop, key, None)
    else:
        _FEEDBACK.move_to_end(key)
    rec.queries += 1
    rec.iterations += int(iters)
    rec.push_iters += int(getattr(stats, "push_iters", 0) or 0)
    rec.pull_iters += int(getattr(stats, "pull_iters", 0) or 0)
    rec.edge_work += float(getattr(stats, "edge_work", 0.0) or 0.0)
    rec.resolve_work += float(getattr(stats, "resolve_work", 0.0) or 0.0)
    if not getattr(stats, "converged", True):
        rec.nonconverged += 1
    rec.epoch += 1


def feedback_for(g, kind: tuple) -> Optional[FeedbackRecord]:
    hit = _FEEDBACK.get((id(g), kind))
    if hit is None:
        return None
    ref, rec = hit
    return rec if ref() is g else None


def _adapted_switch_k(rec: FeedbackRecord) -> float:
    """Feedback rule (DESIGN.md §14): a query shape that ran ≥ ADAPT_PUSH_HI
    of its iterations as pushes gets a halved k (push keeps winning — let it
    run longer before the pull switch); one that pushed ≤ ADAPT_PUSH_LO gets
    a doubled k (push rarely paid off — raise the bar).  Always clamped to
    [SWITCH_K/ADAPT_SPAN, SWITCH_K*ADAPT_SPAN]."""
    if rec.iterations <= 0:
        return SWITCH_K
    frac = rec.push_iters / rec.iterations
    if frac >= ADAPT_PUSH_HI:
        k = SWITCH_K / 2.0
    elif frac <= ADAPT_PUSH_LO:
        k = SWITCH_K * 2.0
    else:
        k = SWITCH_K
    return float(min(max(k, SWITCH_K / ADAPT_SPAN), SWITCH_K * ADAPT_SPAN))


def _adapted_resolution(rec: FeedbackRecord) -> Optional[str]:
    """Flip to the reference scatter when the dst-sorted resolution pass did
    MORE edge work than the full rectangles it replaced would have (hub-free
    graphs where every resolution tile stays live) — observed, per graph."""
    if rec.push_iters > 0 and rec.resolve_work > rec.edge_work > 0:
        return "scatter"
    return None


# ---------------------------------------------------------------------------
# plan_execution — the single resolution point.
# ---------------------------------------------------------------------------

@obs.span("grafs.plan")
def plan_execution(g, prog=None, *, engine: Optional[str] = None,
                   model: Optional[str] = None,
                   mesh=None, axes=("data",),
                   switch_k="auto", dense_threshold: Optional[float] = None,
                   push_resolution: Optional[str] = None,
                   shard_strategy: Optional[str] = None,
                   batch: Optional[int] = None,
                   validate: bool = True,
                   on_nonconverge: str = "raise",
                   fallback: bool = False,
                   divergence_sentinel: bool = True,
                   adaptive: bool = False,
                   mutation=None,
                   default_engine: str = "pull",
                   explain: bool = False):
    """Resolve every execution knob of one query into an ``ExecutionPlan``.

    Hint precedence (DESIGN.md §14): an explicit caller kwarg always wins;
    ``engine=None`` takes the entry point's documented default
    (``default_engine``); ``engine="auto"`` picks from the graph statistics
    and device topology; unset knobs take the documented defaults —
    bitwise-identical to the historical explicit-kwarg paths.  With
    ``adaptive=True`` AND an idempotent query shape, unset ``switch_k`` /
    ``push_resolution`` consult the recorded-stats feedback of this
    (graph, kind) instead (bounded adjustments; see ``FeedbackRecord``).

    ``mutation=`` (a ``graph.mutate.MutationDelta`` or anything with
    ``inserted``/``deleted``/``touched``/``has_deletes``) resolves the
    ``incremental`` knob from mutation-size statistics: an edit touching
    ≤ ``INCREMENTAL_DELTA`` of |E| plans ``"delta"`` (warm start + touched
    frontier seed), a larger one — or an idempotent query after deletions,
    whose stale monotone values cannot retract — plans ``"full"``.

    Plans are cached per (graph identity, kind, hints[, feedback epoch]) in
    a bounded LRU; ``explain=True`` bypasses the cache and returns a
    ``PlanExplanation`` carrying the statistics behind each choice."""
    from repro.graph import structure

    decisions: dict = {} if explain else None
    kind = program_kind(prog)
    idempotent = _prog_idempotent(prog)

    fb = feedback_for(g, kind) if adaptive else None
    fb_epoch = fb.epoch if fb is not None else 0
    mut_key = None
    if mutation is not None:
        touched = getattr(mutation, "touched", None)
        mut_key = (int(getattr(mutation, "inserted", 0)),
                   int(getattr(mutation, "deleted", 0)),
                   0 if touched is None else int(getattr(touched, "size",
                                                         len(touched))),
                   bool(getattr(mutation, "has_deletes", False)))
    # The plan depends on the mesh only through its device count (the mesh
    # object itself is threaded to execution separately) — keying the hint
    # by id(mesh) would go stale when a freed mesh's id is reused.
    hints_key = (engine, model,
                 None if mesh is None else _mesh_device_count(mesh),
                 _axes_key(axes), switch_k, dense_threshold, push_resolution,
                 shard_strategy, batch, validate, on_nonconverge, fallback,
                 divergence_sentinel, adaptive, mut_key, default_engine)
    cache_key = (id(g), kind, hints_key, fb_epoch)
    if not explain:
        hit = _PLAN_CACHE.get(cache_key)
        if hit is not None:
            ref, plan = hit
            if ref() is g:
                _PLAN_CACHE.move_to_end(cache_key)
                return plan

    stats = structure.graph_stats(g)
    _check_on_nonconverge(on_nonconverge)

    # --- engine ------------------------------------------------------------
    if engine is None:
        eng = default_engine
        reason = f"entry-point default ({default_engine!r})"
    elif engine == "auto":
        if mesh is not None and _mesh_device_count(mesh) > 1:
            eng = "pallas_sharded"
            reason = (f"auto: mesh with {_mesh_device_count(mesh)} devices "
                      "→ shard-local fused sweeps")
        else:
            eng = "pallas"
            reason = "auto: single device → fused blocked-ELL kernel engine"
    else:
        eng = engine
        reason = "caller hint"
    if eng not in ENGINES:
        raise ValueError(f"unknown engine {eng}")
    if decisions is not None:
        decisions["engine"] = reason

    # --- direction policy ----------------------------------------------------
    if eng in ("pallas", "pallas_sharded"):
        direction = _pallas_direction(model)
        if decisions is not None:
            decisions["direction"] = (
                "forced by model hint" if direction != "auto" else
                ("per-iteration Gemini switch (idempotent rounds)"
                 if idempotent else
                 "auto (non-idempotent rounds run the pull− recompute)"))
    else:
        direction = "auto"
        if decisions is not None:
            decisions["direction"] = "reference engines take model directly"

    # --- switch_k ------------------------------------------------------------
    dt = DENSE_FRONTIER if dense_threshold is None else float(dense_threshold)
    k_norm = _normalize_switch_k(switch_k, dt)
    k_reason = ("caller hint" if switch_k != "auto"
                else f"documented Gemini default k={SWITCH_K}")
    if (adaptive and idempotent and switch_k == "auto" and fb is not None
            and fb.queries > 0):
        k_norm = _adapted_switch_k(fb)
        k_reason = (f"feedback: {fb.push_iters}/{fb.iterations} push "
                    f"iterations over {fb.queries} queries → k={k_norm}")
    if decisions is not None:
        decisions["switch_k"] = k_reason
        decisions["dense_threshold"] = (
            "caller hint (switch_k=None fallback)" if dense_threshold
            is not None else "documented DENSE_FRONTIER default")

    # --- push resolution -----------------------------------------------------
    # Engine-independent since the sharded engine grew its own per-shard
    # resolution stack: every pallas engine takes the dst-sorted default,
    # "scatter" stays the reference oracle everywhere.
    res = _check_resolution(push_resolution)
    res_reason = ("caller hint" if push_resolution is not None else
                  "documented dst-sorted default (all pallas engines)")
    if (adaptive and idempotent and push_resolution is None
            and fb is not None):
        flipped = _adapted_resolution(fb)
        if flipped is not None:
            res = flipped
            res_reason = (f"feedback: resolve_work {fb.resolve_work:.0f} > "
                          f"edge_work {fb.edge_work:.0f} → reference scatter")
    if decisions is not None:
        decisions["push_resolution"] = res_reason

    # --- sharding / batching -------------------------------------------------
    strat = shard_strategy if shard_strategy is not None else "contiguous"
    if strat not in ("contiguous", "dst_hash"):
        raise ValueError(f"unknown shard strategy {strat!r}")
    if decisions is not None:
        decisions["shard_strategy"] = ("caller hint" if shard_strategy
                                       is not None else "contiguous default")
    lane = None
    if batch is not None:
        lane = "vmapped" if eng == "pallas" else "sequential"
        if decisions is not None:
            decisions["batch_lane"] = (
                f"B={batch} sources in one vmapped launch" if lane == "vmapped"
                else f"engine {eng!r} has no batched fixpoint — B={batch} "
                     "sequential runs (recorded degradation)")

    # --- incremental (mutation-aware) mode -----------------------------------
    inc = None
    if mut_key is not None:
        n_ins, n_del, _n_touched, has_del = mut_key
        sz = n_ins + n_del
        small = sz <= INCREMENTAL_DELTA * max(1, stats.num_edges)
        if idempotent and has_del:
            inc = "full"
            inc_reason = ("idempotent round after deletions: stale monotone "
                          "values cannot retract — planned full recompute")
        elif small:
            inc = "delta"
            inc_reason = (f"{sz} mutated edges ≤ {INCREMENTAL_DELTA:.0%} of "
                          f"|E|={stats.num_edges} → warm+delta propagation")
        else:
            inc = "full"
            inc_reason = (f"{sz} mutated edges > {INCREMENTAL_DELTA:.0%} of "
                          f"|E|={stats.num_edges} → planned full recompute")
        if decisions is not None:
            decisions["incremental"] = inc_reason

    plan = ExecutionPlan(
        engine=eng, model=model, direction=direction,
        switch_k=k_norm, dense_threshold=dt,
        push_resolution=res, resolution_hint=push_resolution,
        shard_strategy=strat, axes=_axes_key(axes),
        batch_size=batch, batch_lane=lane,
        validate=validate, on_nonconverge=on_nonconverge,
        fallback=fallback, divergence_sentinel=divergence_sentinel,
        adaptive=adaptive, incremental=inc, kind=kind)

    if explain:
        return PlanExplanation(
            plan=plan, stats=stats,
            feedback=fb.as_dict() if fb is not None else None,
            decisions=decisions)
    _lru_put(_PLAN_CACHE, _PLAN_CACHE_MAX, cache_key, (weakref.ref(g), plan))
    weakref.finalize(g, _PLAN_CACHE.pop, cache_key, None)
    return plan


def degrade_plan(plan: ExecutionPlan, engine: str) -> ExecutionPlan:
    """The plan a guard-fallback step executes under: same normalized knobs,
    target engine, with the resolution re-resolved from the raw hint —
    resolution is engine-independent now that the sharded engine runs its
    own per-shard sorted stack, so an explicit caller hint (e.g. a pinned
    "scatter" oracle) survives the hop and a hintless plan lands back on
    the documented dst-sorted default."""
    if engine == plan.engine:
        return plan
    return dataclasses.replace(
        plan, engine=engine,
        push_resolution=_check_resolution(plan.resolution_hint))


def _axes_key(axes) -> tuple:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _mesh_device_count(mesh) -> int:
    try:
        import numpy as np
        return int(np.ravel(mesh.devices).size)
    except Exception:
        return 1
