"""Iterative reduction engines (paper §3, Fig. 5) on JAX.

Implements the paper's four synchronous models —

  pull+  Def. 1: gather from predecessors, merge with previous value
                 (idempotent R)
  pull−  Def. 2: gather from ALL predecessors, full recompute (non-idempotent)
  push+  Def. 3: frontier-masked scatter from changed predecessors
  push−  Def. 4: scatter recompute from all predecessors

— over *reduction plans*: trees of ``Prim`` (componentwise monoid) and
``Lex`` (lexicographic tie-break, the result of fusing nested reductions,
rule FPNEST).  Lexicographic reductions use the classic two-pass trick
(extremize the primary key, then reduce the secondaries over the tied edges),
which keeps everything expressible with ``segment_*`` / scatter primitives —
the TPU-idiomatic replacement for the CPU frameworks' per-edge atomics
(DESIGN.md §2).

Engines in this module: pull/push (sparse, frontier-masked), dense (GridGraph
analogue), distributed (PowerGraph-style vertex-cut over shard_map).  The
Pallas engine lives in repro.kernels and reuses this plan algebra.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fusion import FusedRound, Lex, Prim
from repro.graph import segment
from repro.graph.partition import partition_edges
from repro.graph.structure import Graph, w_out_deg as structure_w_out_deg

DTYPES = {"int": jnp.int32, "float": jnp.float32, "vert": jnp.int32}

_IDEMPOTENT_OPS = ("min", "max", "or", "and")


@dataclasses.dataclass(frozen=True)
class CompRuntime:
    """Everything an engine needs for one component of the fused tuple.

    ``source`` is the component's *default* query source from the spec; the
    engines treat the value as runtime data (``_init_state`` accepts per-call
    overrides, the pallas executor takes it as a traced argument), so only
    ``source is not None`` — whether the initial state is ⊥-masked to one
    vertex at all — is structural."""
    idx: int
    op: str                          # monoid from its plan position
    dtype: object                    # jnp dtype
    p_fn: Callable                   # env → propagated value (synthesized P)
    init_fn: Callable                # (v_ids, src) → initial value (synthesized
                                     # I; legacy single-arg closures accepted)
    source: Optional[int]
    e_fn: Optional[Callable] = None  # epilogue (PageRank); None = identity

    @property
    def ident(self):
        return segment.identity(self.op, self.dtype)


def comp_runtimes(round_: FusedRound, synth: dict) -> list:
    """Assign each component its plan-position monoid + synthesized kernels.

    ``synth[idx]`` = (p_fn, init_fn[, e_fn]) from repro.core.synthesis."""
    ops = {}

    def walk(plan):
        ops[plan.comp] = plan.op
        if isinstance(plan, Lex):
            walk(plan.secondary)

    for leaf in round_.leaves:
        walk(leaf.plan)
    out = []
    for comp in round_.components:
        entry = synth[comp.idx]
        p_fn, init_fn = entry[0], entry[1]
        e_fn = entry[2] if len(entry) > 2 else None
        out.append(CompRuntime(
            idx=comp.idx, op=ops[comp.idx], dtype=DTYPES[comp.f.dtype],
            p_fn=p_fn, init_fn=init_fn, source=comp.source, e_fn=e_fn))
    return out


# ---------------------------------------------------------------------------
# Plan algebra: segment-reduce, scatter-reduce and two-state merge.
# ---------------------------------------------------------------------------

def _plan_comps(plan):
    if isinstance(plan, Prim):
        return (plan.comp,)
    return (plan.comp,) + _plan_comps(plan.secondary)


def plan_idempotent(plan) -> bool:
    if isinstance(plan, Prim):
        return plan.op in _IDEMPOTENT_OPS
    return plan_idempotent(plan.secondary)   # Lex primary is always min/max


def plan_segment_reduce(plan, evals: dict, dst, n: int, comps) -> dict:
    """Reduce per-edge values into per-vertex partials (pull side)."""
    if isinstance(plan, Prim):
        return {plan.comp: segment.segment_reduce(plan.op, evals[plan.comp], dst, n)}
    prim = segment.segment_reduce(plan.op, evals[plan.comp], dst, n)
    tie = evals[plan.comp] == prim[dst]
    masked = dict(evals)
    for j in _plan_comps(plan.secondary):
        masked[j] = jnp.where(tie, evals[j], comps[j].ident)
    return {plan.comp: prim,
            **plan_segment_reduce(plan.secondary, masked, dst, n, comps)}


def plan_scatter_reduce(plan, old: dict, evals: dict, dst, eactive, keep, comps) -> dict:
    """Push side: scatter per-edge values onto (lex-masked) old state.

    ``keep`` [n] marks vertices whose old value is still lexicographically
    eligible at this plan level; ``eactive`` [E] marks eligible edges."""
    c = plan.comp
    init = jnp.where(keep, old[c], comps[c].ident)
    vals = jnp.where(eactive, evals[c], comps[c].ident)
    prim = segment.scatter_reduce(plan.op, init, vals, dst)
    if isinstance(plan, Prim):
        return {c: prim}
    tie_e = eactive & (evals[c] == prim[dst])
    keep2 = keep & (old[c] == prim)
    rec = plan_scatter_reduce(plan.secondary, old, evals, dst, tie_e, keep2, comps)
    return {c: prim, **rec}


def plan_merge(plan, a: dict, b: dict, comps) -> dict:
    """Lexicographic/componentwise merge of two candidate states.

    Associative + commutative given per-component identities, so it is also
    the cross-shard combiner of the distributed engine."""
    c = plan.comp
    prim = segment.combine(plan.op, a[c], b[c])
    if isinstance(plan, Prim):
        return {c: prim}
    a_w = a[c] == prim
    b_w = b[c] == prim
    tie = a_w & b_w
    rec = plan_merge(plan.secondary, a, b, comps)
    out = {c: prim}
    for j in _plan_comps(plan.secondary):
        out[j] = jnp.where(tie, rec[j], jnp.where(a_w, a[j], b[j]))
    return out


def _recompute_merge(plans, comps_by_idx, state_d, red, has_pred) -> dict:
    """Update rule of the non-idempotent (−) models: the recomputed value
    wins unless the previous value is strictly better (protects the source's
    trivial-path init, cf. Thm. 3/5 side conditions), and vertices with no
    non-⊥ predecessor contribution keep their value (Def. 2/4: update only
    when CPreds ≠ ∅).  Components with an epilogue (PageRank) always take the
    recomputed value — E supplies the base term."""
    new_d = {}
    for p in plans:
        c = p.comp
        if comps_by_idx[c].e_fn is not None:
            for j in _plan_comps(p):
                new_d[j] = red[j]
            continue
        if isinstance(p, Prim) and p.op not in _IDEMPOTENT_OPS:
            new_d[c] = jnp.where(has_pred[c], red[c], state_d[c])
            continue
        comb = segment.combine(p.op, state_d[c], red[c])
        strictly = (comb == state_d[c]) & (state_d[c] != red[c])
        take_old = strictly | ~has_pred[c]
        for j in _plan_comps(p):
            new_d[j] = jnp.where(take_old, state_d[j], red[j])
    return new_d


# ---------------------------------------------------------------------------
# Shared iteration scaffolding.
# ---------------------------------------------------------------------------


def _host(x, cast):
    """Host-convert when concrete; pass tracers through (lets the engines
    be wrapped in jax.jit for HLO inspection, e.g. benchmarks/state_metrics)."""
    try:
        return cast(x)
    except (jax.errors.ConcretizationTypeError, jax.errors.TracerArrayConversionError):
        return x

@dataclasses.dataclass
class IterationResult:
    state: tuple                     # per-component [n] arrays
    iterations: int
    edge_work: float
    converged: object = True         # fixpoint reached (no active vertices,
                                     # sentinel clean) — bool, or [B]/tracer
    diverged: object = False         # NaN/Inf sentinel fired in-loop
    active_count: object = 0         # still-active vertices at exit (> 0
                                     # exactly when max_iter exhausted)
    residual: object = 0.0           # max |Δ| of the LAST iteration over
                                     # float components (0 if none)


def _divergence(comps, new):
    """In-loop NaN/Inf sentinel (zero extra launches: elementwise reductions
    folded into the fixpoint body).  NaN anywhere is divergence; ±Inf is
    divergence only for non-extremal components (sum/prod or an epilogue),
    where the identities are finite and Inf means overflow — for min/max
    components ±Inf is the legitimate ⊥."""
    bad = jnp.asarray(False)
    for i, cr in enumerate(comps):
        if not jnp.issubdtype(cr.dtype, jnp.floating):
            continue
        bad = bad | jnp.any(jnp.isnan(new[i]))
        if cr.op in ("sum", "prod") or cr.e_fn is not None:
            bad = bad | jnp.any(jnp.isinf(new[i]))
    return bad


def _residual(comps, new, old):
    """Max |new − old| over float components — the last iteration's residual,
    reported in NonConvergence diagnostics.  Non-finite diffs (a vertex
    leaving ⊥) are masked: 'changed from unreachable' is active_count's
    story, not a numeric residual."""
    r = jnp.float32(0)
    for i, cr in enumerate(comps):
        if not jnp.issubdtype(cr.dtype, jnp.floating):
            continue
        d = jnp.abs(new[i] - old[i])
        r = jnp.maximum(r, jnp.max(jnp.where(jnp.isfinite(d), d,
                                             jnp.float32(0))))
    return r


def _finish_result(comps, state, active, k, work, div, resid) -> IterationResult:
    """Shared exit bookkeeping: host-convert the loop carry into an
    ``IterationResult`` with structured convergence fields.  ``active`` may
    be longer than n (padded engines pass the logical slice)."""
    active_n = jnp.sum(active.astype(jnp.int32))
    return IterationResult(
        state=state, iterations=_host(k, int), edge_work=_host(work, float),
        converged=_host(jnp.logical_and(~div, active_n == 0), bool),
        diverged=_host(div, bool),
        active_count=_host(active_n, int),
        residual=_host(resid, float))


def check_shard_replication(counts, what: str, engine: str) -> None:
    """Replication contract of the sharded engines: state (and with it the
    iteration count / direction sequence) is replicated, so every shard must
    report the identical value.  On divergence, report the per-shard values
    and the offending shard ids — the minority shards whose collectives
    broke — instead of a bare mismatch."""
    counts = np.asarray(counts)
    if counts.size == 0 or (counts == counts.flat[0]).all():
        return
    vals, freq = np.unique(counts, return_counts=True)
    majority = vals[int(freq.argmax())]
    offenders = np.flatnonzero(counts != majority)
    raise RuntimeError(
        f"{engine} shards diverged on {what}: per-shard {what} = "
        f"{counts.tolist()}; majority value {majority} held by "
        f"{int(freq.max())}/{counts.size} shards, offending shard ids "
        f"{offenders.tolist()} — replicated-state contract broken")


def _init_arity(init_fn) -> int:
    """Positional arity of an init kernel: 2 for the source-generic form
    ``init_fn(v, src)``, 1 for legacy closures that bake the source in."""
    try:
        params = inspect.signature(init_fn).parameters.values()
    except (TypeError, ValueError):          # builtins / odd callables
        return 1
    n_pos = sum(p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                for p in params)
    return 2 if n_pos >= 2 else 1


def _init_state(comps, n: int, sources: Optional[dict] = None):
    """Initial per-component state (condition C1/C2): the synthesized I on
    the source vertex, ⊥ everywhere else; sourceless components initialize
    every vertex.

    ``sources`` optionally overrides ``cr.source`` per component index with a
    runtime value — a Python int or a TRACED scalar.  Tracing through the
    source (rather than closing over it) is what lets one compiled executor
    serve every query source (DESIGN.md §8); overrides only apply to
    components that are sourced in the spec (sourced-ness is structural)."""
    v = jnp.arange(n, dtype=jnp.int32)
    state = []
    for cr in comps:
        src = cr.source
        if sources is not None and cr.source is not None:
            src = sources.get(cr.idx, cr.source)
        if _init_arity(cr.init_fn) >= 2:
            vals = cr.init_fn(v, src)
        else:
            vals = cr.init_fn(v)
        vals = jnp.asarray(vals, dtype=cr.dtype)
        vals = jnp.broadcast_to(vals, (n,))
        if cr.source is not None:
            vals = jnp.where(v == src, vals, cr.ident)
        state.append(vals)
    return tuple(state)


def _edge_env(src, dst, w, c, out_deg, n, wdeg=None):
    env = {"w": w, "c": c, "esrc": src, "edst": dst,
           "outdeg": jnp.maximum(out_deg, 1).astype(jnp.float32)[src],
           "nv": jnp.float32(n)}
    # weighted out-degree normalizer ("wdeg", weighted-PageRank-style P);
    # computed ONCE per graph (structure.w_out_deg) so every engine — and
    # both pallas sweep directions — divides by the bit-identical vector
    env["wdeg"] = jnp.ones_like(env["outdeg"]) if wdeg is None else wdeg[src]
    return env


def _propagate(comps, state, src, env):
    """P'(n, e): synthesized P wrapped with the ⊥ guard (condition C3)."""
    evals = {}
    for cr in comps:
        nvals = state[cr.idx][src]
        p = jnp.asarray(cr.p_fn({"n": nvals, **env}), dtype=cr.dtype)
        evals[cr.idx] = jnp.where(nvals == cr.ident, cr.ident, p)
    return evals


def _changed(comps, new, old, tol):
    ch = jnp.zeros(new[0].shape, dtype=bool)
    for i, cr in enumerate(comps):
        if tol > 0 and jnp.issubdtype(cr.dtype, jnp.floating):
            ch = ch | (jnp.abs(new[i] - old[i]) > tol)
        else:
            ch = ch | (new[i] != old[i])
    return ch


def _apply_epilogue(comps, red: dict) -> dict:
    out = dict(red)
    for cr in comps:
        if cr.e_fn is not None:
            out[cr.idx] = jnp.asarray(cr.e_fn({"n": red[cr.idx]}), dtype=cr.dtype)
    return out


def _has_pred(comps, state, src, dst, valid_e, n) -> dict:
    out = {}
    for cr in comps:
        nonbot = (state[cr.idx][src] != cr.ident) & valid_e
        out[cr.idx] = segment.segment_reduce("or", nonbot, dst, n)
    return out


# ---------------------------------------------------------------------------
# pull / push engines.
# ---------------------------------------------------------------------------

def iterate_graph(g: Graph, comps, plans, model: str = "pull+",
                  max_iter: Optional[int] = None, tol: float = 0.0,
                  sources: Optional[dict] = None) -> IterationResult:
    """Run the fused reduction to fixpoint.  ``plans`` = [leaf.plan, ...].
    ``sources`` optionally overrides per-component query sources
    (see ``_init_state``)."""
    n = g.n
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    idempotent = all(plan_idempotent(p) for p in plans)
    if model in ("pull+", "push+") and not idempotent:
        model = {"pull+": "pull-", "push+": "push-"}[model]
    comps_by_idx = {cr.idx: cr for cr in comps}

    eo = g.by_dst if model.startswith("pull") else g.by_src
    src, dst = eo.src, eo.dst
    env = _edge_env(src, dst, eo.weight, eo.capacity, g.out_deg, n,
                    wdeg=structure_w_out_deg(g))
    valid_e = jnp.ones_like(src, dtype=bool)

    def body(carry):
        state, active, k, work, div, resid = carry
        state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
        evals = _propagate(comps, state, src, env)
        if model in ("pull+", "push+"):
            eactive = active[src]
            work = work + jnp.sum(eactive.astype(jnp.float32))
            if model == "pull+":
                masked = {i: jnp.where(eactive, evals[i], comps_by_idx[i].ident)
                          for i in evals}
                red = {}
                for p in plans:
                    red.update(plan_segment_reduce(p, masked, dst, n, comps_by_idx))
                new_d = {}
                for p in plans:
                    new_d.update(plan_merge(p, state_d, red, comps_by_idx))
            else:
                new_d = {}
                keep = jnp.ones(n, dtype=bool)
                for p in plans:
                    new_d.update(plan_scatter_reduce(
                        p, state_d, evals, dst, eactive, keep, comps_by_idx))
        else:
            # pull−/push−: ALL predecessors propagate; full recompute.
            work = work + jnp.float32(src.shape[0])
            red = {}
            if model == "pull-":
                for p in plans:
                    red.update(plan_segment_reduce(p, evals, dst, n, comps_by_idx))
            else:
                ident = {cr.idx: jnp.full((n,), cr.ident, cr.dtype) for cr in comps}
                keep = jnp.zeros(n, dtype=bool)
                for p in plans:
                    red.update(plan_scatter_reduce(
                        p, ident, evals, dst, valid_e, keep, comps_by_idx))
            red = _apply_epilogue(comps, red)
            has_pred = _has_pred(comps, state, src, dst, valid_e, n)
            new_d = _recompute_merge(plans, comps_by_idx, state_d, red, has_pred)
        new = tuple(new_d[cr.idx] for cr in comps)
        ch = _changed(comps, new, state, tol)
        div = div | _divergence(comps, new)
        resid = _residual(comps, new, state)
        ch = ch & ~div                     # divergence drains the frontier:
        return new, ch, k + 1, work, div, resid   # the loop exits next cond

    def cond(carry):
        _, active, k, _, _, _ = carry
        return jnp.any(active) & (k < max_iter)

    state0 = _init_state(comps, n, sources)
    state, active, k, work, div, resid = jax.lax.while_loop(
        cond, body, (state0, jnp.ones(n, bool), jnp.int32(0), jnp.float32(0),
                     jnp.asarray(False), jnp.float32(0)))
    return _finish_result(comps, state, active, k, work, div, resid)


# ---------------------------------------------------------------------------
# adaptive engine (Gemini): per-iteration push/pull direction switch.
# ---------------------------------------------------------------------------

def iterate_adaptive(g: Graph, comps, plans, max_iter: Optional[int] = None,
                     tol: float = 0.0, dense_threshold: float = 0.05,
                     sources: Optional[dict] = None) -> IterationResult:
    """Gemini's signature feature: each iteration picks the propagation
    direction from the frontier density — a dense frontier favours the
    pull-side segment reduce (sequential reads, no contention), a sparse
    frontier favours the push-side frontier-masked scatter (work ∝ active
    out-degree).  Idempotent plans only (Gemini requires both a push and a
    pull implementation; non-idempotent falls back to pull−)."""
    n = g.n
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    if not all(plan_idempotent(p) for p in plans):
        return iterate_graph(g, comps, plans, model="pull-",
                             max_iter=max_iter, tol=tol, sources=sources)
    comps_by_idx = {cr.idx: cr for cr in comps}
    pull_eo, push_eo = g.by_dst, g.by_src
    wdeg = structure_w_out_deg(g)
    env_pull = _edge_env(pull_eo.src, pull_eo.dst, pull_eo.weight,
                         pull_eo.capacity, g.out_deg, n, wdeg=wdeg)
    env_push = _edge_env(push_eo.src, push_eo.dst, push_eo.weight,
                         push_eo.capacity, g.out_deg, n, wdeg=wdeg)

    def pull_branch(args):
        state, active = args
        state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
        evals = _propagate(comps, state, pull_eo.src, env_pull)
        eactive = active[pull_eo.src]
        masked = {i: jnp.where(eactive, evals[i], comps_by_idx[i].ident)
                  for i in evals}
        red = {}
        for p in plans:
            red.update(plan_segment_reduce(p, masked, pull_eo.dst, n,
                                           comps_by_idx))
        new_d = {}
        for p in plans:
            new_d.update(plan_merge(p, state_d, red, comps_by_idx))
        return tuple(new_d[cr.idx] for cr in comps)

    def push_branch(args):
        state, active = args
        state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
        evals = _propagate(comps, state, push_eo.src, env_push)
        eactive = active[push_eo.src]
        new_d = {}
        keep = jnp.ones(n, dtype=bool)
        for p in plans:
            new_d.update(plan_scatter_reduce(
                p, state_d, evals, push_eo.dst, eactive, keep, comps_by_idx))
        return tuple(new_d[cr.idx] for cr in comps)

    def body(carry):
        state, active, k, work, pulls, div, resid = carry
        frac = jnp.mean(active.astype(jnp.float32))
        use_pull = frac > dense_threshold
        new = jax.lax.cond(use_pull, pull_branch, push_branch,
                           (state, active))
        work = work + jnp.sum(active.astype(jnp.float32)
                              * g.out_deg.astype(jnp.float32))
        ch = _changed(comps, new, state, tol)
        div = div | _divergence(comps, new)
        resid = _residual(comps, new, state)
        ch = ch & ~div
        return (new, ch, k + 1, work, pulls + use_pull.astype(jnp.int32),
                div, resid)

    def cond(carry):
        _, active, k, _, _, _, _ = carry
        return jnp.any(active) & (k < max_iter)

    state0 = _init_state(comps, n, sources)
    state, active, k, work, pulls, div, resid = jax.lax.while_loop(
        cond, body,
        (state0, jnp.ones(n, bool), jnp.int32(0), jnp.float32(0),
         jnp.int32(0), jnp.asarray(False), jnp.float32(0)))
    res = _finish_result(comps, state, active, k, work, div, resid)
    res.pull_iters = _host(pulls, int)
    return res


# ---------------------------------------------------------------------------
# dense engine (GridGraph analogue): dense semiring products.
# ---------------------------------------------------------------------------

def iterate_dense(g: Graph, comps, plans, model: str = "pull+",
                  max_iter: Optional[int] = None, tol: float = 0.0,
                  sources: Optional[dict] = None) -> IterationResult:
    """Reference engine on a dense [n, n] edge matrix (small graphs only)."""
    n = g.n
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    src, dst, w, c = g.host_edges()
    adj = np.zeros((n, n), dtype=bool)
    wm = np.zeros((n, n), dtype=np.float32)
    cm = np.zeros((n, n), dtype=np.float32)
    adj[src, dst] = True
    wm[src, dst] = w
    cm[src, dst] = c
    adj, wm, cm = jnp.asarray(adj), jnp.asarray(wm), jnp.asarray(cm)
    comps_by_idx = {cr.idx: cr for cr in comps}
    idempotent = all(plan_idempotent(p) for p in plans)

    vs = jnp.arange(n, dtype=jnp.int32)
    env = {"w": wm, "c": cm,
           "esrc": jnp.broadcast_to(vs[:, None], (n, n)),
           "edst": jnp.broadcast_to(vs[None, :], (n, n)),
           "outdeg": jnp.broadcast_to(
               jnp.maximum(g.out_deg, 1).astype(jnp.float32)[:, None], (n, n)),
           "wdeg": jnp.broadcast_to(
               structure_w_out_deg(g)[:, None], (n, n)),
           "nv": jnp.float32(n)}

    _DENSE_RED = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum,
                  "prod": jnp.prod, "or": jnp.max, "and": jnp.min}

    def dense_reduce(plan, mats: dict) -> dict:
        cidx = plan.comp
        if isinstance(plan, Prim):
            red = _DENSE_RED[plan.op](mats[cidx], axis=0)
            return {cidx: red}
        prim = _DENSE_RED[plan.op](mats[cidx], axis=0)
        tie = mats[cidx] == prim[None, :]
        masked = dict(mats)
        for j in _plan_comps(plan.secondary):
            masked[j] = jnp.where(tie, mats[j], comps_by_idx[j].ident)
        return {cidx: prim, **dense_reduce(plan.secondary, masked)}

    def body(carry):
        state, active, k, work, div, resid = carry
        state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
        work = work + jnp.float32(g.num_edges)
        mats = {}
        for cr in comps:
            nmat = jnp.broadcast_to(state_d[cr.idx][:, None], (n, n))
            p = jnp.asarray(cr.p_fn({"n": nmat, **env}), dtype=cr.dtype)
            bot = state_d[cr.idx][:, None] == cr.ident
            mats[cr.idx] = jnp.where(adj & ~bot, p, cr.ident)
        red = {}
        for pl in plans:
            red.update(dense_reduce(pl, mats))
        red = _apply_epilogue(comps, red)
        if idempotent:
            new_d = {}
            for pl in plans:
                new_d.update(plan_merge(pl, state_d, red, comps_by_idx))
        else:
            has_pred = {cr.idx: jnp.any(adj & (state_d[cr.idx][:, None] != cr.ident),
                                        axis=0) for cr in comps}
            new_d = _recompute_merge(plans, comps_by_idx, state_d, red, has_pred)
        new = tuple(new_d[cr.idx] for cr in comps)
        ch = _changed(comps, new, state, tol)
        div = div | _divergence(comps, new)
        resid = _residual(comps, new, state)
        ch = ch & ~div
        return new, ch, k + 1, work, div, resid

    def cond(carry):
        _, active, k, _, _, _ = carry
        return jnp.any(active) & (k < max_iter)

    state0 = _init_state(comps, n, sources)
    state, active, k, work, div, resid = jax.lax.while_loop(
        cond, body, (state0, jnp.ones(n, bool), jnp.int32(0), jnp.float32(0),
                     jnp.asarray(False), jnp.float32(0)))
    return _finish_result(comps, state, active, k, work, div, resid)


# ---------------------------------------------------------------------------
# distributed engine: PowerGraph-style vertex-cut over shard_map.
# ---------------------------------------------------------------------------

def iterate_distributed(g: Graph, comps, plans, mesh, axes=("data",),
                        model: str = "pull+", max_iter: Optional[int] = None,
                        tol: float = 0.0,
                        sources: Optional[dict] = None) -> IterationResult:
    """Edge-partitioned fused reduction under shard_map.

    Each shard: local masked segment-reduce (Gather+Apply); partials merge
    across shards with monoid collectives (Scatter).  State is replicated, so
    the convergence flag is identical on every shard and the while_loop is
    collective-safe."""
    from jax.sharding import PartitionSpec as P

    n = g.n
    axes = tuple(axes) if not isinstance(axes, str) else (axes,)
    k_shards = int(np.prod([mesh.shape[a] for a in axes]))
    part = partition_edges(g, k_shards)
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    idempotent = all(plan_idempotent(p) for p in plans)
    if model == "pull+" and not idempotent:
        model = "pull-"
    comps_by_idx = {cr.idx: cr for cr in comps}
    out_deg = jnp.maximum(g.out_deg, 1).astype(jnp.float32)
    wdeg_v = structure_w_out_deg(g)

    def shard_fn(src, dst, w, c, mask):
        src, dst = src[0], dst[0]            # [1, e_loc] → [e_loc]
        w, c, mask = w[0], c[0], mask[0]
        env = {"w": w, "c": c, "esrc": src, "edst": dst,
               "outdeg": out_deg[src], "wdeg": wdeg_v[src],
               "nv": jnp.float32(n)}

        def cross_plan(plan, red: dict) -> dict:
            """Cross-shard lexicographic combine with monoid collectives only:
            global primary via pmin/pmax, tie-mask the local secondaries to
            identity, recurse.  Value-invariant across shards (replicated),
            and k× less traffic than an all_gather merge."""
            best = segment.psum_like(plan.op, red[plan.comp], axes)
            out = {plan.comp: best}
            if isinstance(plan, Lex):
                tie = red[plan.comp] == best
                masked = {j: jnp.where(tie, red[j], comps_by_idx[j].ident)
                          for j in _plan_comps(plan.secondary)}
                out.update(cross_plan(plan.secondary, masked))
            return out

        def cross_shard(red: dict) -> dict:
            out = dict(red)
            for p in plans:
                out.update(cross_plan(p, red))
            return out

        def body(carry):
            state, active, k, work, div, resid = carry
            state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
            evals = _propagate(comps, state, src, env)
            eactive = (active[src] & mask) if model == "pull+" else mask
            # SHARD-LOCAL work (no psum): the [k] output vector surfaces the
            # per-shard balance; the total is their host-side sum.
            work = work + jnp.sum(eactive.astype(jnp.float32))
            masked = {i: jnp.where(eactive, evals[i], comps_by_idx[i].ident)
                      for i in evals}
            red = {}
            for p in plans:
                red.update(plan_segment_reduce(p, masked, dst, n, comps_by_idx))
            red = cross_shard(red)
            if model == "pull+":
                new_d = {}
                for p in plans:
                    new_d.update(plan_merge(p, state_d, red, comps_by_idx))
            else:
                red = _apply_epilogue(comps, red)
                nonbot = {cr.idx: segment.segment_reduce(
                    "or", (state_d[cr.idx][src] != cr.ident) & mask, dst, n)
                    for cr in comps}
                has_pred = {i: segment.psum_like("or", nonbot[i], axes).astype(bool)
                            for i in nonbot}
                new_d = _recompute_merge(plans, comps_by_idx, state_d, red, has_pred)
            new = tuple(new_d[cr.idx] for cr in comps)
            ch = _changed(comps, new, state, tol)
            # sentinel on the replicated post-combine state: every shard
            # computes the identical flag, so the drain stays collective-safe
            div = div | _divergence(comps, new)
            resid = _residual(comps, new, state)
            ch = ch & ~div
            return new, ch, k + 1, work, div, resid

        def cond(carry):
            _, active, k, _, _, _ = carry
            return jnp.any(active) & (k < max_iter)

        state0 = _init_state(comps, n, sources)
        state, active, k, work, div, resid = jax.lax.while_loop(
            cond, body, (state0, jnp.ones(n, bool), jnp.int32(0),
                         jnp.float32(0), jnp.asarray(False), jnp.float32(0)))
        active_n = jnp.sum(active.astype(jnp.int32))
        return (state, k[None], work[None], div[None], resid[None],
                active_n[None])

    pspec = P(axes)
    # check_vma off: the shard-local work carry varies over the mesh axis
    # while the loop's initial carry does not; replication of k is asserted
    # on the host instead.
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(pspec, pspec, pspec, pspec, pspec),
                       out_specs=(tuple(P() for _ in comps), P(axes),
                                  P(axes), P(axes), P(axes), P(axes)),
                       check_vma=False)
    state, k, work, div, resid, active_n = fn(
        part.src, part.dst, part.weight, part.capacity, part.mask)
    k_host = np.asarray(k)
    work_host = np.asarray(work)
    # Replication contract: the state (and with it the convergence flag) is
    # replicated, so every shard must report the same iteration count.  A
    # mismatch means a collective went wrong — fail loud (naming the
    # offending shards) instead of silently trusting shard 0.
    check_shard_replication(k_host, "iteration count", "distributed")
    div_h = bool(np.asarray(div)[0])
    act_h = int(np.asarray(active_n)[0])
    res = IterationResult(state=state, iterations=int(k_host[0]),
                          edge_work=float(work_host.sum()),
                          converged=(not div_h) and act_h == 0,
                          diverged=div_h, active_count=act_h,
                          residual=float(np.asarray(resid)[0]))
    res.shards = k_shards
    res.shard_work = tuple(float(w) for w in work_host)   # per-shard balance
    return res
