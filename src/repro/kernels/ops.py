"""Jit'd dispatch wrappers around the Pallas kernels.

``iterate_pallas`` is the direction-optimized GraphIt/Gemini-analogue engine
(DESIGN.md §2): the same fixpoint semantics as ``iterate.iterate_graph`` but
with every edge sweep executed by a blocked-ELL Pallas kernel.  One engine
iteration executes exactly ONE ``pallas_call`` — either the pull sweep
(``fused_ell_sweep``: dst-keyed gather over predecessor tiles) or the push
sweep (``fused_ell_push_sweep``: source-keyed propagate over frontier-active
row tiles, dst-keyed resolution through the dst-sorted segment layout by
default — ``push_resolution="sorted"``, one extra frontier-proportional
resolution tile pass; ``"scatter"`` keeps the reference full-rectangle
scatter) — chosen per iteration by the Gemini |E_frontier| ≤ |E|/k rule
when ``direction="auto"`` (``switch_k`` tunes k per query; ``switch_k=None``
falls back to the ``DENSE_FRONTIER`` vertex-fraction threshold).  Both
sweeps produce the identity-initialised per-plan reduction that
``iterate.plan_merge`` resolves against the old state, so the direction
switch is invisible to the plan algebra.
Non-idempotent rounds always run the pull− full recompute (has-pred probe
fused in the same launch) unless the push direction is forced, in which
case the push− scatter recompute runs instead.

The fixpoint itself is compiled once per (plan structure, kernel set,
graph shape, direction) and memoized in ``_EXEC_CACHE`` — a true LRU keyed
WITHOUT the query source: the source vertex enters the compiled program as
a traced argument (``run(*arrays, srcs)``), not a closure constant, so a
32-source BFS/SSSP sweep reuses ONE traced ``lax.while_loop`` instead of
retracing per source (DESIGN.md §8).  ``iterate_pallas_batch`` goes one
step further and ``jax.vmap``s the same fixpoint over a batch of sources
sharing one blocked-ELL layout: B concurrent queries per launch, per-query
convergence via the existing active mask (DESIGN.md §9).

``iterate_pallas_sharded`` composes this engine with the distributed
vertex-cut model (DESIGN.md §11): every shard holds its own blocked-ELL
pair (``structure.sharded_ell_cached``), runs the SAME fused sweeps
shard-locally inside ``shard_map`` — including the dst-sorted push
resolution over each shard's own ``PushResolution`` stack
(``structure.sharded_push_resolution_cached``) — and merges per-vertex
partials with monoid/lex collectives; the direction switch stays global
via a psum'd frontier edge mass, so the sharded fixpoint walks the exact
iteration sequence of the single-device one.

The other wrappers expose the embedding-bag and ELL-softmax kernels behind
plain jit'd functions that the models call.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import iterate
from repro.core.fusion import Lex
from repro.graph import segment
from repro.graph.structure import (Graph, blocked_ell_cached,
                                   push_resolution_cached,
                                   sharded_ell_cached,
                                   sharded_push_resolution_cached, w_out_deg)
from repro.kernels import edge_reduce as _er
from repro.kernels import embedding_bag as _eb
from repro.kernels import segment_softmax as _ss

embedding_bag = jax.jit(_eb.embedding_bag,
                        static_argnames=("mode", "block_b", "block_d",
                                         "interpret"))
ell_softmax = jax.jit(_ss.ell_softmax,
                      static_argnames=("block_v", "block_e", "interpret"))


def _plan_levels(plan):
    levels = []
    p = plan
    while isinstance(p, Lex):
        levels.append((p.comp, p.op))
        p = p.secondary
    levels.append((p.comp, p.op))
    return levels


# ---------------------------------------------------------------------------
# Compiled-executor cache (true LRU, source-free keys).
# ---------------------------------------------------------------------------

_EXEC_CACHE: OrderedDict = OrderedDict()
_EXEC_CACHE_MAX = 128


def clear_executor_cache():
    _EXEC_CACHE.clear()


def executor_cache_size() -> int:
    return len(_EXEC_CACHE)


def _exec_cache_get(key):
    hit = _EXEC_CACHE.get(key)
    if hit is None:
        return None
    _EXEC_CACHE.move_to_end(key)       # hits refresh recency: under serving
    return hit[0]                      # churn the hot executor survives


class _Recorded:
    """A jitted executor that remembers the abstract arguments of its last
    call, so ``compiled_executor_texts`` can show what the device ran."""

    def __init__(self, fn):
        self.fn = fn
        self.arg_specs = None

    def __call__(self, *args):
        self.arg_specs = jax.tree.map(self._spec, args)
        return self.fn(*args)

    @staticmethod
    def _spec(a):
        """Shape, dtype and — for an array split over several devices —
        its sharding (single-device arguments follow the default device,
        as uncommitted arrays do at the call)."""
        sharding = getattr(a, "sharding", None)
        if sharding is not None and len(sharding.device_set) < 2:
            sharding = None
        return jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                    sharding=sharding)


def _exec_cache_put(key, run, comps):
    """Insert a built executor (or a chunked ``(init, step)`` pair) and
    return it wrapped in ``_Recorded`` — the object callers must run."""
    while len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
        _EXEC_CACHE.popitem(last=False)          # evict least-recently-USED
    run = tuple(map(_Recorded, run)) if isinstance(run, tuple) \
        else _Recorded(run)
    # The key carries id(p_fn)/id(init_fn)/id(e_fn).  Keep strong references
    # to exactly those closures in the value so a GC'd kernel set can never
    # hand its id to a new closure while the entry is alive (the id-reuse
    # hazard structure.blocked_ell_cached guards with a weakref; functions
    # are tiny, so pinning them is the simpler mirror).
    keyed = tuple((cr.p_fn, cr.init_fn, cr.e_fn) for cr in comps)
    _EXEC_CACHE[key] = (run, keyed)
    return run


def compiled_executor_texts() -> list:
    """Optimized HLO text of every cached executor that has run, compiled
    again from the abstract arguments of its last call (with a persistent
    compilation cache that is a lookup).  A Mosaic kernel shows up in it as
    a ``tpu_custom_call``; an interpreted one does not."""
    texts = []
    for run, _keyed in _EXEC_CACHE.values():
        for rec in (run if isinstance(run, tuple) else (run,)):
            if rec.arg_specs is not None:
                texts.append(
                    rec.fn.lower(*rec.arg_specs).compile().as_text())
    return texts


def _comps_key(comps):
    """Kernel-set identity: stable across calls because synthesize_round
    memoizes its compiled closures per round structure.  The source VALUE is
    deliberately absent — it is a traced argument of the executor, so every
    query source shares one entry; only sourced-ness (the ⊥-masking shape of
    the initial state) is structural."""
    return tuple((cr.idx, cr.op, str(cr.dtype), cr.source is not None,
                  id(cr.p_fn), id(cr.init_fn),
                  None if cr.e_fn is None else id(cr.e_fn), cr.l1_tol)
                 for cr in comps)


# Knob semantics live in the planner (core.plan, DESIGN.md §14) — ops
# re-exports the documented constants and normalizers for direct kernel
# callers; engine-level callers arrive with an already-normalized
# ExecutionPlan whose fields are asserted (never re-parsed) below.
from repro.core.plan import (DENSE_FRONTIER,            # noqa: E402
                             PUSH_RESOLUTION, SWITCH_K, _check_resolution,
                             _normalize_switch_k, assert_normalized)


def _apply_plan(plan, direction, dense_threshold, switch_k, push_resolution,
                idempotent):
    """Resolve the direction-switch/resolution knobs of one kernels call:
    from an ``ExecutionPlan`` (fields pre-normalized by ``plan_execution`` —
    asserted here) when the engine lowered through the planner, else by
    normalizing the legacy kwargs exactly as before.  Returns
    ``(use, dense_threshold, switch_k, push_resolution)``."""
    if plan is not None:
        assert_normalized(plan)
        use = _directions_used(plan.direction, idempotent)
        return use, plan.dense_threshold, plan.switch_k, plan.push_resolution
    use = _directions_used(direction, idempotent)
    # the dense_threshold-vs-Gemini conflict only exists when a switch is
    # actually traced; pinned directions ignore both knobs
    switch_k = _normalize_switch_k(
        switch_k, dense_threshold if len(use) == 2 else DENSE_FRONTIER)
    return use, dense_threshold, switch_k, _check_resolution(push_resolution)


def _directions_used(direction: str, idempotent: bool):
    """Which sweep layouts an executor needs.  The heuristic only arbitrates
    idempotent (+model) rounds — Gemini's precondition: both directions must
    be admissible, which the push+/push− conditions (Defs. 3/4, checked by
    core/conditions via the shared plan algebra) grant exactly when pull's
    are.  Non-idempotent rounds run one full-recompute direction."""
    if direction == "auto":
        return ("pull", "push") if idempotent else ("pull",)
    if direction == "pull":
        return ("pull",)
    if direction == "push":
        return ("push",)
    raise ValueError(f"direction must be auto|pull|push, got {direction!r}")


def _padded_init_state(comps, n, n_pad, srcs):
    """Initial per-component state padded to the layout rectangle, with the
    traced per-component sources applied (the executor-argument contract of
    DESIGN.md §8).  Shared by the single-device and sharded builders so
    their fixpoints can never diverge on the C1/C2 initial state."""
    overrides = {cr.idx: srcs[i] for i, cr in enumerate(comps)
                 if cr.source is not None}
    base = iterate._init_state(comps, n, overrides)
    return tuple(jnp.full((n_pad,), cr.ident, s.dtype).at[:n].set(s)
                 for s, cr in zip(base, comps))


# executor arguments per blocked-ELL direction: nbrs, weight, capacity, mask,
# tile_nnz, slot_pos, slot_nbr, then one more: the single-device executor's
# live-tile grid (``edge_reduce.live_grid``, None for the full grid), the
# sharded executor's row_deg
_ELL_ARGS = 7


def _build_pallas_executor(comps, plans, n, max_iter, tol, block_v, block_e,
                           interpret, use, dense_threshold, switch_k,
                           push_resolution, batch=False, sentinel=True,
                           chunked=False, warm=False):
    """Trace + jit the whole fixpoint once.  The returned function takes the
    blocked-ELL arrays (``_ELL_ARGS`` + the live-tile grid per direction in
    ``use``, pull first), out-degrees (plain + weighted), the dst-sorted
    resolution arrays (when the push direction resolves ``"sorted"``:
    slot_pos, slot_src, the ``contrib`` class table as one tuple argument,
    tile_nnz, the live-tile grid — the table's class count and shapes and
    the grid's length are the graph's, and jit retraces on them like on any
    other shape), AND the per-component query sources as arguments
    (NOT closure constants): ``run(*arrays, srcs)``
    with ``srcs`` an [n_comps] int32 vector, so one compiled executor serves
    every graph with the same padded shapes and EVERY query source without
    retracing.  It returns the full exit diagnostics
    ``(state, k, work, pushes, res_work, gather_work, tiles, div, resid,
    active_n)``, ``tiles`` the live tiles the main sweeps ran.

    ``use`` = ("pull",) | ("push",) | ("pull", "push"); with both, each
    iteration picks its sweep via ``lax.cond`` — both branches trace (two
    pallas_calls appear in the HLO) but exactly one executes per iteration
    at runtime.  The switch is the Gemini rule when ``switch_k`` is a
    number (push while Σ out_deg over the frontier ≤ |E|/k) and the
    legacy frontier-fraction threshold when ``switch_k`` is None.

    With ``batch=True`` the same fixpoint is ``jax.vmap``ped over a leading
    source axis (``srcs`` [B, n_comps]; the ELL arrays stay shared): state
    and frontier grow a batch dimension, the while_loop's batching rule
    keeps per-query convergence exact (converged queries stop updating via
    the per-element carry select), and the direction lax.cond lowers to a
    per-query select — bit-identical to the sequential runs (DESIGN.md §9).

    ``sentinel`` folds the NaN/Inf divergence sentinel + last-iteration
    residual into the loop carry (elementwise reductions, zero extra
    launches); off, the carry keeps constant placeholders so both variants
    share one signature.

    With ``warm=True`` (batch only) the vmapped fixpoint additionally takes
    one per-component ``[B, n]`` state block after ``srcs`` —
    ``run(*arrays, srcs, *state0)`` — and overrides each batch element's
    initial state rows with its own supplied block (padding rows keep the
    reduction identity, the frontier resets to all-ones exactly like
    ``_warm_start_carry``).  This is the continuous-batching join point
    (DESIGN.md §13): unconverged queries resume from their last chunk's
    state while fresh joiners ride in with their C1/C2 init rows, all in
    the same launch.

    With ``chunked=True`` the SAME traced body is exposed as a host-steppable
    pair ``(init, step)``: ``init(*arrays, srcs)`` builds the initial carry,
    ``step(*arrays, carry, k_stop)`` advances the while_loop until ``k ==
    k_stop`` or quiescence.  The loop body is the identical jaxpr in both
    variants and the carry crosses the host boundary as concrete buffers, so
    a chunked run visits the exact iteration sequence of the monolithic one
    and stays bitwise-identical (DESIGN.md §12) — which is what lets long
    fixpoints snapshot through CheckpointManager and warm-resume."""
    comps_by_idx = {cr.idx: cr for cr in comps}
    plan_levels = tuple(tuple(_plan_levels(p)) for p in plans)
    idempotent = all(iterate.plan_idempotent(p) for p in plans)
    comps_order = _er.comps_in_plan_order(plan_levels)
    idents = {c: comps_by_idx[c].ident for c in comps_order}
    p_fns = {c: comps_by_idx[c].p_fn for c in comps_order}
    sorted_res = push_resolution == "sorted" and "push" in use

    def _split(arrays):
        """(ELL dict, out_deg, wdeg, resolution arrays|None, rest)."""
        per = _ELL_ARGS + 1
        ell = {d: arrays[per * i:per * (i + 1)] for i, d in enumerate(use)}
        idx = per * len(use)
        out_deg = arrays[idx]
        wdeg = arrays[idx + 1]
        idx += 2
        res = None
        if sorted_res:
            res = arrays[idx:idx + 5]
            idx += 5
        return ell, out_deg, wdeg, res, arrays[idx:]

    def _fixpoint(arrays, carry0, k_stop):
        """Run the while_loop from ``carry0`` until quiescence or ``k ==
        k_stop`` — THE single traced body both the monolithic executor
        (k_stop = max_iter, static) and the chunked stepper (k_stop traced)
        share."""
        ell, out_deg, wdeg, res_arrays, _ = _split(arrays)
        if sorted_res:
            res_pos, res_src, res_contrib, res_nnz, res_live = res_arrays
        n_pad = ell[use[0]][0].shape[0]
        out_deg_pad = jnp.zeros(n_pad, jnp.float32).at[:n].set(
            jnp.maximum(out_deg, 1).astype(jnp.float32))
        # UNclamped degrees for the Gemini |E_frontier| estimate: the clamp
        # exists for PageRank division, but zero-out-degree vertices carry
        # zero frontier edges and must not inflate the switch signal.
        out_deg_raw = jnp.zeros(n_pad, jnp.float32).at[:n].set(
            out_deg.astype(jnp.float32))
        wdeg_pad = jnp.ones(n_pad, jnp.float32).at[:n].set(
            wdeg.astype(jnp.float32))
        num_edges = jnp.sum(ell[use[0]][3].astype(jnp.float32))
        ones_act = jnp.ones(n_pad, jnp.int32)

        def sweep(d, state_d, active_i32, tile_act, need_hp):
            """One fused sweep + its dst-keyed resolution.  Returns
            (red, hp, resolution edge work, gather work): 0/0 for pull (the
            cross-tile fold is O(n_pad·n_tiles) elementwise — not edge
            work), the kept resolution tiles' Σ nnz / the dst-major
            rectangle for sorted push (XLA gathers every slot of it before
            the resolution kernel), and rectangle/0 for the reference
            scatter (full-rectangle work, no permutation gather)."""
            nbrs, weight, capacity, mask, _nnz, s_pos, s_nbr, live = ell[d]
            states = {c: state_d[c] for c in comps_order}
            common = dict(plans=plan_levels, idents=idents, p_fns=p_fns,
                          nv=float(n), need_haspred=need_hp, wdeg=wdeg_pad,
                          live=live, block_v=block_v, block_e=block_e,
                          interpret=interpret)
            if d == "pull":
                red, hp = _er.fused_ell_sweep(
                    nbrs, weight, capacity, mask, tile_act, states,
                    active_i32, out_deg_pad, slots=(s_pos, s_nbr), **common)
                return red, hp, jnp.float32(0), jnp.float32(0)
            if sorted_res:
                res_tile_act = _er.resolution_tile_activity(
                    res_contrib, tile_act, res_nnz)
                red, hp = _er.fused_ell_push_sweep(
                    nbrs, weight, capacity, mask, tile_act, states,
                    active_i32, out_deg_pad, resolution="sorted",
                    res=(None, None, res_tile_act),
                    res_slots=(res_pos, res_src), res_live=res_live,
                    **common)
                res_w = jnp.sum(res_nnz * res_tile_act).astype(jnp.float32)
                return red, hp, res_w, jnp.sum(res_nnz).astype(jnp.float32)
            red, hp = _er.fused_ell_push_sweep(
                nbrs, weight, capacity, mask, tile_act, states,
                active_i32, out_deg_pad, resolution="scatter", **common)
            return (red, hp, jnp.float32(nbrs.shape[0] * nbrs.shape[1]),
                    jnp.float32(0))

        def masked_branch(d):
            """One frontier-masked (+model) sweep in direction ``d``; edge
            work is the real slots inside the tiles actually processed."""
            def branch(args):
                state_d, active_i32 = args
                nbrs, _w, _c, mask, tile_nnz, s_pos, s_nbr, _live = ell[d]
                if d == "pull":
                    tile_act = _er.tile_activity(nbrs, mask, tile_nnz,
                                                 active_i32, block_v, block_e,
                                                 slots=(s_pos, s_nbr))
                else:
                    tile_act = _er.tile_activity_push(tile_nnz, active_i32,
                                                      block_v)
                red, _, res_w, gat_w = sweep(d, state_d, active_i32, tile_act,
                                             False)
                w_inc = jnp.sum((tile_nnz * tile_act)).astype(jnp.float32)
                return (tuple(red[c] for c in comps_order), w_inc, res_w,
                        gat_w, jnp.sum(tile_act))
            return branch

        def body(carry):
            (state, active, k, work, pushes, res_work, gather_work, tiles,
             div, resid) = carry
            state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
            if idempotent:
                active_i32 = active.astype(jnp.int32)
                if len(use) == 2:
                    # Direction switch: sparse frontier → push (work ∝
                    # active rows), dense frontier → pull (gather tiles).
                    with jax.named_scope("grafs.merge"):
                        if switch_k is not None:
                            # Gemini rule: compare the frontier's outgoing
                            # EDGE mass against |E|/k — degree data already
                            # in the layout.  Padding rows carry 0 in
                            # out_deg_raw.
                            e_frontier = jnp.sum(active.astype(jnp.float32)
                                                 * out_deg_raw)
                            use_push = e_frontier <= num_edges / switch_k
                        else:
                            # documented fallback: frontier VERTEX fraction
                            # over the logical vertex count (padding rows,
                            # never active after iteration 1, must not
                            # dilute).
                            frac = jnp.sum(active.astype(jnp.float32)) / n
                            use_push = frac <= dense_threshold
                    red_t, w_inc, res_w, gat_w, t_inc = jax.lax.cond(
                        use_push, masked_branch("push"), masked_branch("pull"),
                        (state_d, active_i32))
                    pushes = pushes + use_push.astype(jnp.int32)
                else:
                    red_t, w_inc, res_w, gat_w, t_inc = masked_branch(use[0])(
                        (state_d, active_i32))
                    pushes = pushes + (1 if use[0] == "push" else 0)
                red = {c: red_t[i] for i, c in enumerate(comps_order)}
                work = work + w_inc
                res_work = res_work + res_w
                gather_work = gather_work + gat_w
                tiles = tiles + t_inc
                new_d = {}
                with jax.named_scope("grafs.merge"):
                    for p in plans:
                        new_d.update(iterate.plan_merge(p, state_d, red,
                                                        comps_by_idx))
            else:
                # full recompute (− models): has-pred probe in the same
                # launch; only all-padding tiles skip.
                d = use[0]
                work = work + num_edges
                tiles_static = (ell[d][4] > 0).astype(jnp.int32)
                red, hp, res_w, gat_w = sweep(d, state_d, ones_act,
                                              tiles_static, True)
                res_work = res_work + res_w
                gather_work = gather_work + gat_w
                tiles = tiles + jnp.sum(tiles_static)
                red = iterate._apply_epilogue(comps, red, n)
                with jax.named_scope("grafs.merge"):
                    new_d = iterate._recompute_merge(plans, comps_by_idx,
                                                     state_d, red, hp)
                pushes = pushes + (1 if d == "push" else 0)
            new = tuple(new_d[cr.idx] for cr in comps)
            with jax.named_scope("grafs.merge"):
                ch = iterate._changed(comps, new, state, tol, n)
                if sentinel:
                    # fold divergence + residual into the existing carry:
                    # pure elementwise reductions, no extra kernel launches.
                    # A fired sentinel drains the frontier so the loop exits
                    # on its own condition.
                    div = div | iterate._divergence(comps, new)
                    resid = iterate._residual(comps, new, state, n)
                    ch = ch & ~div
            return (new, ch, k + 1, work, pushes, res_work, gather_work,
                    tiles, div, resid)

        def cond(carry):
            active, k = carry[1], carry[2]
            return jnp.any(active) & (k < k_stop)

        return jax.lax.while_loop(cond, body, carry0)

    def _init(arrays):
        """Initial carry from the shared arrays (+ srcs, the trailing one)."""
        ell, _, _, _, rest = _split(arrays)
        srcs = rest[0]
        n_pad = ell[use[0]][0].shape[0]
        state0 = _padded_init_state(comps, n, n_pad, srcs)
        return (state0, jnp.ones(n_pad, bool), jnp.int32(0),
                jnp.float32(0), jnp.int32(0), jnp.float32(0),
                jnp.float32(0), jnp.int32(0), jnp.asarray(False),
                jnp.float32(0))

    def _exit(carry):
        """The executor's outputs from the final carry: the frontier
        becomes its live count over the real vertices."""
        state, active, *counters = carry
        return (state, *counters, jnp.sum(active[:n].astype(jnp.int32)))

    def run(*arrays):
        return _exit(_fixpoint(arrays, _init(arrays), max_iter))

    if warm and not batch:
        raise ValueError("warm start rows are a batched-executor feature; "
                         "single queries warm-start via init_state= on the "
                         "chunked path")
    if chunked:
        if batch:
            raise ValueError("chunked execution does not batch")

        def step(*args_carry):
            *arrays, carry, k_stop = args_carry
            return _fixpoint(tuple(arrays), carry, k_stop)

        def init(*arrays):
            return _init(tuple(arrays))

        return jax.jit(init), jax.jit(step)
    if batch:
        # everything but srcs (ELL tuples, degrees, resolution arrays) is
        # shared across the batch
        n_shared = (_ELL_ARGS + 1) * len(use) + 2 + (5 if sorted_res else 0)
        if warm:
            def run_warm(*all_args):
                arrays = all_args[:n_shared + 1]      # shared + this row's srcs
                state0 = all_args[n_shared + 1:]      # per-component [n] rows
                st, *rest = _init(arrays)
                st = tuple(ref.at[:n].set(s.astype(ref.dtype))
                           for ref, s in zip(st, state0))
                return _exit(_fixpoint(arrays, (st, *rest), max_iter))

            return jax.jit(jax.vmap(
                run_warm,
                in_axes=(None,) * n_shared + (0,) * (1 + len(comps))))
        return jax.jit(jax.vmap(run, in_axes=(None,) * n_shared + (0,)))
    return jax.jit(run)


def _srcs_vector(comps, sources=None):
    """Per-component source ids as an [n_comps] int32 vector: the executor's
    traced source argument.  ``sources`` optionally overrides ``cr.source``
    per component index (sourced components only — sourced-ness is
    structural); sourceless components carry an ignored −1 placeholder."""
    vals = []
    for cr in comps:
        if cr.source is None:
            vals.append(-1)
        elif sources is not None and cr.idx in sources:
            vals.append(int(sources[cr.idx]))
        else:
            vals.append(int(cr.source))
    return jnp.asarray(vals, jnp.int32)


@obs.span("grafs.executor")
def _pallas_executor(g, comps, plans, max_iter, tol, block_v, block_e,
                     interpret, use, dense_threshold, switch_k,
                     push_resolution, batch=False, sentinel=True,
                     chunked=False, warm=False):
    """Cache lookup / build of the compiled fixpoint, the shared argument
    prefix (ELL arrays + degree vectors + dst-sorted resolution arrays) it
    runs on, the entries of the contributing-tile table one push round
    reads (0 without the sorted resolution), and the grid steps the sweep
    kernels of one iteration launch, per direction in ``use``."""
    ells = {"pull": blocked_ell_cached(g, block_v=block_v, block_e=block_e,
                                       direction="in") if "pull" in use else None,
            "push": blocked_ell_cached(g, block_v=block_v, block_e=block_e,
                                       direction="out") if "push" in use else None}
    # Normalize knobs a pinned executor never reads out of its cache key,
    # so e.g. model="pull" runs with different push_resolution values share
    # one compiled entry instead of retracing per knob.
    if len(use) != 2:                # pinned direction: no switch traced
        dense_threshold = None
        switch_k = None
    if "push" not in use:            # no push sweep: no resolution traced
        push_resolution = "unused"
    res = push_resolution_cached(g, block_v=block_v, block_e=block_e) \
        if (push_resolution == "sorted" and "push" in use) else None
    key = (g.n, tuple(tuple(_plan_levels(p)) for p in plans),
           _comps_key(comps), max_iter, tol, block_v, block_e, interpret,
           use, dense_threshold, switch_k, push_resolution, batch,
           sentinel, chunked, warm)
    run = _exec_cache_get(key)
    if run is None:
        run = _exec_cache_put(key, _build_pallas_executor(
            comps, plans, g.n, max_iter, tol, block_v, block_e, interpret,
            use, dense_threshold, switch_k, push_resolution, batch=batch,
            sentinel=sentinel, chunked=chunked, warm=warm), comps)
    args, steps = [], {}
    for d in use:
        e = ells[d]
        args += [e.nbrs, e.weight, e.capacity, e.mask, e.tile_nnz,
                 e.slot_pos, e.slot_nbr,
                 _er.live_grid(e.live_tiles, e.tile_nnz.size)]
        steps[d] = _er.grid_steps(e.live_tiles, e.tile_nnz.size)
    args.append(g.out_deg)
    args.append(w_out_deg(g))
    if res is None:
        return run, args, 0, steps
    args += [res.slot_pos, res.slot_src, res.contrib, res.tile_nnz,
             _er.live_grid(res.live_tiles, res.tile_nnz.size)]
    steps["push"] += _er.grid_steps(res.live_tiles, res.tile_nnz.size)
    return run, args, res.contrib_entries, steps


def _grid_steps(steps, iters, pushes):
    """Grid steps the sweep kernels of a fixpoint launched: ``steps`` per
    pull and per push iteration (``_pallas_executor``) times how many of
    each ran."""
    return (steps.get("pull", 0) * (iters - pushes)
            + steps.get("push", 0) * pushes)


def _fixpoint_fingerprint(g, comps, plans, use, max_iter, tol, block_v,
                          block_e, push_resolution, switch_k, srcs):
    """JSON-able identity of a chunked fixpoint: a checkpoint written under
    one fingerprint must never warm-resume an executor built for another
    (different graph, plan structure, query sources, or knobs would silently
    continue a DIFFERENT query — ``CheckpointMismatchError`` instead)."""
    return {
        "n": int(g.n), "num_edges": int(g.num_edges),
        "plans": repr(tuple(tuple(_plan_levels(p)) for p in plans)),
        "comps": repr(tuple((cr.idx, cr.op, str(np.dtype(cr.dtype)),
                             cr.e_fn is not None, cr.l1_tol)
                            for cr in comps)),
        "use": list(use), "max_iter": int(max_iter), "tol": float(tol),
        "block_v": int(block_v), "block_e": int(block_e),
        "push_resolution": str(push_resolution),
        "switch_k": None if switch_k is None else float(switch_k),
        "srcs": [int(s) for s in np.asarray(srcs)],
    }


def _warm_start_carry(carry, comps, init_state, n):
    """Override the initial carry's state with user-supplied per-component
    [n] arrays (the warm-start primitive): padding rows keep the reduction
    identity, the frontier resets to all-ones so the first sweep re-derives
    the true active set from the supplied state."""
    state0, *rest = carry
    init_state = tuple(init_state)
    if len(init_state) != len(comps):
        raise ValueError(
            f"init_state has {len(init_state)} arrays for "
            f"{len(comps)} components")
    new_state = []
    for ref, cr, arr in zip(state0, comps, init_state):
        a = jnp.asarray(arr, dtype=ref.dtype)
        if a.shape != (n,):
            raise ValueError(
                f"init_state for component {cr.idx} has shape {a.shape}, "
                f"expected ({n},)")
        new_state.append(ref.at[:n].set(a))
    return (tuple(new_state), *rest)


def iterate_pallas(g: Graph, comps, plans, max_iter: Optional[int] = None,
                   tol: float = 0.0, block_v: int = 8, block_e: int = 128,
                   interpret: Optional[bool] = None, direction: str = "auto",
                   dense_threshold: float = DENSE_FRONTIER,
                   switch_k="auto", push_resolution: str = PUSH_RESOLUTION,
                   sources: Optional[dict] = None,
                   divergence_sentinel: bool = True,
                   init_state=None, delta=None,
                   checkpoint_every: Optional[int] = None,
                   ckpt_dir=None, resume: bool = False,
                   fault_hook=None, plan=None) -> iterate.IterationResult:
    """Fixpoint of the fused reduction with single-launch Pallas edge sweeps.

    ``direction`` selects the sweep model per DESIGN.md §2:

      "auto"  (default) Gemini-style: idempotent rounds pick push vs pull
              per iteration from the frontier; non-idempotent rounds run
              pull− full recompute.
      "pull"  dst-keyed gather sweeps only (Def. 1 / Def. 2).
      "push"  src-keyed scatter sweeps only (Def. 3 / Def. 4).

    ``switch_k`` tunes the "auto" switch: "auto" (default) applies the
    Gemini rule with k = ``SWITCH_K`` (push while |E_frontier| ≤ |E|/k,
    from the out-degree data already in the layout), a positive number
    overrides k per query, and None falls back to the documented
    ``DENSE_FRONTIER`` vertex-fraction threshold (``dense_threshold`` —
    only read under switch_k=None; a custom threshold with the Gemini
    rule active raises rather than being silently inert).

    ``push_resolution`` selects the push sweep's dst-keyed resolution
    (DESIGN.md §10): "sorted" (default) resolves through the precomputed
    dst-major segment layout with a frontier-proportional Pallas tile
    pass; "scatter" keeps the reference full-rectangle XLA scatter.

    ``sources`` optionally overrides per-component query sources; overrides
    (like the spec's own sources) are runtime arguments of the compiled
    executor, never trace constants.

    The returned result carries ``pull_iters``/``push_iters`` — the runtime
    per-direction iteration counts — and ``resolve_work`` — the resolution
    edge work actually performed.

    Guarded-execution knobs (DESIGN.md §12):

    ``divergence_sentinel``
        fold the NaN/Inf sentinel + last-iteration residual into the loop
        carry (default on; zero extra launches — off only for overhead
        benchmarking).
    ``init_state``
        per-component [n] arrays to warm-start the fixpoint from (e.g. a
        previous query's converged state); padding and the frontier reset
        are handled here.
    ``delta``
        vertex ids whose values may have changed (a mutation's touched set,
        ``mutate.MutationDelta.touched``): seeds the warm-started frontier
        with exactly these vertices instead of all-ones, so an idempotent
        round after a small edit converges in a handful of
        frontier-proportional sweeps (DESIGN.md §15).  Requires
        ``init_state``; for non-idempotent rounds (whose per-iteration
        recompute ignores the frontier — the warm state, not the mask, is
        the saving) a positive ``tol`` is required, because their
        convergence to the unique attractive fixpoint is a tolerance
        statement, not a bitwise one.
    ``checkpoint_every`` / ``ckpt_dir`` / ``resume``
        run the SAME traced loop body in host-stepped chunks of
        ``checkpoint_every`` iterations, snapshotting the carry through
        ``checkpoint.FixpointCheckpointer`` after each chunk;
        ``resume=True`` restores the newest fingerprint-matching snapshot
        and continues.  Chunked execution is bitwise-identical to the
        monolithic loop (shared body jaxpr, exact integer chunk bounds).
    ``fault_hook``
        test-only callable invoked with the iteration count after each
        chunk — fault-injection tests raise from it to kill a run
        mid-fixpoint.

    ``plan``
        an engine-resolved ``core.plan.ExecutionPlan``: overrides
        ``direction``/``dense_threshold``/``switch_k``/``push_resolution``/
        ``divergence_sentinel`` with the plan's pre-normalized fields
        (asserted, not re-parsed — DESIGN.md §14).  Cache keys are identical
        to the legacy-kwarg path for identical decisions.
    """
    n = g.n
    interpret = _er.resolve_interpret(interpret)
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    idempotent = all(iterate.plan_idempotent(p) for p in plans)
    use, dense_threshold, switch_k, push_resolution = _apply_plan(
        plan, direction, dense_threshold, switch_k, push_resolution,
        idempotent)
    if plan is not None:
        divergence_sentinel = plan.divergence_sentinel
    if checkpoint_every is not None and int(checkpoint_every) < 1:
        raise ValueError("checkpoint_every must be >= 1")
    if (checkpoint_every is not None or resume) and ckpt_dir is None:
        raise ValueError("checkpoint_every/resume require ckpt_dir")
    srcs = _srcs_vector(comps, sources)
    if delta is not None:
        if init_state is None:
            raise ValueError(
                "delta= seeds the frontier of a warm start; pass init_state= "
                "(the previous solution) with it")
        if not idempotent and not tol > 0:
            raise ValueError(
                "delta warm start of a non-idempotent round requires tol > 0:"
                " convergence to the unique attractive fixpoint is a "
                "tolerance statement, not a bitwise one (DESIGN.md §15)")
        delta = np.asarray(delta, dtype=np.int64).ravel()
        if delta.size and (delta.min() < 0 or delta.max() >= n):
            raise ValueError(f"delta vertex ids out of range [0, {n})")
    chunk_mode = (checkpoint_every is not None or init_state is not None
                  or resume or fault_hook is not None)
    if not chunk_mode:
        run, args, entries, steps = _pallas_executor(
            g, comps, plans, max_iter, tol, block_v, block_e, interpret, use,
            dense_threshold, switch_k, push_resolution,
            sentinel=divergence_sentinel)
        with obs.span("grafs.dispatch"):
            out = run(*args, srcs)
        if not isinstance(out[1], jax.core.Tracer):   # not under a jit
            with obs.span("grafs.device_wait"):
                jax.block_until_ready(out)
        (state, k, work, pushes, res_work, gather_work, tiles, div, resid,
         act_n) = out
    else:
        pair, args, entries, steps = _pallas_executor(
            g, comps, plans, max_iter, tol, block_v, block_e, interpret, use,
            dense_threshold, switch_k, push_resolution,
            sentinel=divergence_sentinel, chunked=True)
        init_f, step_f = pair
        ckpt = None
        if ckpt_dir is not None:
            from repro.checkpoint.fixpoint import FixpointCheckpointer
            ckpt = FixpointCheckpointer(
                ckpt_dir,
                fingerprint=_fixpoint_fingerprint(
                    g, comps, plans, use, max_iter, tol, block_v, block_e,
                    push_resolution, switch_k, srcs))
        carry = None
        carry0 = init_f(*args, srcs)
        if resume:
            carry = ckpt.restore(carry0)
        if carry is None:
            carry = carry0
            if init_state is not None:
                carry = _warm_start_carry(carry, comps, init_state, n)
            if delta is not None:
                # replace the all-ones warm-start frontier with exactly the
                # mutation's touched vertices: the first sweep propagates
                # only from them (padding rows stay inactive)
                seed = np.zeros(int(carry[1].shape[0]), dtype=bool)
                seed[delta] = True
                carry = (carry[0], jnp.asarray(seed)) + tuple(carry[2:])
        chunk = int(checkpoint_every) if checkpoint_every else max_iter
        while True:
            k_h = int(np.asarray(carry[2]))
            # the FULL padded frontier, exactly the monolithic loop condition
            if k_h >= max_iter or not bool(np.any(np.asarray(carry[1]))):
                break
            carry = step_f(*args, carry,
                           jnp.int32(min(k_h + chunk, max_iter)))
            k_done = int(np.asarray(carry[2]))
            if ckpt is not None and checkpoint_every is not None:
                ckpt.save(carry, k_done)
            if fault_hook is not None:
                fault_hook(k_done)
        (state, active, k, work, pushes, res_work, gather_work, tiles, div,
         resid) = carry
        act_n = jnp.sum(active[:n].astype(jnp.int32))
    with obs.span("grafs.stats_to_host"):
        k_i = iterate._host(k, int)
        p_i = iterate._host(pushes, int)
        res = iterate.IterationResult(
            state=tuple(s[:n] for s in state),
            iterations=k_i,
            edge_work=iterate._host(work, float),
            converged=iterate._host(jnp.logical_and(~div, act_n == 0), bool),
            diverged=iterate._host(div, bool),
            active_count=iterate._host(act_n, int),
            residual=iterate._host(resid, float))
        res.push_iters = p_i
        res.pull_iters = k_i - p_i        # valid for ints and tracers alike
        res.resolve_work = iterate._host(res_work, float)
        res.gather_work = iterate._host(gather_work, float)
        res.activity_reads = entries * p_i
        res.tiles_swept = iterate._host(tiles, int)
        res.grid_steps = _grid_steps(steps, k_i, p_i)
        res.tile_shape = (block_v, block_e)
    return res


def iterate_pallas_batch(g: Graph, comps, plans, sources: Sequence,
                         max_iter: Optional[int] = None, tol: float = 0.0,
                         block_v: int = 8, block_e: int = 128,
                         interpret: Optional[bool] = None,
                         direction: str = "auto",
                         dense_threshold: float = DENSE_FRONTIER,
                         switch_k="auto",
                         push_resolution: str = PUSH_RESOLUTION,
                         init_state=None, plan=None) -> iterate.IterationResult:
    """Run B concurrent queries of one fused round in ONE launch (DESIGN.md
    §9): the compiled fixpoint of ``iterate_pallas``, ``jax.vmap``ped over a
    batch of query sources sharing one blocked-ELL layout.

    ``sources`` is either a [B] sequence of source ids (applied to every
    sourced component — the single-source query case: BFS/SSSP/WP sweeps) or
    a [B, n_comps] array of per-component sources.  Each query converges
    independently through its own active mask (the while_loop batching rule
    selects per-element carries), so results are bit-identical to B
    sequential ``iterate_pallas`` calls; the batch reuses the SAME traced
    executor family (one ``_EXEC_CACHE`` entry per direction set, regardless
    of B — jit re-specializes on the batch shape inside the entry).

    ``init_state`` optionally warm-starts every batch element: one
    per-component ``[B, n]`` array, each row overriding that element's
    initial state (the frontier resets to all-ones, mirroring the
    single-query ``iterate_pallas(init_state=...)`` contract).  This is the
    continuous-batching join hook (DESIGN.md §13): carry the returned state
    between bounded-``max_iter`` chunk launches, splicing fresh C1/C2 init
    rows into retired slots as new queries join.

    Returns an ``IterationResult`` whose ``state`` entries are [B, n], and
    whose ``iterations`` / ``edge_work`` / ``push_iters`` / ``pull_iters``
    are per-query [B] vectors."""
    n = g.n
    interpret = _er.resolve_interpret(interpret)
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    idempotent = all(iterate.plan_idempotent(p) for p in plans)
    srcs = jnp.asarray(sources, jnp.int32)
    if srcs.ndim == 1:                     # [B] → [B, n_comps] per-component
        per_comp = jnp.asarray([-1 if cr.source is None else 0
                                for cr in comps], jnp.int32)
        srcs = jnp.where(per_comp[None, :] < 0, per_comp[None, :],
                         srcs[:, None])
    if srcs.ndim != 2 or srcs.shape[1] != len(comps):
        raise ValueError(f"sources must be [B] or [B, {len(comps)}], got "
                         f"shape {srcs.shape}")
    use, dense_threshold, switch_k, push_resolution = _apply_plan(
        plan, direction, dense_threshold, switch_k, push_resolution,
        idempotent)
    if init_state is not None:
        init_state = tuple(jnp.asarray(a) for a in init_state)
        if len(init_state) != len(comps):
            raise ValueError(f"init_state has {len(init_state)} arrays for "
                             f"{len(comps)} components")
        B = int(srcs.shape[0])
        for cr, a in zip(comps, init_state):
            if a.shape != (B, n):
                raise ValueError(
                    f"init_state for component {cr.idx} has shape "
                    f"{a.shape}, expected ({B}, {n})")
    run, args, entries, steps = _pallas_executor(
        g, comps, plans, max_iter, tol, block_v, block_e, interpret, use,
        dense_threshold, switch_k, push_resolution, batch=True,
        warm=init_state is not None)
    if init_state is not None:
        (state, k, work, pushes, res_work, gather_work, tiles, div, resid,
         act_n) = run(*args, srcs, *init_state)
    else:
        (state, k, work, pushes, res_work, gather_work, tiles, div, resid,
         act_n) = run(*args, srcs)
    res = iterate.IterationResult(
        state=tuple(s[:, :n] for s in state),
        iterations=k,                     # [B] per-query iteration counts
        edge_work=work,                   # [B] per-query edge work
        converged=jnp.logical_and(~div, act_n == 0),   # [B]
        diverged=div,                     # [B] per-query sentinel flags
        active_count=act_n,               # [B]
        residual=resid)                   # [B]
    res.push_iters = pushes
    res.pull_iters = k - pushes
    res.resolve_work = res_work           # [B] per-query resolution work
    res.gather_work = gather_work         # [B] per-query gather work
    res.tiles_swept = tiles               # [B] per-query live tiles swept
    res.grid_steps = _grid_steps(         # [B] per-query kernel grid steps
        steps, np.asarray(k, np.int64), np.asarray(pushes, np.int64))
    res.tile_shape = (block_v, block_e)
    # [B] contrib-table entries read (int64 on the host: a large table times
    # the push rounds passes int32)
    res.activity_reads = entries * np.asarray(pushes, np.int64)
    return res


# ---------------------------------------------------------------------------
# Sharded pallas engine: shard-local fused ELL sweeps under shard_map
# (DESIGN.md §11).
# ---------------------------------------------------------------------------


def _axes_tuple(axes):
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _mesh_cache_key(mesh, axes):
    """Mesh identity for the executor cache: the device set (ids), the mesh
    axis name→size layout, and the shard axes the executor reduces over.
    Two meshes over the same devices with the same layout share one
    compiled entry; a different device set or a RESHAPED mesh (same ids,
    different axis sizes — which changes how shard_map splits the stacked
    layouts) retraces."""
    return (tuple(int(d.id) for d in np.ravel(mesh.devices)),
            tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names),
            _axes_tuple(axes))


def cross_combines_per_iter(plans, comps, idempotent: bool) -> int:
    """Cross-shard state-combine collectives one ``pallas_sharded`` (or
    ``distributed``) iteration executes: one monoid psum/pmin/pmax per lex
    level of every plan, plus one OR-combine per component for the has-pred
    probe of non-idempotent rounds.  (The direction-switch edge-mass psum
    and the work accounting are control traffic, not state combines, and are
    not counted.)"""
    c = sum(len(_plan_levels(p)) for p in plans)
    if not idempotent:
        c += len(comps)
    return c


def _build_sharded_executor(comps, plans, n, max_iter, tol, block_v, block_e,
                            interpret, use, dense_threshold, switch_k,
                            push_resolution, mesh, axes):
    """Trace + jit the sharded fixpoint once per (plan structure, kernel set,
    graph shape, direction set, resolution, mesh).  The returned function
    takes one 8-tuple of STACKED ``[k, ...]`` sharded-ELL arrays per
    direction in ``use`` (nbrs, weight, capacity, mask, tile_nnz, slot_pos,
    slot_nbr, row_deg — split on the shard axis by ``shard_map``), then
    (when the push direction resolves ``"sorted"``) the 4 stacked per-shard
    resolution arguments of ``structure.ShardedPushResolution`` (slot_pos,
    slot_src, the contrib class table, tile_nnz — also shard-split), the
    replicated degree
    vectors, and the
    traced per-component query sources: ``run(*arrays, srcs)``.

    Inside ``shard_map`` every shard runs the SAME fused Pallas sweeps as
    the single-device engine over its own blocked-ELL pair — frontier-aware
    tile skipping included — producing an identity-initialised per-vertex
    partial reduction; partials merge across shards with the monoid/lex
    ``cross_plan`` combine (primary via psum/pmin/pmax, tie-masked
    secondaries, k× less traffic than an all_gather), and the replicated
    merged state feeds ``plan_merge`` / ``_recompute_merge`` exactly like
    the single-device fixpoint.  The per-iteration direction switch stays
    GLOBAL: the frontier's outgoing edge mass is a psum of shard-local
    out-layout row degrees, so every shard compares the same (integer-exact)
    mass against |E|/k and picks the same sweep.  State is replicated, so
    the convergence flag is identical on every shard and the while_loop is
    collective-safe.  The push sweep resolves its dst-keyed reduction
    shard-locally with the dst-sorted segment pass by default (each shard's
    own ``PushResolution`` stack over its widened out-layout — the
    permutation gather and the frontier-proportional tile skipping work
    per shard exactly as on one device, and the cross-shard monoid/lex
    combine contract is unchanged); ``"scatter"`` keeps the per-shard
    reference scatter as the oracle (DESIGN.md §11)."""
    from jax.sharding import PartitionSpec as P


    ax = _axes_tuple(axes)
    comps_by_idx = {cr.idx: cr for cr in comps}
    plan_levels = tuple(tuple(_plan_levels(p)) for p in plans)
    idempotent = all(iterate.plan_idempotent(p) for p in plans)
    comps_order = _er.comps_in_plan_order(plan_levels)
    idents = {c: comps_by_idx[c].ident for c in comps_order}
    p_fns = {c: comps_by_idx[c].p_fn for c in comps_order}
    sorted_res = push_resolution == "sorted" and "push" in use

    def shard_fn(*arrays):
        ell = {}
        idx = 0
        for d in use:
            # [1, ...] → [...]
            ell[d] = tuple(a[0] for a in arrays[idx:idx + _ELL_ARGS + 1])
            idx += _ELL_ARGS + 1
        if sorted_res:
            res_pos, res_src, res_contrib, res_nnz = \
                jax.tree.map(lambda a: a[0], tuple(arrays[idx:idx + 4]))
            idx += 4
        out_deg = arrays[idx]
        wdeg = arrays[idx + 1]
        srcs = arrays[idx + 2]
        n_pad = ell[use[0]][0].shape[0]
        out_deg_pad = jnp.zeros(n_pad, jnp.float32).at[:n].set(
            jnp.maximum(out_deg, 1).astype(jnp.float32))
        wdeg_pad = jnp.ones(n_pad, jnp.float32).at[:n].set(
            wdeg.astype(jnp.float32))
        # Shard-local real-edge count; the direction switch compares against
        # the GLOBAL |E| via psum so every shard sees the same threshold.
        local_edges = jnp.sum(ell[use[0]][3].astype(jnp.float32))
        num_edges_g = jax.lax.psum(local_edges, ax)
        ones_act = jnp.ones(n_pad, jnp.int32)

        def cross_plan(plan, red: dict) -> dict:
            """Cross-shard lexicographic combine with monoid collectives
            only (the distributed engine's combiner over the pallas sweeps'
            partials): global primary via psum/pmin/pmax, tie-mask the local
            secondaries to identity, recurse.  Replicated across shards."""
            best = segment.psum_like(plan.op, red[plan.comp], ax)
            out = {plan.comp: best}
            if isinstance(plan, Lex):
                tie = red[plan.comp] == best
                masked = {j: jnp.where(tie, red[j], comps_by_idx[j].ident)
                          for j in iterate._plan_comps(plan.secondary)}
                out.update(cross_plan(plan.secondary, masked))
            return out

        def cross_shard(red: dict) -> dict:
            out = dict(red)
            for p in plans:
                out.update(cross_plan(p, red))
            return out

        def sweep(d, state_d, active_i32, tile_act, need_hp):
            """One shard-local fused sweep: the SAME pallas kernels as the
            single-device engine, over this shard's blocked-ELL slice.
            Returns (red, hp, resolution edge work, gather work) exactly
            like the single-device ``sweep`` — the sorted push resolve runs
            shard-locally over this shard's own ``PushResolution`` slice."""
            nbrs, weight, capacity, mask, _nnz, s_pos, s_nbr, _rdeg = ell[d]
            states = {c: state_d[c] for c in comps_order}
            common = dict(plans=plan_levels, idents=idents, p_fns=p_fns,
                          nv=float(n), need_haspred=need_hp, wdeg=wdeg_pad,
                          block_v=block_v, block_e=block_e,
                          interpret=interpret)
            if d == "pull":
                red, hp = _er.fused_ell_sweep(
                    nbrs, weight, capacity, mask, tile_act, states,
                    active_i32, out_deg_pad, slots=(s_pos, s_nbr), **common)
                return red, hp, jnp.float32(0), jnp.float32(0)
            if sorted_res:
                res_tile_act = _er.resolution_tile_activity(
                    res_contrib, tile_act, res_nnz)
                red, hp = _er.fused_ell_push_sweep(
                    nbrs, weight, capacity, mask, tile_act, states,
                    active_i32, out_deg_pad, resolution="sorted",
                    res=(None, None, res_tile_act),
                    res_slots=(res_pos, res_src), **common)
                res_w = jnp.sum(res_nnz * res_tile_act).astype(jnp.float32)
                return red, hp, res_w, jnp.sum(res_nnz).astype(jnp.float32)
            red, hp = _er.fused_ell_push_sweep(
                nbrs, weight, capacity, mask, tile_act, states,
                active_i32, out_deg_pad, resolution="scatter", **common)
            return (red, hp, jnp.float32(nbrs.shape[0] * nbrs.shape[1]),
                    jnp.float32(0))

        def masked_branch(d):
            """One frontier-masked (+model) shard-local sweep; edge work is
            the real slots inside the tiles THIS shard processed."""
            def branch(args):
                state_d, active_i32 = args
                nbrs, _w, _c, mask, tile_nnz, s_pos, s_nbr, _rdeg = ell[d]
                if d == "pull":
                    tile_act = _er.tile_activity(nbrs, mask, tile_nnz,
                                                 active_i32, block_v, block_e,
                                                 slots=(s_pos, s_nbr))
                else:
                    tile_act = _er.tile_activity_push(tile_nnz, active_i32,
                                                      block_v)
                red, _, res_w, gat_w = sweep(d, state_d, active_i32, tile_act,
                                             False)
                w_inc = jnp.sum((tile_nnz * tile_act)).astype(jnp.float32)
                return (tuple(red[c] for c in comps_order), w_inc, res_w,
                        gat_w, jnp.sum(tile_act))
            return branch

        def body(carry):
            (state, active, k, work, pushes, res_work, gather_work, tiles,
             div, resid) = carry
            state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
            if idempotent:
                active_i32 = active.astype(jnp.int32)
                if len(use) == 2:
                    if switch_k is not None:
                        # Gemini rule, computed GLOBALLY: psum the frontier's
                        # shard-local out-edge mass (out-layout row degrees —
                        # padding rows carry 0) so every shard compares the
                        # identical (integer-exact) edge mass and picks the
                        # same direction as the single-device engine.
                        local_mass = jnp.sum(active.astype(jnp.float32)
                                             * ell["push"][-1])
                        e_frontier = jax.lax.psum(local_mass, ax)
                        use_push = e_frontier <= num_edges_g / switch_k
                    else:
                        # fallback frontier-fraction rule: the frontier is
                        # replicated, so this is shard-invariant by itself.
                        frac = jnp.sum(active.astype(jnp.float32)) / n
                        use_push = frac <= dense_threshold
                    red_t, w_inc, res_w, gat_w, t_inc = jax.lax.cond(
                        use_push, masked_branch("push"), masked_branch("pull"),
                        (state_d, active_i32))
                    pushes = pushes + use_push.astype(jnp.int32)
                else:
                    red_t, w_inc, res_w, gat_w, t_inc = masked_branch(use[0])(
                        (state_d, active_i32))
                    pushes = pushes + (1 if use[0] == "push" else 0)
                red = cross_shard({c: red_t[i]
                                   for i, c in enumerate(comps_order)})
                work = work + w_inc
                res_work = res_work + res_w
                gather_work = gather_work + gat_w
                tiles = tiles + t_inc
                new_d = {}
                for p in plans:
                    new_d.update(iterate.plan_merge(p, state_d, red,
                                                    comps_by_idx))
            else:
                # full recompute (− models): every shard sweeps its real
                # tiles, partial sums/extrema combine across shards, then
                # the epilogue applies to the GLOBAL reduction.
                d = use[0]
                work = work + local_edges
                tiles_static = (ell[d][4] > 0).astype(jnp.int32)
                red, hp, res_w, gat_w = sweep(d, state_d, ones_act,
                                              tiles_static, True)
                res_work = res_work + res_w
                gather_work = gather_work + gat_w
                tiles = tiles + jnp.sum(tiles_static)
                red = cross_shard(red)
                hp = {c: segment.psum_like(
                    "or", hp[c].astype(jnp.int32), ax).astype(bool)
                    for c in hp}
                red = iterate._apply_epilogue(comps, red, n)
                new_d = iterate._recompute_merge(plans, comps_by_idx,
                                                 state_d, red, hp)
                pushes = pushes + (1 if d == "push" else 0)
            new = tuple(new_d[cr.idx] for cr in comps)
            ch = iterate._changed(comps, new, state, tol, n)
            # divergence sentinel on the REPLICATED post-combine state: every
            # shard computes the identical flag, so draining the frontier
            # through it stays collective-safe.
            div = div | iterate._divergence(comps, new)
            resid = iterate._residual(comps, new, state, n)
            ch = ch & ~div
            return (new, ch, k + 1, work, pushes, res_work, gather_work,
                    tiles, div, resid)

        def cond(carry):
            active, k = carry[1], carry[2]
            return jnp.any(active) & (k < max_iter)

        state0 = _padded_init_state(comps, n, n_pad, srcs)
        (state, active, k, work, pushes, res_work, gather_work, tiles, div,
         resid) = jax.lax.while_loop(
            cond, body, (state0, jnp.ones(n_pad, bool), jnp.int32(0),
                         jnp.float32(0), jnp.int32(0), jnp.float32(0),
                         jnp.float32(0), jnp.int32(0), jnp.asarray(False),
                         jnp.float32(0)))
        # k/pushes/div/resid/active_n are replicated (k and pushes asserted
        # host-side); work/res_work/gather_work/tiles are per-shard.
        active_n = jnp.sum(active[:n].astype(jnp.int32))
        return (state, k[None], work[None], pushes[None], res_work[None],
                gather_work[None], tiles[None], div[None], resid[None],
                active_n[None])

    pspec = P(ax)
    in_specs = tuple([pspec] * ((_ELL_ARGS + 1) * len(use))
                     + ([pspec] * 4 if sorted_res else [])
                     + [P(), P(), P()])
    out_specs = (tuple(P() for _ in comps), P(ax), P(ax), P(ax), P(ax),
                 P(ax), P(ax), P(ax), P(ax), P(ax))
    # check_vma off: the checker cannot see through pallas_call —
    # replication of state/k/pushes is an engine-level contract asserted on
    # the host instead.
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _sharded_executor(g, comps, plans, mesh, axes, strategy, max_iter, tol,
                      block_v, block_e, interpret, use, dense_threshold,
                      switch_k, push_resolution):
    """Cache lookup / build of the compiled sharded fixpoint, the stacked
    argument prefix it runs on, the shard count, the entries of the
    stacked contributing-tile table one push round reads (0 without the
    sorted resolution), and the grid steps the sweep kernels of one
    iteration launch, per direction, summed over shards.  Every shard
    walks its full grid: the shards' live-tile lists differ in length."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ax = _axes_tuple(axes)
    k_shards = int(np.prod([mesh.shape[a] for a in ax]))
    split = NamedSharding(mesh, P(ax))     # layouts go host → their shards
    ells = {d: sharded_ell_cached(
        g, k_shards, strategy=strategy, block_v=block_v, block_e=block_e,
        direction={"pull": "in", "push": "out"}[d], sharding=split)
        for d in use}
    if len(use) != 2:                # pinned direction: no switch traced
        dense_threshold = None
        switch_k = None
    if "push" not in use:            # no push sweep: resolution never traced
        push_resolution = "unused"
    key = ("sharded", g.n, tuple(tuple(_plan_levels(p)) for p in plans),
           _comps_key(comps), max_iter, tol, block_v, block_e, interpret,
           use, dense_threshold, switch_k, push_resolution, strategy,
           _mesh_cache_key(mesh, ax))
    run = _exec_cache_get(key)
    if run is None:
        run = _exec_cache_put(key, _build_sharded_executor(
            comps, plans, g.n, max_iter, tol, block_v, block_e, interpret,
            use, dense_threshold, switch_k, push_resolution, mesh, ax),
            comps)
    args, steps = [], {}
    for d in use:
        e = ells[d]
        args += [e.nbrs, e.weight, e.capacity, e.mask, e.tile_nnz,
                 e.slot_pos, e.slot_nbr, e.row_deg]
        steps[d] = e.tile_nnz.size
    entries = 0
    if push_resolution == "sorted":
        sres = sharded_push_resolution_cached(
            g, k_shards, strategy=strategy, block_v=block_v, block_e=block_e,
            sharding=split)
        args += [sres.slot_pos, sres.slot_src, sres.contrib, sres.tile_nnz]
        entries = sres.contrib_entries
        steps["push"] += sres.tile_nnz.size
    args.append(g.out_deg)
    args.append(w_out_deg(g))
    return run, args, k_shards, entries, steps


def iterate_pallas_sharded(g: Graph, comps, plans, mesh, axes=("data",),
                           strategy: str = "contiguous",
                           max_iter: Optional[int] = None, tol: float = 0.0,
                           block_v: int = 8, block_e: int = 128,
                           interpret: Optional[bool] = None,
                           direction: str = "auto",
                           dense_threshold: float = DENSE_FRONTIER,
                           switch_k="auto",
                           push_resolution: Optional[str] = None,
                           sources: Optional[dict] = None,
                           plan=None) -> iterate.IterationResult:
    """Fixpoint of the fused reduction with SHARD-LOCAL fused Pallas sweeps
    under ``shard_map`` (DESIGN.md §11): each vertex-cut shard holds its own
    blocked-ELL pair, runs the existing pull/push sweeps locally (one
    ``pallas_call`` per shard per iteration — frontier-aware tile skipping
    included), and merges per-vertex partials across shards with the
    monoid/lex ``cross_plan`` combine.  The per-iteration direction switch
    is GLOBAL (psum'd frontier edge mass), so the sharded engine takes the
    same push/pull sequence — and produces bitwise-identical states for
    idempotent rounds — as the single-device ``iterate_pallas``.

    ``strategy`` picks the edge partitioning (``partition.partition_edges``:
    "contiguous" | "dst_hash").  ``push_resolution`` selects the shard-local
    dst-keyed resolution exactly like the single-device engine: "sorted"
    (default) resolves through each shard's own precomputed dst-major
    segment layout (``structure.to_sharded_push_resolution`` — per-shard
    ``PushResolution`` stacks over the widened out-layout, permutation
    gather and frontier-proportional tile skipping included), "scatter" keeps the
    per-shard reference full-rectangle XLA scatter as the oracle.  Both are
    exact for the idempotent min/max plans and feed the same cross-shard
    monoid/lex combine, so the choice never changes results.

    The result carries ``shards`` / ``shard_work`` (per-shard processed-tile
    edge work) / ``shard_launches`` (traced pallas launches per shard per
    round) / ``cross_combines`` (cross-shard state-combine collectives
    executed) on top of the usual pallas stats (including ``resolve_work``
    and ``gather_work``, summed over shards)."""
    n = g.n
    interpret = _er.resolve_interpret(interpret)
    max_iter = max_iter if max_iter is not None else 2 * n + 4
    idempotent = all(iterate.plan_idempotent(p) for p in plans)
    if plan is not None:
        assert_normalized(plan)
        push_resolution = plan.push_resolution
        use = _directions_used(plan.direction, idempotent)
        dense_threshold, switch_k = plan.dense_threshold, plan.switch_k
        strategy = plan.shard_strategy
    else:
        use = _directions_used(direction, idempotent)
        switch_k = _normalize_switch_k(
            switch_k, dense_threshold if len(use) == 2 else DENSE_FRONTIER)
        push_resolution = _check_resolution(
            PUSH_RESOLUTION if push_resolution is None else push_resolution)
        if strategy not in ("contiguous", "dst_hash"):
            raise ValueError(f"unknown shard strategy {strategy!r}")
    run, args, k_shards, entries, steps = _sharded_executor(
        g, comps, plans, mesh, axes, strategy, max_iter, tol, block_v,
        block_e, interpret, use, dense_threshold, switch_k, push_resolution)
    (state, k, work, pushes, res_work, gather_work, tiles, div, resid,
     act_n) = run(*args, _srcs_vector(comps, sources))
    k_host = np.asarray(k)
    work_host = np.asarray(work)
    push_host = np.asarray(pushes)
    # Replication contract: every shard must have run the identical fixpoint
    # (same iteration count, same direction sequence).  A divergence means
    # the collective combine or the global switch broke — fail loud, naming
    # the offending shards, instead of trusting shard 0.
    iterate.check_shard_replication(k_host, "iteration count",
                                    "pallas_sharded")
    iterate.check_shard_replication(push_host, "push-iteration count",
                                    "pallas_sharded")
    k_i = int(k_host[0])
    p_i = int(push_host[0])
    div_h = bool(np.asarray(div)[0])
    act_h = int(np.asarray(act_n)[0])
    res = iterate.IterationResult(
        state=tuple(s[:n] for s in state),
        iterations=k_i,
        edge_work=float(work_host.sum()),
        converged=(not div_h) and act_h == 0,
        diverged=div_h,
        active_count=act_h,
        residual=float(np.asarray(resid)[0]))
    res.push_iters = p_i
    res.pull_iters = k_i - p_i
    res.resolve_work = float(np.asarray(res_work).sum())
    res.gather_work = float(np.asarray(gather_work).sum())
    res.activity_reads = entries * p_i
    res.tiles_swept = int(np.asarray(tiles).sum())
    res.grid_steps = _grid_steps(steps, k_i, p_i)
    res.tile_shape = (block_v, block_e)
    res.shards = k_shards
    res.shard_work = tuple(float(w) for w in work_host)
    res.shard_launches = len(use)        # traced sweeps per shard per round
    res.cross_combines = k_i * cross_combines_per_iter(plans, comps,
                                                       idempotent)
    return res
