"""The fused single-launch Pallas sweep (DESIGN.md §2).

Covers the acceptance contract of the fused execution layer:

* multi-level lexicographic plans (WSP/DRR-style) on the pallas engine are
  bit-compatible with the pull engine and the dense oracle engine,
* one engine iteration of ANY fused plan executes exactly ONE
  ``pallas_call`` at runtime; a forced direction traces exactly 1 per
  round, the direction-optimized default traces 2 (one per lax.cond
  branch) while still executing one per iteration (``SWEEP_STATS``
  trace-time launch counters + runtime direction counters),
* frontier-skipped tiles (no active source) return identities bit-for-bit,
* cross-tile lexicographic resolution on graphs whose padded width spans
  several slot tiles,
* the compiled-executor cache reuses traced fixpoints across repeats,
* the live-tile grid: a sweep over a layout's live-tile list gives the
  full grid's candidates bit for bit, the executor takes it only where it
  is shorter than the grid, and ``ExecStats.grid_steps`` counts its steps.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine, fusion
from repro.core import usecases as U
from repro.graph import segment
from repro.graph.structure import (blocked_ell_cached, from_edges, rmat_graph,
                                   to_blocked_ell, uniform_graph)
from repro.kernels import edge_reduce as er

MULTI_LEVEL = ["WSP", "NSP", "Trust", "DRR", "RDS"]
PRIM_ONLY = ["SSSP", "BFS", "WP", "REACH"]


def _run(g, name, eng):
    prog = fusion.fuse(U.ALL_SPECS[name]())
    return engine.run_program(g, prog, engine=eng)


def _cold():
    engine.clear_program_caches()
    er.reset_sweep_stats()


# ---------------------------------------------------------------------------
# multi-level lex plans: pallas ≡ pull ≡ dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", MULTI_LEVEL)
def test_fused_lex_matches_pull_and_dense(name, small_graphs):
    g = small_graphs["rmat"]
    a = _run(g, name, "pull").value
    b = _run(g, name, "pallas").value
    c = _run(g, name, "dense").value
    np.testing.assert_allclose(np.asarray(a, np.float64),
                               np.asarray(b, np.float64), atol=1e-4)
    np.testing.assert_allclose(np.asarray(c, np.float64),
                               np.asarray(b, np.float64), atol=1e-4)


def test_fused_lex_cross_tile_resolution():
    """Hub graph: one vertex with 300 predecessors ⇒ width spans 3 slot
    tiles, so lexicographic ties must resolve across tile boundaries."""
    rng = np.random.default_rng(7)
    src = np.concatenate([np.arange(1, 301), np.ones(150, np.int64), [0]])
    dst = np.concatenate([np.zeros(300, np.int64), np.arange(2, 152), [301]])
    w = rng.integers(1, 9, size=src.shape[0]).astype(np.float32)
    c = rng.integers(1, 9, size=src.shape[0]).astype(np.float32)
    g = from_edges(302, src, dst, w, c)
    assert to_blocked_ell(g).width > 128
    for name in ("SSSP", "WSP", "NSP", "Trust"):
        a = _run(g, name, "pull").value
        b = _run(g, name, "pallas").value
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=1e-4)


# ---------------------------------------------------------------------------
# launch counting: ≤ 2 per iteration, exactly 1 for Prim-only plans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", PRIM_ONLY)
def test_prim_only_plans_single_launch(name, small_graphs):
    """BFS/SSSP/WP/REACH with a forced direction: exactly ONE pallas_call
    per engine iteration (the while_loop body traces once, so trace-time
    launch counts ARE the per-iteration launch counts)."""
    for model, counter in (("pull", "pull_launches"), ("push", "push_launches")):
        _cold()
        prog = fusion.fuse(U.ALL_SPECS[name]())
        res = engine.run_program(small_graphs["rmat"], prog, engine="pallas",
                                 model=model)
        assert res.stats.rounds == 1
        assert er.SWEEP_STATS["launches"] == 1
        assert er.SWEEP_STATS[counter] == 1


@pytest.mark.parametrize("name", PRIM_ONLY)
def test_prim_only_auto_traces_one_sweep_per_direction(name, small_graphs):
    """The direction-optimized default traces BOTH lax.cond branches — one
    pull and one push pallas_call per round — but executes exactly one sweep
    per iteration at runtime (pull_iters + push_iters == iterations)."""
    _cold()
    res = _run(small_graphs["rmat"], name, "pallas")
    assert res.stats.rounds == 1
    assert er.SWEEP_STATS["launches"] == 2
    assert er.SWEEP_STATS["pull_launches"] == 1
    assert er.SWEEP_STATS["push_launches"] == 1
    assert (res.stats.pull_iters + res.stats.push_iters
            == res.stats.iterations)


@pytest.mark.parametrize("name", MULTI_LEVEL)
def test_fused_plans_at_most_two_launches_per_round(name, small_graphs):
    """Any fused plan (multi-level lex, non-idempotent with has-pred probe,
    multi-plan rounds like Trust's 4 reductions) traces ≤ 2 launches per
    round — one per admissible direction; non-idempotent rounds keep the
    single pull− sweep.  A forced direction is always exactly 1 per round."""
    _cold()
    res = _run(small_graphs["rmat"], name, "pallas")
    assert er.SWEEP_STATS["launches"] <= 2 * res.stats.rounds
    _cold()
    res = engine.run_program(small_graphs["rmat"],
                             fusion.fuse(U.ALL_SPECS[name]()),
                             engine="pallas", model="pull")
    assert er.SWEEP_STATS["launches"] == res.stats.rounds


def test_haspred_probe_is_fused(small_graphs):
    """NSP's secondary is a non-idempotent sum ⇒ pull− model with the
    has-pred probe — still one launch per iteration."""
    _cold()
    _run(small_graphs["rmat"], "NSP", "pallas")
    assert er.SWEEP_STATS["launches"] == 1


def test_pagerank_direct_pallas_single_launch(small_graphs):
    """PageRank (non-idempotent sum + epilogue, Fig. 4b direct kernels):
    pull− recompute with the fused has-pred probe — one launch, matching
    the pull engine."""
    from repro.core.synthesis import pagerank_kernels
    g = small_graphs["rmat"]
    dk = pagerank_kernels(g.n)
    a = engine.run_direct(g, dk, engine="pull").value
    _cold()
    b = engine.run_direct(g, dk, engine="pallas").value
    assert er.SWEEP_STATS["launches"] == 1
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_all_specs_match_pull(small_graphs):
    """The full use-case suite: pallas ≡ pull bit-for-bit through norm_inf."""
    from conftest import norm_inf
    from repro.graph.structure import undirected
    for name in U.ALL_SPECS:
        g = small_graphs["uniform"]
        g = undirected(g) if name == "CC" else g
        a = _run(g, name, "pull").value
        b = _run(g, name, "pallas").value
        np.testing.assert_allclose(norm_inf(a), norm_inf(b), atol=1e-4,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# frontier-aware tile skipping
# ---------------------------------------------------------------------------

def test_frontier_skipped_tiles_return_identities():
    """Tiles with zero active sources must emit the reduction identities
    bit-for-bit (the pl.when short-circuit path)."""
    g = uniform_graph(48, 300, seed=2)
    ell = to_blocked_ell(g)
    rng = np.random.default_rng(2)
    state = jnp.asarray(rng.uniform(1, 9, ell.n_pad).astype(np.float32))
    ident = float(segment.identity("min", jnp.float32))
    outdeg = jnp.ones(ell.n_pad, jnp.float32)

    # no active sources at all: every tile must short-circuit
    active = jnp.zeros(ell.n_pad, jnp.int32)
    tile_act = er.tile_activity(ell.srcs, ell.mask, ell.tile_nnz, active,
                                ell.block_v, ell.block_e)
    assert not np.asarray(tile_act).any()
    red, _, cands = er.fused_ell_sweep(
        ell.srcs, ell.weight, ell.capacity, ell.mask, tile_act,
        {0: state}, active, outdeg, plans=(((0, "min"),),), idents={0: ident},
        p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n,
        return_candidates=True)
    assert np.all(np.asarray(cands[0]) == np.float32(ident))
    assert np.all(np.asarray(red[0]) == np.float32(ident))


def test_frontier_partial_skip_matches_full_sweep():
    """A sparse frontier must give the same reduction as running every tile
    (identity contributions are absorbed by the monoid)."""
    g = uniform_graph(64, 400, seed=5)
    ell = to_blocked_ell(g)
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.uniform(1, 9, ell.n_pad).astype(np.float32))
    ident = float(segment.identity("min", jnp.float32))
    outdeg = jnp.ones(ell.n_pad, jnp.float32)
    active = jnp.asarray((rng.random(ell.n_pad) < 0.1).astype(np.int32))
    kw = dict(plans=(((0, "min"),),), idents={0: ident},
              p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n)
    tile_act = er.tile_activity(ell.srcs, ell.mask, ell.tile_nnz, active,
                                ell.block_v, ell.block_e)
    red_skip, _ = er.fused_ell_sweep(ell.srcs, ell.weight, ell.capacity,
                                     ell.mask, tile_act, {0: state}, active,
                                     outdeg, **kw)
    all_tiles = jnp.ones_like(ell.tile_nnz, jnp.int32)
    red_full, _ = er.fused_ell_sweep(ell.srcs, ell.weight, ell.capacity,
                                     ell.mask, all_tiles, {0: state}, active,
                                     outdeg, **kw)
    np.testing.assert_array_equal(np.asarray(red_skip[0]),
                                  np.asarray(red_full[0]))


@pytest.mark.parametrize("need_haspred", [False, True])
@pytest.mark.parametrize("direction", ["in", "out"])
def test_slot_list_gather_matches_per_slot_gather(direction, need_haspred):
    """The engines gather neighbour values through the layout's slot list
    (E-sized gather + scatter into the rectangle); the result — tile
    activity, per-tile candidates, reductions and has-pred — is bit-for-bit
    the plain per-slot gather's, on a multi-tile hub layout with a NaN
    among the states."""
    g = rmat_graph(96, 900, seed=7)
    ell = to_blocked_ell(g, direction=direction)
    rng = np.random.default_rng(7)
    st = rng.uniform(1, 9, ell.n_pad).astype(np.float32)
    st[3] = np.nan
    state = jnp.asarray(st)
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.asarray((rng.random(ell.n_pad) < 0.4).astype(np.int32))
    outdeg = jnp.asarray(rng.integers(1, 5, ell.n_pad).astype(np.float32))
    slots = (ell.slot_pos, ell.slot_nbr)
    acts = [er.tile_activity(ell.nbrs, ell.mask, ell.tile_nnz, active,
                             ell.block_v, ell.block_e, slots=s)
            for s in (None, slots)]
    np.testing.assert_array_equal(np.asarray(acts[0]), np.asarray(acts[1]))
    outs = [er.fused_ell_sweep(
        ell.nbrs, ell.weight, ell.capacity, ell.mask, acts[0], {0: state},
        active, outdeg, plans=(((0, "min"),),), idents={0: ident},
        p_fns={0: lambda env: env["n"] + env["w"] / env["outdeg"]}, nv=g.n,
        need_haspred=need_haspred, slots=s, return_candidates=True)
        for s in (None, slots)]
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---------------------------------------------------------------------------
# the live-tile grid: the kernels walk only the tiles with real slots
# ---------------------------------------------------------------------------

# kind → (plans, identity, P, has-pred); "pagerank" is the executor's
# full-recompute round: a float sum of n / outdeg with the has-pred probe
PULL_KINDS = {
    "value": ((((0, "min"),),), float("inf"),
              lambda env: env["n"] + env["w"], False),
    "haspred": ((((0, "min"),),), float("inf"),
                lambda env: env["n"] + env["w"], True),
    "pagerank": ((((0, "sum"),),), 0.0,
                 lambda env: env["n"] / env["outdeg"], True),
}


def _pull_sweep(ell, kind, bitmap, live, seed=4):
    plans, ident, p_fn, need_hp = PULL_KINDS[kind]
    rng = np.random.default_rng(seed)
    state = jnp.asarray(rng.uniform(1, 9, ell.n_pad).astype(np.float32))
    outdeg = jnp.asarray(rng.integers(1, 5, ell.n_pad).astype(np.float32))
    active = jnp.asarray(
        np.ones(ell.n_pad, np.int32) if kind == "pagerank" else
        (rng.random(ell.n_pad) < (0 if bitmap == "none" else 0.3))
        .astype(np.int32))
    tile_act = {"all": lambda: jnp.ones_like(ell.tile_nnz),
                "none": lambda: jnp.zeros_like(ell.tile_nnz)}.get(
        bitmap, lambda: er.tile_activity(ell.nbrs, ell.mask, ell.tile_nnz,
                                         active, ell.block_v, ell.block_e))()
    return er.fused_ell_sweep(
        ell.nbrs, ell.weight, ell.capacity, ell.mask, tile_act, {0: state},
        active, outdeg, plans=plans, idents={0: ident}, p_fns={0: p_fn},
        nv=ell.n, need_haspred=need_hp, slots=(ell.slot_pos, ell.slot_nbr),
        live=live, return_candidates=True)


@pytest.mark.parametrize("bitmap", ["frontier", "none", "all"])
@pytest.mark.parametrize("kind", sorted(PULL_KINDS))
def test_live_grid_pull_sweep_matches_full_grid(kind, bitmap):
    """On a layout whose tiles are mostly dead, with a 128-row group that
    has no live tile, the pull sweep over the live-tile list gives the full
    grid's reductions, has-pred and per-tile candidates bit for bit:
    dead tiles and empty groups keep the identities the first-visit fill
    writes.  With an all-ones bitmap the empty groups' placeholder tiles run
    on ⊥ operands; with no active source every candidate is the identity."""
    from conftest import skewed_graph
    ell = to_blocked_ell(skewed_graph())
    live = er.live_grid(ell.live_tiles, ell.tile_nnz.size)
    assert live is not None
    got = _pull_sweep(ell, kind, bitmap, live)
    want = _pull_sweep(ell, kind, bitmap, None)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    if bitmap == "none":
        ident = np.float32(PULL_KINDS[kind][1])
        assert np.all(np.asarray(got[2][0]) == ident)


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr, nested ones
    (the while_loop body, both direction branches) included."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
                continue
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        walk(sub.jaxpr)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


def _bfs_executor(g):
    from repro.core import synthesis
    from repro.kernels import ops
    round_ = fusion.fuse(U.bfs(0)).rounds[0][1]
    comps, plans = engine._round_runtime(round_,
                                         synthesis.synthesize_round(round_))
    use, dense, switch_k, resolution = ops._apply_plan(
        None, "auto", ops.DENSE_FRONTIER, "auto", "sorted", True)
    run, args, _entries, steps = ops._pallas_executor(
        g, comps, plans, 2 * g.n + 4, 0.0, er.BLOCK_V, er.BLOCK_E, True, use,
        dense, switch_k, resolution)
    return run.fn, (*args, jnp.zeros((len(comps),), jnp.int32)), steps


@pytest.mark.parametrize("graph", ["every-tile-live", "skewed"])
def test_live_grid_selection_rule(graph):
    """Where every tile is live (the ER shape) the executor passes no list
    and its kernels keep the 2-D (row tile, slot tile) grid; on the skewed
    layout each kernel's grid is 1-D over the list: the live tiles plus one
    placeholder per 128-row group without any."""
    from conftest import skewed_graph
    from repro.graph.structure import to_push_resolution
    g = uniform_graph(64, 640, seed=1) if graph == "every-tile-live" \
        else skewed_graph()
    ell = to_blocked_ell(g)
    nnz = np.asarray(ell.tile_nnz)
    n_i, n_j = nnz.shape
    groups = -(-n_i // (er.LANES // ell.block_v))
    has_live = np.unique(np.flatnonzero(nnz.reshape(-1))
                         // (n_j * er.LANES // ell.block_v))
    assert ell.live_tiles.shape[0] == \
        int((nnz > 0).sum()) + groups - has_live.size
    assert np.all(np.diff(np.asarray(ell.live_tiles)) > 0)
    live = er.live_grid(ell.live_tiles, nnz.size)
    fn, args, steps = _bfs_executor(g)
    grids = _pallas_grids(fn, *args)
    assert len(grids) == 3                      # pull, push, resolve
    if graph == "every-tile-live":
        assert (nnz > 0).all() and live is None
        assert set(grids) == {(n_i, n_j)}
        assert steps == {"pull": nnz.size, "push": 2 * nnz.size}
    else:
        assert live is not None and live.shape[0] == 36 < nnz.size
        res = to_push_resolution(g)
        assert set(grids) == {(36,), (res.live_tiles.shape[0],)}
        assert steps == {"pull": 36, "push": 36 + res.live_tiles.shape[0]}


@pytest.mark.parametrize("model", [None, "pull", "push"])
@pytest.mark.parametrize("graph", ["every-tile-live", "skewed"])
def test_grid_steps_counter(graph, model):
    """``ExecStats.grid_steps`` is the launches times the grid length: the
    live-tile list's on the skewed graph, n_i·n_j on the all-live one, with
    the push sweep's resolve kernel counted beside it."""
    from conftest import skewed_graph
    from repro.graph.structure import to_push_resolution
    g = uniform_graph(64, 640, seed=1) if graph == "every-tile-live" \
        else skewed_graph()
    res = engine.run_program(g, fusion.fuse(U.bfs(0)), engine="pallas",
                             model=model, fallback=False)
    st = res.stats
    steps = [er.grid_steps(lay.live_tiles, lay.tile_nnz.size)
             for lay in (to_blocked_ell(g), to_blocked_ell(g, direction="out"),
                         to_push_resolution(g))]
    grid = {"pull": steps[0], "push": steps[1] + steps[2]}
    assert st.grid_steps == (st.pull_iters * grid["pull"]
                             + st.push_iters * grid["push"]) > 0
    n_tiles = to_blocked_ell(g).tile_nnz.size
    if graph == "every-tile-live":
        assert grid == {"pull": n_tiles, "push": 2 * n_tiles}
    else:
        assert grid["pull"] / n_tiles < 0.2


def test_batched_executor_live_grid(monkeypatch):
    """The vmapped batch executor over the live-tile lists answers like the
    sequential runs and like the batch over the full grids, bit for bit,
    with the same grid steps per query as the sequential runs."""
    from conftest import skewed_graph
    g = skewed_graph()
    prog = fusion.fuse(U.sssp(0))
    sources = [0, 5, 300]
    got = engine.run_program_batch(g, prog, sources, engine="pallas")
    seq = [engine.run_program(g, fusion.fuse(U.sssp(s)), engine="pallas",
                              fallback=False) for s in sources]
    monkeypatch.setattr(er, "live_grid", lambda live, n_tiles: None)
    full = engine.run_program_batch(g, prog, sources, engine="pallas")
    for a, s, f in zip(got, seq, full):
        assert np.asarray(a.value).tobytes() == np.asarray(s.value).tobytes()
        assert np.asarray(a.value).tobytes() == np.asarray(f.value).tobytes()
        assert a.stats.grid_steps == s.stats.grid_steps < f.stats.grid_steps


def test_tile_nnz_marks_padding_tiles():
    g = rmat_graph(64, 256, seed=3)          # power-law: padded tail tiles
    ell = to_blocked_ell(g)
    nnz = np.asarray(ell.tile_nnz)
    mask = np.asarray(ell.mask)
    n_i, n_j = nnz.shape
    want = mask.reshape(n_i, ell.block_v, n_j, ell.block_e).sum(axis=(1, 3))
    np.testing.assert_array_equal(nnz, want)


# ---------------------------------------------------------------------------
# compiled-program cache
# ---------------------------------------------------------------------------

def test_executor_cache_reused_across_repeats(small_graphs):
    from repro.kernels import ops as kops
    _cold()
    g = small_graphs["rmat"]
    r1 = _run(g, "WSP", "pallas")
    n_exec = kops.executor_cache_size()
    launches = er.SWEEP_STATS["launches"]
    assert n_exec >= 1
    r2 = _run(g, "WSP", "pallas")            # repeat: no new trace
    assert kops.executor_cache_size() == n_exec
    assert er.SWEEP_STATS["launches"] == launches
    np.testing.assert_array_equal(np.asarray(r1.value), np.asarray(r2.value))


def test_ell_cache_keyed_on_graph_identity(small_graphs):
    g1 = small_graphs["rmat"]
    g2 = small_graphs["uniform"]
    assert blocked_ell_cached(g1) is blocked_ell_cached(g1)
    assert blocked_ell_cached(g1) is not blocked_ell_cached(g2)


def test_cache_stats_and_clear(small_graphs):
    _cold()
    assert engine.program_cache_stats()["pallas_executors"] == 0
    _run(small_graphs["rmat"], "SSSP", "pallas")
    stats = engine.program_cache_stats()
    assert stats["pallas_executors"] >= 1 and stats["synth_rounds"] >= 1
    engine.clear_program_caches()
    assert engine.program_cache_stats()["pallas_executors"] == 0
