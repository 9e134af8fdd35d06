"""Dst-sorted push resolution + the Gemini direction autotune (DESIGN.md §10).

Covers the acceptance contract of the frontier-proportional resolution path:

* the `structure.PushResolution` permutation maps every dst-major slot to
  the out-layout slot of the SAME edge (weights/destinations round-trip),
* `fused_ell_push_sweep(resolution="sorted")` ≡ `"scatter"` bit-for-bit at
  the kernel level across random graphs and frontier densities,
* the resolution activity test over the compact class table of the live
  resolution tiles is bitwise the dense OR over the real (resolution tile,
  out tile) pairs — single-device, sharded and mutation-patched layouts —
  and the table holds under 2× the real pairs,
* resolution work is frontier-proportional: Σ tile_nnz of the resolution
  tiles actually processed, strictly under the scatter's full rectangle on
  sparse frontiers, and 0 when nothing is active,
* the resolution tile pass is its own launch class (`resolve_launches`):
  1 per traced push sweep under "sorted", 0 under "scatter"/pull — the
  edge-sweep launch contract (`launches`) is unchanged,
* `push_resolution` is an executor-cache key (no silent cross-knob reuse),
* the Gemini |E_frontier| ≤ |E|/k switch replaces the fixed vertex-fraction
  threshold, is per-query tunable, and `switch_k=None` falls back to the
  documented `DENSE_FRONTIER` rule,
* stat bumps happen only after a successful launch construction.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import norm_inf
from repro.core import engine, fusion
from repro.core import usecases as U
from repro.graph import mutate, segment
from repro.graph.structure import (blocked_ell_cached,
                                   push_resolution_cached, rmat_graph,
                                   to_blocked_ell, to_push_resolution,
                                   to_sharded_push_resolution, uniform_graph)
from repro.kernels import edge_reduce as er
from repro.kernels import ops as kops

SAMPLES = [(9, 1.5, 11), (17, 2.5, 22), (26, 3.0, 33)]


def _cold():
    engine.clear_program_caches()
    er.reset_sweep_stats()


def _iteration_results(monkeypatch):
    """Every ``IterationResult`` that ``ops.iterate_pallas`` returns from
    here on, in call order."""
    seen = []
    real = kops.iterate_pallas

    def spy(*args, **kwargs):
        seen.append(real(*args, **kwargs))
        return seen[-1]

    monkeypatch.setattr(kops, "iterate_pallas", spy)
    return seen


# ---------------------------------------------------------------------------
# layout: the dst-major permutation is exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,density,seed", SAMPLES)
def test_resolution_permutation_roundtrips_edges(n, density, seed):
    """in2out must map the k-th dst-major slot of v to the out-layout slot
    holding the SAME edge: gathering the out rectangle's weights and
    destinations through it reproduces the in-layout rectangle exactly,
    and `valid` IS the in-layout mask (same fill order ⇒ the sorted
    reduction tree is the pull sweep's reduction tree)."""
    g = uniform_graph(n, max(1, int(density * n)), seed=seed)
    res = to_push_resolution(g)
    ell_in = to_blocked_ell(g)
    ell_out = to_blocked_ell(g, direction="out")
    valid = np.asarray(res.valid)
    in2out = np.asarray(res.in2out)
    assert res.width == ell_in.width and res.out_width == ell_out.width
    np.testing.assert_array_equal(valid, np.asarray(ell_in.mask))
    w_via = np.asarray(ell_out.weight).reshape(-1)[in2out]
    np.testing.assert_array_equal(np.where(valid, w_via, 0),
                                  np.where(valid, np.asarray(ell_in.weight), 0))
    # the out-slot's stored destination is the dst-major slot's own row
    dst_via = np.asarray(ell_out.nbrs).reshape(-1)[in2out]
    rows = np.broadcast_to(np.arange(res.n_pad)[:, None], valid.shape)
    np.testing.assert_array_equal(dst_via[valid], rows[valid])
    # every real out-slot is hit exactly once (it is a permutation of edges)
    assert sorted(in2out[valid].tolist()) == \
        sorted(np.flatnonzero(np.asarray(ell_out.mask).reshape(-1)).tolist())
    # src_tile agrees with the out-layout grid geometry
    n_j_out = ell_out.width // ell_out.block_e
    want_tile = ((in2out // ell_out.width) // res.block_v) * n_j_out + \
        (in2out % ell_out.width) // res.block_e
    np.testing.assert_array_equal(np.asarray(res.src_tile), want_tile)


def test_resolution_layout_cached_per_graph():
    g1 = uniform_graph(12, 30, seed=1)
    g2 = uniform_graph(12, 30, seed=2)
    assert push_resolution_cached(g1) is push_resolution_cached(g1)
    assert push_resolution_cached(g1) is not push_resolution_cached(g2)
    assert engine.program_cache_stats()["push_resolutions"] >= 2


# ---------------------------------------------------------------------------
# kernel level: sorted ≡ scatter, and work is frontier-proportional
# ---------------------------------------------------------------------------

def _push_sweep(g, frontier_frac, seed, resolution):
    ell = to_blocked_ell(g, direction="out")
    res = to_push_resolution(g)
    rng = np.random.default_rng(seed)
    state = jnp.asarray(rng.integers(1, 9, ell.n_pad).astype(np.float32))
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.asarray((rng.random(ell.n_pad) < frontier_frac)
                         .astype(np.int32))
    tile_act = er.tile_activity_push(ell.tile_nnz, active, ell.block_v)
    kw = dict(plans=(((0, "min"),),), idents={0: ident},
              p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n)
    if resolution == "sorted":
        res_tile_act = er.resolution_tile_activity(
            res.contrib, tile_act, res.tile_nnz)
        red, _ = er.fused_ell_push_sweep(
            ell.nbrs, ell.weight, ell.capacity, ell.mask, tile_act,
            {0: state}, active, jnp.ones(ell.n_pad, jnp.float32),
            resolution="sorted",
            res=(res.in2out, res.valid, res_tile_act), **kw)
        work = float(jnp.sum(res.tile_nnz * res_tile_act))
    else:
        red, _ = er.fused_ell_push_sweep(
            ell.nbrs, ell.weight, ell.capacity, ell.mask, tile_act,
            {0: state}, active, jnp.ones(ell.n_pad, jnp.float32),
            resolution="scatter", **kw)
        work = float(ell.n_pad * ell.width)
    return np.asarray(red[0]), work


@pytest.mark.parametrize("n,density,seed", SAMPLES)
@pytest.mark.parametrize("frontier", [0.0, 0.1, 0.5, 1.0])
def test_sorted_resolution_matches_scatter_kernel_level(n, density, seed,
                                                        frontier):
    g = uniform_graph(n, max(1, int(density * n)), seed=seed)
    got, w_sorted = _push_sweep(g, frontier, seed, "sorted")
    want, w_scatter = _push_sweep(g, frontier, seed, "scatter")
    np.testing.assert_array_equal(got, want)
    assert w_sorted <= w_scatter


def _push_sweep_grids(ell, res, frontier, resolution, live, res_live):
    rng = np.random.default_rng(6)
    state = jnp.asarray(rng.integers(1, 9, ell.n_pad).astype(np.float32))
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.asarray((rng.random(ell.n_pad) < frontier).astype(np.int32))
    tile_act = er.tile_activity_push(ell.tile_nnz, active, ell.block_v)
    kw = dict(plans=(((0, "min"),),), idents={0: ident},
              p_fns={0: lambda env: env["n"] + env["w"]}, nv=ell.n,
              need_haspred=True, live=live, return_candidates=True)
    if resolution == "sorted":
        kw.update(res=(None, None, er.resolution_tile_activity(
            res.contrib, tile_act, res.tile_nnz)),
            res_slots=(res.slot_pos, res.slot_src), res_live=res_live)
    return er.fused_ell_push_sweep(
        ell.nbrs, ell.weight, ell.capacity, ell.mask, tile_act, {0: state},
        active, jnp.ones(ell.n_pad, jnp.float32), resolution=resolution, **kw)


@pytest.mark.parametrize("frontier", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("resolution", ["sorted", "scatter"])
def test_live_grid_push_sweep_matches_full_grid(resolution, frontier):
    """On a layout whose tiles are mostly dead, with 128-row groups that
    have no live tile, the push sweep and the sorted resolution over their
    live-tile lists give the full grids' reductions, has-pred and per-edge
    candidates bit for bit: the candidates of dead tiles, which the list
    never visits, read as identities wherever a consumer reads the whole
    rectangle."""
    from conftest import skewed_graph
    g = skewed_graph()
    ell = to_blocked_ell(g, direction="out")
    res = to_push_resolution(g)
    live = er.live_grid(ell.live_tiles, ell.tile_nnz.size)
    res_live = er.live_grid(res.live_tiles, res.tile_nnz.size)
    assert live is not None and res_live is not None
    got = _push_sweep_grids(ell, res, frontier, resolution, live, res_live)
    want = _push_sweep_grids(ell, res, frontier, resolution, None, None)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_sorted_resolution_work_frontier_proportional():
    """A one-vertex frontier on a power-law graph must keep only the
    resolution tiles holding that vertex's successors — Σ kept nnz bounded
    by the frontier's out-edges padded to tile granularity, and far under
    the scatter's full rectangle."""
    g = rmat_graph(128, 1024, seed=4)
    ell = to_blocked_ell(g, direction="out")
    res = to_push_resolution(g)
    # a TAIL vertex (power-law: low out-degree, co-blocked with other tail
    # rows) — a hub frontier legitimately lights most resolution tiles
    active = jnp.zeros(ell.n_pad, jnp.int32).at[125].set(1)
    tile_act = er.tile_activity_push(ell.tile_nnz, active, ell.block_v)
    res_tile_act = er.resolution_tile_activity(
        res.contrib, tile_act, res.tile_nnz)
    kept = float(jnp.sum(res.tile_nnz * res_tile_act))
    full = float(jnp.sum(res.tile_nnz))
    # the frontier-active out tiles hold ≤ block_v rows of successors; their
    # candidates land in ≤ that many resolution tiles' worth of real slots
    out_edge_bound = float(jnp.sum(ell.tile_nnz * tile_act))
    assert kept <= out_edge_bound * res.block_v * res.block_e
    assert kept < full, "sparse frontier must not light every resolution tile"
    # and an empty frontier keeps nothing
    none_act = er.resolution_tile_activity(
        res.contrib, jnp.zeros_like(tile_act), res.tile_nnz)
    assert float(jnp.sum(none_act)) == 0.0


# ---------------------------------------------------------------------------
# the compact contributing-tile table: bitwise the dense OR over real pairs
# ---------------------------------------------------------------------------

def _activity_layouts(g, layout):
    """``(contrib, tile_nnz, valid, src_tile, n_out_tiles)`` per shard of
    one resolution layout: the single-device layout, a k-shard stack, or
    the layout ``mutate_edges`` patches in for an edited graph."""
    if layout.startswith("sharded"):
        k = int(layout[len("sharded"):])
        sres = to_sharded_push_resolution(g, k)
        n_tiles = sres.tile_nnz[0].size
        # the shards' real rows differ in some class, so the stack pads
        real = [(np.asarray(ids) < n_tiles).sum(axis=1)
                for ids, _ in sres.contrib]
        assert any(len(set(r.tolist())) > 1 for r in real)
        n_out = (sres.n_pad // sres.block_v) * (sres.out_width // sres.block_e)
        return [(tuple((ids[s], lists[s]) for ids, lists in sres.contrib),
                 sres.tile_nnz[s], sres.valid[s], sres.src_tile[s], n_out)
                for s in range(k)]
    if layout == "mutated":
        blocked_ell_cached(g, direction="in")
        blocked_ell_cached(g, direction="out")
        push_resolution_cached(g)
        src, dst, _w, _c = g.host_edges()
        g, md = mutate.mutate_edges(g, insert=([1, 2, 3], [4, 5, 6]),
                                    delete=(src[:3], dst[:3]))
        assert md.patched_layouts >= 1 and md.rebuilt_layouts == 0
    res = push_resolution_cached(g)
    n_out = (res.n_pad // res.block_v) * (res.out_width // res.block_e)
    return [(res.contrib, res.tile_nnz, res.valid, res.src_tile, n_out)]


@pytest.mark.parametrize("layout", ["single", "sharded2", "sharded4",
                                    "mutated"])
@pytest.mark.parametrize("act", ["random", "zeros", "ones"])
@pytest.mark.parametrize("graph", ["rmat", "uniform"])
def test_compact_activity_matches_dense_pairs(graph, act, layout):
    """``resolution_tile_activity`` over the compact class table is bitwise
    the dense numpy OR of the out-tile bitmap over every real (resolution
    tile, out tile) pair of the layout's slots."""
    g = rmat_graph(256, 2048, seed=5) if graph == "rmat" \
        else uniform_graph(256, 2048, seed=5)
    rng = np.random.default_rng(3)
    for contrib, tile_nnz, valid, src_tile, n_out in _activity_layouts(
            g, layout):
        push_act = {"random": (rng.random(n_out) < 0.2).astype(np.int32),
                    "zeros": np.zeros(n_out, np.int32),
                    "ones": np.ones(n_out, np.int32)}[act]
        n_i, n_j = tile_nnz.shape
        block_v, block_e = valid.shape[0] // n_i, valid.shape[1] // n_j
        rows, cols = np.nonzero(np.asarray(valid))
        r_tile = (rows // block_v) * n_j + cols // block_e
        want = np.zeros(n_i * n_j, bool)
        np.logical_or.at(want, r_tile,
                         push_act[np.asarray(src_tile)[rows, cols]] != 0)
        got = er.resolution_tile_activity(contrib, jnp.asarray(push_act),
                                          tile_nnz)
        np.testing.assert_array_equal(
            np.asarray(got), want.reshape(n_i, n_j).astype(np.int32))


def test_contrib_table_counts_and_activity_reads():
    """On a skewed graph the class table stays within 2× of the real pairs
    and far below the dense ``n_tiles × c_max`` rectangle, and a BFS
    query's ``activity_reads`` is the table's entries per push round."""
    g = rmat_graph(1024, 8192, seed=1)
    res = push_resolution_cached(g)
    c_max = max(lists.shape[0] for _, lists in res.contrib)
    n_tiles = res.tile_nnz.size
    assert res.contrib_pairs < res.contrib_entries <= 2 * res.contrib_pairs
    assert 4 * res.contrib_entries < n_tiles * c_max
    ids = np.concatenate([np.asarray(i) for i, _ in res.contrib])
    live = np.flatnonzero(np.asarray(res.tile_nnz).reshape(-1))
    assert sorted(ids.tolist()) == live.tolist()
    assert live.size < n_tiles // 2
    # one column of out-tile ids per tile
    assert all(lists.shape[1] == i.shape[0] for i, lists in res.contrib)
    out = engine.run_program(g, fusion.fuse(U.ALL_SPECS["BFS"]()),
                             engine="pallas", push_resolution="sorted")
    assert out.stats.push_iters >= 1
    assert out.stats.activity_reads == \
        res.contrib_entries * out.stats.push_iters


# ---------------------------------------------------------------------------
# engine level: knob equivalence, launch classes, cache keying, work stats
# ---------------------------------------------------------------------------

def _value(g, name, model=None, push_resolution=None, **kw):
    prog = fusion.fuse(U.ALL_SPECS[name]())
    return engine.run_program(g, prog, engine="pallas", model=model,
                              push_resolution=push_resolution, **kw)


@pytest.mark.parametrize("name", ["BFS", "SSSP", "CC"])
@pytest.mark.parametrize("model", ["push", None])
def test_sorted_matches_scatter_engine_level(name, model, small_graphs):
    from repro.graph.structure import undirected
    g = small_graphs["rmat"]
    g = undirected(g) if name == "CC" else g
    a = _value(g, name, model=model, push_resolution="sorted")
    _cold()
    b = _value(g, name, model=model, push_resolution="scatter")
    np.testing.assert_array_equal(np.asarray(a.value), np.asarray(b.value))
    want = norm_inf(engine.run_program(
        g, fusion.fuse(U.ALL_SPECS[name]()), engine="pull").value)
    np.testing.assert_allclose(norm_inf(a.value), want, atol=1e-4)


def test_sorted_matches_scatter_nonidempotent_push():
    """NSP forced push−: the full-recompute scatter path vs the sorted
    segment path (sum secondary — candidate multisets are identical and the
    test values are exactly representable, so bitwise still holds)."""
    g = uniform_graph(14, 34, seed=6)
    a = _value(g, "NSP", model="push", push_resolution="sorted")
    _cold()
    b = _value(g, "NSP", model="push", push_resolution="scatter")
    np.testing.assert_array_equal(np.asarray(a.value), np.asarray(b.value))


def test_resolve_launch_class(small_graphs):
    """"sorted" adds exactly one resolution tile pass per traced push sweep
    — counted under resolve_launches, NEVER under the edge-sweep counters
    (the sweep launch contract of DESIGN.md §2 is direction-symmetric)."""
    g = small_graphs["rmat"]
    prog = fusion.fuse(U.ALL_SPECS["BFS"]())
    _cold()
    engine.run_program(g, prog, engine="pallas", model="push",
                       push_resolution="sorted")
    assert er.SWEEP_STATS["launches"] == 1
    assert er.SWEEP_STATS["push_launches"] == 1
    assert er.SWEEP_STATS["resolve_launches"] == 1
    _cold()
    engine.run_program(g, prog, engine="pallas", model="push",
                       push_resolution="scatter")
    assert er.SWEEP_STATS["launches"] == 1
    assert er.SWEEP_STATS["resolve_launches"] == 0
    _cold()
    engine.run_program(g, prog, engine="pallas", model="pull")
    assert er.SWEEP_STATS["resolve_launches"] == 0
    _cold()
    engine.run_program(g, prog, engine="pallas")      # auto: 1 traced push
    assert er.SWEEP_STATS["launches"] == 2
    assert er.SWEEP_STATS["resolve_launches"] == 1


def test_push_resolution_is_cache_key(small_graphs):
    g = small_graphs["rmat"]
    prog = fusion.fuse(U.ALL_SPECS["SSSP"]())
    _cold()
    engine.run_program(g, prog, engine="pallas", push_resolution="sorted")
    assert kops.executor_cache_size() == 1
    engine.run_program(g, prog, engine="pallas", push_resolution="scatter")
    assert kops.executor_cache_size() == 2
    engine.run_program(g, prog, engine="pallas", push_resolution="sorted")
    assert kops.executor_cache_size() == 2              # hit, no new entry


def test_resolve_work_reported_and_frontier_proportional(monkeypatch):
    """The engine-level acceptance quantity: on a power-law BFS the sorted
    path's resolution work must stay strictly under the scatter path's
    full-rectangle cost and be reported from the fixpoint's
    ``IterationResult`` through ``ExecStats``."""
    g = rmat_graph(256, 2048, seed=17)
    prog = fusion.fuse(U.ALL_SPECS["BFS"]())
    _cold()
    results = _iteration_results(monkeypatch)
    srt = engine.run_program(g, prog, engine="pallas",
                             push_resolution="sorted")
    rw_sorted = srt.stats.resolve_work
    assert results[-1].resolve_work == rw_sorted
    _cold()
    sct = engine.run_program(g, prog, engine="pallas",
                             push_resolution="scatter")
    assert sct.stats.push_iters >= 1, "heuristic must take push iterations"
    assert srt.stats.push_iters == sct.stats.push_iters
    assert 0 < rw_sorted < sct.stats.resolve_work
    np.testing.assert_array_equal(np.asarray(srt.value),
                                  np.asarray(sct.value))


def test_invalid_push_resolution_rejected(small_graphs):
    prog = fusion.fuse(U.ALL_SPECS["BFS"]())
    with pytest.raises(ValueError, match="push_resolution"):
        engine.run_program(small_graphs["rmat"], prog, engine="pallas",
                           push_resolution="radix")


# ---------------------------------------------------------------------------
# Gemini direction autotune (|E_frontier| vs |E|/k)
# ---------------------------------------------------------------------------

def test_switch_k_is_edge_mass_not_vertex_fraction():
    """A single active HUB carries pull-worthy edge volume: under the
    Gemini rule a k that classifies the hub's edge mass as dense must force
    pull even though the vertex fraction is tiny — the case the old
    DENSE_FRONTIER vertex rule gets wrong by construction."""
    # star: vertex 0 → all others; BFS from 0 has a 1-vertex frontier with
    # (n−1)/|E| = 100% of the edges behind it
    n = 40
    src = np.zeros(n - 1, np.int64)
    dst = np.arange(1, n)
    from repro.graph.structure import from_edges
    g = from_edges(n, src, dst)
    dk = U.handwritten_bfs_depth(0)
    _cold()
    res = engine.run_direct(g, dk, engine="pallas", switch_k=2.0)
    # iteration 1: e_frontier = |E| > |E|/2 → pull, every iteration after
    # has an empty-out-degree frontier (leaves) → push
    assert res.stats.pull_iters >= 1
    _cold()
    res2 = engine.run_direct(g, dk, engine="pallas", switch_k=0.5)
    # |E|/0.5 = 2|E|: even the full-graph frontier reads as sparse → push
    assert res2.stats.pull_iters == 0 and res2.stats.push_iters >= 1
    np.testing.assert_array_equal(np.asarray(res.value),
                                  np.asarray(res2.value))


def test_switch_k_none_falls_back_to_dense_frontier():
    """switch_k=None restores the documented vertex-fraction fallback, and
    both rules agree on the fixpoint (direction never changes values)."""
    from repro.graph.structure import line_graph
    g = line_graph(48, weighted=True, seed=3)
    dk = U.handwritten_bfs_depth(0)
    _cold()
    gem = engine.run_direct(g, dk, engine="pallas")           # Gemini default
    _cold()
    frac = engine.run_direct(g, dk, engine="pallas", switch_k=None)
    np.testing.assert_array_equal(np.asarray(gem.value),
                                  np.asarray(frac.value))
    for r in (gem, frac):
        assert r.stats.pull_iters > 0 and r.stats.push_iters > 0
    # distinct heuristics are distinct executor entries (key carries k)
    _cold()
    engine.run_direct(g, dk, engine="pallas", switch_k=10.0)
    engine.run_direct(g, dk, engine="pallas", switch_k=30.0)
    assert kops.executor_cache_size() == 2


def test_switch_k_rejects_junk():
    from repro.graph.structure import line_graph
    g = line_graph(8)
    dk = U.handwritten_bfs_depth(0)
    with pytest.raises(ValueError, match="switch_k"):
        engine.run_direct(g, dk, engine="pallas", switch_k="fastest")
    for bad in (0.0, -5):
        with pytest.raises(ValueError, match="switch_k must be > 0"):
            engine.run_direct(g, dk, engine="pallas", switch_k=bad)


def test_dense_threshold_conflict_rejected():
    """A custom dense_threshold while the Gemini rule is active would be
    silently inert — reject it instead; switch_k=None restores it."""
    from repro.core import iterate
    from repro.graph.structure import line_graph
    from repro.core.synthesis import synthesize_round
    g = line_graph(8)
    dk = U.handwritten_bfs_depth(0)
    from repro.core.fusion import Prim
    comp = iterate.CompRuntime(idx=0, op=dk.rop, dtype=iterate.DTYPES[dk.dtype],
                               p_fn=dk.p_fn, init_fn=dk.init_fn,
                               source=dk.source)
    with pytest.raises(ValueError, match="dense_threshold"):
        kops.iterate_pallas(g, [comp], [Prim(dk.rop, 0)],
                            dense_threshold=0.2)
    res = kops.iterate_pallas(g, [comp], [Prim(dk.rop, 0)],
                              dense_threshold=0.2, switch_k=None)
    assert res.iterations > 0
    # a PINNED direction never traces the switch, so a custom threshold is
    # harmless there and must not raise (pre-PR calls keep working)
    res = kops.iterate_pallas(g, [comp], [Prim(dk.rop, 0)],
                              direction="pull", dense_threshold=0.2)
    assert res.iterations > 0


def test_pinned_direction_ignores_unused_knobs_in_cache_key(small_graphs):
    """model="pull" never traces a push resolution or a direction switch —
    varying those knobs must reuse ONE compiled executor, not retrace."""
    g = small_graphs["rmat"]
    prog = fusion.fuse(U.ALL_SPECS["SSSP"]())
    _cold()
    engine.run_program(g, prog, engine="pallas", model="pull",
                       push_resolution="sorted")
    engine.run_program(g, prog, engine="pallas", model="pull",
                       push_resolution="scatter")
    engine.run_program(g, prog, engine="pallas", model="pull",
                       switch_k=7.0)
    assert kops.executor_cache_size() == 1


# ---------------------------------------------------------------------------
# gather_work: the in-kernel permutation gather is frontier-proportional
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frontier", [0.0, 0.05, 0.3, 1.0])
def test_gather_work_bounded_by_active_resolution_nnz(frontier):
    """Satellite (b): the candidate slots the in-kernel gather reads are
    exactly the ACTIVE resolution tiles' real slots — ≤ Σ nnz over active
    tiles with skipped tiles contributing zero, and 0 on an empty
    frontier."""
    g = rmat_graph(128, 1024, seed=11)
    res = to_push_resolution(g)
    ell = to_blocked_ell(g, direction="out")
    rng = np.random.default_rng(21)
    active = jnp.asarray((rng.random(ell.n_pad) < frontier).astype(np.int32))
    tile_act = er.tile_activity_push(ell.tile_nnz, active, ell.block_v)
    res_tile_act = er.resolution_tile_activity(
        res.contrib, tile_act, res.tile_nnz)
    gather = float(jnp.sum(res.tile_nnz * res_tile_act))
    active_nnz = float(jnp.sum(jnp.where(res_tile_act > 0, res.tile_nnz, 0)))
    assert gather <= active_nnz
    skipped_nnz = float(jnp.sum(jnp.where(res_tile_act == 0, res.tile_nnz, 0)))
    assert gather + skipped_nnz == float(jnp.sum(res.tile_nnz))
    if frontier == 0.0:
        assert gather == 0.0


def test_gather_work_reported_and_under_rectangle(monkeypatch):
    """Engine level: gather_work rides the fixpoint's ``IterationResult``
    into ExecStats, equals the real (valid) slots of the dst-major rectangle
    per push iteration under "sorted" (XLA gathers them through the slot
    list before the resolution kernel, whose own work — resolve_work, the
    kept tiles' real slots — is at most that), stays strictly under the
    padded n_pad·width rectangle, and is 0 under "scatter" (no permutation
    gather at all)."""
    g = rmat_graph(256, 2048, seed=17)
    res = to_push_resolution(g)
    prog = fusion.fuse(U.ALL_SPECS["BFS"]())
    _cold()
    results = _iteration_results(monkeypatch)
    srt = engine.run_program(g, prog, engine="pallas",
                             push_resolution="sorted")
    assert srt.stats.push_iters >= 1
    gw = srt.stats.gather_work
    assert results[-1].gather_work == gw
    rectangle = float(res.n_pad * res.width)
    assert gw == srt.stats.push_iters * float(np.sum(res.valid))
    assert 0 < srt.stats.resolve_work <= gw < srt.stats.push_iters * rectangle
    _cold()
    sct = engine.run_program(g, prog, engine="pallas",
                             push_resolution="scatter")
    assert sct.stats.gather_work == 0.0
    assert results[-1].gather_work == 0.0


# ---------------------------------------------------------------------------
# stat bumps only after successful launch construction
# ---------------------------------------------------------------------------

def test_launch_stats_not_bumped_on_failed_trace(monkeypatch):
    """A pallas_call whose construction/trace raises must leave every
    launch counter untouched (interrupted traces used to pre-increment
    push_launches and skew bench launch counts)."""
    g = uniform_graph(12, 30, seed=5)
    ell = to_blocked_ell(g, direction="out")
    state = jnp.ones(ell.n_pad, jnp.float32)
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.ones(ell.n_pad, jnp.int32)
    er.reset_sweep_stats()

    def boom(*a, **k):
        raise RuntimeError("trace interrupted")

    monkeypatch.setattr(er.pl, "pallas_call", boom)
    kw = dict(plans=(((0, "min"),),), idents={0: ident},
              p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n)
    with pytest.raises(RuntimeError, match="trace interrupted"):
        er.fused_ell_push_sweep(
            ell.nbrs, ell.weight, ell.capacity, ell.mask,
            jnp.ones_like(ell.tile_nnz), {0: state}, active,
            jnp.ones(ell.n_pad, jnp.float32), **kw)
    ell_in = to_blocked_ell(g)
    with pytest.raises(RuntimeError, match="trace interrupted"):
        er.fused_ell_sweep(
            ell_in.srcs, ell_in.weight, ell_in.capacity, ell_in.mask,
            jnp.ones_like(ell_in.tile_nnz), {0: state}, active,
            jnp.ones(ell_in.n_pad, jnp.float32), **kw)
    assert all(v == 0 for v in er.SWEEP_STATS.values())


def test_resolve_launch_not_bumped_on_failed_resolve_trace(monkeypatch):
    """Satellite fix: a sorted push sweep whose RESOLUTION pallas_call fails
    to construct must leave resolve_launches untouched — the edge sweep's
    own launch (the first pallas_call, which succeeded) still counts, but
    the interrupted resolution pass must not (the same skew PR 4 fixed for
    edge sweeps)."""
    g = uniform_graph(12, 30, seed=5)
    ell = to_blocked_ell(g, direction="out")
    res = to_push_resolution(g)
    state = jnp.ones(ell.n_pad, jnp.float32)
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.ones(ell.n_pad, jnp.int32)
    er.reset_sweep_stats()
    real = er.pl.pallas_call
    calls = {"n": 0}

    def second_call_boom(*a, **k):
        calls["n"] += 1
        if calls["n"] >= 2:                 # 1st: push sweep, 2nd: resolve
            raise RuntimeError("resolve trace interrupted")
        return real(*a, **k)

    monkeypatch.setattr(er.pl, "pallas_call", second_call_boom)
    tile_act = er.tile_activity_push(ell.tile_nnz, active, ell.block_v)
    res_tile_act = er.resolution_tile_activity(
        res.contrib, tile_act, res.tile_nnz)
    kw = dict(plans=(((0, "min"),),), idents={0: ident},
              p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n)
    with pytest.raises(RuntimeError, match="resolve trace interrupted"):
        er.fused_ell_push_sweep(
            ell.nbrs, ell.weight, ell.capacity, ell.mask, tile_act,
            {0: state}, active, jnp.ones(ell.n_pad, jnp.float32),
            resolution="sorted",
            res=(res.in2out, res.valid, res_tile_act), **kw)
    assert calls["n"] == 2
    assert er.SWEEP_STATS["resolve_launches"] == 0
    # the successfully constructed edge-sweep launch still counts
    assert er.SWEEP_STATS["launches"] == 1
    assert er.SWEEP_STATS["push_launches"] == 1


# ---------------------------------------------------------------------------
# weighted push− epilogue parity (weighted PageRank)
# ---------------------------------------------------------------------------

def test_weighted_pagerank_pull_push_parity():
    """The weighted push− epilogue round: reference pull−/push−/dense agree
    to float tolerance, and on the pallas engine the dst-sorted resolution
    reduces the SAME dst-major rectangle as the pull sweep — so forced push
    is bitwise identical to pull, float sums included (DESIGN.md §10)."""
    g = rmat_graph(48, 220, seed=7, weighted=True)
    dk = U.handwritten_weighted_pagerank(g.n)
    pull_ref = engine.run_direct(g, dk, engine="pull")
    push_ref = engine.run_direct(g, dk, engine="push")
    dense = engine.run_direct(g, dk, engine="dense")
    np.testing.assert_allclose(np.asarray(pull_ref.value),
                               np.asarray(push_ref.value), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pull_ref.value),
                               np.asarray(dense.value), rtol=1e-4)
    _cold()
    pp = engine.run_direct(g, dk, engine="pallas", model="pull")
    ps = engine.run_direct(g, dk, engine="pallas", model="push")  # sorted
    np.testing.assert_array_equal(np.asarray(pp.value), np.asarray(ps.value))
    np.testing.assert_allclose(np.asarray(pp.value),
                               np.asarray(pull_ref.value), rtol=1e-5)
    # mass actually flows along weights: the unweighted kernels disagree
    uw = engine.run_direct(g, U.handwritten_pagerank(g.n), engine="pull")
    assert not np.allclose(np.asarray(uw.value), np.asarray(pull_ref.value))


def test_weighted_pagerank_scatter_close():
    """The scatter fallback associates the float sums differently, so it is
    only allclose — which is exactly why the sorted path is the one that
    carries the bitwise pull ≡ push guarantee."""
    g = rmat_graph(48, 220, seed=9, weighted=True)
    dk = U.handwritten_weighted_pagerank(g.n)
    _cold()
    a = engine.run_direct(g, dk, engine="pallas", model="push",
                          push_resolution="sorted")
    _cold()
    b = engine.run_direct(g, dk, engine="pallas", model="push",
                          push_resolution="scatter")
    np.testing.assert_allclose(np.asarray(a.value), np.asarray(b.value),
                               rtol=1e-5)
