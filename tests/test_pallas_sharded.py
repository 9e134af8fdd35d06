"""Sharded pallas engine (DESIGN.md §11): shard-local fused ELL sweeps under
shard_map must reproduce the single-device pallas engine exactly.

The multi-device equivalence tests run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 because device count
locks at first jax init (the main test process stays 1-device); they carry
the ``distributed`` marker so the PR multi-device CI lane runs them without
waiting for nightly.  Layout invariants and the k=1 degenerate mesh run
in-process in the fast lane."""
import json

import numpy as np
import pytest

from conftest import run_forced_devices


def _run(code: str) -> str:
    return run_forced_devices(code, 8)


# ---------------------------------------------------------------------------
# In-process: sharded layout invariants (no mesh needed).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["contiguous", "dst_hash"])
@pytest.mark.parametrize("direction", ["in", "out"])
def test_sharded_ell_covers_partition(strategy, direction):
    """Each shard's layout holds exactly its partition block's edges; the
    union over shards is the graph, and row_deg psums to the global degree."""
    from repro.graph.structure import rmat_graph, to_sharded_ell
    g = rmat_graph(50, 300, seed=2)
    k = 3                                   # uneven split exercises padding
    ell = to_sharded_ell(g, k, strategy=strategy, direction=direction)
    assert ell.num_edges == g.num_edges
    nbrs = np.asarray(ell.nbrs)
    mask = np.asarray(ell.mask)
    rows = np.broadcast_to(np.arange(ell.n_pad)[None, :, None], nbrs.shape)
    if direction == "in":                   # rows = dst, slots = src
        got = sorted(zip(nbrs[mask].tolist(), rows[mask].tolist()))
    else:                                   # rows = src, slots = dst
        got = sorted(zip(rows[mask].tolist(), nbrs[mask].tolist()))
    src_g, dst_g, _, _ = g.host_edges()
    assert got == sorted(zip(src_g.tolist(), dst_g.tolist()))
    # per-shard row degrees sum to the global degree of the direction
    deg = np.asarray(ell.row_deg).sum(axis=0)[:g.n]
    want = np.asarray(g.in_deg if direction == "in" else g.out_deg)
    assert np.array_equal(deg, want.astype(np.float32))
    # tile_nnz counts exactly the real slots of each tile
    n_i = ell.n_pad // ell.block_v
    n_j = ell.width // ell.block_e
    nnz = mask.reshape(k, n_i, ell.block_v, n_j, ell.block_e).sum(axis=(2, 4))
    assert np.array_equal(np.asarray(ell.tile_nnz), nnz.astype(np.int32))


def test_sharded_ell_cache_and_clear():
    from repro.core.engine import clear_program_caches, program_cache_stats
    from repro.graph.structure import sharded_ell_cached, uniform_graph
    g = uniform_graph(12, 30, seed=7)
    a = sharded_ell_cached(g, 2, direction="in")
    assert sharded_ell_cached(g, 2, direction="in") is a
    assert sharded_ell_cached(g, 2, direction="out") is not a
    assert program_cache_stats()["sharded_layouts"] == 2
    clear_program_caches()
    assert program_cache_stats()["sharded_layouts"] == 0


@pytest.mark.parametrize("strategy", ["contiguous", "dst_hash"])
def test_sharded_push_resolution_roundtrips_per_shard(strategy):
    """Each shard's in2out permutation must address its own WIDENED out
    rectangle (all shards share the max width so shard_map can stack them)
    and round-trip exactly the shard's edges — the same contract
    test_push_resolution checks on one device, per shard."""
    from repro.graph.structure import (rmat_graph, to_sharded_ell,
                                       to_sharded_push_resolution)
    g = rmat_graph(50, 300, seed=2)
    k = 3
    sres = to_sharded_push_resolution(g, k, strategy=strategy)
    ell_out = to_sharded_ell(g, k, strategy=strategy, direction="out")
    assert sres.out_width == ell_out.width
    got = []
    for s in range(k):
        valid = np.asarray(sres.valid[s])
        in2out = np.asarray(sres.in2out[s])
        # every real out-slot of THIS shard is hit exactly once
        out_mask = np.asarray(ell_out.mask[s]).reshape(-1)
        assert sorted(in2out[valid].tolist()) == \
            np.flatnonzero(out_mask).tolist()
        # the out-slot's stored destination is the dst-major slot's own row
        dst_via = np.asarray(ell_out.nbrs[s]).reshape(-1)[in2out]
        rows = np.broadcast_to(np.arange(sres.n_pad)[:, None], valid.shape)
        np.testing.assert_array_equal(dst_via[valid], rows[valid])
        src_rows = np.asarray(in2out // sres.out_width)
        got += list(zip(src_rows[valid].tolist(), dst_via[valid].tolist()))
    # the union over shards is the graph
    src_g, dst_g, _, _ = g.host_edges()
    assert sorted(got) == sorted(zip(src_g.tolist(), dst_g.tolist()))
    # the contrib classes hold one non-empty list for each resolution tile
    # with real slots and none for any other (padding columns' ids are past
    # the last tile)
    nnz = np.asarray(sres.tile_nnz).reshape(k, -1)
    for s in range(k):
        ids = np.concatenate([np.asarray(i[s]) for i, _ in sres.contrib])
        lists = [np.asarray(li[s]) for _, li in sres.contrib]
        real = ids < nnz.shape[1]
        assert sorted(ids[real].tolist()) == np.flatnonzero(nnz[s]).tolist()
        filled = np.concatenate([(li >= 0).any(axis=0) for li in lists])
        assert (filled == real).all()


def test_sharded_empty_shards_are_all_padding():
    """k > |E| leaves empty shards whose tiles all skip (mask/tile_nnz 0)."""
    from repro.graph.structure import line_graph, to_sharded_ell
    g = line_graph(4)                       # 3 edges
    ell = to_sharded_ell(g, 5, direction="in")
    mask = np.asarray(ell.mask)
    per_shard = mask.sum(axis=(1, 2))
    assert per_shard.sum() == g.num_edges
    assert (np.asarray(ell.tile_nnz)[per_shard == 0] == 0).all()


# ---------------------------------------------------------------------------
# In-process: k=1 degenerate mesh (single cpu device) + argument validation.
# ---------------------------------------------------------------------------


def _mesh1():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


@pytest.mark.parametrize("name", ["BFS", "SSSP", "NSP", "PageRank"])
def test_sharded_k1_matches_single_device_bitwise(name):
    """A 1-shard mesh must reproduce the single-device engine exactly —
    the degenerate case runs in the fast lane on one CPU device.  PageRank
    takes the non-idempotent full-recompute branch of the shared fixpoint
    body (33 iterations here, past the default 2n + 4)."""
    from repro.core import engine, fusion
    from repro.core import usecases as U
    from repro.graph.structure import uniform_graph
    g = uniform_graph(9, 18, seed=3)
    mesh = _mesh1()
    if name == "PageRank":
        prog, kw = fusion.fuse(U.pagerank()), {"max_iter": 200}
    else:
        prog, kw = fusion.fuse(U.ALL_SPECS[name]()), {}
    r1 = engine.run_program(g, prog, engine="pallas", **kw)
    rs = engine.run_program(g, prog, engine="pallas_sharded", mesh=mesh,
                            **kw)
    assert np.asarray(r1.value).tobytes() == np.asarray(rs.value).tobytes()
    assert rs.stats.iterations == r1.stats.iterations
    assert rs.stats.shards == 1
    assert len(rs.stats.shard_work) == 1


def test_sharded_resolution_knob_validation():
    """The sharded engine takes the same push_resolution surface as the
    single-device one: "sorted" (default) runs the per-shard resolution
    stack, "scatter" stays the reference oracle, junk is rejected with the
    shared normalizer text — the old "single-device-only" rejection of
    "sorted" is gone."""
    from repro.core import engine, fusion
    from repro.core import usecases as U
    from repro.graph.structure import uniform_graph
    g = uniform_graph(9, 18, seed=3)
    prog = fusion.fuse(U.bfs(0))
    mesh = _mesh1()
    with pytest.raises(ValueError, match="push_resolution"):
        engine.run_program(g, prog, engine="pallas_sharded", mesh=mesh,
                           push_resolution="radix")
    with pytest.raises(ValueError, match="strategy"):
        engine.run_program(g, prog, engine="pallas_sharded", mesh=mesh,
                           shard_strategy="nope")
    with pytest.raises(AssertionError, match="mesh"):
        engine.run_program(g, prog, engine="pallas_sharded")
    # both resolutions are first-class on the sharded engine and agree
    rs = engine.run_program(g, prog, engine="pallas_sharded", mesh=mesh,
                            push_resolution="sorted")
    rc = engine.run_program(g, prog, engine="pallas_sharded", mesh=mesh,
                            push_resolution="scatter")
    assert rs.stats.iterations > 0
    np.testing.assert_array_equal(np.asarray(rs.value), np.asarray(rc.value))


def test_sharded_resolution_cache_and_clear():
    """Per-shard resolution stacks are identity-cached, reported by
    program_cache_stats, and dropped per graph by clear_graph_caches."""
    from repro.core.engine import (clear_graph_caches, clear_program_caches,
                                   program_cache_stats)
    from repro.graph.structure import (sharded_push_resolution_cached,
                                       uniform_graph)
    g1 = uniform_graph(12, 30, seed=7)
    g2 = uniform_graph(12, 30, seed=8)
    a = sharded_push_resolution_cached(g1, 2)
    assert sharded_push_resolution_cached(g1, 2) is a
    assert sharded_push_resolution_cached(g1, 3) is not a
    sharded_push_resolution_cached(g2, 2)
    assert program_cache_stats()["sharded_resolutions"] == 3
    dropped = clear_graph_caches(g1)
    assert dropped >= 2
    assert program_cache_stats()["sharded_resolutions"] == 1   # g2 survives
    assert sharded_push_resolution_cached(g2, 2) is not None
    clear_program_caches()
    assert program_cache_stats()["sharded_resolutions"] == 0


# ---------------------------------------------------------------------------
# Multi-device equivalence (subprocess, 8 forced host devices).
# ---------------------------------------------------------------------------

_EQUIV_CODE = """
    import numpy as np, jax, json
    from jax.sharding import Mesh
    from repro.graph.structure import uniform_graph, rmat_graph
    from repro.core import usecases as U, fusion, engine

    graphs = {{'uniform': uniform_graph(9, 18, seed=3),
               'rmat': rmat_graph(16, 48, seed=5)}}
    ok = {{}}
    for gname, g in graphs.items():
        for name in {usecases}:
            prog = fusion.fuse(U.ALL_SPECS[name]())
            refs = {{m: engine.run_program(g, prog, engine='pallas', model=m)
                     for m in {models}}}
            for k in {ks}:
                mesh = Mesh(np.asarray(jax.devices()[:k]), ('data',))
                for model in {models}:
                    rs = engine.run_program(
                        g, prog, engine='pallas_sharded', mesh=mesh,
                        model=model, shard_strategy={strategy!r})
                    r1 = refs[model]
                    key = f'{{gname}}/{{name}}/k{{k}}/{{model}}'
                    ok[key] = (
                        bool({cmp}) and
                        rs.stats.iterations == r1.stats.iterations and
                        rs.stats.push_iters == r1.stats.push_iters and
                        rs.stats.shards == k and
                        len(rs.stats.shard_work) == k)
    print(json.dumps(ok))
"""

_BITWISE = ("np.array_equal(np.asarray(r1.value), np.asarray(rs.value))")
_ALLCLOSE = ("np.allclose(np.nan_to_num(np.asarray(r1.value, np.float64)),"
             " np.nan_to_num(np.asarray(rs.value, np.float64)),"
             " atol=1e-5, rtol=1e-5)")


def _check(out: str):
    ok = json.loads(out.strip().splitlines()[-1])
    bad = {k: v for k, v in ok.items() if not v}
    assert not bad, bad


@pytest.mark.distributed
@pytest.mark.parametrize("strategy", ["contiguous", "dst_hash"])
def test_sharded_idempotent_bitwise(strategy):
    """pallas_sharded ≡ pallas BITWISE for idempotent (pull+/push+) rounds:
    BFS and SSSP, k ∈ {2, 4}, direction ∈ {pull, push, auto} — and the
    global direction switch must take the identical push/pull sequence."""
    _check(_run(_EQUIV_CODE.format(
        usecases=("BFS", "SSSP"), models=(None, "pull", "push"),
        ks=(2, 4), strategy=strategy, cmp=_BITWISE)))


@pytest.mark.distributed
@pytest.mark.parametrize("strategy", ["contiguous", "dst_hash"])
def test_sharded_pull_minus_allclose(strategy):
    """Non-idempotent (pull−) rounds: cross-shard psum reassociates float
    sums, so NSP/NWR are allclose (min/lex primaries stay exact)."""
    _check(_run(_EQUIV_CODE.format(
        usecases=("NSP", "NWR"), models=(None,),
        ks=(2, 4), strategy=strategy, cmp=_ALLCLOSE)))


@pytest.mark.distributed
def test_sharded_pagerank_direct_allclose():
    """run_direct PageRank (epilogue pull− round) on the sharded engine."""
    out = _run("""
        import numpy as np, jax, json
        from jax.sharding import Mesh
        from repro.core import usecases as U, engine
        from repro.graph.structure import uniform_graph
        g = uniform_graph(12, 30, seed=7)
        ok = {}
        r1 = engine.run_direct(g, U.handwritten_pagerank(g.n),
                               engine='pallas')
        for k in (2, 4):
            mesh = Mesh(np.asarray(jax.devices()[:k]), ('data',))
            rs = engine.run_direct(g, U.handwritten_pagerank(g.n),
                                   engine='pallas_sharded', mesh=mesh)
            ok[f'k{k}'] = bool(
                np.allclose(np.asarray(r1.value), np.asarray(rs.value),
                            atol=1e-5)
                and rs.stats.iterations == r1.stats.iterations)
        print(json.dumps(ok))
    """)
    _check(out)


@pytest.mark.distributed
def test_sharded_reshaped_mesh_does_not_collide():
    """Two meshes over the SAME devices with the same axis names but
    different shapes must compile separate executors: the cache key carries
    the axis name→size layout, not just the device set (a collision would
    silently split a [k2, ...] stack over a k1-sized axis and drop edges)."""
    out = _run("""
        import numpy as np, jax, json
        from jax.sharding import Mesh
        from repro.core import usecases as U, fusion, engine
        from repro.graph.structure import uniform_graph
        g = uniform_graph(12, 30, seed=7)
        devs = np.asarray(jax.devices()[:4])
        mesh_a = Mesh(devs.reshape(2, 2), ('data', 'model'))
        mesh_b = Mesh(devs.reshape(4, 1), ('data', 'model'))
        prog = fusion.fuse(U.sssp(0))
        ref = engine.run_program(g, prog, engine='pallas')
        ra = engine.run_program(g, prog, engine='pallas_sharded', mesh=mesh_a)
        rb = engine.run_program(g, prog, engine='pallas_sharded', mesh=mesh_b)
        ok = {'k_a': ra.stats.shards == 2, 'k_b': rb.stats.shards == 4,
              'a_bitwise': bool(np.array_equal(np.asarray(ref.value),
                                               np.asarray(ra.value))),
              'b_bitwise': bool(np.array_equal(np.asarray(ref.value),
                                               np.asarray(rb.value)))}
        print(json.dumps(ok))
    """)
    _check(out)


@pytest.mark.distributed
@pytest.mark.parametrize("resolution", ["sorted", "scatter"])
def test_sharded_resolution_matches_single_device(resolution):
    """Satellite (c): sharded resolution ≡ single-device resolution BITWISE
    for pull/push/auto × k∈{2,4} on idempotent rounds — under the default
    per-shard sorted stack AND the scatter oracle — and the sorted stack's
    resolve_work stays strictly under the per-shard scatter rectangle."""
    out = _run("""
        import numpy as np, jax, json
        from jax.sharding import Mesh
        from repro.core import usecases as U, fusion, engine
        from repro.graph.structure import (rmat_graph,
                                           sharded_push_resolution_cached)
        resolution = {resolution!r}
        g = rmat_graph(16, 48, seed=5)
        prog = fusion.fuse(U.ALL_SPECS['BFS']())
        ok = {{}}
        for model in (None, 'pull', 'push'):
            r1 = engine.run_program(g, prog, engine='pallas', model=model,
                                    push_resolution=resolution)
            for k in (2, 4):
                mesh = Mesh(np.asarray(jax.devices()[:k]), ('data',))
                rs = engine.run_program(
                    g, prog, engine='pallas_sharded', mesh=mesh, model=model,
                    push_resolution=resolution)
                rec = (np.array_equal(np.asarray(r1.value),
                                      np.asarray(rs.value))
                       and rs.stats.iterations == r1.stats.iterations
                       and rs.stats.push_iters == r1.stats.push_iters)
                if resolution == 'sorted' and rs.stats.push_iters:
                    # the sharded sorted resolve is frontier-proportional:
                    # strictly under the per-shard scatter rectangle, and
                    # every push iteration gathers each shard's real
                    # dst-major slots through its slot list
                    sc = engine.run_program(
                        g, prog, engine='pallas_sharded', mesh=mesh,
                        model=model, push_resolution='scatter')
                    real = int(np.sum(
                        sharded_push_resolution_cached(g, k).valid))
                    rec = (rec and
                           0 < rs.stats.resolve_work < sc.stats.resolve_work
                           and rs.stats.resolve_work <= rs.stats.gather_work
                           and rs.stats.gather_work
                           == rs.stats.push_iters * real
                           and sc.stats.gather_work == 0)
                ok[f'{{model}}/k{{k}}'] = bool(rec)
        print(json.dumps(ok))
    """.format(resolution=resolution))
    _check(out)


@pytest.mark.distributed
def test_sharded_sources_share_one_executor():
    """The sharded executor is source-generic like the single-device one:
    an N-source sweep holds ONE cache entry, and the sharded stats carry
    per-shard work + cross-combine counts."""
    out = _run("""
        import numpy as np, jax, json
        from jax.sharding import Mesh
        from repro.core import usecases as U, fusion, engine
        from repro.kernels import ops as kops
        from repro.graph.structure import uniform_graph
        g = uniform_graph(12, 30, seed=7)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
        prog = fusion.fuse(U.sssp(0))
        res = [engine.run_program(g, prog, engine='pallas_sharded',
                                  mesh=mesh, source=s) for s in range(6)]
        ref = [engine.run_program(g, prog, engine='pallas', source=s)
               for s in range(6)]
        st = res[0].stats
        rec = {
          'one_entry': kops.executor_cache_size() == 2,  # sharded + single
          'bitwise': all(np.array_equal(np.asarray(a.value),
                                        np.asarray(b.value))
                         for a, b in zip(res, ref)),
          'shards': st.shards == 4,
          'shard_work': len(st.shard_work) == 4 and
                        abs(sum(st.shard_work) - st.edge_work) < 1e-6,
          'launches': st.shard_launches >= 1,
          'combines': st.cross_combines == st.iterations * 1,
        }
        print(json.dumps(rec))
    """)
    _check(out)
