"""Distributed engine + sampler + partition tests on forced host devices.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count
because device count locks at first jax init (the main test process stays
1-device).  The shard_map cases carry the ``distributed`` marker and run in
the PR multi-device CI lane; only the heaviest also carry ``slow`` and stay
nightly-only (pyproject marker split)."""
import json

import numpy as np
import pytest

from conftest import run_forced_devices


def _run(code: str) -> str:
    return run_forced_devices(code, 4)


@pytest.mark.distributed
def test_distributed_engine_matches_oracle():
    out = _run("""
        import numpy as np, jax, json
        from repro.graph.structure import uniform_graph, undirected
        from repro.core import usecases as U, fusion, engine
        from repro.core.lang import paths_semantics
        mesh = jax.make_mesh((4,), ('data',))
        g = uniform_graph(9, 18, seed=3)
        ok = {}
        for name in ['SSSP','CC','WSP','NSP','Trust','RADIUS','RDS']:
            gg = undirected(g) if name=='CC' else g
            spec = U.ALL_SPECS[name]()
            want = paths_semantics(spec, gg, max_len=gg.n)
            if hasattr(want,'dtype') and want.dtype==object:
                want = np.array([float(x) for x in want])
            got = engine.run_program(gg, fusion.fuse(spec),
                                     engine='distributed', mesh=mesh).value
            w = np.nan_to_num(np.where(np.abs(np.asarray(want,np.float64))>=1e8,
                np.sign(np.asarray(want,np.float64))*np.inf, np.asarray(want,np.float64)),
                posinf=1e9, neginf=-1e9)
            gv = np.nan_to_num(np.where(np.abs(np.asarray(got,np.float64))>=1e8,
                np.sign(np.asarray(got,np.float64))*np.inf, np.asarray(got,np.float64)),
                posinf=1e9, neginf=-1e9)
            ok[name] = bool(np.allclose(w, gv, atol=1e-4))
        print(json.dumps(ok))
    """)
    ok = json.loads(out.strip().splitlines()[-1])
    assert all(ok.values()), ok


@pytest.mark.distributed
def test_compressed_cross_pod_allreduce():
    """int8 error-feedback all-reduce over a 'pod' axis ≈ exact mean."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import error_feedback_update, CompressState
        mesh = jax.make_mesh((4,), ('pod',))
        rng = np.random.default_rng(0)
        g_all = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
        def f(g, e):
            g, e = g[0], e[0]
            red, st = error_feedback_update({'w': g}, CompressState({'w': e}),
                                            'pod')
            return red['w'][None], st.error['w'][None]
        fn = jax.shard_map(f, mesh=mesh, in_specs=(P('pod'), P('pod')),
                       out_specs=(P('pod'), P('pod')))
        e0 = jnp.zeros((4, 256), jnp.float32)
        red, e1 = fn(g_all, e0)
        true = np.asarray(g_all).mean(axis=0)
        err = float(np.abs(np.asarray(red)[0] - true).max())
        scale = float(np.abs(np.asarray(g_all)).max() / 127.0)
        print(json.dumps({'err': err, 'bound': 4*scale}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["err"] <= rec["bound"], rec


@pytest.mark.distributed
def test_distributed_shard_stats_replicated():
    """The distributed engine asserts cross-shard replication of the
    iteration count (instead of silently trusting shard 0) and surfaces
    per-shard edge work whose sum is the total."""
    out = _run("""
        import numpy as np, jax, json
        from repro.core import usecases as U, fusion, engine
        from repro.graph.structure import uniform_graph
        mesh = jax.make_mesh((4,), ('data',))
        g = uniform_graph(12, 30, seed=7)
        res = engine.run_program(g, fusion.fuse(U.sssp(0)),
                                 engine='distributed', mesh=mesh)
        st = res.stats
        rec = {'shards': st.shards,
               'n_shard_work': len(st.shard_work),
               'sum_ok': abs(sum(st.shard_work) - st.edge_work) < 1e-6,
               'iters': st.iterations}
        print(json.dumps(rec))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["shards"] == 4 and rec["n_shard_work"] == 4, rec
    assert rec["sum_ok"] and rec["iters"] > 0, rec


def test_neighbor_sampler_shapes_and_membership():
    from repro.graph.sampler import NeighborSampler, max_nodes_for
    from repro.graph.structure import rmat_graph
    g = rmat_graph(200, 1600, seed=0)
    fan = [4, 3]
    s = NeighborSampler(g, fan, seed=1)
    seeds = np.arange(8)
    batch = s.sample(seeds)
    assert batch.nodes.shape[0] == max_nodes_for(8, fan)
    assert len(batch.edge_src) == 2
    assert batch.edge_src[0].shape == batch.edge_dst[0].shape
    # sampled edges reference real in-neighbours
    src_g, dst_g, _, _ = g.host_edges()
    edge_set = set(zip(src_g.tolist(), dst_g.tolist()))
    hop = 1                                # seed-adjacent hop (last)
    srcs = batch.nodes[batch.edge_src[hop]]
    dsts = batch.nodes[batch.edge_dst[hop]]
    mask = batch.edge_mask[hop]
    ok = sum((int(a), int(b)) in edge_set
             for a, b, m in zip(srcs, dsts, mask) if m)
    tot = int(np.sum(mask))
    assert tot == 0 or ok / tot > 0.99


def test_partition_covers_all_edges():
    from repro.graph.partition import partition_edges
    from repro.graph.structure import rmat_graph
    g = rmat_graph(50, 300, seed=2)
    part = partition_edges(g, 4)
    assert int(np.sum(np.asarray(part.mask))) == g.num_edges
    src_g, dst_g, _, _ = g.host_edges()
    got = sorted(zip(np.asarray(part.src)[np.asarray(part.mask)].tolist(),
                     np.asarray(part.dst)[np.asarray(part.mask)].tolist()))
    want = sorted(zip(src_g.tolist(), dst_g.tolist()))
    assert got == want


@pytest.mark.distributed
@pytest.mark.slow                    # heaviest shard_map case: nightly-only
def test_mgn_dist_multishard_matches_reference():
    """Hillclimb B correctness: 4-shard vertex-cut MGN loss ≡ single-device
    reference on a real mesh graph."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        import repro.configs as C
        from repro.models import gnn as G
        from repro.data import graphs as DG
        from repro.data.graphs import dst_block_partition

        cfg = C.get('meshgraphnet').smoke()
        b = DG.mesh_batch(rows=8, cols=8, d_node_in=cfg.d_node_in,
                          d_edge_in=cfg.d_edge_in, d_out=cfg.d_out)
        key = jax.random.PRNGKey(0)
        p = G.mgn_init(cfg, key)
        ref = float(G.mgn_loss(cfg, p, b))

        k = 4
        n = b['node_x'].shape[0]
        src, dst = np.asarray(b['src']), np.asarray(b['dst'])
        part = dst_block_partition(src, dst, n, k, pad_factor=2.0)
        n_loc = part['n_loc']; npad = k * n_loc
        node_x = np.zeros((npad, cfg.d_node_in), np.float32)
        node_x[:n] = np.asarray(b['node_x'])
        target = np.zeros((npad, cfg.d_out), np.float32)
        target[:n] = np.asarray(b['target'])
        nmask = np.zeros(npad, bool); nmask[:n] = True
        ex = np.asarray(b['edge_x'])
        edge_x = np.zeros((k, part['e_pad'], cfg.d_edge_in), np.float32)
        blocks = dst // n_loc
        for j in range(k):
            sel = np.nonzero(blocks == j)[0][:part['e_pad']]
            edge_x[j, :len(sel)] = ex[sel]
        batch = {'node_x': jnp.asarray(node_x),
                 'edge_x': jnp.asarray(edge_x.reshape(-1, cfg.d_edge_in)),
                 'src': jnp.asarray(part['src'].reshape(-1)),
                 'dst': jnp.asarray(part['dst'].reshape(-1)),
                 'emask': jnp.asarray(part['mask'].reshape(-1)),
                 'nmask': jnp.asarray(nmask), 'target': jnp.asarray(target)}
        mesh = jax.make_mesh((4,), ('d',))
        bspecs = {kk: P('d', None) if v.ndim == 2 else P('d')
                  for kk, v in batch.items()}
        fn = jax.shard_map(
            lambda params, bb: G.mgn_loss_dist(cfg, params, bb, ('d',)),
            mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), p), bspecs),
            out_specs=P(), check_vma=False)
        got = float(fn(p, batch))
        print(json.dumps({'ref': ref, 'got': got}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert abs(rec["ref"] - rec["got"]) < 1e-4, rec
