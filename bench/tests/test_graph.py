"""The generator copies give GAP's shapes: undirected, no self loops, no
duplicates, degree 2 x edge factor, Kronecker skew, weights in [1, 255]."""
import json
import os

import numpy as np
import pytest

import graph as G

CONFIGS = os.path.join(os.path.dirname(G.__file__), "configs")


def _cfg(name, scale):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    cfg["scale"] = scale
    return cfg


@pytest.mark.parametrize("name", ["gap-urand-20", "gap-kron-15"])
def test_undirected_simple_weighted(name):
    g = G.build(_cfg(name, 10))
    assert g.n == 1024
    assert np.all(g.u < g.v)                       # no self loops, u < v
    key = g.u.astype(np.int64) * g.n + g.v
    assert np.unique(key).size == key.size         # no duplicate edges
    assert g.w.min() >= 1 and g.w.max() <= 255
    assert np.all(g.w == np.round(g.w))
    src, dst, w = g.arcs()
    adj = g.csr(weighted=True)
    assert (adj != adj.T).nnz == 0                 # symmetric, same weights
    assert src.size == 2 * g.num_edges


def test_urand_degree():
    g = G.build(_cfg("gap-urand-20", 12))
    d = g.degree()
    # 16 * 2^12 draws, each an edge of two endpoints: mean degree ~32
    assert 31.0 < d.mean() <= 32.0
    assert d.max() < 80


def test_kron_skew():
    g = G.build(_cfg("gap-kron-15", 12))
    d = g.degree()
    assert d.max() > 40 * d[d > 0].mean()          # hubs
    assert (d == 0).mean() > 0.1                   # many isolated ids


def test_graph_and_roots_fixed_by_graph_seed_order_by_run_seed():
    cfg = _cfg("gap-kron-15", 9)
    a, b = G.build(cfg), G.build(cfg)
    for x, y in ((a.u, b.u), (a.v, b.v), (a.w, b.w)):
        assert np.array_equal(x, y)
    other = G.build(dict(cfg, graph_seed=cfg["graph_seed"] + 1))
    assert not np.array_equal(a.u, other.u)
    roots, warm = G.roots(a, 16, cfg["graph_seed"])
    assert warm not in roots and len(set(roots)) == len(roots)
    assert np.all(a.degree()[roots + [warm]] > 0)
    big = 2 ** 40 + 3
    assert G.order(roots, big) == G.order(roots, big)
    assert G.order(roots, big) != G.order(roots, big + 1)
    assert sorted(G.order(roots, big)) == sorted(roots)


def test_components_count_edges_once():
    g = G.Graph(n=6, u=np.array([0, 1, 3], np.int32),
                v=np.array([1, 2, 4], np.int32),
                w=np.ones(3, np.float32))
    label, verts, edges = G.components(g)
    assert edges[label[0]] == 2 and verts[label[0]] == 3
    assert edges[label[3]] == 1 and verts[label[3]] == 2
    assert edges[label[5]] == 0 and verts[label[5]] == 1
