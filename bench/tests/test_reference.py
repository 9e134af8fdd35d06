"""The plain reference agrees with the program's jnp ``pull`` engine on small
graphs, and each query's control disagrees with it."""
import json
import os

import numpy as np
import pytest

import graph as G

CONFIGS = os.path.join(os.path.dirname(G.__file__), "configs")


def _graph(name, scale, graph_seed):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    cfg["scale"] = scale
    cfg["graph_seed"] = graph_seed
    return G.build(cfg)


def _pull(g, query, root):
    from repro.core import engine, fusion, usecases
    from repro.graph import structure
    src, dst, w = g.arcs()
    pg = structure.from_edges(g.n, src, dst, weight=w)
    res = engine.run_program(pg, fusion.fuse(query.spec(usecases, root)),
                             engine="pull")
    return np.asarray(res.value)


CASES = [("gap-kron-15", 8, "bfs"), ("gap-kron-15", 8, "sssp"),
         ("gap-urand-20", 8, "bfs"), ("gap-urand-20", 8, "sssp")]


@pytest.mark.parametrize("name,scale,kind", CASES)
def test_reference_matches_pull_engine(name, scale, kind):
    query = G.load_module("queries", kind)
    g = _graph(name, scale, graph_seed=2 ** 35 + 9)
    roots, _warm = G.roots(g, 4, seed=11)
    for r in roots:
        ref = query.reference(g, r)
        assert query.mismatches(_pull(g, query, r), ref) == 0
        assert query.reached(ref)[r]


# The SSSP control rounds distances to bfloat16, which holds every integer
# up to 256 exactly: on a small uniform graph no distance reaches that, so
# the SSSP control is checked on the Kronecker graph, whose paths are longer.
@pytest.mark.parametrize("name,scale,kind", [c for c in CASES
                                             if c[0] == "gap-kron-15"
                                             or c[2] != "sssp"])
def test_control_fails(name, scale, kind):
    query = G.load_module("queries", kind)
    g = _graph(name, scale, graph_seed=2 ** 35 + 9)
    control = query.control(g)
    roots, _warm = G.roots(g, 3, seed=12)
    for r in roots:
        assert query.mismatches(control(r), query.reference(g, r)) > 0


def test_bfs_reference_ties_to_least_id():
    query = G.load_module("queries", "bfs")
    # 0-1, 0-2, 1-3, 2-3: vertex 3 has two parents at depth 1; 4 unreached
    g = G.Graph(n=5, u=np.array([0, 0, 1, 2], np.int32),
                v=np.array([1, 2, 3, 3], np.int32),
                w=np.ones(4, np.float32))
    assert query.reference(g, 0).tolist() == [0, 0, 0, 1, -1]
    assert query.control(g)(0).tolist() == [0, 0, 0, 2, -1]
    # the engine's bottom reads as unreached
    assert query.mismatches([0, 0, 0, 1, 2 ** 30 - 1],
                            query.reference(g, 0)) == 0


def test_sssp_reference_exact():
    query = G.load_module("queries", "sssp")
    g = G.Graph(n=4, u=np.array([0, 1, 0], np.int32),
                v=np.array([1, 2, 2], np.int32),
                w=np.array([3, 4, 255], np.float32))
    ref = query.reference(g, 0)
    assert ref[:3].tolist() == [0, 3, 7] and np.isinf(ref[3])
    assert query.mismatches(np.array([0, 3, 7, np.inf], np.float32), ref) == 0
    assert query.mismatches(np.array([0, 3, 7, 1e9], np.float32), ref) == 0
    assert query.mismatches(np.array([0, 3, 8, np.inf], np.float32), ref) == 1
