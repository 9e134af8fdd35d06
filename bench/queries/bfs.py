"""GAP BFS: the parent of every vertex in a breadth-first tree from the root.

The program answers through ``usecases.bfs``: the penultimate vertex of the
least-hop path, ties to the least vertex id, the root its own parent, and an
unreached vertex at the engine's bottom (``>= 1e8``).  The reference is
plain scipy/numpy on the benchmark's own edge list.
"""
import numpy as np

BOTTOM = 1e8            # engine answers at or above this are "unreached"
ARC_WORDS = 1           # bytes per traversed arc: the neighbour id (4 B)
STATE_WORDS = 2         # per reached vertex: hop count and parent


def spec(usecases, root: int):
    return usecases.bfs(root)


def _depth(g, root: int) -> np.ndarray:
    from scipy.sparse.csgraph import shortest_path
    return shortest_path(g.csr(weighted=False), directed=True,
                         unweighted=True, indices=root)


def _parents(g, root: int, pick) -> np.ndarray:
    """Per vertex, ``pick`` (np.minimum or np.maximum) over the neighbours
    one hop nearer the root; the root is its own parent, -1 unreached."""
    depth = _depth(g, root)
    adj = g.csr(weighted=False)
    row = np.repeat(np.arange(g.n), np.diff(adj.indptr))
    nbr = adj.indices
    ok = np.isfinite(depth[row]) & (depth[nbr] + 1 == depth[row])
    fill = np.iinfo(np.int64).max if pick is np.minimum else -1
    cand = np.where(ok, nbr.astype(np.int64), fill)
    parent = np.full(g.n, fill, np.int64)
    pick.at(parent, row, cand)
    parent[~np.isfinite(depth) | (parent == fill)] = -1
    parent[root] = root
    return parent


def reference(g, root: int) -> np.ndarray:
    return _parents(g, root, np.minimum)


def control(g):
    """The reference with one stated guarantee broken: ties go to the
    greatest parent id instead of the least."""
    return lambda root: _parents(g, root, np.maximum)


def normalize(answer) -> np.ndarray:
    a = np.asarray(answer).astype(np.float64)
    return np.where(np.isfinite(a) & (np.abs(a) < BOTTOM) & (a >= 0),
                    a, -1).astype(np.int64)


def mismatches(answer, ref) -> int:
    """Vertices whose parent differs from the reference's (exact)."""
    return int(np.count_nonzero(normalize(answer) != ref))


def reached(ref) -> np.ndarray:
    return ref >= 0
