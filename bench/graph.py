"""The benchmark's graph: GAP's builder applied to a generator's draws.

A configuration names a generator kind (``gen/<kind>.py``), its sizes, and
the seed of its data.  GAP's benchmark graphs are fixed files, made once by
its generator from one seed (``kRandSeed``), and its trials query a fixed
list of sources picked from that seed.  Here too the configuration's
``graph_seed`` fixes the topology, the weights and the list of roots, so
every run of a cell sweeps the same layout shapes (it finds its compiled
programs in the cache) and does the same work; the run's ``--seed`` orders
the roots.
"""
import dataclasses
import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind_dir: str, name: str):
    """Import ``bench/<kind_dir>/<name>.py`` by path (names may hold dots)."""
    path = os.path.join(HERE, kind_dir, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind_dir} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind_dir}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of the run seed (any non-negative integer)."""
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Graph:
    """An undirected weighted graph as GAP's builder leaves it: no self
    loops, no duplicate edges, one weight per edge.  ``u < v`` per edge."""
    n: int
    u: np.ndarray            # int32 [m]
    v: np.ndarray            # int32 [m]
    w: np.ndarray            # float32 [m], integers in [w_lo, w_hi]
    _csr: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def num_edges(self) -> int:
        return int(self.u.size)

    def arcs(self):
        """Both directions of every edge: ``(src, dst, weight)``."""
        return (np.concatenate([self.u, self.v]),
                np.concatenate([self.v, self.u]),
                np.concatenate([self.w, self.w]))

    def degree(self) -> np.ndarray:
        return (np.bincount(self.u, minlength=self.n)
                + np.bincount(self.v, minlength=self.n))

    def csr(self, weighted: bool):
        """scipy CSR adjacency of the arcs (weights or ones), sorted
        indices, built once per graph."""
        if weighted not in self._csr:
            from scipy.sparse import csr_matrix
            src, dst, w = self.arcs()
            data = w.astype(np.float64) if weighted else np.ones(src.size)
            adj = csr_matrix((data, (src, dst)), shape=(self.n, self.n))
            adj.sort_indices()
            self._csr[weighted] = adj
        return self._csr[weighted]


def symmetrize(n: int, src, dst):
    """GAP's undirected build: drop self loops, keep each unordered pair
    once.  Returns the pairs ``(u, v)`` with ``u < v``, sorted."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def build(cfg: dict) -> Graph:
    """The configuration's graph: topology and weights from its
    ``graph_seed``."""
    gen = load_module("gen", cfg["generator"])
    seed = int(cfg["graph_seed"])
    n, src, dst = gen.edges(cfg, rng(seed, 0))
    u, v = symmetrize(n, src, dst)
    lo, hi = cfg["weights"]
    w = rng(seed, 1).integers(lo, hi + 1, u.size).astype(np.float32)
    return Graph(n=n, u=u, v=v, w=w)


def roots(g: Graph, count: int, seed: int):
    """GAP's source picker: distinct roots uniform among vertices of nonzero
    degree.  Returns ``count`` roots for the window and one warm-up root that
    is not among them."""
    live = np.flatnonzero(g.degree() > 0)
    pick = rng(seed, 2).choice(live, size=min(count + 1, live.size),
                               replace=False)
    return [int(r) for r in pick[:-1]], int(pick[-1])


def order(roots_: list, seed: int) -> list:
    """The roots in the order that the run ``seed`` draws."""
    return [roots_[i] for i in rng(seed, 3).permutation(len(roots_))]


def components(g: Graph):
    """Connected components: per vertex its component, and per component
    its vertices and edges (each undirected edge once) — what GAP counts as
    the traversed edges of a query from a root in that component."""
    from scipy.sparse.csgraph import connected_components
    _k, label = connected_components(g.csr(weighted=False), directed=False)
    vertices = np.bincount(label)
    edges = np.bincount(label[g.u], minlength=vertices.size)
    return label, vertices, edges
