"""GAP's Kronecker generator (Graph500 R-MAT quadrant descent).

A vectorized copy of ``MakeRMatEL`` in the GAP Benchmark Suite's
``generator.h``: ``edge_factor * 2**scale`` draws, each descending ``scale``
levels of the adjacency matrix with quadrant probabilities A, B, C (and
1-A-B-C), then every vertex id passed through one random permutation.
"""
import numpy as np


def edges(cfg: dict, rng: np.random.Generator):
    """Directed edge draws ``(n, src, dst)`` for one Kronecker graph."""
    scale = int(cfg["scale"])
    n = 1 << scale
    m = int(cfg["edge_factor"]) * n
    a, b, c = float(cfg["A"]), float(cfg["B"]), float(cfg["C"])
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        r = rng.random(m, dtype=np.float32)
        right = r >= np.float32(a + b)            # lower half: src bit set
        src = (src << 1) | right
        dst = (dst << 1) | np.where(right, r > np.float32(a + b + c),
                                    r > np.float32(a))
    perm = rng.permutation(n)
    return n, perm[src], perm[dst]
