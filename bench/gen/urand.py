"""GAP's uniform random generator.

A vectorized copy of ``MakeUniformEL`` in the GAP Benchmark Suite's
``generator.h``: ``edge_factor * 2**scale`` edges whose two endpoints are
drawn independently and uniformly from all ``2**scale`` vertices.
"""
import numpy as np


def edges(cfg: dict, rng: np.random.Generator):
    """Directed edge draws ``(n, src, dst)`` for one uniform random graph."""
    n = 1 << int(cfg["scale"])
    m = int(cfg["edge_factor"]) * n
    return n, rng.integers(0, n, m), rng.integers(0, n, m)
