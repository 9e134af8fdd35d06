"""GAP SSSP: exact shortest-path distances from the root.

The program answers through ``usecases.sssp`` in float32; the weights are
integers in [1, 255], so every distance below 2**24 is exact and the answer
is compared exactly.  An unreached vertex is at infinity or at the engine's
bottom (``>= 1e8``).  The reference is scipy's Dijkstra in float64.
"""
import numpy as np

BOTTOM = 1e8
ARC_WORDS = 2           # per traversed arc: neighbour id and weight (8 B)
STATE_WORDS = 1         # per reached vertex: its distance


def spec(usecases, root: int):
    return usecases.sssp(root)


def reference(g, root: int) -> np.ndarray:
    from scipy.sparse.csgraph import dijkstra
    return dijkstra(g.csr(weighted=True), directed=True, indices=root)


def control(g):
    """The reference computed one precision below the configuration's
    float32: Bellman-Ford with bfloat16 distances and weights, in jnp."""
    import jax
    import jax.numpy as jnp
    src, dst, w = g.arcs()
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    w = jnp.asarray(w, jnp.bfloat16)
    n = g.n

    @jax.jit
    def solve(root):
        d0 = jnp.full(n, jnp.inf, jnp.bfloat16).at[root].set(0)

        def step(carry):
            d, _ = carry
            cand = jax.ops.segment_min(d[src] + w, dst, num_segments=n)
            nd = jnp.minimum(d, cand)
            return nd, jnp.any(nd != d)

        return jax.lax.while_loop(lambda c: c[1], step,
                                  (d0, jnp.asarray(True)))[0]

    return lambda root: np.asarray(solve(root).astype(jnp.float32))


def normalize(answer) -> np.ndarray:
    a = np.asarray(answer).astype(np.float64)
    return np.where(np.isfinite(a) & (a < BOTTOM), a, np.inf)


def mismatches(answer, ref) -> int:
    """Vertices whose distance differs from the reference's (exact)."""
    return int(np.count_nonzero(normalize(answer) != ref))


def reached(ref) -> np.ndarray:
    return np.isfinite(ref)
