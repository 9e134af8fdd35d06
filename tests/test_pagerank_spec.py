"""PageRank as a spec: ``usecases.pagerank`` fuses, synthesizes and runs on
the ``pallas`` engine (interpreted here) and on the ``pull`` reference
engine, and both agree with a plain float64 Jacobi under GAP's L1 stopping
rule.

Tolerances.  The spec and the float64 reference run the same number of
Jacobi steps, so they differ by float32 rounding alone: under 1e-6
relative on these graphs (a few hundred vertices, degrees under 100), so
``RTOL`` = 1e-5 leaves ten times that.  One step more or fewer moves
vertices by 1e-4 relative or more here, so an off-by-one stop fails it.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import engine, fusion, lang, synthesis, usecases
from repro.graph import structure

D = 0.85
TOL = 1e-4
RTOL = 1e-5


def _jacobi(g, steps=None, tol=TOL, norm="l1", d=D):
    """Plain float64 Jacobi from (1−d)/|V| on the graph's edge list:
    ``steps`` exactly, or until the change (L1, or "linf") is under
    ``tol``.  Returns (scores, steps, per-step changes)."""
    src, dst, _w, _c = g.host_edges()
    deg = np.bincount(src, minlength=g.n).astype(np.float64)
    x = np.full(g.n, (1.0 - d) / g.n)
    changes = []
    for k in range(1, (steps or 100_000) + 1):
        total = np.bincount(dst, weights=x[src] / deg[src], minlength=g.n)
        nxt = (1.0 - d) / g.n + d * total
        diff = np.abs(nxt - x)
        changes.append(diff.sum() if norm == "l1" else diff.max())
        x = nxt
        if steps is None and changes[-1] < tol:
            break
    return x, k, changes


def _kron(seed):
    return structure.undirected(structure.rmat_graph(256, 1500, seed=seed))


def _run(g, eng, spec=None):
    res = engine.run_program(g, fusion.fuse(spec or usecases.pagerank(D, TOL)),
                             engine=eng, fallback=False)
    return np.asarray(res.value, np.float64), res.stats


@pytest.mark.parametrize("eng", ["pull", "pallas"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pagerank_spec_matches_float64_jacobi(seed, eng):
    g = _kron(seed)
    ref, k_ref, changes = _jacobi(g)
    got, st = _run(g, eng)
    assert st.engine_used == eng and st.iterations == k_ref
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=0)
    # the first iteration under the rule: the one before it was not
    assert changes[-1] < TOL <= changes[-2]
    assert st.exit_change == pytest.approx(changes[-1], rel=1e-3)


def test_l1_rule_not_linf():
    """On 400 vertices the per-vertex changes fall under 1e-4 long before
    their sum does: the spec runs to the L1 rule's iteration, not to the
    per-vertex (L∞) rule's, on both engines."""
    g = structure.undirected(structure.uniform_graph(400, 1600, seed=5))
    _x, k_l1, _ = _jacobi(g)
    _x, k_inf, _ = _jacobi(g, norm="linf")
    assert k_inf < k_l1
    for eng in ("pull", "pallas"):
        _got, st = _run(g, eng)
        assert st.iterations == k_l1
        assert st.converged and st.exit_change < TOL


def test_pagerank_spec_matches_handwritten_kernels():
    """The handwritten Fig. 4b kernels, started from the same vector and run
    for the spec's iteration count, give the spec's answer (they differ in
    the order of float32 operations only)."""
    g = _kron(3)
    got, st = _run(g, "pallas")
    dk = dataclasses.replace(
        synthesis.pagerank_kernels(g.n, D),
        init_fn=lambda v: v * 0 + (1.0 - D) / g.n, tol=0.0,
        max_iter=st.iterations)
    hand = engine.run_direct(g, dk, engine="pallas", on_nonconverge="ignore")
    assert hand.stats.iterations == st.iterations
    np.testing.assert_allclose(np.asarray(hand.value, np.float64), got,
                               rtol=RTOL, atol=0)


@pytest.mark.parametrize("k", [0, 1, 2, 3, 5])
def test_paths_semantics_is_truncated_jacobi(k):
    """The oracle's walk sum over walks of length ≤ k is the k-th Jacobi
    iterate, on a 7-vertex graph with a cycle, a hub and a dangling vertex."""
    src = np.array([0, 1, 2, 0, 3, 3, 3, 4, 5, 1], np.int32)
    dst = np.array([1, 2, 0, 3, 4, 5, 1, 3, 3, 6], np.int32)
    g = structure.from_edges(7, src, dst)
    want = _jacobi(g, steps=k)[0] if k else np.full(7, (1.0 - D) / 7)
    got = lang.paths_semantics(usecases.pagerank(D, TOL), g, max_len=k)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-12, atol=0)


def test_pagerank_synthesis_and_plan():
    """I, P and E come out of the grammar; the sum contracts by d in L1;
    the round plans the pull− recompute and sweeps every live tile."""
    prog = fusion.fuse(usecases.pagerank(D, TOL))
    (_name, round_), = prog.rounds
    (comp,) = round_.components
    assert comp.source is None and comp.tol == TOL
    sk = synthesis.synthesize_round(round_)[("kernels", 0)]
    assert str(sk.i_expr) == "((1 - 0.85) / nv)"
    assert str(sk.p_expr) == "(n * (0.85 / outdeg))"
    assert str(sk.e_expr) == "(n + ((1 - 0.85) / nv))"
    assert sk.terminating and not sk.idempotent
    assert synthesis.C.contraction_factor(sk.p_expr) == pytest.approx(D)
    g = _kron(4)
    _got, st = _run(g, "pallas")
    assert st.plan.direction == "auto" and st.push_iters == 0
    assert st.pull_iters == st.iterations
    assert st.tile_shape == (8, 128)
    live = int(np.count_nonzero(np.asarray(structure.blocked_ell_cached(
        g, direction="in").tile_nnz)))
    assert st.tiles_swept == live * st.iterations
    assert st.edge_work == g.num_edges * st.iterations


def test_damping_and_tolerance_are_part_of_the_program():
    """Two PageRank specs that differ in damping or tolerance never share
    kernels or a compiled executor."""
    g = _kron(6)
    base, st = _run(g, "pallas")
    lower, st_d = _run(g, "pallas", usecases.pagerank(0.5, TOL))
    looser, st_t = _run(g, "pallas", usecases.pagerank(D, 1e-2))
    assert st_d.iterations < st.iterations
    assert st_t.iterations < st.iterations
    np.testing.assert_allclose(lower, _jacobi(g, d=0.5)[0], rtol=RTOL, atol=0)
    np.testing.assert_allclose(looser, _jacobi(g, tol=1e-2)[0], rtol=RTOL,
                               atol=0)
    assert not np.allclose(base, looser, rtol=RTOL, atol=0)
