"""Push ≡ pull ≡ reference across randomized graphs (DESIGN.md §2).

The direction-optimized pallas engine is only sound if every direction of
every admissible round computes the same fixpoint.  The tests below drive
randomized graphs (seeded parametrized samples always; hypothesis fuzzing
on top when available) through

  * the pallas push sweep (``model="push"``: Defs. 3/4 on the out-edge
    blocked layout),
  * the pallas pull sweep (``model="pull"``: Defs. 1/2 on the in-edge
    layout),
  * the direction-optimized default (per-iteration heuristic switch),
  * the segment-op pull/push engines (``iterate.iterate_graph``), and
  * the ``kernels/ref.py`` oracle at the single-sweep level,

and require agreement through ``conftest.norm_inf`` for BFS / SSSP / WCC
(idempotent, frontier-masked + models) plus one non-idempotent round (NSP's
count-of-shortest-paths sum ⇒ the − full-recompute models with the
has-pred probe).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import norm_inf
from repro.core import engine, fusion
from repro.core import usecases as U
from repro.graph import segment
from repro.graph.structure import to_blocked_ell, undirected, uniform_graph
from repro.kernels import edge_reduce as er
from repro.kernels import ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # container without the test extra:
    HAVE_HYPOTHESIS = False               # the seeded samples below still run

# seeded (n, edge-density, seed) samples — deterministic "randomized graphs"
SAMPLES = [(7, 1.2, 101), (10, 2.0, 202), (13, 2.8, 303),
           (16, 1.6, 404), (19, 2.4, 505), (24, 3.0, 606)]

IDEMPOTENT = ["BFS", "SSSP", "CC"]        # CC == WCC on the symmetrized graph


def _rand_graph(n, density, seed, symmetric=False):
    g = uniform_graph(n, max(1, int(density * n)), seed=seed)
    return undirected(g) if symmetric else g


def _value(g, name, eng, model=None, **kw):
    prog = fusion.fuse(U.ALL_SPECS[name]())
    return engine.run_program(g, prog, engine=eng, model=model, **kw).value


def _assert_directions_agree_idempotent(name, n, density, seed):
    g = _rand_graph(n, density, seed, symmetric=(name == "CC"))
    want = norm_inf(_value(g, name, "pull"))
    resolutions = {}
    for eng, model, resolution in (
            ("push", None, None), ("pallas", "pull", None),
            ("pallas", "push", "sorted"), ("pallas", "push", "scatter"),
            ("pallas", None, "sorted"), ("pallas", None, "scatter")):
        raw = _value(g, name, eng, model=model,
                     **({} if resolution is None else
                        {"push_resolution": resolution}))
        got = norm_inf(raw)
        np.testing.assert_allclose(got, want, atol=1e-4,
                                   err_msg=f"{name} {eng}/{model}")
        # the two resolution paths of one (engine, model) must agree
        # bit-for-bit, not just through norm_inf
        if resolution is not None:
            other = resolutions.setdefault((eng, model), np.asarray(raw))
            np.testing.assert_array_equal(
                np.asarray(raw), other,
                err_msg=f"{name} {model}: sorted != scatter bitwise")


def _assert_directions_agree_nonidempotent(n, density, seed):
    """NSP fuses a min-lex primary with a non-idempotent sum secondary ⇒
    the engines run the − (full recompute) models with the has-pred probe:
    pallas pull− and forced push− (both resolution paths) must all match
    the pull engine."""
    g = _rand_graph(n, density, seed)
    want = norm_inf(_value(g, "NSP", "pull"))
    for eng, model, kw in (("pallas", None, {}),
                           ("pallas", "push", {"push_resolution": "sorted"}),
                           ("pallas", "push", {"push_resolution": "scatter"})):
        got = norm_inf(_value(g, "NSP", eng, model=model, **kw))
        np.testing.assert_allclose(got, want, atol=1e-4,
                                   err_msg=f"NSP {eng}/{model}")


def _assert_push_sweep_matches_ref(n, density, seed, frontier):
    """One frontier-masked min sweep: ``fused_ell_push_sweep`` over the
    out-edge layout must equal ``ref.ref_edge_level`` over the in-edge
    layout bit-for-bit (both reduce the same logical edge set)."""
    g = _rand_graph(n, density, seed)
    ell_in = to_blocked_ell(g)
    ell_out = to_blocked_ell(g, direction="out")
    rng = np.random.default_rng(seed)
    n_pad = ell_in.n_pad
    state = jnp.asarray(rng.integers(1, 9, n_pad).astype(np.float32))
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.asarray((rng.random(n_pad) < frontier).astype(np.int32))
    outdeg = jnp.ones(n_pad, jnp.float32)

    # oracle: pull-layout gather with frontier-inactive sources masked to ⊥
    masked_state = jnp.where(active != 0, state, ident)
    want = ref.ref_edge_level(
        "min", masked_state, ell_in.srcs, ell_in.mask,
        lambda nvals, srcs: nvals + ell_in.weight, ident, ident)

    tile_act = er.tile_activity_push(ell_out.tile_nnz, active, ell_out.block_v)
    got, _ = er.fused_ell_push_sweep(
        ell_out.nbrs, ell_out.weight, ell_out.capacity, ell_out.mask,
        tile_act, {0: state}, active, outdeg,
        plans=(((0, "min"),),), idents={0: ident},
        p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))


# ---------------------------------------------------------------------------
# seeded parametrized samples (always run, no optional deps)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", IDEMPOTENT)
@pytest.mark.parametrize("n,density,seed", SAMPLES[:3])
def test_push_pull_auto_agree_idempotent(name, n, density, seed):
    _assert_directions_agree_idempotent(name, n, density, seed)


@pytest.mark.parametrize("n,density,seed", SAMPLES[:3])
def test_push_pull_agree_nonidempotent_round(n, density, seed):
    _assert_directions_agree_nonidempotent(n, density, seed)


@pytest.mark.parametrize("n,density,seed", SAMPLES)
@pytest.mark.parametrize("frontier", [0.1, 0.6, 1.0])
def test_push_sweep_matches_ref_oracle(n, density, seed, frontier):
    _assert_push_sweep_matches_ref(n, density, seed, frontier)


def test_push_sweep_skipped_row_tiles_emit_identities():
    """Row tiles with no frontier-active source must short-circuit and emit
    the reduction identities bit-for-bit (pl.when path, C6)."""
    g = uniform_graph(48, 300, seed=9)
    ell = to_blocked_ell(g, direction="out")
    rng = np.random.default_rng(9)
    state = jnp.asarray(rng.uniform(1, 9, ell.n_pad).astype(np.float32))
    ident = float(segment.identity("min", jnp.float32))
    active = jnp.zeros(ell.n_pad, jnp.int32)   # nothing active anywhere
    tile_act = er.tile_activity_push(ell.tile_nnz, active, ell.block_v)
    assert not np.asarray(tile_act).any()
    red, _, cands = er.fused_ell_push_sweep(
        ell.nbrs, ell.weight, ell.capacity, ell.mask, tile_act, {0: state},
        active, jnp.ones(ell.n_pad, jnp.float32),
        plans=(((0, "min"),),), idents={0: ident},
        p_fns={0: lambda env: env["n"] + env["w"]}, nv=g.n,
        return_candidates=True)
    assert np.all(np.asarray(cands[0]) == np.float32(ident))
    assert np.all(np.asarray(red[0]) == np.float32(ident))


@pytest.mark.parametrize("name,model,resolution", [
    ("BFS", None, "sorted"), ("SSSP", None, "sorted"),
    ("SSSP", "push", "scatter"), ("NSP", "push", "sorted"),
    ("PR", None, "sorted")])
def test_live_grid_fixpoint_matches_full_grid(name, model, resolution,
                                              monkeypatch):
    """Whole fixpoints through ``run_program`` on a layout whose tiles are
    mostly dead, with 128-row groups that have no live tile: over the
    layouts' live-tile lists and over the full grids they answer bit for
    bit alike, in as many iterations, in each direction the planner picks
    (and the forced push− full recompute of NSP)."""
    from conftest import skewed_graph
    g = skewed_graph()
    prog = fusion.fuse(U.pagerank() if name == "PR" else U.ALL_SPECS[name]())
    kw = dict(engine="pallas", model=model, push_resolution=resolution,
              fallback=False)
    got = engine.run_program(g, prog, **kw)
    engine.clear_program_caches()
    monkeypatch.setattr(er, "live_grid", lambda live, n_tiles: None)
    want = engine.run_program(g, prog, **kw)
    assert np.asarray(got.value).tobytes() == \
        np.asarray(want.value).tobytes()
    assert got.stats.iterations == want.stats.iterations
    assert got.stats.push_iters == want.stats.push_iters
    assert 0 < got.stats.grid_steps < want.stats.grid_steps


def test_direction_optimized_does_less_work_on_sparse_frontier():
    """The tentpole claim at engine level: on a power-law BFS the adaptive
    pallas engine's total edge work is ≤ the pull-only engine's, with at
    least one iteration actually taking the push direction."""
    from repro.graph.structure import rmat_graph
    from repro.kernels import edge_reduce as er
    g = rmat_graph(256, 2048, seed=17)
    prog = fusion.fuse(U.ALL_SPECS["BFS"]())
    engine.clear_program_caches()
    er.reset_sweep_stats()
    auto = engine.run_program(g, prog, engine="pallas")
    pushed = auto.stats.push_iters
    engine.clear_program_caches()
    pull = engine.run_program(g, prog, engine="pallas", model="pull")
    assert pushed >= 1
    assert auto.stats.edge_work <= pull.stats.edge_work
    np.testing.assert_allclose(norm_inf(auto.value), norm_inf(pull.value),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# hypothesis fuzz layer (runs wherever the test extra is installed, e.g. CI)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=6, deadline=None)
    @given(name=st.sampled_from(IDEMPOTENT), n=st.integers(6, 20),
           density=st.floats(1.0, 3.0), seed=st.integers(0, 10_000))
    @pytest.mark.slow
    def test_push_pull_fuzz_idempotent(name, n, density, seed):
        _assert_directions_agree_idempotent(name, n, density, seed)

    @settings(max_examples=6, deadline=None)
    @given(n=st.integers(6, 16), density=st.floats(1.0, 2.5),
           seed=st.integers(0, 10_000))
    @pytest.mark.slow
    def test_push_pull_fuzz_nonidempotent(n, density, seed):
        _assert_directions_agree_nonidempotent(n, density, seed)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(8, 48), density=st.floats(1.0, 6.0),
           seed=st.integers(0, 10_000), frontier=st.floats(0.05, 1.0))
    def test_push_sweep_fuzz_matches_ref_oracle(n, density, seed, frontier):
        _assert_push_sweep_matches_ref(n, density, seed, frontier)
