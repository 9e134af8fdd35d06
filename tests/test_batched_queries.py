"""Source-parameterized batched executors (DESIGN.md §8/§9).

The contract under test: the query source is a *traced argument* of the
compiled pallas executor — never a closure constant — so

* one ``_EXEC_CACHE`` entry (and zero re-traces) serves a sweep over many
  distinct sources of the same query shape,
* ``jax.vmap``-batched runs over a batch of sources are BIT-identical to
  the per-source sequential runs, under pull, push and auto directions,
* ``run_direct(engine="pallas")`` defaults to the documented per-iteration
  direction heuristic (regression: ``pull_like`` used to pin push),
* the executor cache is a true LRU (hits refresh recency),
* the ``grafs.synthesize`` span times synthesis (cold > warm ≈ 0).
"""
import jax
import numpy as np
import pytest

from repro.core import engine, fusion
from repro.core import usecases as U
from repro.graph.structure import line_graph, rmat_graph
from repro.kernels import edge_reduce as er
from repro.kernels import ops as kops

BATCHABLE = {"BFS": U.bfs, "SSSP": U.sssp, "WP": U.wp}


def _cold():
    engine.clear_program_caches()
    er.reset_sweep_stats()


def _sources(g, k, seed):
    rng = np.random.default_rng(seed)
    return [int(s) for s in rng.choice(g.n, size=min(k, g.n), replace=False)]


# ---------------------------------------------------------------------------
# batched ≡ sequential, bit-for-bit, all directions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(BATCHABLE))
@pytest.mark.parametrize("model", [None, "pull", "push"])
def test_batched_matches_sequential_bitwise(name, model, small_graphs):
    """vmap-batched fixpoints must agree with per-source sequential runs
    bit-for-bit: the while_loop batching rule freezes converged queries via
    per-element carry selects, and the direction lax.cond lowers to a
    per-query select of identically-computed branch values."""
    g = small_graphs["rmat"]
    srcs = _sources(g, 6, seed=11)
    prog = fusion.fuse(BATCHABLE[name](srcs[0]))
    seq = [np.asarray(engine.run_program(g, prog, engine="pallas",
                                         model=model, source=s).value)
           for s in srcs]
    batch = engine.run_program_batch(g, prog, sources=srcs, engine="pallas",
                                     model=model)
    for s, got, want in zip(srcs, batch, seq):
        np.testing.assert_array_equal(np.asarray(got.value), want,
                                      err_msg=f"{name} model={model} src={s}")


def test_batched_direction_switch_bitwise():
    """Auto direction on a graph whose BFS frontier goes sparse: some
    queries take push iterations, and the batched select-of-both-branches
    still reproduces the sequential runs exactly."""
    g = line_graph(48, weighted=True, seed=3)
    prog = fusion.fuse(U.bfs_depth(0))
    srcs = [0, 7, 23, 40]
    seq = [engine.run_program(g, prog, engine="pallas", source=s)
           for s in srcs]
    assert any(r.stats.push_iters > 0 for r in seq)   # heuristic does switch
    batch = engine.run_program_batch(g, prog, sources=srcs, engine="pallas")
    for s, got, want in zip(srcs, batch, seq):
        np.testing.assert_array_equal(np.asarray(got.value),
                                      np.asarray(want.value),
                                      err_msg=f"src={s}")
        assert got.stats.iterations == want.stats.iterations
        assert got.stats.push_iters == want.stats.push_iters


@pytest.mark.parametrize("resolution", ["sorted", "scatter"])
def test_batched_matches_sequential_both_resolutions(resolution, small_graphs):
    """The dst-sorted push resolution composes with the vmapped executors:
    each resolution path's batched run is bit-identical to its own
    sequential runs AND the two paths agree bit-for-bit on the batch."""
    g = small_graphs["rmat"]
    srcs = _sources(g, 5, seed=13)
    prog = fusion.fuse(U.bfs(srcs[0]))
    seq = [np.asarray(engine.run_program(
        g, prog, engine="pallas", model="push", source=s,
        push_resolution=resolution).value) for s in srcs]
    batch = engine.run_program_batch(g, prog, sources=srcs, engine="pallas",
                                     model="push",
                                     push_resolution=resolution)
    other = engine.run_program_batch(g, prog, sources=srcs, engine="pallas",
                                     model="push",
                                     push_resolution=("scatter" if resolution
                                                      == "sorted" else
                                                      "sorted"))
    for s, got, alt, want in zip(srcs, batch, other, seq):
        np.testing.assert_array_equal(np.asarray(got.value), want,
                                      err_msg=f"src={s} {resolution}")
        np.testing.assert_array_equal(np.asarray(got.value),
                                      np.asarray(alt.value),
                                      err_msg=f"src={s} cross-resolution")
        assert got.stats.resolve_work > 0


def test_batched_reports_per_query_resolve_work(small_graphs):
    """Batched stats carry per-query resolution work, matching the
    sequential runs exactly (deterministic tile counts)."""
    g = small_graphs["rmat"]
    srcs = _sources(g, 4, seed=3)
    prog = fusion.fuse(U.sssp(srcs[0]))
    batch = engine.run_program_batch(g, prog, sources=srcs, engine="pallas")
    for s, got in zip(srcs, batch):
        want = engine.run_program(g, prog, engine="pallas", source=s)
        assert got.stats.resolve_work == want.stats.resolve_work
        assert got.stats.push_iters == want.stats.push_iters


def test_batched_matches_reference_engines(small_graphs):
    """The batched pallas path agrees with the pull reference engine (which
    run_program_batch uses as its sequential fallback) across sources."""
    g = small_graphs["uniform2"]
    srcs = _sources(g, 5, seed=2)
    prog = fusion.fuse(U.sssp(0))
    ref = engine.run_program_batch(g, prog, sources=srcs, engine="pull")
    got = engine.run_program_batch(g, prog, sources=srcs, engine="pallas")
    for s, a, b in zip(srcs, ref, got):
        np.testing.assert_allclose(np.asarray(a.value, np.float64),
                                   np.asarray(b.value, np.float64),
                                   atol=1e-5, err_msg=f"src={s}")


def test_run_direct_batched_matches_sequential(small_graphs):
    g = small_graphs["rmat"]
    dk = U.handwritten_sssp(0)
    srcs = _sources(g, 5, seed=7)
    batch = engine.run_direct(g, dk, engine="pallas", sources=srcs)
    for s, got in zip(srcs, batch):
        want = engine.run_direct(g, dk, engine="pallas", source=s)
        np.testing.assert_array_equal(np.asarray(got.value),
                                      np.asarray(want.value))
        assert got.stats.iterations == want.stats.iterations


def test_run_direct_source_override_needs_generic_kernels(small_graphs):
    from repro.core.synthesis import pagerank_kernels
    dk = pagerank_kernels(small_graphs["rmat"].n)      # sourceless
    with pytest.raises(ValueError, match="source-generic"):
        engine.run_direct(small_graphs["rmat"], dk, engine="pallas",
                          sources=[0, 1])


def test_run_direct_rejects_source_with_legacy_init(small_graphs):
    """A legacy 1-arg init closure bakes its source; pairing it with the
    ``source`` field would let an override move the ⊥-mask without moving
    the init value — must raise, never silently corrupt."""
    import jax.numpy as jnp
    from repro.core.synthesis import DirectKernels
    dk = DirectKernels(
        name="sssp", rop="min", dtype="float",
        p_fn=lambda env: env["n"] + env["w"],
        init_fn=lambda v: jnp.where(v == 3, 0.0, jnp.inf),   # baked source
        source=3)
    for kwargs in ({}, {"source": 5}, {"sources": [1, 2]}):
        with pytest.raises(ValueError, match="source-generic init_fn"):
            engine.run_direct(small_graphs["rmat"], dk, engine="pull",
                              **kwargs)


def test_run_program_batch_rejects_2d_sources(small_graphs):
    """[B, n_comps] per-component batching is the kernels-layer API; the
    engine wrapper takes a flat [B] source vector and must not silently
    flatten a 2-D array into B*n_comps separate queries."""
    prog = fusion.fuse(U.sssp(0))
    with pytest.raises(ValueError, match=r"\[B\] vector"):
        engine.run_program_batch(small_graphs["rmat"], prog,
                                 sources=np.array([[0, 1], [2, 3]]))


# ---------------------------------------------------------------------------
# cache stability: one executor, zero re-traces, across distinct sources
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["BFS", "SSSP"])
def test_executor_cache_stable_across_32_sources(name):
    """32 distinct sources of one query shape: exactly ONE executor cache
    entry, and the trace-time launch counters stop moving after the first
    query (zero re-traces — the bug this PR fixes gave one entry and one
    full while_loop retrace PER source).  The acceptance criterion of the
    source-parameterized executors, verbatim."""
    g = rmat_graph(64, 256, seed=9)
    _cold()
    results = {}
    for i, s in enumerate(_sources(g, 32, seed=5)):
        prog = fusion.fuse(BATCHABLE[name](s))         # fresh spec per source
        results[s] = engine.run_program(g, prog, engine="pallas")
        if i == 0:
            launches = er.SWEEP_STATS["launches"]
    assert len(results) == 32
    assert engine.program_cache_stats()["pallas_executors"] == 1
    assert er.SWEEP_STATS["launches"] == launches
    # sanity: different sources really produce different answers
    vals = [np.asarray(r.value) for r in results.values()]
    assert any(not np.array_equal(vals[0], v) for v in vals[1:])


def test_batched_run_adds_one_executor_entry(small_graphs):
    """A batched sweep compiles its own (vmapped) executor — one entry for
    ANY batch size, alongside the sequential entry."""
    g = small_graphs["rmat"]
    prog = fusion.fuse(U.sssp(0))
    _cold()
    engine.run_program_batch(g, prog, sources=[0, 1, 2], engine="pallas")
    assert engine.program_cache_stats()["pallas_executors"] == 1
    engine.run_program_batch(g, prog, sources=[3, 4, 5, 6], engine="pallas")
    assert engine.program_cache_stats()["pallas_executors"] == 1
    engine.run_program(g, prog, engine="pallas", source=7)
    assert engine.program_cache_stats()["pallas_executors"] == 2


def test_round_cache_source_free(small_graphs):
    """synthesize_round memoizes across sources too: the synthesized closure
    set (and hence the executor key) is shared by BFS(0) and BFS(5)."""
    _cold()
    for s in (0, 3, 5):
        engine.run_program(small_graphs["rmat"],
                           fusion.fuse(U.bfs_depth(s)), engine="pallas")
    assert engine.program_cache_stats()["synth_rounds"] == 1


# ---------------------------------------------------------------------------
# run_direct pallas direction regression
# ---------------------------------------------------------------------------

def test_run_direct_pallas_auto_direction():
    """Regression (engine.py pull_like omitted "pallas"): run_direct on the
    pallas engine must default to the per-iteration direction heuristic —
    on a sparse-frontier BFS both directions execute (dense first wave →
    pull, sparse tail → push), not push-pinned for every iteration."""
    g = line_graph(48, weighted=True, seed=3)
    dk = U.handwritten_bfs_depth(0)
    _cold()
    res = engine.run_direct(g, dk, engine="pallas")
    assert res.stats.pull_iters > 0, "auto must pull on the dense first wave"
    assert res.stats.push_iters > 0, "auto must push on the sparse tail"
    assert res.stats.pull_iters + res.stats.push_iters == res.stats.iterations
    # both traced branches present: pull and push sweeps in one executor
    assert er.SWEEP_STATS["pull_launches"] == 1
    assert er.SWEEP_STATS["push_launches"] == 1
    want = engine.run_direct(g, dk, engine="pull")
    np.testing.assert_array_equal(np.asarray(res.value),
                                  np.asarray(want.value))


def test_run_direct_model_forces_direction(small_graphs):
    """An explicit model pins the sweep (one traced launch per direction)."""
    g = small_graphs["rmat"]
    dk = U.handwritten_sssp(0)
    for model, counter in (("pull", "pull_launches"),
                           ("push", "push_launches")):
        _cold()
        res = engine.run_direct(g, dk, engine="pallas", model=model)
        assert er.SWEEP_STATS["launches"] == 1
        assert er.SWEEP_STATS[counter] == 1
        want = engine.run_direct(g, dk, engine="pull")
        np.testing.assert_array_equal(np.asarray(res.value),
                                      np.asarray(want.value))


# ---------------------------------------------------------------------------
# LRU cache behaviour + the synthesis span
# ---------------------------------------------------------------------------

def test_exec_cache_is_lru(small_graphs, monkeypatch):
    """Hits refresh recency: with capacity 2, re-touching the oldest entry
    before inserting a third must evict the *untouched* entry (FIFO would
    evict the hot one — the serving-churn bug)."""
    g = small_graphs["rmat"]
    _cold()
    monkeypatch.setattr(kops, "_EXEC_CACHE_MAX", 2)
    progs = {n: fusion.fuse(BATCHABLE[n](0)) for n in ("SSSP", "WP", "BFS")}
    engine.run_program(g, progs["SSSP"], engine="pallas")
    engine.run_program(g, progs["WP"], engine="pallas")
    assert kops.executor_cache_size() == 2
    engine.run_program(g, progs["SSSP"], engine="pallas")   # touch: SSSP hot
    launches = er.SWEEP_STATS["launches"]
    engine.run_program(g, progs["BFS"], engine="pallas")    # evicts WP
    assert kops.executor_cache_size() == 2
    engine.run_program(g, progs["SSSP"], engine="pallas")   # still cached:
    assert er.SWEEP_STATS["launches"] > launches            # (BFS traced)
    launches = er.SWEEP_STATS["launches"]
    engine.run_program(g, progs["SSSP"], engine="pallas")
    assert er.SWEEP_STATS["launches"] == launches           # no re-trace


def test_exec_cache_pins_keyed_closures(small_graphs):
    """Cache values hold strong references to the kernel closures whose ids
    the key carries, so id() reuse after GC can never alias an entry."""
    g = small_graphs["rmat"]
    _cold()
    engine.run_program(g, fusion.fuse(U.sssp(0)), engine="pallas")
    ((key, (run, keyed)),) = list(kops._EXEC_CACHE.items())
    pinned = {id(f) for fns in keyed for f in fns if f is not None}
    assert pinned, "executor entry pins no closures"

    def flat(t):
        for x in t:
            if isinstance(x, tuple):
                yield from flat(x)
            else:
                yield x

    key_ints = {x for x in flat(key) if isinstance(x, int)}
    assert pinned <= key_ints, "a keyed closure id is missing from the key"


def test_synth_ms_populated(small_graphs):
    """The ``grafs.synthesize`` span times synthesis: a cold run's is the
    enumerative search, a warm (round-cache hit) run's ~0."""
    g = small_graphs["rmat"]
    _cold()
    spans = []

    def listen(event, start, end, **_kw):
        if event == "/grafs/grafs.synthesize":
            spans.append(1e3 * (end - start))

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        cold = engine.run_program(g, fusion.fuse(U.wsp(0)), engine="pallas")
        cold_ms = spans[:]
        warm = engine.run_program(g, fusion.fuse(U.wsp(0)), engine="pallas")
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    warm_ms = spans[len(cold_ms):]
    assert len(cold_ms) == len(warm_ms) == 1
    assert cold_ms[0] > warm_ms[0]
    assert warm_ms[0] < 50.0               # memo hit: microseconds, not a
    np.testing.assert_array_equal(         # fresh enumerative search
        np.asarray(cold.value), np.asarray(warm.value))
