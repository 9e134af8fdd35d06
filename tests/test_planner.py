"""ExecutionPlan query planner (DESIGN.md §14).

The contract under test: every knob resolves in ONE place
(``plan_execution``) from cached per-graph statistics with caller kwargs as
hints; default plans reproduce the documented heuristics BITWISE (Gemini
SWITCH_K, dst-sorted resolution, auto direction), so planned execution is
bit-identical to the historical explicit-kwarg paths; identical decisions
hit identical executor-cache entries; and the recorded-stats feedback loop
adapts ``switch_k``/resolution only within bounds, only when opted in.
"""
import numpy as np
import pytest

from repro.core import engine, fusion, plan as P
from repro.core import usecases as U
from repro.graph import structure
from repro.kernels import ops as kops


@pytest.fixture
def g():
    return structure.uniform_graph(16, 48, seed=5, weighted=True)


class _FakeMesh:
    """Planning only reads ``mesh.devices`` (topology) and ``id(mesh)``
    (hint identity), so decision-table tests can model a multi-device mesh
    without forcing host devices."""

    def __init__(self, k):
        self.devices = np.empty((k,), dtype=object)


# ---------------------------------------------------------------------------
# Graph statistics (the planner's input)
# ---------------------------------------------------------------------------

def test_graph_stats_shape_and_skew(small_graphs):
    st_u = structure.graph_stats(small_graphs["uniform"])
    st_r = structure.graph_stats(small_graphs["rmat"])
    assert st_u.n == 9 and 0 < st_u.num_edges <= 18   # generator dedupes
    assert st_u.avg_degree == pytest.approx(st_u.num_edges / st_u.n)
    # R-MAT hubs: max degree further above the mean than a uniform draw
    assert st_r.degree_skew > st_u.degree_skew
    assert st_r.max_out_degree >= st_r.avg_degree
    assert st_u.device_count >= 1 and st_u.backend


def test_graph_stats_weight_range(small_graphs):
    st_w = structure.graph_stats(small_graphs["line"])    # weighted
    g_unw = structure.uniform_graph(9, 18, seed=3, weighted=False)
    st_u = structure.graph_stats(g_unw)
    assert st_w.weighted and st_w.w_min <= st_w.w_max
    assert not st_u.weighted and st_u.w_min == st_u.w_max == 1.0


def test_graph_stats_memoized(g):
    assert structure.graph_stats(g) is structure.graph_stats(g)
    assert engine.program_cache_stats()["graph_stats"] >= 1


# ---------------------------------------------------------------------------
# Decision table: hints, defaults, statistics-driven choices
# ---------------------------------------------------------------------------

def test_default_plan_reproduces_documented_heuristics(g):
    prog = fusion.fuse(U.bfs(0))
    plan = engine.plan_execution(g, prog, engine="pallas")
    assert plan.engine == "pallas"
    assert plan.direction == "auto"
    assert plan.switch_k == P.SWITCH_K == 20.0
    assert plan.dense_threshold == P.DENSE_FRONTIER == 0.05
    assert plan.push_resolution == P.PUSH_RESOLUTION == "sorted"
    assert plan.shard_strategy == "contiguous"
    assert plan.validate and plan.on_nonconverge == "raise"
    assert not plan.fallback and plan.divergence_sentinel


def test_engine_hints_and_defaults(g):
    prog = fusion.fuse(U.bfs(0))
    assert engine.plan_execution(g, prog).engine == "pull"
    assert engine.plan_execution(g, prog, default_engine="pallas").engine \
        == "pallas"
    assert engine.plan_execution(g, prog, engine="adaptive").engine \
        == "adaptive"
    with pytest.raises(ValueError, match="unknown engine"):
        engine.plan_execution(g, prog, engine="gpu_magic")


def test_auto_engine_follows_device_topology(g):
    prog = fusion.fuse(U.bfs(0))
    assert engine.plan_execution(g, prog, engine="auto").engine == "pallas"
    plan = engine.plan_execution(g, prog, engine="auto", mesh=_FakeMesh(4))
    assert plan.engine == "pallas_sharded"
    assert plan.push_resolution == "sorted"      # per-shard sorted stack:
    assert plan.resolution_hint is None          # same default everywhere
    assert engine.plan_execution(g, prog, engine="auto",
                                 mesh=_FakeMesh(1)).engine == "pallas"


def test_sharded_resolution_hints_first_class(g):
    """Resolution is engine-independent now that the sharded engine runs its
    own per-shard sorted stack: "sorted" is accepted (and the default),
    "scatter" pins the reference oracle, junk still raises the shared
    normalizer error."""
    prog = fusion.fuse(U.bfs(0))
    srt = engine.plan_execution(g, prog, engine="pallas_sharded",
                                push_resolution="sorted")
    assert srt.push_resolution == "sorted" and srt.resolution_hint == "sorted"
    sct = engine.plan_execution(g, prog, engine="pallas_sharded",
                                push_resolution="scatter")
    assert sct.push_resolution == "scatter"
    with pytest.raises(ValueError, match="push_resolution must be"):
        engine.plan_execution(g, prog, engine="pallas_sharded",
                              push_resolution="radix")


def test_knob_normalization_single_copy(g):
    prog = fusion.fuse(U.bfs(0))
    assert engine.plan_execution(g, prog, switch_k=None).switch_k is None
    assert engine.plan_execution(g, prog, switch_k=8).switch_k == 8.0
    with pytest.raises(ValueError, match="switch_k must be"):
        engine.plan_execution(g, prog, switch_k="fast")
    with pytest.raises(ValueError, match="switch_k must be > 0"):
        engine.plan_execution(g, prog, switch_k=-1)
    with pytest.raises(ValueError, match="push_resolution must be"):
        engine.plan_execution(g, prog, push_resolution="atomic")
    with pytest.raises(ValueError, match="dense_threshold only governs"):
        engine.plan_execution(g, prog, switch_k=5.0, dense_threshold=0.5)
    with pytest.raises(ValueError, match="on_nonconverge must be"):
        engine.plan_execution(g, prog, on_nonconverge="retry")
    with pytest.raises(ValueError, match="unknown model"):
        engine.plan_execution(g, prog, engine="pallas", model="sideways")
    with pytest.raises(ValueError, match="unknown shard strategy"):
        engine.plan_execution(g, prog, shard_strategy="random")


def test_model_hint_forces_direction(g):
    prog = fusion.fuse(U.bfs(0))
    for model, want in [(None, "auto"), ("pull", "pull"), ("push+", "push")]:
        got = engine.plan_execution(g, prog, engine="pallas", model=model)
        assert got.direction == want
    # reference engines take the model directly; direction stays "auto"
    assert engine.plan_execution(g, prog, engine="pull",
                                 model="pull+").direction == "auto"


def test_program_kind_is_source_free(g):
    k0 = P.program_kind(fusion.fuse(U.bfs(0)))
    k3 = P.program_kind(fusion.fuse(U.bfs(3)))
    ks = P.program_kind(fusion.fuse(U.sssp(0)))
    assert k0 == k3                      # every source shares one identity
    assert k0 != ks                      # distinct shapes stay distinct
    kd = P.program_kind(U.handwritten_sssp(0))
    assert kd[0] == "direct" and kd != k0


# ---------------------------------------------------------------------------
# Determinism + cache identity
# ---------------------------------------------------------------------------

def test_plan_determinism_and_cache_hit(g):
    prog = fusion.fuse(U.bfs(0))
    p1 = engine.plan_execution(g, prog, engine="pallas")
    p2 = engine.plan_execution(g, prog, engine="pallas")
    assert p1 is p2                      # LRU hit: same frozen plan object
    assert engine.program_cache_stats()["plans"] >= 1
    # a different hint is a different plan, same normalized result
    p3 = engine.plan_execution(g, prog, engine="pallas", switch_k=20.0)
    assert p3 is not p1 and p3.switch_k == p1.switch_k


def test_identical_decisions_share_executor_cache_entries(g):
    """The tentpole cache contract: plan-lowered execution and the legacy
    explicit-kwarg kernels API produce THE SAME ``_EXEC_CACHE`` keys, so
    identical decisions never compile twice."""
    prog = fusion.fuse(U.bfs(0))
    engine.run_program(g, prog, engine="pallas")
    n0 = kops.executor_cache_size()
    keys0 = set(kops._EXEC_CACHE)
    # the same round through the legacy kwarg surface: no new entry
    rnd = prog.rounds[0][1]
    comps, plans = engine._round_runtime(rnd, engine.synthesize_round(rnd))
    kops.iterate_pallas(g, comps, plans, direction="auto", switch_k="auto",
                        push_resolution="sorted")
    assert kops.executor_cache_size() == n0
    assert set(kops._EXEC_CACHE) == keys0
    # and re-planning the same query is also a no-op on the cache
    engine.run_program(g, prog, engine="pallas", source=5)
    assert kops.executor_cache_size() == n0


def test_degrade_plan_reresolves_engine_dependent_fields(g):
    prog = fusion.fuse(U.bfs(0))
    sharded = engine.plan_execution(g, prog, engine="pallas_sharded")
    assert sharded.push_resolution == "sorted"   # per-shard sorted default
    down = P.degrade_plan(sharded, "pallas")
    assert down.engine == "pallas"
    assert down.push_resolution == "sorted"   # hintless → sorted default
    assert down.switch_k == sharded.switch_k
    # an explicit caller hint survives the walk down the chain — both ways
    pinned = engine.plan_execution(g, prog, engine="pallas",
                                   push_resolution="scatter")
    assert P.degrade_plan(pinned, "adaptive").push_resolution == "scatter"
    assert P.degrade_plan(pinned, "pallas") is pinned
    pinned_sh = engine.plan_execution(g, prog, engine="pallas_sharded",
                                      push_resolution="scatter")
    assert P.degrade_plan(pinned_sh, "pallas").push_resolution == "scatter"


# ---------------------------------------------------------------------------
# Bitwise parity: planned vs explicit-kwarg execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eng", ["pull", "push", "adaptive", "dense",
                                 "pallas"])
def test_planned_matches_explicit_kwargs_bitwise(eng, small_graphs):
    for spec in (U.bfs(2), U.sssp(2), U.wp(2)):
        prog = fusion.fuse(spec)
        for g in (small_graphs["uniform"], small_graphs["rmat"]):
            got = engine.run_program(g, prog, engine=eng)
            want = engine.run_program(g, prog, engine=eng, model=None,
                                      switch_k=20.0, push_resolution="sorted"
                                      if eng == "pallas" else None)
            a, b = np.asarray(got.value), np.asarray(want.value)
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
            assert got.stats.iterations == want.stats.iterations
            ka, kb = got.stats.plan.knobs(), want.stats.plan.knobs()
            ka.pop("resolution_hint"), kb.pop("resolution_hint")
            assert ka == kb              # raw hints differ; decisions don't


def test_direct_planned_matches_explicit_bitwise(g):
    dk = U.handwritten_sssp(3)
    got = engine.run_direct(g, dk, engine="pallas")
    want = engine.run_direct(g, dk, engine="pallas", switch_k=20.0,
                             push_resolution="sorted")
    a, b = np.asarray(got.value), np.asarray(want.value)
    assert a.tobytes() == b.tobytes()
    assert got.stats.iterations == want.stats.iterations


# ---------------------------------------------------------------------------
# ExecStats.plan + explain mode
# ---------------------------------------------------------------------------

def test_exec_stats_record_plan_on_every_entry_point(g):
    prog = fusion.fuse(U.bfs(0))
    r = engine.run_program(g, prog, engine="pallas")
    assert r.stats.plan.engine == "pallas"
    outs = engine.run_program_batch(g, prog, [0, 2], engine="pallas")
    assert all(o.stats.plan.batch_lane == "vmapped" for o in outs)
    d = engine.run_direct(g, U.handwritten_sssp(0), engine="pull")
    assert d.stats.plan.engine == "pull"
    # every resolved knob is reported by name
    assert set(r.stats.plan.knobs()) >= {
        "engine", "model", "direction", "switch_k", "dense_threshold",
        "push_resolution", "shard_strategy", "axes", "batch_size",
        "batch_lane", "validate", "on_nonconverge", "fallback",
        "divergence_sentinel"}


def test_explain_reports_plan_and_driving_statistics(g):
    prog = fusion.fuse(U.sssp(0))
    before = engine.program_cache_stats()["feedback"]
    ex = engine.run_program(g, prog, engine="pallas", explain=True)
    assert isinstance(ex, P.PlanExplanation)
    assert ex.plan.engine == "pallas"
    assert ex.stats is structure.graph_stats(g)
    for field in ("engine", "direction", "switch_k", "push_resolution",
                  "shard_strategy"):
        assert field in ex.decisions
    # explain never executes: no feedback recorded
    assert engine.program_cache_stats()["feedback"] == before
    exd = engine.run_direct(g, U.handwritten_sssp(0), engine="pull",
                            explain=True)
    assert exd.plan.engine == "pull"
    exb = engine.run_program_batch(g, prog, [0, 1], explain=True)
    assert exb.plan.batch_lane == "vmapped" and exb.plan.batch_size == 2


# ---------------------------------------------------------------------------
# Heterogeneous-batch degradation: an explicit, recorded decision
# ---------------------------------------------------------------------------

def test_sequential_batch_lane_is_recorded(g):
    prog = fusion.fuse(U.bfs(0))
    outs = engine.run_program_batch(g, prog, [0, 3], engine="pull")
    want = [engine.run_program(g, prog, engine="pull", source=s)
            for s in (0, 3)]
    for o, w in zip(outs, want):
        assert np.asarray(o.value).tobytes() == np.asarray(w.value).tobytes()
        assert o.stats.plan.batch_lane == "sequential"
        frm, to, why = o.stats.fallbacks[0]
        assert frm == "batch[2]:pull" and to == "sequential:pull"
        assert "no batched fixpoint" in why
    douts = engine.run_direct(g, U.handwritten_sssp(0), engine="adaptive",
                              sources=[1, 4])
    assert all(o.stats.fallbacks[0][0] == "batch[2]:adaptive" for o in douts)


# ---------------------------------------------------------------------------
# Recorded-stats feedback loop
# ---------------------------------------------------------------------------

def test_feedback_recorded_per_graph_and_kind(g):
    prog = fusion.fuse(U.bfs(0))
    engine.run_program(g, prog, engine="pallas", source=1)
    kind = P.program_kind(prog)
    rec = P.feedback_for(g, kind)
    assert rec is not None and rec.queries == 1
    assert rec.iterations == rec.push_iters + rec.pull_iters > 0
    engine.run_program(g, prog, engine="pallas", source=2)
    assert rec.queries == 2
    # a different shape gets its own record
    assert P.feedback_for(g, P.program_kind(fusion.fuse(U.sssp(0)))) is None


def test_adapted_switch_k_stays_within_bounds():
    lo = P.SWITCH_K / P.ADAPT_SPAN
    hi = P.SWITCH_K * P.ADAPT_SPAN
    for push, total in [(0, 1), (1, 1), (99, 100), (1, 100), (50, 100)]:
        rec = P.FeedbackRecord(queries=1, iterations=total, push_iters=push,
                               pull_iters=total - push)
        k = P._adapted_switch_k(rec)
        assert lo <= k <= hi
    all_push = P.FeedbackRecord(queries=1, iterations=10, push_iters=10)
    no_push = P.FeedbackRecord(queries=1, iterations=10, push_iters=0)
    assert P._adapted_switch_k(all_push) == P.SWITCH_K / 2
    assert P._adapted_switch_k(no_push) == P.SWITCH_K * 2
    assert P._adapted_switch_k(P.FeedbackRecord()) == P.SWITCH_K


def test_adaptive_plans_consult_feedback_only_when_opted_in(g):
    prog = fusion.fuse(U.bfs(0))
    engine.run_program(g, prog, engine="pallas", source=0)
    rec = P.feedback_for(g, P.program_kind(prog))
    assert rec is not None
    # force a decisive push fraction so adaptation must move k
    rec.iterations, rec.push_iters, rec.pull_iters = 10, 10, 0
    rec.epoch += 1
    dflt = engine.plan_execution(g, prog, engine="pallas")
    assert dflt.switch_k == P.SWITCH_K          # default stays bitwise-stable
    adapted = engine.plan_execution(g, prog, engine="pallas", adaptive=True)
    assert adapted.switch_k == P.SWITCH_K / 2
    # an explicit hint always beats feedback
    pinned = engine.plan_execution(g, prog, engine="pallas", adaptive=True,
                                   switch_k=7.0)
    assert pinned.switch_k == 7.0


def test_adaptive_resolution_flip_needs_observed_waste():
    wasteful = P.FeedbackRecord(queries=3, iterations=9, push_iters=6,
                                pull_iters=3, edge_work=100.0,
                                resolve_work=500.0)
    lean = P.FeedbackRecord(queries=3, iterations=9, push_iters=6,
                            pull_iters=3, edge_work=500.0,
                            resolve_work=100.0)
    assert P._adapted_resolution(wasteful) == "scatter"
    assert P._adapted_resolution(lean) is None


def test_adaptive_execution_stays_correct(g):
    """Adaptation may change the direction SCHEDULE, never the value:
    idempotent rounds are bitwise direction-independent per iteration."""
    prog = fusion.fuse(U.bfs(1))
    base = engine.run_program(g, prog, engine="pallas")
    for _ in range(3):
        r = engine.run_program(g, prog, engine="pallas", adaptive=True)
        assert np.asarray(r.value).tobytes() == \
            np.asarray(base.value).tobytes()
    lo = P.SWITCH_K / P.ADAPT_SPAN
    hi = P.SWITCH_K * P.ADAPT_SPAN
    assert r.stats.plan.switch_k is None or lo <= r.stats.plan.switch_k <= hi


def test_nonidempotent_shapes_never_adapt(g):
    dk = U.handwritten_pagerank(g.n)
    assert not P._prog_idempotent(dk)
    r = engine.run_direct(g, dk, engine="pallas", adaptive=True)
    assert r.stats.plan.switch_k == P.SWITCH_K


# ---------------------------------------------------------------------------
# Cache plumbing (satellite 2)
# ---------------------------------------------------------------------------

def test_cache_stats_and_per_graph_eviction(g):
    g2 = structure.uniform_graph(12, 30, seed=7)
    prog = fusion.fuse(U.bfs(0))
    engine.run_program(g, prog, engine="pallas")
    engine.run_program(g2, prog, engine="pallas")
    st = engine.program_cache_stats()
    assert st["plans"] >= 2 and st["feedback"] >= 2 and st["graph_stats"] == 2
    dropped = engine.clear_graph_caches(g)
    assert dropped > 0
    st2 = engine.program_cache_stats()
    assert st2["graph_stats"] == 1
    assert P.feedback_for(g, P.program_kind(prog)) is None
    assert P.feedback_for(g2, P.program_kind(prog)) is not None
    engine.clear_program_caches()
    st3 = engine.program_cache_stats()
    assert st3["plans"] == st3["feedback"] == st3["graph_stats"] == 0


def test_plan_caches_are_lru_bounded(g):
    prog = fusion.fuse(U.bfs(0))
    for k in range(P._PLAN_CACHE_MAX + 16):
        engine.plan_execution(g, prog, switch_k=float(k + 1))
    assert P.plan_cache_size() <= P._PLAN_CACHE_MAX


def test_service_adaptive_serving_stays_bitwise(g):
    from repro.launch import service as S
    svc = S.AnalyticsService(S.ServiceConfig(max_batch=4, chunk_iters=3,
                                             adaptive=True))
    svc.add_graph("g", g)
    svc.register("BFS", U.bfs)
    svc.register("SSSP", U.sssp)
    arrivals = S.open_loop_arrivals(
        24, rate=800.0, seed=11, make_request=S.standard_mix("g", g.n))
    svc.run_open_loop(arrivals)
    assert S.verify_sequential(svc) == 24
    assert engine.program_cache_stats()["feedback"] >= 1
