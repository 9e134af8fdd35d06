"""The PageRank query kind and its cell, on the CPU at small scales: the
float64 reference agrees with a dense solve, GAP's verifier accepts it,
``mismatches`` flags the bfloat16 control and an answer stopped at half the
iterations, and a whole run of ``kron15.pr`` comes out correct while its
control and a broken fixpoint come out incorrect."""
import io
import json
import os
import time

import numpy as np
import pytest

import graph as G
import harness

pr = G.load_module("queries", "pr")
CONFIG = os.path.join(os.path.dirname(G.__file__), "configs",
                      "gap-kron-15-pr.json")


def _graph(scale, graph_seed=2 ** 35 + 9):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg["scale"] = scale
    cfg["graph_seed"] = graph_seed
    return G.build(cfg)


def test_config_states_the_query_constants():
    """The configuration's PageRank block is what the query kind runs, and
    its graph keys are gap-kron-15's, so both cells sweep one graph."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(CONFIG.replace("-pr.json", ".json")) as f:
        bfs = json.load(f)
    assert cfg["pagerank"] == {"damping": pr.DAMPING, "tolerance": pr.TOL,
                               "norm": "L1", "dtype": "float32"}
    for key in ("generator", "scale", "edge_factor", "A", "B", "C",
                "undirected", "weights", "trials", "graph_seed"):
        assert cfg[key] == bfs[key], key


@pytest.mark.parametrize("scale", [6, 8, 10])
def test_reference_matches_dense_solve(scale):
    """(I − d·A·D⁻¹) x = (1−d)/|V| solved densely; the L1 rule leaves the
    reference within d/(1−d)·tol of it in L1 (the contraction bound), and
    the reference's own loop stops at the first change under tol."""
    g = _graph(scale)
    ref = pr.reference(g, 0)
    assert pr.reference(g, 5) is ref                 # memoised, rootless
    adj = g.csr(weighted=False).toarray()
    deg = adj.sum(axis=1)
    walk = adj.T * np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    exact = np.linalg.solve(np.eye(g.n) - pr.DAMPING * walk,
                            np.full(g.n, (1 - pr.DAMPING) / g.n))
    bound = pr.DAMPING / (1 - pr.DAMPING) * pr.TOL
    assert np.abs(ref.scores - exact).sum() <= bound
    before, _ = ref.jacobi.solve(iters=ref.iterations - 1)
    assert np.abs(ref.scores - before).sum() < pr.TOL
    earlier, _ = ref.jacobi.solve(iters=ref.iterations - 2)
    assert np.abs(before - earlier).sum() >= pr.TOL


def test_verifier_accepts_reference_and_refuses_early_stop():
    g = _graph(10)
    ref = pr.reference(g, 0)
    assert ref.jacobi.residual(ref.scores) < pr.TOL
    assert pr.mismatches(ref.scores, ref) == 0
    assert pr.mismatches(ref.scores.astype(np.float32), ref) == 0
    early, _ = ref.jacobi.solve(iters=ref.iterations // 4)
    assert ref.jacobi.residual(early) >= pr.TOL
    assert pr.mismatches(early, ref) == g.n
    assert pr.mismatches(np.full(g.n, np.nan), ref) == g.n


def test_mismatches_flags_control_and_half_iterations():
    g = _graph(10)
    ref = pr.reference(g, 0)
    half, _ = ref.jacobi.solve(iters=ref.iterations // 2)
    assert pr.mismatches(half.astype(np.float32), ref) > 0
    assert pr.mismatches(pr.control(g)(0), ref) > 0


def test_program_answer_within_tolerance():
    """The program's jnp reference engine (float32) answers within RTOL."""
    from repro.core import engine, fusion, usecases
    from repro.graph import structure
    g = _graph(10)
    src, dst, w = g.arcs()
    pg = structure.from_edges(g.n, src, dst, weight=w)
    res = engine.run_program(pg, fusion.fuse(pr.spec(usecases, 0)),
                             engine="pull")
    ref = pr.reference(g, 0)
    assert res.stats.iterations == ref.iterations
    assert pr.mismatches(np.asarray(res.value), ref) == 0


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """BENCHMARK.json with the kron15.pr cell alone, its graph at scale 8."""
    out = tmp_path_factory.mktemp("bench")
    bench = harness.load_bench()
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] == "kron15.pr"]
    for c in bench["configs"]:
        if c["name"] == "gap-kron-15-pr":
            with open(os.path.join(harness.ROOT, c["file"])) as f:
                cfg = json.load(f)
            cfg["scale"] = 8
            c["file"] = str(out / "gap-kron-15-pr.json")
            with open(c["file"], "w") as f:
                json.dump(cfg, f)
    return bench


def _run(bench, trace=False, control=False, seed=2 ** 33 + 17):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run("kron15.pr", seed, 0.3, trace, time.perf_counter(),
                     control=control, require_chip=False, bench=bench,
                     out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_cell_run_is_correct_and_reports_its_metrics(bench):
    r = _run(bench)
    assert r["correct"] and r["attempted"] >= 8 and r["failed"] == 0
    assert set(r["metrics"]) == {"teps", "setup_s"}
    traced = _run(bench, trace=True)
    assert traced["correct"]
    # a CPU trace has no TPU plane: the two device metrics find nothing
    assert set(traced["metrics"]) == {"pr.slot_fill"}
    fill = traced["metrics"]["pr.slot_fill"]["value"]
    assert 0 < fill <= 100


def test_metric_readers():
    """The three readers on a made-up window of two queries: 40 and 44
    iterations, 1,000 arcs an iteration, 3 tiles of 8 × 128 slots an
    iteration, 2 s busy."""
    from types import SimpleNamespace as NS
    stats = [NS(iterations=k, edge_work=1000.0 * k, tiles_swept=3 * k,
                tile_shape=(8, 128)) for k in (40, 44)]
    run = harness.Run({}, [{"stats": s} for s in stats],
                      {"busy_s": 2.0, "window_s": 2.5}, 0,
                      {"hbm_bytes_per_s": 819e9})
    read = {m: G.load_module("metrics", m).read for m in
            ("pr.ms_per_iter", "pr.sweep_roofline", "pr.slot_fill")}
    assert read["pr.ms_per_iter"](run) == pytest.approx(2000.0 / 84)
    assert read["pr.sweep_roofline"](run) == pytest.approx(
        100 * 8 * 84_000 / 819e9 / 2.0)
    assert read["pr.slot_fill"](run) == pytest.approx(
        100 * 1000 / (3 * 1024))
    # a program without the tile counters, or a trace without a device
    bare = harness.Run({}, [{"stats": NS(iterations=40, edge_work=4e4)}],
                       {}, 0, {"hbm_bytes_per_s": 819e9})
    assert all(r(bare) is None for r in read.values())


def test_cell_control_is_incorrect(bench):
    r = _run(bench, control=True)
    assert not r["correct"] and r["checks"]["wrong_vertices"]["value"] > 0


def test_cell_with_a_fixpoint_stopped_early_is_incorrect(bench, monkeypatch):
    """A fixpoint capped at half its iterations (the answer of a broken
    stopping rule) is refused."""
    from repro.core import engine
    real = engine.run_program

    def capped(*args, **kw):
        return real(*args, max_iter=15, on_nonconverge="ignore", **kw)

    monkeypatch.setattr(engine, "run_program", capped)
    r = _run(bench)
    assert not r["correct"] and r["checks"]["wrong_vertices"]["value"] > 0
