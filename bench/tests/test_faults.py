"""A whole run of a cell, on the CPU at a small size, with the chip checks
skipped: sound, it comes out correct; with the timed path broken underneath
(a fixpoint that returns its initial state, one answer altered where it is
produced, another engine, a fallback, a compile inside the window) or with
the control in the program's place, ``correct`` comes out false.  A traffic
mix of two query kinds is judged answer by answer, each by its kind."""
import dataclasses
import io
import json
import os
import shutil
import time

import numpy as np
import pytest

import harness

SMALL = {"gap-kron-15": 8, "gap-urand-20": 8}
BOTTOM = 2 ** 30 - 1


CELLS = {"urand20.bfs": ("gap-urand-20", "bfs"),
         "kron15.bfs": ("gap-kron-15", "bfs"),
         "kron15.sssp": ("gap-kron-15", "sssp")}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """BENCHMARK.json with each configuration cut to a small scale, and
    with a cell for each query kind that ``bench/queries`` holds."""
    out = tmp_path_factory.mktemp("bench")
    bench = harness.load_bench()
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test"} for n, (c, t) in CELLS.items()]
    for c in bench["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg["scale"] = SMALL[c["name"]]
        c["file"] = str(out / (c["name"] + ".json"))
        with open(c["file"], "w") as f:
            json.dump(cfg, f)
    return bench


def _run(bench, cell, trace=False, control=False, seed=2 ** 33 + 17):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(cell, seed, 0.3, trace, time.perf_counter(),
                     control=control, require_chip=False, bench=bench,
                     out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith(
        "check window_compiles")
    return result


def _break(monkeypatch, alter, kind=None):
    """Route every program answer (of one query ``kind``, BFS answers being
    integers and SSSP ones floats, or of all) through ``alter(value)``."""
    from repro.core import engine
    real = engine.run_program

    def broken(*args, **kw):
        res = real(*args, **kw)
        value = np.asarray(res.value)
        if kind is not None and (value.dtype.kind == "f") != (kind == "sssp"):
            return res
        return dataclasses.replace(res, value=alter(value))

    monkeypatch.setattr(engine, "run_program", broken)


def _initial_state(v):
    """What a fixpoint whose step returns its state unchanged answers:
    only the root is reached."""
    v = v.copy()
    if v.dtype.kind == "f":
        keep = v == 0
        v[~keep] = np.inf
    else:
        keep = v == np.arange(v.size)
        v[~keep] = BOTTOM
    return v


def _one_altered(v):
    v = v.copy()
    i = int(np.flatnonzero((v > 0) & (v < 1e8))[0])
    v[i] = v[i] + 1
    return v


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(bench, cell):
    r = _run(bench, cell)
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"teps", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_initial_state, _one_altered])
def test_broken_timed_path_is_incorrect(bench, monkeypatch, cell, fault):
    _break(monkeypatch, fault)
    r = _run(bench, cell)
    assert not r["correct"]
    assert r["checks"]["wrong_vertices"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_incorrect(bench, cell):
    r = _run(bench, cell, control=True)
    assert not r["correct"]


@pytest.mark.parametrize("field,value", [("engine_used", "pull"),
                                         ("fallbacks", (("pallas", "adaptive",
                                                         "boom"),))])
def test_engine_guards(bench, monkeypatch, field, value):
    from repro.core import engine
    real = engine.run_program

    def other(*args, **kw):
        res = real(*args, **kw)
        setattr(res.stats, field, value)
        return res

    monkeypatch.setattr(engine, "run_program", other)
    r = _run(bench, "kron15.bfs")
    assert not r["correct"]
    assert r["checks"]["guard_violations"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(bench):
    r = _run(bench, "kron15.bfs", trace=True)
    assert r["correct"]
    # a CPU trace has no TPU plane: the device metrics find nothing to read
    assert set(r["metrics"]) == {"setup.compile_s", "layout.build_s",
                                 "executor.iters_per_query"}


def test_no_tpu_no_result(bench):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run("kron15.bfs", 1, 0.3, False, time.perf_counter(),
                     bench=bench, out=out, err=err)
    assert rc != 0 and out.getvalue() == ""
    assert "no TPU" in err.getvalue()


def test_checkout_without_program_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench")
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run("kron15.bfs", 1, 0.3, False, time.perf_counter(),
                     root=str(tmp_path), out=out, err=err)
    assert rc != 0 and out.getvalue() == ""


def test_compile_inside_the_window_is_incorrect(bench, monkeypatch):
    """A program that compiles on every query: the warm-up takes its
    compiles into set-up, the window's are counted against the limit 0."""
    import jax
    from repro.core import engine
    real = engine.run_program
    calls = []

    def compiling(*args, **kw):
        calls.append(1)
        jax.jit(lambda x: x + len(calls))(np.zeros(len(calls), np.int32))
        return real(*args, **kw)

    monkeypatch.setattr(engine, "run_program", compiling)
    r = _run(bench, "kron15.bfs")
    assert not r["correct"]
    assert r["checks"]["window_compiles"]["value"] > 0
    assert r["checks"]["wrong_vertices"]["value"] == 0


@pytest.fixture
def mixed(monkeypatch):
    """The cell kron15.bfs with its traffic replaced by a BFS:SSSP 1:1 mix:
    a new mix is a traffic file, read as this dict."""
    real = harness.resolve

    def resolve(bench, name, root=harness.ROOT):
        w, cfg, _traffic = real(bench, name, root)
        return w, cfg, {"driver": "closed_loop", "mix": {"bfs": 1, "sssp": 1}}

    monkeypatch.setattr(harness, "resolve", resolve)


def test_mixed_traffic_is_correct(bench, mixed):
    r = _run(bench, "kron15.bfs")
    assert r["correct"] and r["attempted"] % 2 == 0 and r["attempted"] > 0


@pytest.mark.parametrize("kind", ["bfs", "sssp"])
def test_mixed_traffic_judges_each_kind(bench, mixed, monkeypatch, kind):
    _break(monkeypatch, _one_altered, kind=kind)
    r = _run(bench, "kron15.bfs")
    assert not r["correct"]
    assert r["checks"]["failed_queries"]["value"] == r["attempted"] // 2


@pytest.mark.parametrize("traffic", [
    {"driver": "closed_loop", "mix": {"bfs": 1}, "clients": 4},
    {"driver": "closed_loop", "mix": {}},
    {"driver": "closed_loop", "mix": {"bfs": 0.5}}])
def test_traffic_the_driver_cannot_read_no_result(bench, monkeypatch,
                                                  traffic):
    real = harness.resolve
    monkeypatch.setattr(harness, "resolve", lambda b, n, root=harness.ROOT:
                        real(b, n, root)[:2] + (traffic,))
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run("kron15.bfs", 1, 0.3, False, time.perf_counter(),
                     require_chip=False, bench=bench, out=out, err=err)
    assert rc != 0 and out.getvalue() == ""
    assert "traffic" in err.getvalue()
