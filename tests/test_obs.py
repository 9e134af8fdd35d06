"""Program spans and device scopes (DESIGN.md §16).

* ``obs.span`` hands its interval to ``jax.monitoring`` time-span
  listeners, also when the spanned work raises;
* one solo ``run_program`` BFS on a new graph emits the ``grafs.*`` host
  spans nested and in order, the layout builds inside the executor lookup,
  to a listener and into a profiler trace;
* the optimized HLO of the ``pallas`` executor, and of the
  ``pallas_sharded`` one on a one-device mesh, names every ``grafs.``
  device scope and Pallas kernel in its ``op_name`` metadata;
* answers are bitwise equal with and without a listener registered.
"""
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.core import engine, fusion
from repro.core import usecases as U
from repro.graph import structure
from repro.kernels import ops as kops


class Spans:
    """A ``jax.monitoring`` listener that keeps the ``grafs.*`` spans."""

    def __init__(self):
        self.spans = []

    def __call__(self, event, start, end, **_kw):
        if event.startswith(obs.EVENT_PREFIX):
            self.spans.append((event[len(obs.EVENT_PREFIX):], start, end))

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self)

    def ordered(self):
        """Spans by start; a parent (longer) before a child that starts
        with it."""
        return sorted(self.spans, key=lambda s: (s[1], -(s[2] - s[1])))


def _inside(child, parent):
    return parent[1] <= child[1] <= child[2] <= parent[2]


def test_span_records_interval_and_survives_errors():
    with Spans() as rec:
        with obs.span("grafs.test_ok"):
            pass
        with pytest.raises(RuntimeError, match="boom"):
            with obs.span("grafs.test_raises"):
                raise RuntimeError("boom")
    assert [s[0] for s in rec.spans] == ["grafs.test_ok", "grafs.test_raises"]
    assert all(s[1] <= s[2] for s in rec.spans)


def test_run_program_spans_nested_in_order():
    src, dst = structure.uniform_graph(96, 400, seed=5).host_edges()[:2]
    with Spans() as rec:
        g = structure.from_edges(96, src, dst)
        res = engine.run_program(g, fusion.fuse(U.bfs_depth(0)),
                                 engine="pallas", fallback=False)
    assert res.stats.engine_used == "pallas"
    spans = rec.ordered()
    assert [s[0] for s in spans] == [
        "grafs.from_edges",
        "grafs.run_program",
        "grafs.plan", "grafs.validate", "grafs.synthesize",
        "grafs.executor",
        "grafs.layout.ell", "grafs.layout.upload",          # pull layout
        "grafs.layout.ell", "grafs.layout.upload",          # push layout
        "grafs.layout.resolution", "grafs.layout.upload",
        "grafs.dispatch", "grafs.device_wait", "grafs.stats_to_host",
        "grafs.finish", "grafs.finish"]
    run = spans[1]
    children = [s for s in spans[2:] if not s[0].startswith("grafs.layout")]
    assert all(_inside(s, run) for s in spans[2:])
    # the children of run_program follow one another without overlap
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    executor = next(s for s in spans if s[0] == "grafs.executor")
    builds = [s for s in spans if s[0] in ("grafs.layout.ell",
                                           "grafs.layout.resolution")]
    uploads = [s for s in spans if s[0] == "grafs.layout.upload"]
    assert all(_inside(b, executor) for b in builds)
    assert all(_inside(u, b) for u, b in zip(uploads, builds))
    assert not _inside(spans[0], run)               # set-up, before the query


def test_repeat_query_skips_layout_spans():
    g = structure.uniform_graph(96, 400, seed=6)
    prog = fusion.fuse(U.bfs_depth(0))
    engine.run_program(g, prog, engine="pallas", fallback=False)
    with Spans() as rec:
        engine.run_program(g, prog, engine="pallas", fallback=False,
                           source=3)
    names = [s[0] for s in rec.ordered()]
    assert not any(n.startswith("grafs.layout") for n in names)
    assert names.count("grafs.device_wait") == 1


def test_spans_land_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    g = structure.uniform_graph(96, 400, seed=8)
    prog = fusion.fuse(U.bfs_depth(0))
    engine.run_program(g, prog, engine="pallas", fallback=False)
    with jax.profiler.trace(str(tmp_path)):
        engine.run_program(g, prog, engine="pallas", fallback=False,
                           source=2)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("grafs.")]
    assert names == ["grafs.run_program", "grafs.plan", "grafs.validate",
                     "grafs.synthesize", "grafs.executor", "grafs.dispatch",
                     "grafs.device_wait", "grafs.stats_to_host",
                     "grafs.finish", "grafs.finish"]


@pytest.fixture(scope="module", params=["pallas", "pallas_sharded"])
def executor_op_names(request):
    """The ``op_name`` metadata of the optimized HLO of the executor (auto
    direction, sorted resolution) that one BFS ran: the ``pallas`` one, and
    the ``pallas_sharded`` one on a one-device mesh, which runs the same
    fixpoint body."""
    kops.clear_executor_cache()
    g = structure.uniform_graph(96, 400, seed=7)
    mesh = None
    if request.param == "pallas_sharded":
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    engine.run_program(g, fusion.fuse(U.bfs_depth(0)), engine=request.param,
                       mesh=mesh, fallback=False)
    (text,) = kops.compiled_executor_texts()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("name", [
    "grafs.slot_gather", "grafs.slot_scatter", "grafs.tile_activity",
    "grafs.res_activity", "grafs.merge",
    "grafs_pull_sweep", "grafs_push_sweep", "grafs_resolve"])
def test_executor_hlo_names_scope(executor_op_names, name):
    assert any(name in n.split("/") for n in executor_op_names)


@pytest.mark.parametrize("spec", [U.bfs_depth, U.sssp])
def test_answers_bitwise_equal_with_and_without_listener(spec):
    g = structure.rmat_graph(128, 800, seed=11)
    prog = fusion.fuse(spec(0))
    plain = engine.run_program(g, prog, engine="pallas", fallback=False)
    with Spans() as rec:
        heard = engine.run_program(g, prog, engine="pallas", fallback=False)
    assert rec.spans
    assert np.asarray(plain.value).tobytes() == \
        np.asarray(heard.value).tobytes()
    assert plain.stats.iterations == heard.stats.iterations
