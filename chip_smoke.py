#!/usr/bin/env python3
"""Bring-up smoke of the GraFS main path on a TPU.

    python chip_smoke.py [--seed N] [--chips 4]

One chip (the default) runs, in this one process, on two graphs generated
from ``--seed`` — ER-20, ``uniform_graph(2**20, 2**24)``, and RMAT-15,
``rmat_graph(2**15, 2**19)`` with the Graph500 A/B/C — with the program
caches cleared in between:

* ``bfs_depth(0)`` and ``sssp(0)`` through
  ``engine.run_program(..., engine="pallas", fallback=False)``, checked
  against ``scipy.sparse.csgraph`` shortest paths (unweighted / weighted);
* ``wsp(0)``, the fused lexicographic round, checked bitwise against the
  jnp ``pull`` engine;
* on ER-20, an ``AnalyticsService(ServiceConfig(engine="pallas"))`` answers
  16 mixed BFS/SSSP requests through its batched executor, and
  ``verify_sequential`` checks every answer bitwise against its solo run.

``--chips 4`` runs only ``bfs_depth(0)`` and ``sssp(0)`` on ER-19,
``uniform_graph(2**19, 2**23)``, through the ``pallas_sharded`` engine on a
4-device mesh, against scipy.  Every shard's layout spans all 2^19 rows (a
vertex-cut), so each chip sweeps 67M slots per direction.

Every phase prints one JSON line: the graph and its padded slots,
iterations, the engine that answered, backend compile seconds (cache hits
included), the cold and warm wall seconds, and the check.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script exits
non-zero without that line when JAX sees no TPU, when it does not stand in a
checkout of the repo, or when any phase fails: a non-pallas engine, a
fallback event, a compiled executor without a Mosaic kernel
(``tpu_custom_call``), or an answer that differs from its reference.

JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` beside this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BOT = 1e8                    # engine ⊥ sentinels are ≥ this (or ±inf)
SERVICE_REQUESTS = 16
SERVICE_BATCH = 4            # B=8 needs 16.7 GB of HBM at ER-20 (compile)


def _emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


class _CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit counts
    its retrieval time) and counts cache hits, per phase."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = (round(self.seconds, 3), self.hits)
        self.seconds, self.hits = 0.0, 0
        return out


def _reference_paths(g, weighted: bool):
    """scipy shortest-path distances from vertex 0 along src → dst edges
    (float64, inf where unreachable)."""
    import numpy as np
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import shortest_path
    src, dst, w, _c = g.host_edges()
    data = w.astype(np.float64) if weighted else np.ones(src.shape[0])
    adj = csr_matrix((data, (src, dst)), shape=(g.n, g.n))
    return shortest_path(adj, directed=True, unweighted=not weighted,
                         indices=0)


def _check_paths(value, ref) -> int:
    """Equal reachability and equal distances; returns the reached count."""
    import numpy as np
    v = np.asarray(value, np.float64)
    reached = np.abs(v) < BOT
    if not np.array_equal(reached, np.isfinite(ref)):
        bad = int(np.flatnonzero(reached != np.isfinite(ref))[0])
        raise AssertionError(f"reachability differs from scipy at vertex "
                             f"{bad}: {v[bad]} vs {ref[bad]}")
    if not np.array_equal(v[reached], ref[reached]):
        bad = int(np.flatnonzero(reached & (v != ref))[0])
        raise AssertionError(f"distance differs from scipy at vertex {bad}: "
                             f"{v[bad]} vs {ref[bad]}")
    return int(reached.sum())


def _check_device_run(res, engine_name: str) -> None:
    if res.stats.engine_used != engine_name:
        raise AssertionError(f"answered by {res.stats.engine_used!r}, "
                             f"not {engine_name!r}")
    if res.stats.fallbacks:
        raise AssertionError(f"fallback events: {res.stats.fallbacks}")


def _check_kernels_compiled() -> int:
    """Every executor that ran must hold a Mosaic kernel."""
    from repro.kernels import ops
    texts = ops.compiled_executor_texts()
    if not texts:
        raise AssertionError("no pallas executor ran")
    missing = sum("tpu_custom_call" not in t for t in texts)
    if missing:
        raise AssertionError(f"{missing} of {len(texts)} compiled executors "
                             "hold no tpu_custom_call")
    return len(texts)


def _layout_slots(g):
    from repro.graph import structure
    return {"in_slots": int(structure.blocked_ell_cached(g).nbrs.size),
            "out_slots": int(structure.blocked_ell_cached(
                g, direction="out").nbrs.size)}


def _timed_query(clock, g, prog, **kw):
    """Cold then warm run of one query: (result, compile s, cache hits,
    cold s, warm s).  Both runs must agree bitwise."""
    import numpy as np
    from repro.core import engine
    clock.take()
    t0 = time.perf_counter()
    cold = engine.run_program(g, prog, fallback=False, **kw)
    np.asarray(cold.value)
    t1 = time.perf_counter()
    warm = engine.run_program(g, prog, fallback=False, **kw)
    np.asarray(warm.value)
    t2 = time.perf_counter()
    compile_s, hits = clock.take()
    if np.asarray(cold.value).tobytes() != np.asarray(warm.value).tobytes():
        raise AssertionError("a repeated query changed its answer")
    return warm, compile_s, hits, round(t1 - t0, 3), round(t2 - t1, 3)


def _path_phases(clock, gname, g, engine_name, mesh=None, wsp=True):
    import numpy as np
    from repro.core import engine, fusion, usecases as U
    kw = {"engine": engine_name}
    if mesh is not None:
        kw["mesh"] = mesh
    for qname, spec, weighted in (("bfs_depth", U.bfs_depth(0), False),
                                  ("sssp", U.sssp(0), True)):
        res, compile_s, hits, cold_s, warm_s = _timed_query(
            clock, g, fusion.fuse(spec), **kw)
        _check_device_run(res, engine_name)
        t0 = time.perf_counter()
        reached = _check_paths(res.value, _reference_paths(g, weighted))
        _emit(phase=f"{gname} {qname}", engine_used=res.stats.engine_used,
              iterations=res.stats.iterations,
              push_iters=res.stats.push_iters, compile_s=compile_s,
              cache_hits=hits, cold_s=cold_s, wall_s=warm_s,
              check=f"scipy equal ({reached} reached)",
              ref_s=round(time.perf_counter() - t0, 3))
    if wsp:
        prog = fusion.fuse(U.wsp(0))
        res, compile_s, hits, cold_s, warm_s = _timed_query(
            clock, g, prog, **kw)
        _check_device_run(res, engine_name)
        ref = engine.run_program(g, prog, engine="pull").value
        if np.asarray(res.value).tobytes() != np.asarray(ref).tobytes():
            raise AssertionError(f"{gname} wsp differs from the pull engine")
        _emit(phase=f"{gname} wsp", engine_used=res.stats.engine_used,
              iterations=res.stats.iterations,
              push_iters=res.stats.push_iters, compile_s=compile_s,
              cache_hits=hits, cold_s=cold_s, wall_s=warm_s,
              check="pull engine bitwise equal")
    executors = _check_kernels_compiled()
    _emit(phase=f"{gname} kernels", executors=executors,
          check="every executor holds tpu_custom_call")


def _service_phase(clock, gname, g, seed):
    import numpy as np
    from repro.core import usecases as U
    from repro.launch import service as S
    svc = S.AnalyticsService(S.ServiceConfig(engine="pallas",
                                             max_batch=SERVICE_BATCH))
    svc.add_graph(gname, g)
    svc.register("BFS", U.bfs)
    svc.register("SSSP", U.sssp)
    rng = np.random.default_rng(seed)
    for rid in range(SERVICE_REQUESTS):
        kind = ("BFS", "SSSP")[int(rng.integers(2))]
        svc.submit(gname, S.Request(rid=rid, kind=kind,
                                    source=int(rng.integers(g.n))))
    clock.take()
    t0 = time.perf_counter()
    while svc.step():
        pass
    serve_s = round(time.perf_counter() - t0, 3)
    compile_s, hits = clock.take()
    if len(svc.completed) != SERVICE_REQUESTS or svc.solo_runs:
        raise AssertionError(f"service answered {len(svc.completed)} of "
                             f"{SERVICE_REQUESTS} batched, "
                             f"{svc.solo_runs} solo")
    t1 = time.perf_counter()
    checked = S.verify_sequential(svc)
    if checked != SERVICE_REQUESTS:
        raise AssertionError(f"verified {checked} of {SERVICE_REQUESTS}")
    m = svc.metrics()
    _emit(phase=f"{gname} service", requests=SERVICE_REQUESTS,
          max_batch=SERVICE_BATCH, batch_launches=m["batch_launches"],
          queries_per_launch=m["queries_per_launch"],
          total_iterations=m["total_iterations"], compile_s=compile_s,
          cache_hits=hits, wall_s=serve_s,
          check=f"{checked}/{SERVICE_REQUESTS} bitwise equal to solo runs",
          verify_s=round(time.perf_counter() - t1, 3))
    executors = _check_kernels_compiled()
    _emit(phase=f"{gname} service kernels", executors=executors,
          check="every executor holds tpu_custom_call")


def _graph(gname, build):
    t0 = time.perf_counter()
    g = build()
    _emit(phase=f"{gname} graph", n=g.n, edges=g.num_edges,
          build_s=round(time.perf_counter() - t0, 3))
    return g


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: run it from a checkout of the repo (no src/repro "
              f"beside {__file__})", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import numpy as np
    from repro.core import engine
    from repro.graph import structure

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    clock = _CompileClock(jax.monitoring)
    seed = args.seed

    if args.chips == 4:
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(devices[:4]), ("data",))
        g = _graph("ER-19", lambda: structure.uniform_graph(
            2 ** 19, 2 ** 23, seed=seed))
        _path_phases(clock, "ER-19", g, "pallas_sharded", mesh=mesh,
                     wsp=False)
    else:
        g = _graph("ER-20", lambda: structure.uniform_graph(
            2 ** 20, 2 ** 24, seed=seed))
        _path_phases(clock, "ER-20", g, "pallas")
        _emit(phase="ER-20 layout", **_layout_slots(g))
        _service_phase(clock, "ER-20", g, seed)
        del g
        engine.clear_program_caches()
        g = _graph("RMAT-15", lambda: structure.rmat_graph(
            2 ** 15, 2 ** 19, seed=seed))
        _path_phases(clock, "RMAT-15", g, "pallas")
        _emit(phase="RMAT-15 layout", **_layout_slots(g))

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
