"""Serving driver for the continuous-batching analytics service.

    PYTHONPATH=src python -m repro.launch.analytics --smoke

``--smoke`` runs a small seeded open-loop trace (mixed BFS/SSSP sweep
queries + fused scalar radius/drr queries over an R-MAT graph) through
``repro.launch.service.AnalyticsService``, prints the deterministic
serving metrics, then replays EVERY completed request as a solo
``run_program`` and asserts the service answers are bitwise-identical
(``verify_sequential``) and that continuous batching actually batched
(queries_per_launch > 1).  Exit status is the CI contract.

The production-mesh compile dry-run that used to live at this module path
moved to ``repro.launch.analytics_dryrun``; ``--dryrun`` delegates there
in a subprocess (its XLA host-device flags must be set before jax
imports, so it cannot be imported from an already-initialised process).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def run_smoke(seed: int = 0, n_requests: int = 24, engine_name: str = "pallas",
              verbose: bool = True) -> dict:
    """The open-loop serving smoke: returns the metrics dict (with the
    bitwise-verification count added) or raises on any violation."""
    from repro.core import usecases as U
    from repro.graph import structure
    from repro.launch import service as S

    g = structure.rmat_graph(192, 768, seed=7, weighted=True)
    cfg = S.ServiceConfig(engine=engine_name, max_batch=4, chunk_iters=3,
                          max_scalar_fuse=6)
    svc = S.AnalyticsService(cfg)
    svc.add_graph("rmat", g)
    svc.register("BFS", U.bfs)
    svc.register("SSSP", U.sssp)

    # arrival rate ~8× the per-chunk virtual service time: enough pressure
    # that batches fill and scalar requests queue up to be paired
    arrivals = S.open_loop_arrivals(
        n_requests, rate=1.0 / (cfg.launch_overhead_s + cfg.iter_cost_s),
        seed=seed, make_request=S.standard_mix("rmat", g.n))
    metrics = svc.run_open_loop(arrivals)

    checked = S.verify_sequential(svc)
    metrics["verified_bitwise"] = checked
    if checked != n_requests:
        raise AssertionError(
            f"verified {checked}/{n_requests} requests — some never "
            "completed or lost their graph")
    if metrics["queries_per_launch"] <= 1.0:
        raise AssertionError(
            "continuous batching did not batch: queries_per_launch = "
            f"{metrics['queries_per_launch']} <= 1")
    if verbose:
        print(f"[analytics --smoke] {json.dumps(metrics, indent=1)}")
        print(f"[analytics --smoke] ok: {checked} answers bitwise-equal to "
              f"solo runs, queries_per_launch="
              f"{metrics['queries_per_launch']}")
    return metrics


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seeded open-loop serving run + bitwise check")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--engine", default="pallas")
    ap.add_argument("--dryrun", action="store_true",
                    help="delegate to repro.launch.analytics_dryrun")
    args, rest = ap.parse_known_args(argv)

    if args.dryrun:
        # the dry-run lowers for forced host devices: pin the child to the
        # CPU so it never claims an accelerator this process may hold
        return subprocess.call(
            [sys.executable, "-m", "repro.launch.analytics_dryrun"] + rest,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if rest:
        ap.error(f"unrecognized arguments: {rest}")
    if not args.smoke:
        ap.error("nothing to do: pass --smoke (serving check) or "
                 "--dryrun (mesh compile dry-run)")
    run_smoke(seed=args.seed, n_requests=args.requests,
              engine_name=args.engine)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
