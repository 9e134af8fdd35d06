#!/usr/bin/env python3
"""Run one cell of the GAP graph-analytics benchmark on a TPU.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--control 1] [--trace-dir DIR]

The cells, configurations and metrics are those of ``BENCHMARK.json`` at
the root of the checkout.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
``--trace 0``, per-layer with ``--trace 1``), ``device``, with ``--trace 1``
a ``breakdown``, and last the ``checks``, each number compared beside its
limit; the same checks end standard error.  ``--control 1`` puts the
query's control (the reference one precision lower, or with a stated
guarantee broken) in the program's place; its run must come out incorrect.
``--trace-dir`` keeps the raw trace of a traced run there.

It exits non-zero, with no result line, when JAX sees no TPU or fewer chips
than the cell asks for, or when the checkout holds no program under
``src/``.  JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR``
if set, else ``.jax_cache/`` in the checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T0, control=bool(args.control),
                       trace_dir=args.trace_dir)


if __name__ == "__main__":
    sys.exit(main())
