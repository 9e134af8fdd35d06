"""One run of one benchmark cell.

The cell names a configuration (``configs/<name>.json``: the graph and its
list of roots) and a traffic mix (``traffic/<name>.json``: the driver and
the mix of query kinds, with whatever parameters the driver reads).
Everything that belongs to one of them is found by that name:
``gen/<generator>.py`` makes the graph, ``queries/<kind>.py`` states a query
kind, its reference and its control, ``drivers/<driver>.py`` drives the
window, and ``metrics/<metric>.py`` reads one per-layer metric.

A run: generate the graph and its roots, make one round of requests (every
root asked once for each kind of the mix, as often as the kind's share, in
an order drawn from the seed), hand the edges to the program once
(``structure.from_edges``), warm up every kind of the mix on a root outside
the list (twice: the first query on a new graph builds its layouts), then
let the driver run the window.  After the window it checks the guards (TPU,
the ``pallas`` engine, no fallback, a Mosaic kernel in every executor, no
compile inside the window) and compares every answer of the window, by its
kind, with the plain reference.
"""
import collections
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
import types

import numpy as np

import chip
import graph as G
import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXACT = 0                # answers are exact: no vertex may differ
TRAFFIC_KEYS = ("driver", "mix")     # what the harness reads of a traffic

Request = collections.namedtuple("Request", "kind root")


def load_bench(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(bench: dict, name: str, root: str = ROOT):
    """The cell ``name``: its workload entry, configuration and traffic."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return w, cfg, traffic


def metrics_of(entries, name: str):
    """The metric entries that a cell ``name`` reports."""
    return [m for m in entries if name in m.get("workloads", [name])]


def round_of(roots, mix: dict, seed: int) -> list:
    """One round of requests: every root asked once for each query kind of
    the mix, as many times as the kind's share, in an order drawn from the
    seed.  Every seed gives the same requests, so the same work."""
    reqs = [Request(kind, r) for r in roots for kind in sorted(mix)
            for _ in range(int(mix[kind]))]
    return G.order(reqs, seed)


def _traffic(traffic: dict, driver) -> list:
    """What is wrong with a traffic file: keys that neither the harness nor
    its driver reads, or a mix that asks for nothing."""
    known = set(TRAFFIC_KEYS) | set(getattr(driver, "PARAMS", ()))
    out = [f"traffic key {k!r} is read by neither the harness nor the "
           f"driver {traffic['driver']!r}"
           for k in sorted(set(traffic) - known)]
    mix = traffic.get("mix") or {}
    if not mix or any(int(v) < 1 or int(v) != v for v in mix.values()):
        out.append(f"traffic mix {mix!r} must give each kind a whole share "
                   ">= 1")
    return out


class System:
    """What a driver drives: the program under test (``program.engine``,
    ``.fusion``, ``.usecases``, ``.structure``, ``.ops``), the benchmark's
    graph ``g`` and the program's ``pg`` built from it, and the query kinds
    of the mix (``queries[kind]``, each a ``queries/<kind>.py`` module).
    ``issue(request)`` and ``fetch(handle)`` answer one request alone
    through the Pallas engine.  With ``control`` set they answer
    through each kind's control instead and ``pg`` is None: a driver that
    builds its own path from ``program`` and ``pg`` answers through
    ``issue`` and ``fetch`` then."""

    def __init__(self, program, g, pg, queries, control=False):
        self.program, self.g, self.pg = program, g, pg
        self.queries, self.control = queries, control
        self._controls = ({k: q.control(g) for k, q in queries.items()}
                          if control else None)

    def issue(self, req):
        if self.control:
            return self._controls[req.kind](req.root)
        p = self.program
        spec = self.queries[req.kind].spec(p.usecases, req.root)
        return p.engine.run_program(self.pg, p.fusion.fuse(spec),
                                    engine="pallas", fallback=False)

    def fetch(self, handle):
        return handle if self.control else np.asarray(handle.value)


class Answers:
    """Every answer of the window: the first answer to each request (kind
    and root) is kept for the reference, and every later answer to it is
    compared with it (bitwise) as it arrives."""

    def __init__(self):
        self.first = {}           # request -> first answer
        self.repeats = {}         # request -> later answers equal to it
        self.differing = []       # (request, answer) unequal to the first

    def __call__(self, req, answer):
        prev = self.first.get(req)
        if prev is None:
            self.first[req] = answer
            self.repeats[req] = 0
        elif np.array_equal(prev, answer):
            self.repeats[req] += 1
        else:
            self.differing.append((req, answer))

    def check(self, queries, g):
        """``(wrong vertices, wrong answers)`` against the reference of
        each answer's kind."""
        wrong = failed = 0
        refs = {}

        def bad(req, answer):
            q = queries[req.kind]
            if req not in refs:
                refs[req] = q.reference(g, req.root)
            return q.mismatches(answer, refs[req])

        for req, answer in self.first.items():
            n = bad(req, answer)
            wrong += n
            failed += (1 + self.repeats[req]) if n else 0
        for req, answer in self.differing:
            n = bad(req, answer)
            wrong += n
            failed += n > 0
        return wrong, failed


class Run:
    """What the per-layer metric readers read."""

    def __init__(self, setup, answered, trace, work_bytes, peaks):
        self.setup = setup
        self.answered = answered
        self.trace = trace
        self.work_bytes = work_bytes
        self.peaks = peaks


def _program(root: str):
    """Import the system under test from the checkout's ``src/``."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"no program at {src}/repro: run from a checkout "
                          "of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import engine, fusion, usecases
    from repro.graph import structure
    from repro.kernels import ops
    return types.SimpleNamespace(engine=engine, fusion=fusion,
                                 usecases=usecases, structure=structure,
                                 ops=ops)


def _spread(values):
    """min, median and max of a list, for the run's log."""
    if not values:
        return None
    return [min(values), float(np.median(values)), max(values)]


def _peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def run(name: str, seed: int, seconds: float, trace: bool, t0: float,
        control: bool = False, require_chip: bool = True, bench=None,
        root: str = ROOT, trace_dir=None, out=sys.stdout, err=sys.stderr):
    """One run of cell ``name``; prints the result line and returns the
    exit code."""
    bench = bench if bench is not None else load_bench(root)
    w, cfg, traffic = resolve(bench, name, root)
    try:
        program = _program(root)
    except ImportError as e:
        print(f"bench: {e}", file=err)
        return 1
    driver = G.load_module("drivers", traffic["driver"])
    bad_traffic = _traffic(traffic, driver)
    if bad_traffic:
        print("bench: " + "; ".join(bad_traffic), file=err)
        return 1
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_chip:
        problem = chip.device_problem(devices, int(w["chips"]))
        if problem:
            print(f"bench: {problem}", file=err)
            return 1
        peaks = _peaks(devices[0].device_kind)
    else:
        peaks = _peaks("TPU v5 lite")
    clock = chip.CompileClock(jax.monitoring)
    mix = traffic["mix"]
    queries = {kind: G.load_module("queries", kind) for kind in sorted(mix)}

    g = G.build(cfg)
    roots, warm_root = G.roots(g, int(cfg["trials"]), cfg["graph_seed"])
    requests = round_of(roots, mix, seed)
    pg = None
    if not control:
        src, dst, wt = g.arcs()
        pg = program.structure.from_edges(g.n, src, dst, weight=wt)
    system = System(program, g, pg, queries, control=control)

    # warm-up: each kind twice on a root outside the list; the first query
    # on the new graph also builds its layouts
    setup = {"compile_s": clock.take()[0], "layout_s": None,
             "warm_query_s": 0.0}
    problems = []
    for kind in queries:
        req = Request(kind, warm_root)
        ta = time.perf_counter()
        warm = system.issue(req)
        first = system.fetch(warm)
        tb = time.perf_counter()
        c1 = clock.take()[0]
        again = system.fetch(system.issue(req))
        tc = time.perf_counter()
        c2 = clock.take()[0]
        setup["compile_s"] += c1 + c2
        if setup["layout_s"] is None:
            setup["layout_s"] = (tb - ta) - c1 - (tc - tb)
        setup["warm_query_s"] += tc - tb
        if not control:
            problems += chip.engine_problems(warm.stats)
        if np.asarray(first).tobytes() != np.asarray(again).tobytes():
            problems.append(f"a repeated warm-up {kind} query changed its "
                            "answer")
        del warm, first, again

    trace_tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda _name: contextlib.nullcontext())
    keep = Answers()
    setup_s = time.perf_counter() - t0
    if trace:
        jax.profiler.start_trace(trace_tmp)
        # the first transfer to the host after the trace starts can stall
        # for seconds: take it here, outside the window's spans
        np.asarray(jax.device_put(np.zeros(8, np.float32)))
    window_s, answered = driver.run(system, requests, seconds, span, keep,
                                    traffic)
    if trace:
        jax.profiler.stop_trace()
    _cs, window_compiles, _hits = clock.take()

    used = devices[:int(w["chips"])]
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    if not control:
        for a in answered:
            if a["stats"] is None:
                problems.append("an answer of the window carries no "
                                "ExecStats")
            else:
                problems += chip.engine_problems(a["stats"])
        if require_chip:
            problems += chip.kernel_problems(
                program.ops.compiled_executor_texts())
    problems = sorted(set(problems))

    t_check = time.perf_counter()
    wrong, failed = keep.check(queries, g)
    check_s = time.perf_counter() - t_check

    label, comp_v, comp_e = G.components(g)
    edges = work_bytes = 0
    for a in answered:
        q, c = queries[a["request"].kind], label[a["request"].root]
        edges += int(comp_e[c])
        # 4 B words: both arcs of each edge, each state word read and written
        work_bytes += 8 * (q.ARC_WORDS * int(comp_e[c])
                           + q.STATE_WORDS * int(comp_v[c]))
    e2e = {"teps": edges / window_s / 1e6, "setup_s": setup_s}

    reduced = None
    if trace:
        xplane = T.find_xplane(trace_tmp)
        reduced = T.reduce(T.load(xplane))
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)
            shutil.copy(xplane, os.path.join(trace_dir, f"{name}.{seed}"
                                             ".xplane.pb"))
        shutil.rmtree(trace_tmp, ignore_errors=True)

    units = {m["name"]: m["unit"] for m in
             bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if trace:
        state = Run(setup, answered, reduced, work_bytes, peaks)
        for m in metrics_of(bench["per_layer"], name):
            value = G.load_module("metrics", m["name"]).read(state)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": units[m["name"]]}
    else:
        for m in metrics_of(bench["end_to_end"], name):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": units[m["name"]]}

    checks = {"wrong_vertices": [wrong, EXACT],
              "failed_queries": [failed, 0],
              "guard_violations": [len(problems), 0],
              "window_compiles": [window_compiles, 0]}
    correct = bool(answered) and all(
        v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": len(answered),
              "failed": failed, "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak}}
    if trace and reduced:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    info = {"cell": name, "seed": seed, "control": control,
            "end_to_end": e2e, "setup": setup, "window_s": window_s,
            "query_s": _spread([a["seconds"] for a in answered]),
            "iterations": _spread([a["stats"].iterations for a in answered
                                   if a["stats"] is not None]),
            "requests": [list(r) for r in requests],
            "checked": len(keep.first) + len(keep.differing),
            "check_s": check_s,
            "problems": problems}
    print("bench: " + json.dumps(info), file=err)
    print(json.dumps(result), file=out, flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=err)
    err.flush()
    return 0
