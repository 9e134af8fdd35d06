#!/usr/bin/env python3
"""Reduction of a JAX profiler trace by the program's own names.

    python3 bench/scopes.py <trace.xplane.pb> <optimized-hlo.txt>...

reads a trace that ``run.py --trace 1 --trace-dir DIR`` kept, and the
optimized HLO text of each executor that ran (``ops.compiled_executor_texts``),
and prints one JSON object: the device time of the XLA glue split by the
innermost ``grafs.`` device scope of each operation, the host time per query
that the device does not overlap (``grafs.run_program`` less its
``grafs.device_wait``), and the device's idle gaps named after the host span
the host was in (DESIGN.md §16 lists the spans and scopes).

On a TPU v5e the ``XLA Ops`` events carry no op-name stat: an event is named
by its instruction's text, so its scope is read from the ``op_name``
metadata of the same instruction in the optimized HLO (``op_names``).  The
window is that of ``trace_reduce.reduce``, the span of the benchmark's own
host spans, and the innermost operations are the same, so the glue of the
scopes sums to its ``glue_s``.
"""
import json
import re
import sys

import trace_reduce as T

PROGRAM = "grafs."
UNSCOPED = "unscoped"
OUTSIDE = "outside_spans"
# each device scope, and the glue outside them, with the name of its
# per-query glue metric
SCOPES = {"grafs.slot_gather": "glue.gather_ms_per_query",
          "grafs.slot_scatter": "glue.scatter_ms_per_query",
          "grafs.tile_activity": "glue.tile_activity_ms_per_query",
          "grafs.res_activity": "glue.res_activity_ms_per_query",
          "grafs.merge": "glue.merge_ms_per_query",
          UNSCOPED: "glue.unscoped_ms_per_query"}
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def instruction(text: str) -> str:
    """An HLO instruction's text up to its opcode's parenthesis,
    ``%fusion.3 = s32[8]{0:T(1024)} fusion``: what a trace event's name and
    the optimized HLO line of the same instruction have in common (the
    event spells out operand shapes, the HLO text does not)."""
    lhs, _eq, rhs = text.strip().removeprefix("ROOT ").partition(" = ")
    m = T._OPCODE.search(" " + rhs)
    return f"{lhs} = {rhs[:m.end() - 2]}" if m else ""


def op_names(texts) -> dict:
    """``{instruction: op_name}`` over optimized HLO texts.  An instruction
    that two executors spell alike under different op names maps to ""."""
    out = {}
    for text in texts:
        for line in text.splitlines():
            if " = " not in line or not line.lstrip().startswith(
                    ("%", "ROOT %")):
                continue
            key, m = instruction(line), _OP_NAME.search(line)
            name = m.group(1) if m else ""
            out[key] = name if out.get(key, name) == name else ""
    return out


def load(path: str) -> dict:
    """``trace_reduce.load`` with the program's host spans (``grafs.*``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    device[plane.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns),
                         {k: v for k, v in e.stats if k in T.KEEP_STATS})
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in T.SPANS or e.name.startswith(PROGRAM):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    return {"device": device, "spans": sorted(spans, key=lambda s: s[1])}


def scope(op_name: str) -> str:
    """The innermost ``grafs.`` device scope of an op name, or
    ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _host_doing(spans, gs, ge) -> str:
    """The host span that overlaps the gap ``[gs, ge)`` the longest; of
    equal overlaps the innermost (shortest)."""
    best, name = (0, 0), OUTSIDE
    for n, s, d in spans:
        key = (min(ge, s + d) - max(gs, s), -d)
        if key[0] > 0 and key > best:
            best, name = key, n
    return name


def _innermost(spans, gs, ge, out: dict) -> None:
    """Add to ``out[span]`` each part of the gap ``[gs, ge)`` that lies in
    ``span`` and in no span inside it (host spans of one thread nest)."""
    cuts = sorted({gs, ge} | {x for _n, s, d in spans for x in (s, s + d)
                              if gs < x < ge})
    for a, b in zip(cuts, cuts[1:]):
        inside = [(d, n) for n, s, d in spans if s <= a and b <= s + d]
        name = min(inside)[1] if inside else OUTSIDE
        out[name] = out.get(name, 0) + (b - a)


def reduce(trace: dict, names: dict, top: int = 10) -> dict:
    """Glue seconds by scope (``names`` from ``op_names``), the operations
    that took most time with their scopes, the host seconds of each query
    outside ``grafs.device_wait``, the device's idle seconds by the
    innermost host span the host was in meanwhile, and the longest idle
    gaps, each named after the span that overlaps it longest, over the
    window of ``trace_reduce.reduce``, averaged over the device planes."""
    bench = [s for s in trace["spans"] if s[0] in T.SPANS]
    if not bench or not trace["device"]:
        return {}
    w0 = min(s for _n, s, _d in bench)
    w1 = max(s + d for _n, s, d in bench)
    spans = [s for s in trace["spans"] if s[1] < w1 and s[1] + s[2] > w0]
    planes = sorted(trace["device"])
    glue = dict.fromkeys(SCOPES, 0)
    ops, idle, gaps = {}, {}, []
    for plane in planes:
        events = []
        for name, s, d, stats in trace["device"][plane]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                events.append((name, s0, e0 - s0, stats))
        for name, _s, d, stats in T.leaves(events):
            if T.is_kernel(name, stats):
                key = T.short(name)
            else:
                sc = scope(names.get(instruction(name), ""))
                glue[sc] += d
                key = f"{sc} {T.short(name)}"
            ops[key] = ops.get(key, 0) + d
        _b, merged = T._union([(s, s + d) for _n, s, d, _st in events])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                _innermost(spans, gs, ge, idle)
                gaps.append((ge - gs, _host_doing(spans, gs, ge)))
    k = len(planes)
    runs = [s for s in spans if s[0] == "grafs.run_program"]
    waits = [s for s in spans if s[0] == "grafs.device_wait"]
    frontend = [(d - sum(wd for _w, ws, wd in waits
                         if s <= ws and ws + wd <= s + d)) / 1e9
                for _n, s, d in runs]
    gaps.sort(reverse=True)
    return {
        "queries": sum(1 for s in bench if s[0] == "query"),
        "glue_by_scope_s": {sc: t / k / 1e9 for sc, t in glue.items()},
        "frontend_s": frontend,
        "idle_by_span_s": {n: t / k / 1e9 for n, t in
                           sorted(idle.items(), key=lambda x: -x[1])},
        "device_ops": [[n, t / k / 1e9] for n, t in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, g / k / 1e9] for g, n in gaps[:top]],
    }


def per_query(r: dict) -> dict:
    """The reduction's numbers per query of the window, in milliseconds."""
    q = r["queries"]
    out = {SCOPES[sc]: 1e3 * t / q for sc, t in r["glue_by_scope_s"].items()}
    if r["frontend_s"]:
        out["frontend.ms_per_query"] = \
            1e3 * sum(r["frontend_s"]) / len(r["frontend_s"])
    return out


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__.strip().split("\n\n")[1], file=sys.stderr)
        return 2
    texts = []
    for path in argv[1:]:
        with open(path) as f:
            texts.append(f.read())
    trace = load(argv[0])
    r = reduce(trace, op_names(texts))
    if not r:
        print("scopes: the trace holds no window or no device",
              file=sys.stderr)
        return 1
    bench = {"device": trace["device"],
             "spans": [s for s in trace["spans"] if s[0] in T.SPANS]}
    print(json.dumps({"per_query": per_query(r), "whole": T.reduce(bench),
                      **r}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
