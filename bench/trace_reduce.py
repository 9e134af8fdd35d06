"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

``load(path)`` turns an ``.xplane.pb`` into plain event tuples; ``reduce``
works on those alone, so a small recorded trace checks it
(``tests/test_trace.py``).  On a TPU the device's operations are the events
of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane; the benchmark's
own host spans (``query``, ``answer_to_host``, ``between_queries``) are
``TraceAnnotation`` events on a host plane.  Host and device events share
the profile's clock.
"""
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPANS = ("query", "answer_to_host", "between_queries")
KEEP_STATS = ("hlo_category",)      # the one event stat that is_kernel reads


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"device": {plane: [(name, start_ns, dur_ns, stats)]},
    "spans": [(name, start_ns, dur_ns)]}`` from one xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                device[plane.name] = [
                    (e.name, int(e.start_ns), int(e.duration_ns),
                     {k: v for k, v in e.stats if k in KEEP_STATS})
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    return {"device": device, "spans": sorted(spans, key=lambda s: s[1])}


# the opcode of an HLO instruction's text: ``%x = <shape> <opcode>(...``
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event, named by its instruction
    text (``%fusion.3 = s32[8]{0} fusion(...)``), or "" if it has none."""
    _lhs, eq, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs) if eq else None
    return m.group(1) if m else ""


def short(name: str) -> str:
    """``%fusion.3 = s32[8] fusion``: the instruction, its result shape
    (cut to 80 characters) and its opcode."""
    lhs, eq, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs) if eq else None
    if not m:
        return name[:120]
    shape = re.sub(r"\{[^}]*\}", "", (" " + rhs)[:m.start()].strip())
    return f"{lhs} = {shape[:80]} {m.group(1)}"


def is_kernel(name: str, stats: dict) -> bool:
    """A Mosaic (Pallas) kernel launch: on a TPU the only custom calls in
    the executor are its ``tpu_custom_call`` kernels."""
    return opcode(name) == "custom-call" \
        or stats.get("hlo_category") == "custom-call"


def leaves(events):
    """The events that hold no other event: ``XLA Ops`` nests a ``while``
    or ``conditional`` around the operations that it runs."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def _union(intervals):
    """Total length and merged list of ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy time (the union of all device operations), kernel and glue
    time (the sums of the innermost operations), the operations that took
    most time and the longest idle gaps, over the window that the host
    spans cover, averaged over the device planes."""
    spans = trace["spans"]
    if not spans or not trace["device"]:
        return {}
    w0 = min(s for _n, s, _d in spans)
    w1 = max(s + d for _n, s, d in spans)
    queries = sum(1 for n, _s, _d in spans if n == "query")
    planes = sorted(trace["device"])
    busy = kernel = glue = 0
    ops, gaps = {}, []
    for plane in planes:
        events = []
        for name, s, d, stats in trace["device"][plane]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                events.append((name, s0, e0 - s0, stats))
        for name, _s, d, stats in leaves(events):
            if is_kernel(name, stats):
                kernel += d
            else:
                glue += d
            key = short(name)
            ops[key] = ops.get(key, 0) + d
        b, merged = _union([(s, s + d) for _n, s, d, _st in events])
        busy += b
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, gs, ge))
    k = len(planes)
    gaps.sort(reverse=True)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / k / 1e9,
        "kernel_s": kernel / k / 1e9,
        "glue_s": glue / k / 1e9,
        "queries": queries,
        "device_ops": [[n, t / k / 1e9] for n, t in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[_host_doing(spans, gs, ge), g / 1e9]
                      for g, gs, ge in gaps[:top]],
    }


def _host_doing(spans, gs, ge) -> str:
    """The host span that overlaps the gap ``[gs, ge)`` the longest."""
    best, name = 0, "outside_spans"
    for n, s, d in spans:
        ov = min(ge, s + d) - max(gs, s)
        if ov > best:
            best, name = ov, n
    return name
