"""The reduction from trace events to the per-layer numbers, on a
synthetic trace and on a small one recorded on a TPU v5e."""
import json
import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _synthetic():
    return {
        "device": {"/device:TPU:0": [
            ("%while.1 = (s32[8]{0}, s32[]) while((s32[8]{0}, s32[]) %t)",
             1 * MS, 6 * MS, {}),
            ("%fusion.1 = s32[8]{0:T(1024)} fusion(s32[8]{0} %a)",
             1 * MS, 2 * MS, {}),
            ("%k.2 = (s32[8]{0}, s32[8]{0}) custom-call(s32[8]{0} %fusion.1)",
             3 * MS, 3 * MS, {}),
            ("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %custom-call.9)",
             6 * MS, 1 * MS, {}),
            ("%copy.4 = s32[8]{0} copy(s32[8]{0} %x)", 12 * MS, 1 * MS, {}),
            ("%fusion.5 = s32[8]{0} fusion(s32[8]{0} %y)", 30 * MS, 5 * MS,
             {}),                                      # after the window
        ]},
        "spans": [("query", 0, 8 * MS), ("answer_to_host", 8 * MS, 2 * MS),
                  ("between_queries", 10 * MS, 1 * MS),
                  ("query", 11 * MS, 3 * MS),
                  ("answer_to_host", 14 * MS, 6 * MS)],
    }


def test_opcode_and_short_names():
    name = ("%branch_0_fun.1 = (s32[256,47,128]{2,1,0:T(8,128)S(1)}, "
            "s32[4]{0}) custom-call(s32[6016]{0:T(1024)S(1)} %copy-done.13)")
    assert T.opcode(name) == "custom-call"
    assert T.short(name) == ("%branch_0_fun.1 = (s32[256,47,128], s32[4]) "
                             "custom-call")
    # an operand named after a custom call does not make a fusion a kernel
    assert T.opcode("%f = s32[8]{0} fusion(s32[8]{0} %custom-call.3)") \
        == "fusion"
    assert T.opcode("jit_run(1234)") == ""


def test_reduce_synthetic():
    r = T.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.007)       # [1,7] and [12,13] ms
    assert r["kernel_s"] == pytest.approx(0.003)     # the custom call
    assert r["glue_s"] == pytest.approx(0.004)       # leaves, not the while
    assert r["queries"] == 2
    assert r["device_ops"][0] == ["%k.2 = (s32[8], s32[8]) custom-call",
                                  pytest.approx(0.003)]
    # gaps: [13,20] answer_to_host, [7,12] mostly answer_to_host, [0,1] query
    assert [g[0] for g in r["idle_gaps"]] == ["answer_to_host",
                                               "answer_to_host", "query"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([0.007, 0.005,
                                                            0.001])


def test_reduce_without_device_or_spans_is_empty():
    assert T.reduce({"device": {}, "spans": [("query", 0, 5)]}) == {}
    assert T.reduce({"device": {"/device:TPU:0": []}, "spans": []}) == {}


def test_reduce_recorded_v5e_trace():
    """One kron15.bfs query traced on a TPU v5e: 6 iterations in one
    ``jit_run`` module; the record keeps the ``XLA Ops`` events (names cut
    to 400 characters) and the benchmark's host spans."""
    with open(os.path.join(DATA, "kron15_bfs_v5e_trace.json")) as f:
        rec = json.load(f)
    trace = {"device": {p: [tuple(e) for e in ev]
                        for p, ev in rec["device"].items()},
             "spans": [tuple(s) for s in rec["spans"]]}
    r = T.reduce(trace)
    assert r["queries"] == 1
    # the device ran the whole query: one module of rec["module_s"]
    assert r["busy_s"] == pytest.approx(rec["module_s"], rel=1e-3)
    assert 0 < r["busy_s"] <= r["window_s"]
    # the innermost operations tile the module: kernels plus glue is most of
    # it, and no nested parent is counted twice
    assert 0.9 * r["busy_s"] < r["kernel_s"] + r["glue_s"] <= r["busy_s"]
    kernels = [n for n, _t in r["device_ops"] if n.endswith("custom-call")]
    assert kernels and r["kernel_s"] > 0
    assert not any(n.split(" = ")[0].startswith(("%while", "%conditional"))
                   for n, _t in r["device_ops"])
