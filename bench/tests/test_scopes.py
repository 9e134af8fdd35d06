"""The reduction by the program's own names (``scopes.py``): on a synthetic
trace with nested program spans and scoped operations, and on one
``kron15.bfs`` query recorded on a TPU v5e with the program's spans."""
import json
import os

import pytest

import scopes as S
import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
Q = MS // 4                                   # a quarter millisecond
PATH = "jit(run)/while/body/cond/branch_1_fun/"

# the optimized HLO of the synthetic executor: each line's op_name metadata
HLO = f"""HloModule jit_run, is_scheduled=true
  %fusion.1 = s32[8]{{0:T(1024)}} fusion(%a), kind=kLoop, metadata={{op_name="{PATH}grafs.tile_activity/grafs.slot_gather/gather"}}
  %k.2 = (s32[8]{{0}}, s32[8]{{0}}) custom-call(%fusion.1), metadata={{op_name="{PATH}grafs_pull_sweep"}}
  %fusion.3 = s32[8]{{0}} fusion(%custom-call.9), metadata={{op_name="{PATH}grafs.merge/min"}}
  %fusion.6 = s32[64]{{0}} fusion(%b), metadata={{op_name="{PATH}grafs.slot_scatter/scatter"}}
  %copy.4 = s32[8]{{0}} copy(%x)
  ROOT %fusion.5 = s32[8]{{0}} fusion(%y), metadata={{op_name="{PATH}grafs.res_activity/gather"}}
"""


def _synthetic():
    """Two queries with their program spans, times in quarter
    milliseconds."""
    ops = [
        ("%while.1 = (s32[8]{0}, s32[]) while((s32[8]{0}, s32[]) %t)", 4, 24),
        ("%fusion.1 = s32[8]{0:T(1024)} fusion(s32[8]{0} %a)", 4, 8),
        ("%k.2 = (s32[8]{0}, s32[8]{0}) custom-call(s32[8]{0} %fusion.1)",
         12, 12),
        ("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %custom-call.9)", 24, 4),
        ("%fusion.6 = s32[64]{0} fusion(s32[8]{0} %b)", 31, 3),
        ("%copy.4 = s32[8]{0} copy(s32[8]{0} %x)", 48, 4),
        ("%fusion.5 = s32[8]{0} fusion(s32[8]{0} %y)", 120, 20),  # after
    ]
    bench = [("query", 0, 32), ("answer_to_host", 32, 8),
             ("between_queries", 40, 4), ("query", 44, 12),
             ("answer_to_host", 56, 24)]
    program = [("grafs.run_program", 2, 29), ("grafs.dispatch", 2, 2),
               ("grafs.device_wait", 4, 24), ("grafs.stats_to_host", 28, 3),
               ("grafs.run_program", 44, 11), ("grafs.device_wait", 46, 8)]
    return {"device": {"/device:TPU:0": [(n, s * Q, d * Q, {})
                                         for n, s, d in ops]},
            "spans": sorted(((n, s * Q, d * Q) for n, s, d in
                             bench + program), key=lambda s: s[1])}


def _bench_only(trace):
    """What ``trace_reduce.load`` keeps of the same trace."""
    return {"device": trace["device"],
            "spans": [s for s in trace["spans"] if s[0] in T.SPANS]}


def test_window_and_existing_outputs_unchanged():
    trace = _synthetic()
    plain = T.reduce(_bench_only(trace))
    assert plain["window_s"] == pytest.approx(0.020)
    assert plain["queries"] == 2
    r = S.reduce(trace, S.op_names([HLO]))
    assert r["queries"] == plain["queries"]
    # the program's spans in the trace move neither the window nor the
    # kernel and glue sums
    busy = sum(r["idle_by_span_s"].values())
    assert busy == pytest.approx(plain["window_s"] - plain["busy_s"])


def test_glue_by_scope_sums_to_glue():
    trace = _synthetic()
    plain = T.reduce(_bench_only(trace))
    r = S.reduce(trace, S.op_names([HLO]))
    glue = r["glue_by_scope_s"]
    assert sum(glue.values()) == pytest.approx(plain["glue_s"])
    assert glue == pytest.approx({
        "grafs.slot_gather": 0.002,         # innermost of two scopes
        "grafs.slot_scatter": 0.00075, "grafs.tile_activity": 0.0,
        "grafs.merge": 0.001, "grafs.res_activity": 0.0,   # after the window
        "unscoped": 0.001})                 # no op name
    per = S.per_query(r)
    assert per["glue.gather_ms_per_query"] == pytest.approx(1.0)
    assert per["glue.unscoped_ms_per_query"] == pytest.approx(0.5)
    assert ("grafs.slot_gather %fusion.1 = s32[8] fusion"
            in [n for n, _t in r["device_ops"]])
    assert r["device_ops"][0] == ["%k.2 = (s32[8], s32[8]) custom-call",
                                  pytest.approx(0.003)]


def test_idle_by_innermost_span_and_gap_names():
    r = S.reduce(_synthetic(), {})
    assert r["idle_by_span_s"] == pytest.approx({
        "query": 0.00075, "grafs.dispatch": 0.0005,
        "grafs.stats_to_host": 0.00075, "answer_to_host": 0.0075,
        "between_queries": 0.001, "grafs.run_program": 0.00075,
        "grafs.device_wait": 0.001})
    gaps = {round(t, 6): n for n, t in r["idle_gaps"]}
    # [7, 7.75] ms lies in query, run_program and stats_to_host alike: the
    # innermost names it
    assert gaps[0.00075] == "grafs.stats_to_host"
    assert gaps[0.001] == "query"           # [0, 1] ms: query overlaps most
    assert gaps[0.0035] == gaps[0.007] == "answer_to_host"


def test_frontend_is_run_program_less_device_wait():
    r = S.reduce(_synthetic(), {})
    assert r["frontend_s"] == pytest.approx([0.00125, 0.00075])
    assert S.per_query(r)["frontend.ms_per_query"] == pytest.approx(1.0)


def test_op_names_and_scopes():
    names = S.op_names([HLO])
    assert S.instruction("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %c)") \
        == "%fusion.3 = s32[8]{0} fusion"
    assert names["%fusion.5 = s32[8]{0} fusion"].endswith("res_activity/"
                                                          "gather")
    assert names["%copy.4 = s32[8]{0} copy"] == ""
    # one instruction spelled alike in two executors under two op names
    other = HLO.replace("grafs.merge/min", "grafs.slot_gather/min")
    assert S.op_names([HLO, other])["%fusion.3 = s32[8]{0} fusion"] == ""
    assert S.scope(PATH + "grafs.merge/jit(rem)/rem") == "grafs.merge"
    assert S.scope(PATH + "grafs_pull_sweep/add") == S.UNSCOPED
    assert S.scope("") == S.UNSCOPED


def test_reduce_recorded_v5e_trace_by_scope():
    """One kron15.bfs query traced on a TPU v5e with the program's spans
    (6 iterations in one ``jit_run`` module; names cut to 400 characters)
    and the op names of its executor's optimized HLO."""
    with open(os.path.join(DATA, "kron15_bfs_scoped_v5e_trace.json")) as f:
        rec = json.load(f)
    trace = {"device": {p: [tuple(e) for e in ev]
                        for p, ev in rec["device"].items()},
             "spans": [tuple(s) for s in rec["spans"]]}
    plain = T.reduce(_bench_only(trace))
    r = S.reduce(trace, rec["op_names"])
    assert r["queries"] == plain["queries"] == 1
    glue = r["glue_by_scope_s"]
    assert sum(glue.values()) == pytest.approx(plain["glue_s"], rel=1e-9)
    # the contributing-tile OR is most of the glue; little is unscoped
    assert max(glue, key=glue.get) == "grafs.res_activity"
    assert glue["grafs.res_activity"] > 0.8 * plain["glue_s"]
    assert glue["unscoped"] < 0.1 * plain["glue_s"]
    assert all(glue[sc] > 0 for sc in S.SCOPES)
    # the host spans of the query: the device waits on the host for a few
    # milliseconds, most of it in grafs.stats_to_host
    (front,) = r["frontend_s"]
    assert 0 < front < 0.05
    idle = r["idle_by_span_s"]
    assert max(idle, key=idle.get) == "grafs.stats_to_host"
    assert sum(v for n, v in idle.items() if n.startswith("grafs.")) \
        > 0.9 * sum(idle.values())
