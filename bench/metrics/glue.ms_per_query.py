"""Device milliseconds per query in every other device operation (XLA
gathers, scatters, tile activity, folds), from the trace."""


def read(run):
    t = run.trace
    if not t or not t.get("queries") or t.get("glue_s", 0) <= 0:
        return None
    return 1000.0 * t["glue_s"] / t["queries"]
