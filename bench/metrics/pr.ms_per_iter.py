"""Device busy milliseconds per fixpoint iteration: the traced window's
``busy_s`` over the iterations (``ExecStats.iterations``) of its queries."""


def read(run):
    t = run.trace
    iters = sum(a["stats"].iterations for a in run.answered
                if a["stats"] is not None)
    if not t or t.get("busy_s", 0) <= 0 or iters <= 0:
        return None
    return 1000.0 * t["busy_s"] / iters
