"""Fixpoint iterations per query (``ExecStats.iterations``), mean over the
window's queries."""


def read(run):
    iters = [a["stats"].iterations for a in run.answered
             if a["stats"] is not None]
    return sum(iters) / len(iters) if iters else None
