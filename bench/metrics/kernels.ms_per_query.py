"""Device milliseconds per query in Mosaic (Pallas) kernels, from the
trace."""


def read(run):
    t = run.trace
    if not t or not t.get("queries") or t.get("kernel_s", 0) <= 0:
        return None
    return 1000.0 * t["kernel_s"] / t["queries"]
