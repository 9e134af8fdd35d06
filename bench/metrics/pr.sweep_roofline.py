"""Share of the HBM roofline that the PageRank sweeps reach: 8 B for every
real arc of every iteration (its neighbour id and the contribution it
carries; ``ExecStats.edge_work`` counts the arcs), at the chip's peak
bandwidth, over the device's busy time in the traced window.  The vertex
terms (under 4% of the bytes at kron-15) are left out.  The work is fixed
by the graph, so a sweep that reads less padding reads higher."""


def read(run):
    t = run.trace
    arcs = sum(a["stats"].edge_work for a in run.answered
               if a["stats"] is not None)
    if not t or t.get("busy_s", 0) <= 0 or arcs <= 0:
        return None
    return 100.0 * 8 * arcs / run.peaks["hbm_bytes_per_s"] / t["busy_s"]
