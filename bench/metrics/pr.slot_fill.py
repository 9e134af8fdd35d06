"""Percent of the slots the sweep kernels ran that hold a real arc:
``ExecStats.edge_work`` over ``tiles_swept`` tiles of ``tile_shape``
(block_v × block_e) slots, summed over the window's queries.  A program
that does not count the tiles it sweeps reports nothing."""


def read(run):
    arcs = slots = 0
    for a in run.answered:
        st = a["stats"]
        shape = getattr(st, "tile_shape", ()) if st is not None else ()
        if len(shape) != 2 or not getattr(st, "tiles_swept", 0):
            continue
        arcs += st.edge_work
        slots += st.tiles_swept * shape[0] * shape[1]
    return 100.0 * arcs / slots if slots else None
