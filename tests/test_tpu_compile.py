"""The main-path Pallas kernels compile for a TPU v5e at the widths
``chip_smoke.py`` runs, without a chip.

Mosaic refuses what the interpreter accepts — unaligned blocks, in-kernel
vector gathers, SMEM or VMEM overflow — so every test here lowers a sweep
with ``interpret=False`` for a *described* v5e and compiles it with the
installed TPU compiler: ER-20 (2^20 rows, width 128 → one slot tile per row)
and RMAT-15 (2^15 rows, width 31·128 → 31 slot tiles per row), plus the
vmapped batch sweep of the serving path.  At RMAT-15 the sweeps also
compile over a live-tile list of the longest length ``live_grid`` passes,
which must fit SMEM beside the bitmap.  Each compiled program must hold
its Mosaic kernels (``tpu_custom_call``) and fit the chip's HBM.

The topology is described inside a module fixture — never at import — so
only the test worker that runs this file loads the TPU compiler.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import edge_reduce as er

HBM_BYTES = 16 * 10 ** 9            # v5e: 16 GB per chip
# (rows, padded width, real slots) of the in- and out-layouts
WIDTHS = {"ER-20": (2 ** 20, 128, 16_777_069),
          "RMAT-15": (2 ** 15, 31 * 128, 467_612)}
# a WSP-shaped fused round: min hop count, then max capacity among ties
PLANS = (((0, "min"), (1, "max")),)
IDENTS = {0: 2 ** 30 - 1, 1: float("-inf")}
P_FNS = {0: lambda env: env["n"] + 1,
         1: lambda env: jnp.minimum(env["n"], env["c"])}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args, kernels=1, operand=None):
    """Compile for the described v5e; the program holds ``kernels`` Mosaic
    kernels, each of them taking ``operand`` (an HLO shape) when given, and
    fits HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= kernels
    if operand is not None:
        assert all(operand in line for line in calls), operand
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2 ** 30:.2f} GiB"


def _layout(spec, n, width):
    """(nbrs, weight, capacity, mask, tile_act) of one blocked-ELL layout."""
    return (spec((n, width), jnp.int32), spec((n, width), jnp.float32),
            spec((n, width), jnp.float32), spec((n, width), jnp.bool_),
            spec((n // er.BLOCK_V, width // er.BLOCK_E), jnp.int32))


def _slots(spec, e):
    """A layout's slot list (``structure.slot_list``): the gathers the
    engines run go through it."""
    return (spec((e,), jnp.int32), spec((e,), jnp.int32))


def _states(spec, n, batch=()):
    return {0: spec(batch + (n,), jnp.int32),
            1: spec(batch + (n,), jnp.float32)}


@pytest.mark.parametrize("need_haspred", [False, True])
@pytest.mark.parametrize("graph", sorted(WIDTHS))
def test_pull_sweep_compiles(spec, graph, need_haspred):
    n, width, e = WIDTHS[graph]

    def sweep(nbrs, w, c, mask, tile_act, states, active, outdeg, slots):
        red, hp = er.fused_ell_sweep(
            nbrs, w, c, mask, tile_act, states, active, outdeg, plans=PLANS,
            idents=IDENTS, p_fns=P_FNS, nv=n, need_haspred=need_haspred,
            slots=slots, interpret=False)
        return red, hp

    _compile(sweep, *_layout(spec, n, width), _states(spec, n),
             spec((n,), jnp.int32), spec((n,), jnp.float32), _slots(spec, e))


@pytest.mark.parametrize("graph", sorted(WIDTHS))
def test_push_sweep_compiles(spec, graph):
    n, width, _e = WIDTHS[graph]

    def sweep(dsts, w, c, mask, tile_act, states, active, outdeg):
        red, _ = er.fused_ell_push_sweep(
            dsts, w, c, mask, tile_act, states, active, outdeg, plans=PLANS,
            idents=IDENTS, p_fns=P_FNS, nv=n, resolution="scatter",
            interpret=False)
        return red

    _compile(sweep, *_layout(spec, n, width), _states(spec, n),
             spec((n,), jnp.int32), spec((n,), jnp.float32))


@pytest.mark.parametrize("graph", sorted(WIDTHS))
def test_sorted_resolution_compiles(spec, graph):
    """The push sweep plus the dst-sorted resolution pass: two kernels."""
    n, width, e = WIDTHS[graph]

    def sweep(dsts, w, c, mask, tile_act, states, active, outdeg, res_slots,
              res_tile_act):
        red, _ = er.fused_ell_push_sweep(
            dsts, w, c, mask, tile_act, states, active, outdeg, plans=PLANS,
            idents=IDENTS, p_fns=P_FNS, nv=n, resolution="sorted",
            res=(None, None, res_tile_act), res_slots=res_slots,
            interpret=False)
        return red

    _compile(sweep, *_layout(spec, n, width), _states(spec, n),
             spec((n,), jnp.int32), spec((n,), jnp.float32), _slots(spec, e),
             spec((n // er.BLOCK_V, width // er.BLOCK_E), jnp.int32),
             kernels=2)


def test_vmapped_batch_sweep_compiles(spec):
    """The serving path vmaps the sweep over a batch of queries: per-query
    state, frontier and tile activity, one shared layout (ER-20, B=4)."""
    n, width, e = WIDTHS["ER-20"]
    batch = 4

    def batched(nbrs, w, c, mask, tile_act, states, active, slots):
        def one(t, s, a):
            red, _ = er.fused_ell_sweep(
                nbrs, w, c, mask, t, s, a, jnp.ones((n,), jnp.float32),
                plans=PLANS, idents=IDENTS, p_fns=P_FNS, nv=n, slots=slots,
                interpret=False)
            return red
        return jax.vmap(one)(tile_act, states, active)

    _compile(batched, *_layout(spec, n, width)[:4],
             spec((batch, n // er.BLOCK_V, width // er.BLOCK_E), jnp.int32),
             _states(spec, n, (batch,)), spec((batch, n), jnp.int32),
             _slots(spec, e))


@pytest.mark.parametrize("sweep", ["pull", "pull-haspred", "push",
                                   "push-sorted"])
def test_live_list_sweeps_compile(spec, sweep):
    """The pull, push and resolve kernels over a live-tile list at RMAT-15:
    a 1-D grid over the longest list ``live_grid`` passes, which rides in
    SMEM beside the packed bitmap as a second scalar-prefetch operand."""
    n, width, e = WIDTHS["RMAT-15"]
    n_tiles = (n // er.BLOCK_V) * (width // er.BLOCK_E)
    assert er.LIVE_LIST_MAX < n_tiles
    live = spec((er.LIVE_LIST_MAX,), jnp.int32)
    assert er.live_grid(live, n_tiles) is live
    tiles = spec((n // er.BLOCK_V, width // er.BLOCK_E), jnp.int32)
    kw = dict(plans=PLANS, idents=IDENTS, p_fns=P_FNS, nv=n, interpret=False)

    def pull(nbrs, w, c, mask, tile_act, states, active, outdeg, slots,
             live):
        return er.fused_ell_sweep(
            nbrs, w, c, mask, tile_act, states, active, outdeg,
            need_haspred=sweep == "pull-haspred", slots=slots, live=live,
            **kw)

    def push(dsts, w, c, mask, tile_act, states, active, outdeg, slots,
             live):
        if sweep == "push":
            return er.fused_ell_push_sweep(
                dsts, w, c, mask, tile_act, states, active, outdeg,
                resolution="scatter", live=live, **kw)[0]
        return er.fused_ell_push_sweep(
            dsts, w, c, mask, tile_act, states, active, outdeg,
            resolution="sorted", res=(None, None, tile_act), res_slots=slots,
            live=live, res_live=live, **kw)[0]

    _compile(pull if sweep.startswith("pull") else push,
             *_layout(spec, n, width)[:4], tiles, _states(spec, n),
             spec((n,), jnp.int32), spec((n,), jnp.float32), _slots(spec, e),
             live, kernels=2 if sweep == "push-sorted" else 1,
             operand=f"s32[{er.LIVE_LIST_MAX}]")



# The benchmark's GAP graphs: (vertices, padded width of both layouts, arcs,
# (width, tiles) of each contrib class as structure.contrib_classes builds
# them, length of each layout's live-tile list as structure.live_tiles
# builds it).
GAP = {"gap-kron-15": (2 ** 15, 47 * 128, 882_416,
                       [(1, 3), (2, 2), (4, 6), (8, 44), (16, 220),
                        (32, 812), (64, 1364), (128, 3186), (256, 1736),
                        (461, 309)], 7_682),
       "gap-urand-20": (2 ** 20, 128, 33_553_908,
                        [(256, 68_665), (333, 62_407)], 131_072)}


def _gap_layout(spec, graph):
    """(tile_nnz, the executor arguments of one layout, the live-tile grid
    ``live_grid`` passes) at a GAP graph's shapes: a list at kron-15, the
    full grid (None) at urand-20, where every tile is live."""
    n, width, arcs, _classes, n_live = GAP[graph]
    tiles = spec((n // er.BLOCK_V, width // er.BLOCK_E), jnp.int32)
    live = er.live_grid(spec((n_live,), jnp.int32),
                        (n // er.BLOCK_V) * (width // er.BLOCK_E))
    return tiles, [*_layout(spec, n, width)[:4], tiles, *_slots(spec, arcs),
                   live], live


@pytest.mark.parametrize("graph", sorted(GAP))
def test_bfs_fixpoint_compiles(spec, graph):
    """The whole BFS fixpoint — both sweeps, the sorted resolution and the
    contributing-tile activity over the compact class table — at the
    benchmark graphs' shapes, with its three kernels, within the chip's
    HBM: over the live-tile lists at kron-15 and the full grids at
    urand-20.  (With each class's lists stored as rows rather than columns
    the urand-20 fixpoint took minutes to compile.)"""
    from repro.core import engine, fusion, synthesis, usecases
    from repro.kernels import ops
    n, width, arcs, classes, n_live = GAP[graph]
    round_ = fusion.fuse(usecases.bfs(0)).rounds[0][1]
    comps, plans = engine._round_runtime(round_,
                                         synthesis.synthesize_round(round_))
    use, dense, switch_k, resolution = ops._apply_plan(
        None, "auto", ops.DENSE_FRONTIER, "auto", "sorted", True)
    run = ops._build_pallas_executor(
        comps, plans, n, 2 * n + 4, 0.0, er.BLOCK_V, er.BLOCK_E, False, use,
        dense, switch_k, resolution)
    tiles, layout, live = _gap_layout(spec, graph)
    assert (live is None) == (graph == "gap-urand-20")
    contrib = tuple((spec((r,), jnp.int32), spec((w, r), jnp.int32))
                    for w, r in classes)
    _compile(run, *layout, *layout, spec((n,), jnp.int32),
             spec((n,), jnp.float32), *_slots(spec, arcs), contrib, tiles,
             live, spec((len(comps),), jnp.int32), kernels=3,
             operand=None if live is None else f"s32[{n_live}]")


def test_pr_fixpoint_compiles(spec):
    """The whole PageRank fixpoint at the GAP kron-15 shapes: the pull−
    recompute of every live tile (the float ``sum`` sweep with the has-pred
    probe), the epilogue and the L1 stopping rule in the loop condition —
    one pull layout, one kernel, within the chip's HBM."""
    from repro.core import engine, fusion, synthesis, usecases
    from repro.kernels import ops
    n, _width, _arcs, _classes, n_live = GAP["gap-kron-15"]
    round_ = fusion.fuse(usecases.pagerank()).rounds[0][1]
    comps, plans = engine._round_runtime(round_,
                                         synthesis.synthesize_round(round_))
    use, dense, switch_k, resolution = ops._apply_plan(
        None, "auto", ops.DENSE_FRONTIER, "auto", "sorted", False)
    assert use == ("pull",)
    run = ops._build_pallas_executor(
        comps, plans, n, 2 * n + 4, 0.0, er.BLOCK_V, er.BLOCK_E, False, use,
        dense, switch_k, resolution)
    _tiles, layout, live = _gap_layout(spec, "gap-kron-15")
    assert live is not None
    _compile(run, *layout, spec((n,), jnp.int32), spec((n,), jnp.float32),
             spec((len(comps),), jnp.int32), operand=f"s32[{n_live}]")
