"""Graph containers shared by the GraFS engines and the GNN models.

Edges are stored twice, in destination-sorted order (pull / CSR-style:
``segment_*`` reductions key on ``dst``) and in source-sorted order
(push / CSC-style: frontier-masked scatters key on ``src``).  Both orders
refer to the same logical edge set; per-edge data (weight, capacity) is
carried alongside each order so engines never re-permute at run time.

A ``BlockedELL`` layout additionally pads per-vertex in-degrees to a fixed
width so the Pallas TPU kernel sees fully regular tiles (see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import weakref
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.guard import GraphValidationError


@dataclasses.dataclass(frozen=True)
class EdgeOrder:
    """One ordering of the edge list plus its per-edge data."""
    src: jnp.ndarray        # [E] int32
    dst: jnp.ndarray        # [E] int32
    weight: jnp.ndarray     # [E] float32
    capacity: jnp.ndarray   # [E] float32


@dataclasses.dataclass(frozen=True)
class Graph:
    n: int
    by_dst: EdgeOrder       # sorted by dst (pull engines)
    by_src: EdgeOrder       # sorted by src (push engines)
    in_deg: jnp.ndarray     # [n] int32
    out_deg: jnp.ndarray    # [n] int32
    w_out_deg: Optional[jnp.ndarray] = None   # [n] float32 Σ outgoing weight

    @property
    def num_edges(self) -> int:
        return int(self.by_dst.src.shape[0])

    def host_edges(self):
        """(src, dst, weight, capacity) as numpy, dst-sorted."""
        e = self.by_dst
        return (np.asarray(e.src), np.asarray(e.dst),
                np.asarray(e.weight), np.asarray(e.capacity))


def _check_edge_arrays(n: int, src, dst, weight, capacity,
                       self_loops: str, duplicates: str):
    """Host-side structural validation of raw edge arrays (the contract of
    every engine: DESIGN.md §12).  Raises ``GraphValidationError``; returns
    a boolean keep-mask when ``self_loops="drop"`` asks for filtering, else
    None."""
    if n < 1:
        raise GraphValidationError(f"graph needs n >= 1 vertices, got {n}")
    for name, a in (("src", src), ("dst", dst)):
        if a.ndim != 1:
            raise GraphValidationError(
                f"{name} must be a 1-d index vector, got shape {a.shape}")
        if not np.issubdtype(a.dtype, np.integer):
            raise GraphValidationError(
                f"{name} must be an integer vector, got dtype {a.dtype}")
    if src.shape != dst.shape:
        raise GraphValidationError(
            f"src/dst length mismatch: {src.shape[0]} vs {dst.shape[0]}")
    if src.size and (src.min() < 0 or src.max() >= n or
                     dst.min() < 0 or dst.max() >= n):
        bad = np.flatnonzero((src < 0) | (src >= n) | (dst < 0) | (dst >= n))
        raise GraphValidationError(
            f"edge endpoints out of range [0, {n}): {bad.size} bad edges, "
            f"first at position {int(bad[0])} "
            f"({int(src[bad[0]])} -> {int(dst[bad[0]])})")
    for name, a in (("weight", weight), ("capacity", capacity)):
        if a.shape != src.shape:
            raise GraphValidationError(
                f"{name} length {a.shape} does not match edge count "
                f"{src.shape}")
        if a.size and not np.isfinite(a).all():
            bad = np.flatnonzero(~np.isfinite(a))
            raise GraphValidationError(
                f"{name} has {bad.size} non-finite entries (NaN/Inf), "
                f"first at edge {int(bad[0])}")
    loops = src == dst
    n_loops = int(loops.sum())
    if n_loops and self_loops == "error":
        raise GraphValidationError(
            f"graph has {n_loops} self-loops under self_loops='error' "
            f"policy, first at edge {int(np.flatnonzero(loops)[0])}")
    if duplicates == "error" and src.size:
        key = src.astype(np.int64) * n + dst
        n_dup = key.size - np.unique(key).size
        if n_dup:
            raise GraphValidationError(
                f"graph has {n_dup} duplicate edges under "
                "duplicates='error' policy")
    if n_loops and self_loops == "drop":
        return ~loops
    return None


@obs.span("grafs.from_edges")
def from_edges(n: int, src, dst, weight=None, capacity=None,
               validate: bool = True, self_loops: str = "allow",
               duplicates: str = "allow") -> Graph:
    """Build a Graph from raw edge arrays.

    ``validate`` (default on) runs the host-side structural checks —
    index bounds, dtypes, finite weights/capacities — and the
    ``self_loops`` ("allow" | "drop" | "error") and ``duplicates``
    ("allow" | "error") policies; violations raise a structured
    ``GraphValidationError`` instead of corrupting engine state downstream.
    The generators below pre-dedupe, so their calls keep the default
    allow-all policies."""
    if self_loops not in ("allow", "drop", "error"):
        raise ValueError(f"self_loops must be allow|drop|error, "
                         f"got {self_loops!r}")
    if duplicates not in ("allow", "error"):
        raise ValueError(f"duplicates must be allow|error, got {duplicates!r}")
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.size == 0:                    # [] defaults to float64; the
        src = src.astype(np.int32)       # zero-edge graph is legal
    if dst.size == 0:
        dst = dst.astype(np.int32)
    e = src.shape[0] if src.ndim else 0
    if weight is None:
        weight = np.ones((e,), dtype=np.float32)
    if capacity is None:
        capacity = np.ones((e,), dtype=np.float32)
    weight = np.asarray(weight, dtype=np.float32)
    capacity = np.asarray(capacity, dtype=np.float32)
    if validate:
        keep = _check_edge_arrays(n, src, dst, weight, capacity,
                                  self_loops, duplicates)
        if keep is not None:
            src, dst = src[keep], dst[keep]
            weight, capacity = weight[keep], capacity[keep]
    src = src.astype(np.int32, copy=False)
    dst = dst.astype(np.int32, copy=False)

    def order(key):
        perm = np.argsort(key, kind="stable")
        return EdgeOrder(src=jnp.asarray(src[perm]), dst=jnp.asarray(dst[perm]),
                         weight=jnp.asarray(weight[perm]),
                         capacity=jnp.asarray(capacity[perm]))

    in_deg = np.bincount(dst, minlength=n).astype(np.int32)
    out_deg = np.bincount(src, minlength=n).astype(np.int32)
    w_out = np.bincount(src, weights=weight.astype(np.float64),
                        minlength=n).astype(np.float32)
    return Graph(n=n, by_dst=order(dst), by_src=order(src),
                 in_deg=jnp.asarray(in_deg), out_deg=jnp.asarray(out_deg),
                 w_out_deg=jnp.asarray(w_out))


@dataclasses.dataclass(frozen=True)
class GraphCheck:
    """Validation summary of one graph: the facts engine entry points need
    to guard a query — value ranges for the termination-precondition probe
    (conditions.violated_preconditions), loop/duplicate counts for
    diagnostics.  Computed once per graph (identity-keyed weakref cache,
    like the layout caches) so per-query serving never re-scans edges."""
    n: int
    num_edges: int
    w_min: float
    w_max: float
    c_min: float
    c_max: float
    self_loops: int
    duplicates: int


_VALID_CACHE: dict = {}


def validate_graph(g: Graph) -> GraphCheck:
    """Validate an already-built Graph and return its ``GraphCheck``.

    Engine entry points (``engine.run_program`` / ``run_direct`` /
    ``run_program_batch``) call this on every query; the O(E) host scan runs
    once per graph and is memoized.  Graphs built by ``from_edges`` with
    ``validate=True`` re-verify here too — cheap, and it catches graphs
    assembled by hand or mutated layouts."""
    key = id(g)
    hit = _VALID_CACHE.get(key)
    if hit is not None:
        ref, chk = hit
        if ref() is g:
            return chk
    src, dst, w, c = g.host_edges()
    _check_edge_arrays(g.n, src, dst, w, c,
                       self_loops="allow", duplicates="allow")
    loops = int((src == dst).sum())
    if src.size:
        key64 = src.astype(np.int64) * g.n + dst
        dups = int(key64.size - np.unique(key64).size)
    else:
        dups = 0
    chk = GraphCheck(
        n=g.n, num_edges=int(src.shape[0]),
        w_min=float(w.min()) if w.size else 0.0,
        w_max=float(w.max()) if w.size else 0.0,
        c_min=float(c.min()) if c.size else 0.0,
        c_max=float(c.max()) if c.size else 0.0,
        self_loops=loops, duplicates=dups)
    _VALID_CACHE[key] = (weakref.ref(g), chk)
    weakref.finalize(g, _VALID_CACHE.pop, key, None)
    return chk


@dataclasses.dataclass(frozen=True)
class GraphStats:
    """Per-graph statistics the query planner resolves knobs from
    (``core.plan.plan_execution``; DESIGN.md §14): size, degree shape
    (average/max out-degree and their ratio — the skew signal that separates
    power-law R-MAT graphs from uniform ones), weight range (weighted
    kernels engage the weighted-degree normalizer and min-plus
    preconditions), and the process's device topology.  Computed from the
    host arrays directly — unlike ``validate_graph`` this never *raises* on
    contract violations, because plans must also resolve for
    ``validate=False`` runs on malformed graphs."""
    n: int
    num_edges: int
    avg_degree: float               # |E| / n (out == in in aggregate)
    max_out_degree: int
    max_in_degree: int
    degree_skew: float              # max_out_degree / avg_degree (≥ 1-ish on
                                    # uniform graphs, ≫ 1 on power-law hubs)
    weighted: bool                  # any edge weight ≠ 1.0
    w_min: float
    w_max: float
    device_count: int               # process-visible accelerator topology
    backend: str


_STATS_CACHE: dict = {}


def graph_stats(g: Graph) -> GraphStats:
    """Memoized per-graph statistics (identity key, weakref-guarded,
    finalizer-evicted like every structure cache) — the planner's input;
    one O(E) host scan per graph, never per query."""
    key = id(g)
    hit = _STATS_CACHE.get(key)
    if hit is not None:
        ref, st = hit
        if ref() is g:
            return st
    import jax
    out_deg = np.asarray(g.out_deg)
    in_deg = np.asarray(g.in_deg)
    w = np.asarray(g.by_dst.weight)
    e = int(w.shape[0])
    avg = e / g.n
    max_out = int(out_deg.max()) if out_deg.size else 0
    st = GraphStats(
        n=g.n, num_edges=e, avg_degree=avg,
        max_out_degree=max_out,
        max_in_degree=int(in_deg.max()) if in_deg.size else 0,
        degree_skew=(max_out / avg) if avg > 0 else 0.0,
        weighted=bool(e and np.any(w != 1.0)),
        w_min=float(w.min()) if e else 0.0,
        w_max=float(w.max()) if e else 0.0,
        device_count=jax.device_count(),
        backend=jax.default_backend())
    _STATS_CACHE[key] = (weakref.ref(g), st)
    weakref.finalize(g, _STATS_CACHE.pop, key, None)
    return st


_WDEG_CACHE: dict = {}


def w_out_deg(g: Graph) -> jnp.ndarray:
    """Weighted out-degree (Σ outgoing edge weight per vertex) as the P
    environment's ``wdeg`` normalizer (weighted PageRank-style kernels).

    Computed host-side ONCE per graph — `from_edges` stores the raw sums on
    the Graph and the clamped device vector is memoized here (identity key,
    weakref-guarded like the layout caches) so per-query serving never pays
    a host round-trip — and shared by every engine: pull segment ops, push
    scatters, dense, distributed, and both pallas sweep directions
    normalize by the bit-identical vector (a per-engine recomputation would
    associate the float sums differently and break the pull ≡ push bitwise
    parity the direct-kernel tests assert).  Vertices with no out-edges
    read 1.0 (the value is only ever consumed on edges *leaving* a vertex,
    so the clamp is unreachable on real slots — it just keeps padding-lane
    arithmetic finite)."""
    key = id(g)
    hit = _WDEG_CACHE.get(key)
    if hit is not None:
        ref, wdeg = hit
        if ref() is g:
            return wdeg
    if g.w_out_deg is not None:
        w = np.asarray(g.w_out_deg, dtype=np.float32)
    else:                                    # legacy Graph built by hand
        src, _dst, wt, _c = g.host_edges()
        w = np.bincount(src, weights=wt.astype(np.float64),
                        minlength=g.n).astype(np.float32)
    wdeg = jnp.asarray(np.where(w > 0, w, np.float32(1.0)))
    _WDEG_CACHE[key] = (weakref.ref(g), wdeg)
    weakref.finalize(g, _WDEG_CACHE.pop, key, None)
    return wdeg


# ---------------------------------------------------------------------------
# Blocked-ELL layout for the Pallas edge kernel.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockedELL:
    """Degree-padded neighbour lists in one direction.

    With ``direction="in"`` (the pull layout) row v holds the predecessors of
    v: ``nbrs[v, k]`` is the k-th *source* of an in-edge of v.  With
    ``direction="out"`` (the push layout) row v holds the successors:
    ``nbrs[v, k]`` is the k-th *destination* of an out-edge of v.  ``mask[v,
    k]`` marks real slots.  ``n_pad`` and ``width`` are multiples of the
    requested tile sizes so a Pallas grid covers the arrays exactly.

    ``tile_nnz[i, j]`` counts the real slots inside grid tile (i, j) for the
    layout's own (block_v, block_e); power-law degree distributions leave most
    tail column-tiles fully padded, and the fused sweeps skip those tiles
    before doing any work (DESIGN.md §2).  ``live_tiles`` lists the tiles
    with real slots (``live_tiles``): where that list is shorter than the
    grid, the sweep kernels walk only it.
    """
    n: int                  # logical vertex count
    n_pad: int
    width: int              # padded max degree (in- or out-, per direction)
    block_v: int            # tile sizes the layout was built for
    block_e: int
    nbrs: jnp.ndarray       # [n_pad, width] int32 neighbour vertex ids
    weight: jnp.ndarray     # [n_pad, width] float32
    capacity: jnp.ndarray   # [n_pad, width] float32
    mask: jnp.ndarray       # [n_pad, width] bool
    tile_nnz: jnp.ndarray   # [n_pad/block_v, width/block_e] int32
    slot_pos: jnp.ndarray   # [E] int32 flat positions of the real slots
    slot_nbr: jnp.ndarray   # [E] int32 their neighbour ids (slot_list)
    live_tiles: jnp.ndarray  # [L] int32 flat ids of the tiles a sweep walks
    direction: str = "in"   # "in" (rows = dst, pull) | "out" (rows = src, push)

    @property
    def srcs(self) -> jnp.ndarray:
        """Pull-layout alias: with ``direction="in"`` the neighbour ids ARE
        the edge sources (kept for the original pull-sweep call sites).
        Guarded so an out-layout can never leak destination ids under the
        name ``srcs`` into gather-side code."""
        if self.direction != "in":
            raise AttributeError(
                "BlockedELL.srcs is only meaningful on the pull layout "
                f"(direction='in'); this layout is direction={self.direction!r}"
                " — use .nbrs")
        return self.nbrs


def _padded_width(deg: np.ndarray, block_e: int) -> int:
    """Max degree padded up to the slot-tile size — THE width rule of every
    blocked layout (shared with the push-resolution permutation, which must
    agree with the layouts by construction)."""
    width = int(max(1, deg.max() if deg.size else 1))
    return ((width + block_e - 1) // block_e) * block_e


def _fill_order_slots(row_of: np.ndarray, n: int) -> np.ndarray:
    """Per-edge slot index under the left-to-right row fill rule, edges in
    ``host_edges()`` (dst-sorted) order — THE slot assignment of
    ``to_blocked_ell``.  ``to_push_resolution`` replays the same function,
    so the dst-major permutation can never desynchronize from the layouts
    it permutes between."""
    e = row_of.shape[0]
    # vectorized running-count: stable sort groups each row's edges in
    # original order, rank-within-group = position − first occurrence
    perm = np.argsort(row_of, kind="stable")
    sorted_rows = row_of[perm]
    out = np.empty(e, dtype=np.int64)
    out[perm] = np.arange(e, dtype=np.int64) - \
        np.searchsorted(sorted_rows, sorted_rows)
    return out


def slot_list(idx, mask):
    """The real slots of an [n_pad, width] layout as ``(pos, idx)``: their
    flat positions, ascending, and the value of the index rectangle ``idx``
    at each.  The sweeps gather per-slot values through this list
    (``edge_reduce.gather_slots``): a TPU gather costs per index, and a
    rectangle padded to the max degree holds mostly padding."""
    pos = np.flatnonzero(np.asarray(mask))
    return (pos.astype(np.int32),
            np.asarray(idx).reshape(-1)[pos].astype(np.int32))


def live_tiles(tile_nnz, block_v: int):
    """The tiles a sweep kernel walks over a layout with ``tile_nnz``
    [n_i, n_j] (DESIGN.md §2): the flat ids ``i·n_j + j`` of every tile with
    real slots, ascending, plus the first tile of every 128-row output
    group (``128 // block_v`` row tiles) that has none.  Each lane-dense
    output block of the kernels is then visited, and identity-filled, by
    one contiguous run of steps; a placeholder tile has no real slots, so
    its activity bit is 0 in every bitmap the engines build."""
    nnz = np.asarray(tile_nnz)
    n_i, n_j = nnz.shape
    group = (128 // block_v) * n_j
    live = np.flatnonzero(nnz.reshape(-1) > 0)
    empty = np.setdiff1d(np.arange(-(-n_i // (128 // block_v))),
                         live // group)
    return np.union1d(live, empty * group).astype(np.int32)


def stack_slot_lists(lists, size: int):
    """Per-shard ``slot_list`` pairs stacked to ``[k, E_max]``.  A shorter
    list pads with distinct positions ≥ ``size`` (the flat rectangle size),
    which ``gather_slots`` drops, and index 0."""
    e_max = max(p.shape[0] for p, _ in lists)
    pos = np.stack([np.concatenate([p, size + np.arange(e_max - p.shape[0])])
                    for p, _ in lists]).astype(np.int32)
    idx = np.stack([np.pad(i, (0, e_max - i.shape[0])) for _, i in lists])
    return pos, idx.astype(np.int32)


def _blocked_ell_host(g: Graph, block_v: int, block_e: int, direction: str):
    """The numpy arrays of ``to_blocked_ell``: (width, n_pad, nbrs, weight,
    capacity, mask, tile_nnz)."""
    src, dst, w, c = g.host_edges()
    n = g.n
    if direction == "in":
        row_of, nbr_of = dst, src
    elif direction == "out":
        row_of, nbr_of = src, dst
    else:
        raise ValueError(f"direction must be 'in' or 'out', got {direction!r}")
    width = _padded_width(np.bincount(row_of, minlength=n), block_e)
    n_pad = ((n + block_v - 1) // block_v) * block_v
    nbrs = np.zeros((n_pad, width), dtype=np.int32)
    ws = np.zeros((n_pad, width), dtype=np.float32)
    cs = np.zeros((n_pad, width), dtype=np.float32)
    mask = np.zeros((n_pad, width), dtype=bool)
    ks = _fill_order_slots(row_of, n)
    nbrs[row_of, ks] = nbr_of
    ws[row_of, ks] = w
    cs[row_of, ks] = c
    mask[row_of, ks] = True
    tile_nnz = mask.reshape(n_pad // block_v, block_v,
                            width // block_e, block_e) \
        .sum(axis=(1, 3)).astype(np.int32)
    return width, n_pad, nbrs, ws, cs, mask, tile_nnz


@obs.span("grafs.layout.ell")
def to_blocked_ell(g: Graph, block_v: int = 8, block_e: int = 128,
                   direction: str = "in") -> BlockedELL:
    """Build the blocked-ELL layout keyed by dst (``direction="in"``, the
    pull sweep's predecessor lists) or by src (``direction="out"``, the push
    sweep's successor lists).  Both directions carry the same per-edge
    weight/capacity so the synthesized P functions see identical edges."""
    width, n_pad, nbrs, ws, cs, mask, tile_nnz = _blocked_ell_host(
        g, block_v, block_e, direction)
    pos, nbr = slot_list(nbrs, mask)
    with obs.span("grafs.layout.upload"):
        return BlockedELL(n=g.n, n_pad=n_pad, width=width,
                          block_v=block_v, block_e=block_e,
                          nbrs=jnp.asarray(nbrs), weight=jnp.asarray(ws),
                          capacity=jnp.asarray(cs), mask=jnp.asarray(mask),
                          tile_nnz=jnp.asarray(tile_nnz),
                          slot_pos=jnp.asarray(pos),
                          slot_nbr=jnp.asarray(nbr),
                          live_tiles=jnp.asarray(live_tiles(tile_nnz,
                                                            block_v)),
                          direction=direction)


_ELL_CACHE: dict = {}


def blocked_ell_cached(g: Graph, block_v: int = 8, block_e: int = 128,
                       direction: str = "in") -> BlockedELL:
    """Memoized ``to_blocked_ell``: the padded layout is immutable per graph,
    so repeated queries / rounds / benchmark repeats reuse one conversion.
    The pull ("in") and push ("out") layouts of one graph are separate
    entries, so a direction-optimized executor can hold both at once.

    Keyed on object identity; a weakref guards against id() reuse, and a
    finalizer drops the entry when the graph is garbage-collected so dead
    layouts never pin their padded arrays."""
    key = (id(g), block_v, block_e, direction)
    hit = _ELL_CACHE.get(key)
    if hit is not None:
        ref, ell = hit
        if ref() is g:
            return ell
    ell = to_blocked_ell(g, block_v=block_v, block_e=block_e,
                         direction=direction)
    _ELL_CACHE[key] = (weakref.ref(g), ell)
    weakref.finalize(g, _ELL_CACHE.pop, key, None)
    return ell


# ---------------------------------------------------------------------------
# Sharded blocked-ELL layouts for the pallas_sharded engine (DESIGN.md §11).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedELL:
    """Per-shard blocked-ELL layouts of one vertex-cut, stacked on a leading
    shard axis so ``shard_map`` can split them with ``P(axes)``.

    Shard j's slice ``[j]`` is exactly ``to_blocked_ell`` of the j-th
    ``partition.shard_subgraphs`` block — same fill rule, same tile shapes —
    padded on the slot axis to the widest shard (``width`` = max over
    shards, already a multiple of ``block_e``) with masked-out slots, so
    every shard sees identically-shaped arrays (SPMD requires one trace).
    Padding slots carry ``mask=False`` and ``tile_nnz=0`` and therefore
    reduce to identities / skip entirely (C6).

    ``row_deg[j, v]`` counts shard j's real slots in row v — for the
    ``direction="out"`` layout that is v's shard-local out-degree, whose
    ``psum`` over shards reconstructs the global out-degree exactly (integer
    sums): the signal of the GLOBAL Gemini direction switch every shard must
    agree on (DESIGN.md §11)."""
    k: int
    n: int
    n_pad: int
    width: int              # max over shards, padded to block_e
    block_v: int
    block_e: int
    direction: str
    strategy: str
    nbrs: jnp.ndarray       # [k, n_pad, width] int32
    weight: jnp.ndarray     # [k, n_pad, width] float32
    capacity: jnp.ndarray   # [k, n_pad, width] float32
    mask: jnp.ndarray       # [k, n_pad, width] bool
    tile_nnz: jnp.ndarray   # [k, n_pad/block_v, width/block_e] int32
    row_deg: jnp.ndarray    # [k, n_pad] float32 real slots per row
    slot_pos: jnp.ndarray   # [k, E_max] int32 (stack_slot_lists)
    slot_nbr: jnp.ndarray   # [k, E_max] int32
    num_edges: int          # Σ real edges across shards (== graph |E|)


def _put(a, sharding):
    """Host array → device: the default device, or split across a mesh by
    ``sharding`` straight from the host (a stacked per-shard layout staged
    whole on one chip first would need k chips' worth of its memory)."""
    return jnp.asarray(a) if sharding is None else jax.device_put(a, sharding)


def to_sharded_ell(g: Graph, k: int, strategy: str = "contiguous",
                   block_v: int = 8, block_e: int = 128,
                   direction: str = "in", sharding=None) -> ShardedELL:
    """Build the stacked per-shard blocked-ELL layout of a k-way vertex-cut.

    Each shard's layout is built by the exact single-device rules
    (``to_blocked_ell`` on its ``shard_subgraphs`` block) and then widened to
    the widest shard; a shard's local reduction over its slice is therefore
    bit-identical to a single-device sweep over that shard's edge subset,
    which is what makes the cross-shard monoid combine exact (DESIGN.md
    §11).  ``sharding`` (a sharding of the leading shard axis) places every
    array across the mesh; None keeps them on the default device."""
    from repro.graph.partition import shard_subgraphs  # lazy: partition
    # imports this module at top level
    subs = shard_subgraphs(g, k, strategy)
    ells = [_blocked_ell_host(sg, block_v, block_e, direction)
            for sg in subs]
    width = max(e[0] for e in ells)
    n_pad = ells[0][1]
    n_i, n_j = n_pad // block_v, width // block_e

    def widen(a, fill):
        if a.shape[1] == width:
            return a
        out = np.full((n_pad, width), fill, dtype=a.dtype)
        out[:, :a.shape[1]] = a
        return out

    nbrs = np.stack([widen(e[2], 0) for e in ells])
    ws = np.stack([widen(e[3], 0.0) for e in ells])
    cs = np.stack([widen(e[4], 0.0) for e in ells])
    mask = np.stack([widen(e[5], False) for e in ells])
    tile_nnz = mask.reshape(k, n_i, block_v, n_j, block_e) \
        .sum(axis=(2, 4)).astype(np.int32)
    row_deg = mask.sum(axis=2).astype(np.float32)
    slot_pos, slot_nbr = stack_slot_lists(
        [slot_list(nb, m) for nb, m in zip(nbrs, mask)], n_pad * width)
    return ShardedELL(
        k=k, n=g.n, n_pad=n_pad, width=width, block_v=block_v,
        block_e=block_e, direction=direction, strategy=strategy,
        nbrs=_put(nbrs, sharding), weight=_put(ws, sharding),
        capacity=_put(cs, sharding), mask=_put(mask, sharding),
        tile_nnz=_put(tile_nnz, sharding), row_deg=_put(row_deg, sharding),
        slot_pos=_put(slot_pos, sharding), slot_nbr=_put(slot_nbr, sharding),
        num_edges=int(mask.sum()))


_SHARDED_ELL_CACHE: dict = {}


def sharded_ell_cached(g: Graph, k: int, strategy: str = "contiguous",
                       block_v: int = 8, block_e: int = 128,
                       direction: str = "in", sharding=None) -> ShardedELL:
    """Memoized ``to_sharded_ell`` — cached per (graph, k, strategy, tile
    shape, direction, sharding) exactly like ``blocked_ell_cached``
    (identity key, weakref-guarded, finalizer-evicted), so repeated sharded
    queries never re-partition or re-pad."""
    key = (id(g), k, strategy, block_v, block_e, direction, sharding)
    hit = _SHARDED_ELL_CACHE.get(key)
    if hit is not None:
        ref, ell = hit
        if ref() is g:
            return ell
    ell = to_sharded_ell(g, k, strategy=strategy, block_v=block_v,
                         block_e=block_e, direction=direction,
                         sharding=sharding)
    _SHARDED_ELL_CACHE[key] = (weakref.ref(g), ell)
    weakref.finalize(g, _SHARDED_ELL_CACHE.pop, key, None)
    return ell


# ---------------------------------------------------------------------------
# Dst-sorted push-resolution layout (DESIGN.md §10).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PushResolution:
    """Dst-major permutation of the out-edge rectangle, as segment metadata.

    The push sweep emits its per-edge candidates at *out-layout* positions
    (rows = sources).  Resolving the dst-keyed reduction used to mean a
    full-rectangle XLA scatter; this layout instead precomputes where each
    out-slot's candidate lands in the **dst-major rectangle** — the same
    `[n_pad, width_in]` shape as the pull layout, where row v is exactly
    the contiguous segment of candidates competing for vertex v and the
    segment boundary IS the row boundary (column tiles are resolved by the
    pull sweep's existing cross-tile fold, so the `plan_merge` contract is
    unchanged).

    ``in2out[v, k]`` — flat index into the out rectangle of the edge that
    is the k-th dst-major candidate slot of v (fill order matches
    ``to_blocked_ell(direction="in")`` slot order, so a reduction over this
    rectangle is bit-identical to the pull sweep's reduction tree).
    ``valid`` marks real slots; ``src_tile[v, k]`` is the flat id of the
    out-layout grid tile owning the slot.  ``tile_nnz`` counts real slots
    per resolution tile (the skip test + the work accounting unit).

    ``contrib`` is the *contributing out-tile lists* of the live resolution
    tiles (``tile_nnz > 0``) — the unique out-tiles whose candidates land in
    each — as a compact class table (``contrib_classes``): one
    ``(tile_ids [t], lists [width, t])`` int32 pair per power-of-two
    length class, each tile's list a column, −1 padded to the class's
    longest.  The per-iteration activity test
    (`edge_reduce.resolution_tile_activity`) ORs the push sweep's frontier
    tile-activity bitmap over these lists — O(live pairs) reads, padded by
    under 2× (``contrib_entries`` against ``contrib_pairs``) — candidates
    born in a skipped out-tile are identities, so their resolution tiles
    skip too, making resolution work frontier-proportional.
    """
    n: int
    n_pad: int
    width: int              # dst-major (in-rectangle) padded width
    out_width: int          # the out rectangle's width (gather domain)
    block_v: int
    block_e: int
    in2out: np.ndarray      # [n_pad, width] int32 flat out-rectangle index
    valid: np.ndarray       # [n_pad, width] bool
    src_tile: np.ndarray    # [n_pad, width] int32 flat out-tile id
    tile_nnz: jnp.ndarray   # [n_pad/block_v, width/block_e] int32
    live_tiles: jnp.ndarray  # [L] int32 the tiles the resolve kernel walks
    contrib: tuple          # ((tile_ids [t], lists [w, t]) int32, ...)
    slot_pos: jnp.ndarray   # [E] int32 flat positions of the valid slots
    slot_src: jnp.ndarray   # [E] int32 their in2out (slot_list)
    contrib_entries: int    # Σ t·w over the classes: reads a push round
    contrib_pairs: int      # real (resolution tile, out tile) pairs


def _class_bits(length):
    """Length class of contributing lists: the b with 2^(b-1) < length ≤
    2^b, elementwise."""
    return np.searchsorted(np.int64(1) << np.arange(63, dtype=np.int64),
                           length)


def contrib_classes(r_tile, s_tile, n_out_tiles: int):
    """THE construction of the compact contributing-out-tile table:
    ``(classes, pairs)`` from the per-edge resolution tile ``r_tile`` and
    out tile ``s_tile`` of every real slot.

    The unique (resolution tile, out tile) pairs (``pairs`` of them) give
    each live resolution tile its list of out tiles.  Live tiles group by
    the power of two that bounds their list length; each class is one
    ``(tile_ids [t], lists [width, t])`` int32 pair, tiles in id
    order, each tile's list a column, ``width`` the class's own longest
    list and shorter lists −1 padded.  Every list is longer than half its
    class bound, so the table holds fewer than 2·``pairs`` entries;
    resolution tiles without real slots have no column."""
    pair = np.unique(np.asarray(r_tile, np.int64) * n_out_tiles
                     + np.asarray(s_tile, np.int64))
    r_ids = pair // n_out_tiles
    s_ids = (pair % n_out_tiles).astype(np.int32)
    # pairs come sorted, so each live tile's list is one run of r_ids
    start = np.flatnonzero(np.diff(r_ids, prepend=-1))
    live = r_ids[start]
    length = np.diff(start, append=r_ids.size)
    bits = _class_bits(length)
    classes = []
    for b in np.unique(bits):
        members = np.flatnonzero(bits == b)
        lens = length[members]
        lists = np.full((int(lens.max()), members.size), -1, dtype=np.int32)
        col = np.repeat(np.arange(members.size), lens)
        k = np.arange(col.size) - np.repeat(np.cumsum(lens) - lens, lens)
        lists[k, col] = s_ids[np.repeat(start[members], lens) + k]
        classes.append((live[members].astype(np.int32), lists))
    return classes, int(pair.size)


def stack_contrib_classes(per_shard, n_tiles: int):
    """Per-shard ``contrib_classes`` tables stacked on a leading shard axis.
    Shards share the power-of-two class bounds; each class pads to its
    widest shard, in tiles with distinct ids ≥ ``n_tiles`` (which the
    activity scatter drops) and −1 lists, and in width with −1."""
    k = len(per_shard)
    by_bits: dict = {}
    for s, classes in enumerate(per_shard):
        for ids, lists in classes:
            by_bits.setdefault(int(_class_bits(lists.shape[0])), {})[s] = \
                (np.asarray(ids), np.asarray(lists))
    out = []
    for bits in sorted(by_bits):
        got = by_bits[bits]
        tiles = max(ids.shape[0] for ids, _ in got.values())
        width = max(lists.shape[0] for _, lists in got.values())
        ids_k = np.tile(n_tiles + np.arange(tiles, dtype=np.int32), (k, 1))
        lists_k = np.full((k, width, tiles), -1, dtype=np.int32)
        for s, (ids, lists) in got.items():
            ids_k[s, :ids.shape[0]] = ids
            lists_k[s, :lists.shape[0], :lists.shape[1]] = lists
        out.append((ids_k, lists_k))
    return out


def resolution_from_slots(n, src, dst, k_in, k_out, w_in, w_out,
                          block_v, block_e) -> PushResolution:
    """The push resolution of EXPLICIT per-edge slot assignments and
    rectangle widths: edge i sits at out-slot ``(src[i], k_out[i])`` and
    dst-major slot ``(dst[i], k_in[i])``, so ``in2out[dst[i], k_in[i]] =
    src[i]·w_out + k_out[i]``.  ``to_push_resolution`` passes the canonical
    fill order; ``mutate`` passes the slots of a patched layout pair.  The
    rectangles are written per edge; nothing is computed over the padded
    slots."""
    n_pad = ((n + block_v - 1) // block_v) * block_v
    if n_pad * w_out >= 2 ** 31:
        raise ValueError(
            f"out rectangle {n_pad}×{w_out} overflows int32 flat indices; "
            "the dst-sorted resolution layout needs an int64 gather path "
            "for graphs this hub-heavy")
    # Per-edge tile coordinates: the edge at dst-major slot (dst, k_in) sits
    # in resolution tile (dst//block_v, k_in//block_e) and came from
    # out-tile (src//block_v, k_out//block_e).
    n_j_in = w_in // block_e
    n_j_out = w_out // block_e
    n_tiles = (n_pad // block_v) * n_j_in
    r_tile = (dst // block_v).astype(np.int64) * n_j_in + k_in // block_e
    s_tile = (src // block_v).astype(np.int64) * n_j_out + k_out // block_e
    in2out = np.zeros((n_pad, w_in), dtype=np.int32)
    valid = np.zeros((n_pad, w_in), dtype=bool)
    src_tile = np.zeros((n_pad, w_in), dtype=np.int32)
    in2out[dst, k_in] = src.astype(np.int64) * w_out + k_out
    valid[dst, k_in] = True
    src_tile[dst, k_in] = s_tile
    tile_nnz = np.bincount(r_tile, minlength=n_tiles).astype(np.int32) \
        .reshape(n_pad // block_v, n_j_in)
    classes, pairs = contrib_classes(r_tile, s_tile,
                                     (n_pad // block_v) * n_j_out)
    pos, src_pos = slot_list(in2out, valid)
    with obs.span("grafs.layout.upload"):
        return PushResolution(
            n=n, n_pad=n_pad, width=w_in, out_width=w_out,
            block_v=block_v, block_e=block_e, in2out=in2out, valid=valid,
            src_tile=src_tile,
            tile_nnz=jnp.asarray(tile_nnz),
            live_tiles=jnp.asarray(live_tiles(tile_nnz, block_v)),
            contrib=tuple((jnp.asarray(ids), jnp.asarray(lists))
                          for ids, lists in classes),
            slot_pos=jnp.asarray(pos), slot_src=jnp.asarray(src_pos),
            contrib_entries=sum(lists.size for _, lists in classes),
            contrib_pairs=pairs)


@obs.span("grafs.layout.resolution")
def to_push_resolution(g: Graph, block_v: int = 8, block_e: int = 128,
                       min_width: int = 0,
                       min_out_width: int = 0) -> PushResolution:
    """Build the dst-major resolution permutation for the push sweep.

    Slot assignment replays ``_fill_order_slots`` / ``_padded_width`` — the
    exact rules ``to_blocked_ell`` builds both directions with — so the
    correspondence is exact by construction (``resolution_from_slots``).

    ``min_width`` / ``min_out_width`` (multiples of ``block_e``) floor the
    padded rectangle widths: the sharded stack widens every shard's
    resolution to the widest shard so the flat ``in2out`` indices address
    the widened out rectangles ``to_sharded_ell`` actually sweeps.  Slot
    assignment never changes — widening only appends padding columns."""
    src, dst, _w, _c = g.host_edges()
    n = g.n
    w_in = max(_padded_width(np.bincount(dst, minlength=n), block_e),
               int(min_width))
    w_out = max(_padded_width(np.bincount(src, minlength=n), block_e),
                int(min_out_width))
    return resolution_from_slots(n, src, dst, _fill_order_slots(dst, n),
                                 _fill_order_slots(src, n), w_in, w_out,
                                 block_v, block_e)


_RES_CACHE: dict = {}


def push_resolution_cached(g: Graph, block_v: int = 8,
                           block_e: int = 128) -> PushResolution:
    """Memoized ``to_push_resolution`` — cached per graph exactly like the
    blocked-ELL layouts (identity key, weakref-guarded, finalizer-evicted),
    so the dst-major permutation is built once per graph per tile shape."""
    key = (id(g), block_v, block_e)
    hit = _RES_CACHE.get(key)
    if hit is not None:
        ref, res = hit
        if ref() is g:
            return res
    res = to_push_resolution(g, block_v=block_v, block_e=block_e)
    _RES_CACHE[key] = (weakref.ref(g), res)
    weakref.finalize(g, _RES_CACHE.pop, key, None)
    return res


# ---------------------------------------------------------------------------
# Sharded push-resolution stacks for the pallas_sharded engine (DESIGN.md §11).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedPushResolution:
    """Per-shard dst-sorted resolution layouts of one vertex-cut, stacked on
    a leading shard axis so ``shard_map`` can split them with ``P(axes)``.

    Shard j's slice ``[j]`` is ``to_push_resolution`` of the j-th
    ``partition.shard_subgraphs`` block, built directly against the WIDENED
    rectangle widths (max over shards, the widths ``to_sharded_ell``
    actually sweeps) so the flat ``in2out`` indices address the widened
    out-rectangle candidates without any re-indexing.  A shard-local sorted
    resolve over its slice is therefore bit-identical to a single-device
    sorted resolve over that shard's edge subset, and the cross-shard
    monoid/lex combine contract is unchanged (DESIGN.md §11).  ``contrib``
    is the shards' compact class tables stacked by
    ``stack_contrib_classes``: ``(tile_ids [k, t], lists [k, w, t])``
    per power-of-two length class, each padded to its widest shard with
    dropped tile ids and −1 lists; ``contrib_entries`` counts the stack's
    entries (all shards) and ``contrib_pairs`` the shards' real pairs."""
    k: int
    n: int
    n_pad: int
    width: int              # dst-major width, max over shards
    out_width: int          # out-rectangle width, max over shards
    block_v: int
    block_e: int
    strategy: str
    in2out: np.ndarray      # [k, n_pad, width] int32
    valid: np.ndarray       # [k, n_pad, width] bool
    src_tile: np.ndarray    # [k, n_pad, width] int32
    tile_nnz: jnp.ndarray   # [k, n_pad/block_v, width/block_e] int32
    contrib: tuple          # ((tile_ids [k, t], lists [k, w, t]), ...)
    slot_pos: jnp.ndarray   # [k, E_max] int32 (stack_slot_lists)
    slot_src: jnp.ndarray   # [k, E_max] int32
    contrib_entries: int    # Σ k·t·w over the classes
    contrib_pairs: int      # Σ over shards of the real pairs


def to_sharded_push_resolution(g: Graph, k: int, strategy: str = "contiguous",
                               block_v: int = 8, block_e: int = 128,
                               sharding=None) -> ShardedPushResolution:
    """Build the stacked per-shard push-resolution stack of a k-way
    vertex-cut.  The widened widths are computed FIRST (max over shards of
    each shard's own padded widths — the same rule ``to_sharded_ell`` pads
    with) and every shard's permutation is built against them, so in2out is
    valid for the widened out rectangles by construction rather than by a
    fragile post-hoc index fixup.  ``sharding`` places the device arrays
    as in ``to_sharded_ell``."""
    from repro.graph.partition import shard_subgraphs  # lazy (see above)
    subs = shard_subgraphs(g, k, strategy)
    w_in = w_out = 0
    for sub in subs:
        s_src, s_dst, _w, _c = sub.host_edges()
        w_in = max(w_in, _padded_width(np.bincount(s_dst, minlength=sub.n),
                                       block_e))
        w_out = max(w_out, _padded_width(np.bincount(s_src, minlength=sub.n),
                                         block_e))
    rs = [to_push_resolution(sub, block_v=block_v, block_e=block_e,
                             min_width=w_in, min_out_width=w_out)
          for sub in subs]
    contrib = stack_contrib_classes(
        [[(np.asarray(ids), np.asarray(lists)) for ids, lists in r.contrib]
         for r in rs],
        rs[0].tile_nnz.size)
    slot_pos, slot_src = stack_slot_lists(
        [(np.asarray(r.slot_pos), np.asarray(r.slot_src)) for r in rs],
        rs[0].n_pad * w_in)
    return ShardedPushResolution(
        k=k, n=g.n, n_pad=rs[0].n_pad, width=w_in, out_width=w_out,
        block_v=block_v, block_e=block_e, strategy=strategy,
        in2out=np.stack([r.in2out for r in rs]),
        valid=np.stack([r.valid for r in rs]),
        src_tile=np.stack([r.src_tile for r in rs]),
        tile_nnz=_put(np.stack([np.asarray(r.tile_nnz) for r in rs]),
                      sharding),
        contrib=tuple((_put(ids, sharding), _put(lists, sharding))
                      for ids, lists in contrib),
        slot_pos=_put(slot_pos, sharding), slot_src=_put(slot_src, sharding),
        contrib_entries=sum(lists.size for _, lists in contrib),
        contrib_pairs=sum(r.contrib_pairs for r in rs))


_SHARDED_RES_CACHE: dict = {}


def sharded_push_resolution_cached(g: Graph, k: int,
                                   strategy: str = "contiguous",
                                   block_v: int = 8, block_e: int = 128,
                                   sharding=None) -> ShardedPushResolution:
    """Memoized ``to_sharded_push_resolution`` — cached per (graph, k,
    strategy, tile shape, sharding) exactly like ``sharded_ell_cached``
    (identity key, weakref-guarded, finalizer-evicted), so repeated sharded
    push queries never re-partition or re-sort."""
    key = (id(g), k, strategy, block_v, block_e, sharding)
    hit = _SHARDED_RES_CACHE.get(key)
    if hit is not None:
        ref, res = hit
        if ref() is g:
            return res
    res = to_sharded_push_resolution(g, k, strategy=strategy,
                                     block_v=block_v, block_e=block_e,
                                     sharding=sharding)
    _SHARDED_RES_CACHE[key] = (weakref.ref(g), res)
    weakref.finalize(g, _SHARDED_RES_CACHE.pop, key, None)
    return res


# Per-graph edge→slot maps maintained by graph.mutate: for a PATCHED graph
# the blocked-ELL slot of each edge is no longer the canonical left-to-right
# fill order, so mutate records the actual (k_in, k_out) per edge (aligned to
# host_edges order) here and chained mutations patch from it.  Same
# (identity key, weakref, finalizer) contract as every other structure cache.
_SLOT_CACHE: dict = {}


def clear_graph_caches(g: Graph) -> int:
    """Drop every cached derived structure of ONE graph — the selective
    counterpart of ``engine.clear_program_caches`` used by the serving
    layer's bounded per-graph cache (DESIGN.md §13): evicting a graph from
    residency frees its blocked-ELL layouts, sharded layouts, push
    resolutions, weighted degrees, validation summary and mutation slot
    maps without disturbing the other resident graphs (or the
    graph-shape-generic compiled executors, which carry no per-graph data).
    Returns the number of entries dropped."""
    dropped = 0
    for cache in (_ELL_CACHE, _SHARDED_ELL_CACHE, _RES_CACHE,
                  _SHARDED_RES_CACHE, _WDEG_CACHE, _VALID_CACHE,
                  _STATS_CACHE, _SLOT_CACHE):
        stale = [k for k, (ref, _) in list(cache.items()) if ref() is g]
        for k in stale:
            if cache.pop(k, None) is not None:
                dropped += 1
    return dropped


# ---------------------------------------------------------------------------
# Synthetic graph generators (seeded, host-side numpy).
# ---------------------------------------------------------------------------

def _dedupe(n, src, dst):
    keep = src != dst  # drop self loops
    src, dst = src[keep], dst[keep]
    key = src.astype(np.int64) * n + dst
    _, idx = np.unique(key, return_index=True)
    return src[idx], dst[idx]


def rmat_graph(n: int, e: int, seed: int = 0, weighted: bool = True,
               a=0.57, b=0.19, c=0.19) -> Graph:
    """R-MAT power-law generator (Chakrabarti et al.), deduped, no self loops."""
    rng = np.random.default_rng(seed)
    scale = int(np.ceil(np.log2(max(n, 2))))
    n_round = 1 << scale
    src = np.zeros(e, dtype=np.int64)
    dst = np.zeros(e, dtype=np.int64)
    for level in range(scale):
        r = rng.random(e)
        right = r >= a + b            # quadrant column
        bottom = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src = src * 2 + bottom
        dst = dst * 2 + right
    src, dst = src % n, dst % n
    src, dst = _dedupe(n, src, dst)
    w = rng.integers(1, 64, size=src.shape[0]).astype(np.float32) if weighted \
        else np.ones(src.shape[0], np.float32)
    cap = rng.integers(1, 64, size=src.shape[0]).astype(np.float32) if weighted \
        else np.ones(src.shape[0], np.float32)
    return from_edges(int(n), src.astype(np.int32), dst.astype(np.int32), w, cap)


def uniform_graph(n: int, e: int, seed: int = 0, weighted: bool = True) -> Graph:
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=e)
    dst = rng.integers(0, n, size=e)
    src, dst = _dedupe(n, src, dst)
    w = rng.integers(1, 16, size=src.shape[0]).astype(np.float32) if weighted \
        else np.ones(src.shape[0], np.float32)
    cap = rng.integers(1, 16, size=src.shape[0]).astype(np.float32) if weighted \
        else np.ones(src.shape[0], np.float32)
    return from_edges(int(n), src.astype(np.int32), dst.astype(np.int32), w, cap)


def line_graph(n: int, weighted: bool = False, seed: int = 0) -> Graph:
    src = np.arange(n - 1)
    dst = np.arange(1, n)
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 9, size=n - 1).astype(np.float32) if weighted \
        else np.ones(n - 1, np.float32)
    return from_edges(n, src, dst, w, w[::-1].copy())


def grid_graph(rows: int, cols: int, seed: int = 0) -> Graph:
    """4-neighbour mesh, bidirectional edges (MeshGraphNet-style)."""
    idx = np.arange(rows * cols).reshape(rows, cols)
    s, d = [], []
    s.append(idx[:, :-1].ravel()); d.append(idx[:, 1:].ravel())
    s.append(idx[:-1, :].ravel()); d.append(idx[1:, :].ravel())
    src = np.concatenate(s + d)   # both directions
    dst = np.concatenate(d + s)
    rng = np.random.default_rng(seed)
    w = rng.random(src.shape[0]).astype(np.float32) + 0.5
    return from_edges(rows * cols, src, dst, w, w)


def cora_like(n: int = 2708, e: int = 10556, d_feat: int = 1433, seed: int = 0):
    """Cora-shaped citation graph + features + labels (synthetic, seeded)."""
    g = uniform_graph(n, e + e // 4, seed=seed, weighted=False)
    rng = np.random.default_rng(seed + 1)
    x = (rng.random((n, d_feat)) < 0.012).astype(np.float32)  # sparse bag-of-words
    y = rng.integers(0, 7, size=n).astype(np.int32)
    return g, jnp.asarray(x), jnp.asarray(y)


def undirected(g: Graph) -> Graph:
    """Symmetrize: add reverse edges (CC in the paper assumes undirected).
    Deduplicates — the dense engine represents edges as an adjacency
    MATRIX, so parallel edges would change non-idempotent reductions
    (PageRank) relative to the edge-list engines."""
    src, dst, w, c = g.host_edges()
    s2 = np.concatenate([src, dst])
    d2 = np.concatenate([dst, src])
    w2 = np.concatenate([w, w])
    c2 = np.concatenate([c, c])
    key = s2.astype(np.int64) * g.n + d2
    _, idx = np.unique(key, return_index=True)
    return from_edges(g.n, s2[idx], d2[idx], w2[idx], c2[idx])
