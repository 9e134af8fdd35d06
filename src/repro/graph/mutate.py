"""Batched edge insert/delete with in-place blocked-ELL layout patching.

The incremental half of DESIGN.md §15: a mutation produces a NEW immutable
``Graph`` (every downstream cache is identity-keyed, so mutating in place
would silently serve stale layouts), but the expensive derived structures —
the pull/push blocked-ELL rectangles and the dst-sorted push resolution —
are carried over by an O(delta) patch instead of an O(E) rebuild whenever
the edit fits the existing padding:

* **Deletes** clear the edge's mask slot and decrement the owning tile's
  ``tile_nnz`` — the slot becomes reusable padding.
* **Inserts** take the first free slot of their row (freed or original
  padding).  A row whose free slots run out overflows the layout's padded
  width; that layout falls back to a **counted rebuild** (the patched entry
  is simply not installed, so the canonical lazy build runs for the new
  graph) and the fallback is visible in ``MUTATION_STATS`` / the returned
  ``MutationDelta``.

Patched layouts are *non-canonical*: an edge's slot is wherever a free slot
was, not the left-to-right fill order ``to_blocked_ell`` would assign.
That is value-safe for the idempotent reductions (min/max/or/and are
order-insensitive bitwise) but it means the push resolution can NEVER be
rebuilt canonically against a patched out rectangle — its ``in2out``
permutation would address the wrong slots.  The coupling rule: whenever
either direction's layout is patched, a resolution consistent with the
ACTUAL slot assignments of both directions is derived and installed
alongside (``structure.resolution_from_slots``), and the per-edge slot maps
are recorded in ``structure._SLOT_CACHE`` so chained mutations keep
patching from the real positions.

Touched-vertex contract (consumed by the delta-seeded fixpoint,
``engine.run_program(..., delta=...)``): ``MutationDelta.touched`` is the
unique endpoint set of every inserted and deleted edge — a superset of the
vertices whose fixpoint values can change in one propagation step, which
is exactly the frontier seed that makes warm-started idempotent rounds
sound for insert-only edits (DESIGN.md §15).
"""
from __future__ import annotations

import dataclasses
import weakref

import jax.numpy as jnp
import numpy as np

from repro.core.guard import GraphValidationError
from repro.graph import structure
from repro.graph.structure import (
    BlockedELL, Graph, _check_edge_arrays, _fill_order_slots, _padded_width,
    from_edges, live_tiles, slot_list)

# Global patch/rebuild accounting (bench + tests; reset like SWEEP_STATS).
MUTATION_STATS = {
    "mutations": 0,          # mutate_edges calls
    "patched_layouts": 0,    # cached layouts carried over by in-place patch
    "rebuilt_layouts": 0,    # cached layouts dropped to a counted rebuild
}


def reset_mutation_stats() -> None:
    for k in MUTATION_STATS:
        MUTATION_STATS[k] = 0


@dataclasses.dataclass(frozen=True)
class MutationDelta:
    """Summary of one ``mutate_edges`` batch: the planner's mutation-size
    statistics (``plan_execution(mutation=...)``) and the delta-fixpoint's
    frontier seed (``touched``)."""
    inserted: int            # edges added (post-policy filtering)
    deleted: int             # edges removed (explicit batch + policy drops)
    touched: np.ndarray      # unique int64 endpoint ids of every edit
    has_deletes: bool        # deletions retract support: idempotent rounds
                             # cannot warm-start over them (DESIGN.md §15)
    patched_layouts: int     # cached layouts patched in place this batch
    rebuilt_layouts: int     # cached layouts that overflowed to a rebuild


def _cache_hit(cache: dict, key, g):
    hit = cache.get(key)
    if hit is None:
        return None
    ref, val = hit
    return val if ref() is g else None


def _install(cache: dict, key, g, val) -> None:
    cache[key] = (weakref.ref(g), val)
    weakref.finalize(g, cache.pop, key, None)


def _slot_maps(g: Graph, block_v: int, block_e: int):
    """(k_in, k_out) per edge, aligned to ``host_edges`` (dst-sorted) order:
    the recorded maps of a previously-patched graph, or the canonical
    left-to-right fill order (exactly what ``to_blocked_ell`` /
    ``to_push_resolution`` assign) for a graph built from scratch."""
    maps = _cache_hit(structure._SLOT_CACHE, (id(g), block_v, block_e), g)
    if maps is not None:
        return maps
    src, dst, _w, _c = g.host_edges()
    return _fill_order_slots(dst, g.n), _fill_order_slots(src, g.n)


def _patch_ell(ell: BlockedELL, row_old, k_old, keep,
               row_ins, nbr_ins, w_ins, c_ins):
    """Patch one cached blocked-ELL layout: free deleted slots, place
    inserted edges in free slots of their rows, ±1 the affected tiles'
    ``tile_nnz``.  Returns ``(patched_ell, k_ins)`` with the inserted
    edges' slot indices, or None when an inserted row has no free slot left
    (overflow → counted rebuild)."""
    bv, be = ell.block_v, ell.block_e
    nbrs = np.array(ell.nbrs)
    ws = np.array(ell.weight)
    cs = np.array(ell.capacity)
    mask = np.array(ell.mask)
    tile_nnz = np.array(ell.tile_nnz)
    drop = ~keep
    if drop.any():
        r_del = row_old[drop]
        k_del = k_old[drop]
        mask[r_del, k_del] = False
        nbrs[r_del, k_del] = 0
        ws[r_del, k_del] = 0.0
        cs[r_del, k_del] = 0.0
        np.subtract.at(tile_nnz, (r_del // bv, k_del // be), 1)
    k_ins = np.empty(row_ins.shape[0], dtype=np.int64)
    free: dict = {}
    for i in range(row_ins.shape[0]):
        r = int(row_ins[i])
        slots = free.get(r)
        if slots is None:
            slots = list(np.flatnonzero(~mask[r]))
            free[r] = slots
        if not slots:
            return None
        k = int(slots.pop(0))
        k_ins[i] = k
        mask[r, k] = True
        nbrs[r, k] = nbr_ins[i]
        ws[r, k] = w_ins[i]
        cs[r, k] = c_ins[i]
    if row_ins.size:
        np.add.at(tile_nnz, (np.asarray(row_ins, np.int64) // bv,
                             k_ins // be), 1)
    pos, nbr = slot_list(nbrs, mask)
    patched = BlockedELL(
        n=ell.n, n_pad=ell.n_pad, width=ell.width,
        block_v=bv, block_e=be,
        nbrs=jnp.asarray(nbrs), weight=jnp.asarray(ws),
        capacity=jnp.asarray(cs), mask=jnp.asarray(mask),
        tile_nnz=jnp.asarray(tile_nnz), slot_pos=jnp.asarray(pos),
        slot_nbr=jnp.asarray(nbr),
        live_tiles=jnp.asarray(live_tiles(tile_nnz, bv)),
        direction=ell.direction)
    return patched, k_ins


def mutate_edges(g: Graph, insert=None, delete=None, *,
                 self_loops: str = "allow", duplicates: str = "allow"):
    """Apply one batched edge mutation; returns ``(new_graph, delta)``.

    ``insert`` is ``(src, dst[, weight[, capacity]])`` arrays (weight and
    capacity default to 1.0, like ``from_edges``); ``delete`` is
    ``(src, dst)`` pairs that must all exist — a k-fold request consumes k
    occurrences of a parallel edge, and naming a missing edge raises
    ``GraphValidationError``.  The merged edge list is validated under the
    ``self_loops`` / ``duplicates`` policies of ``from_edges`` (so
    inserting a duplicate under ``duplicates="error"`` raises with the
    standard text, and ``self_loops="drop"`` filters — counted as deletes
    when it removes surviving old edges).

    Every blocked-ELL layout and push resolution cached for ``g`` is
    carried to the new graph by an in-place patch when the edit fits the
    padded widths, falling back to a counted rebuild per layout on row
    overflow (module docstring; DESIGN.md §15)."""
    if self_loops not in ("allow", "drop", "error"):
        raise ValueError(f"self_loops must be allow|drop|error, "
                         f"got {self_loops!r}")
    if duplicates not in ("allow", "error"):
        raise ValueError(f"duplicates must be allow|error, got {duplicates!r}")
    if insert is None and delete is None:
        raise ValueError("mutate_edges needs an insert batch, a delete "
                         "batch, or both")
    src, dst, w, c = g.host_edges()
    n, e = g.n, int(src.shape[0])

    # ---- resolve the delete batch against the current edge list ----------
    keep = np.ones(e, dtype=bool)
    if delete is not None:
        if len(tuple(delete)) != 2:
            raise ValueError("delete must be a (src, dst) pair of vectors")
        dsrc = np.asarray(delete[0])
        ddst = np.asarray(delete[1])
        if dsrc.size == 0:
            dsrc = dsrc.astype(np.int32)
            ddst = ddst.astype(np.int32)
        for name, a in (("src", dsrc), ("dst", ddst)):
            if a.ndim != 1 or not np.issubdtype(a.dtype, np.integer):
                raise GraphValidationError(
                    f"delete {name} must be a 1-d integer vector, got "
                    f"shape {a.shape} dtype {a.dtype}")
        if dsrc.shape != ddst.shape:
            raise GraphValidationError(
                f"delete src/dst length mismatch: {dsrc.shape[0]} vs "
                f"{ddst.shape[0]}")
        if dsrc.size:
            if (dsrc.min() < 0 or dsrc.max() >= n
                    or ddst.min() < 0 or ddst.max() >= n):
                raise GraphValidationError(
                    f"delete batch endpoints out of range [0, {n})")
            key = src.astype(np.int64) * n + dst
            dkey = dsrc.astype(np.int64) * n + ddst.astype(np.int64)
            order = np.argsort(key, kind="stable")
            skey = key[order]
            dorder = np.argsort(dkey, kind="stable")
            sdkey = dkey[dorder]
            # rank-within-key: the j-th request for one (src, dst) key
            # consumes the j-th occurrence of that parallel edge
            rank = np.arange(sdkey.size) - np.searchsorted(sdkey, sdkey)
            lo = np.searchsorted(skey, sdkey, side="left")
            hi = np.searchsorted(skey, sdkey, side="right")
            missing = rank >= (hi - lo)
            if missing.any():
                i = int(dorder[np.flatnonzero(missing)[0]])
                raise GraphValidationError(
                    f"delete batch names {int(missing.sum())} edge(s) not "
                    f"present in the graph, first "
                    f"({int(dsrc[i])} -> {int(ddst[i])})")
            keep[order[lo + rank]] = False

    # ---- the insert batch -------------------------------------------------
    if insert is not None:
        parts = tuple(insert)
        if len(parts) < 2:
            raise ValueError(
                "insert must be (src, dst[, weight[, capacity]]) vectors")
        isrc = np.asarray(parts[0])
        idst = np.asarray(parts[1])
        if isrc.size == 0:
            isrc = isrc.astype(np.int32)
            idst = idst.astype(np.int32)
        n_req = isrc.shape[0] if isrc.ndim else 0
        iw = (np.asarray(parts[2], dtype=np.float32)
              if len(parts) > 2 and parts[2] is not None
              else np.ones(n_req, np.float32))
        ic = (np.asarray(parts[3], dtype=np.float32)
              if len(parts) > 3 and parts[3] is not None
              else np.ones(n_req, np.float32))
    else:
        isrc = np.zeros(0, np.int32)
        idst = np.zeros(0, np.int32)
        iw = np.zeros(0, np.float32)
        ic = np.zeros(0, np.float32)

    # ---- merged edge list, validated under the caller's policies ----------
    new_src = np.concatenate([src[keep], isrc])
    new_dst = np.concatenate([dst[keep], idst])
    new_w = np.concatenate([w[keep], iw]).astype(np.float32)
    new_c = np.concatenate([c[keep], ic]).astype(np.float32)
    fmask = _check_edge_arrays(n, new_src, new_dst, new_w, new_c,
                               self_loops, duplicates)
    if fmask is not None:            # self_loops="drop" filtered the merge
        kept_idx = np.flatnonzero(keep)
        keep[kept_idx[~fmask[:kept_idx.size]]] = False
        ins_keep = fmask[kept_idx.size:]
        isrc, idst = isrc[ins_keep], idst[ins_keep]
        iw, ic = iw[ins_keep], ic[ins_keep]
        new_src, new_dst = new_src[fmask], new_dst[fmask]
        new_w, new_c = new_w[fmask], new_c[fmask]
    new_src = new_src.astype(np.int32, copy=False)
    new_dst = new_dst.astype(np.int32, copy=False)

    new_g = from_edges(n, new_src, new_dst, new_w, new_c, validate=False)
    n_ins = int(isrc.shape[0])
    n_del = e - int(keep.sum())
    touched = np.unique(np.concatenate([
        src[~keep].astype(np.int64), dst[~keep].astype(np.int64),
        isrc.astype(np.int64), idst.astype(np.int64)]))

    # ---- carry cached layouts over by patch (or count the rebuild) --------
    patched = rebuilt = 0
    shapes = set()
    for (gid, bv, be, _d), (ref, _ell) in list(structure._ELL_CACHE.items()):
        if gid == id(g) and ref() is g:
            shapes.add((bv, be))
    perm_new = np.argsort(new_dst, kind="stable")   # from_edges' by_dst order
    for bv, be in sorted(shapes):
        k_in_old, k_out_old = _slot_maps(g, bv, be)
        ell_in = _cache_hit(structure._ELL_CACHE, (id(g), bv, be, "in"), g)
        ell_out = _cache_hit(structure._ELL_CACHE, (id(g), bv, be, "out"), g)
        res_old = _cache_hit(structure._RES_CACHE, (id(g), bv, be), g)
        in_patch = out_patch = None
        if ell_in is not None:
            in_patch = _patch_ell(ell_in, dst, k_in_old, keep,
                                  idst, isrc, iw, ic)
            if in_patch is None:
                rebuilt += 1
        if ell_out is not None:
            out_patch = _patch_ell(ell_out, src, k_out_old, keep,
                                   isrc, idst, iw, ic)
            if out_patch is None:
                rebuilt += 1
        if in_patch is None and out_patch is None:
            if res_old is not None:
                rebuilt += 1         # its layouts rebuild, it follows them
            continue
        # Final per-edge slot maps of the new graph, host_edges-aligned:
        # the patched positions where the patch succeeded, the canonical
        # fill order where the layout falls back to a lazy rebuild.
        if in_patch is not None:
            new_in, k_in_ins = in_patch
            k_in_full = np.concatenate([k_in_old[keep], k_in_ins])[perm_new]
            w_in_f = new_in.width
        else:
            k_in_full = _fill_order_slots(new_dst[perm_new], n)
            w_in_f = _padded_width(np.bincount(new_dst, minlength=n), be)
        if out_patch is not None:
            new_out, k_out_ins = out_patch
            k_out_full = np.concatenate([k_out_old[keep],
                                         k_out_ins])[perm_new]
            w_out_f = new_out.width
        else:
            k_out_full = _fill_order_slots(new_src[perm_new], n)
            w_out_f = _padded_width(np.bincount(new_src, minlength=n), be)
        if in_patch is not None:
            _install(structure._ELL_CACHE, (id(new_g), bv, be, "in"),
                     new_g, new_in)
            patched += 1
        if out_patch is not None:
            _install(structure._ELL_CACHE, (id(new_g), bv, be, "out"),
                     new_g, new_out)
            patched += 1
        # The resolution MUST match the actual slot assignments of both
        # directions (module docstring) — derive and install it whenever
        # either direction is non-canonical.
        res = structure.resolution_from_slots(
            n, new_src[perm_new], new_dst[perm_new],
            k_in_full, k_out_full, w_in_f, w_out_f, bv, be)
        _install(structure._RES_CACHE, (id(new_g), bv, be), new_g, res)
        if res_old is not None:
            patched += 1
        _install(structure._SLOT_CACHE, (id(new_g), bv, be),
                 new_g, (k_in_full, k_out_full))

    MUTATION_STATS["mutations"] += 1
    MUTATION_STATS["patched_layouts"] += patched
    MUTATION_STATS["rebuilt_layouts"] += rebuilt
    return new_g, MutationDelta(
        inserted=n_ins, deleted=n_del, touched=touched,
        has_deletes=bool(n_del), patched_layouts=patched,
        rebuilt_layouts=rebuilt)
