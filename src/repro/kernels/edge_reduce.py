"""Blocked-ELL gather → propagate → reduce Pallas TPU kernel.

This is the hardware adaptation of the paper's per-edge kernel-function
application (DESIGN.md §2): the CPU frameworks' per-edge atomics / worklists
become a dst-tiled, degree-padded ELL sweep where every Pallas grid step
processes a fully regular ``(BLOCK_V dst vertices × BLOCK_E predecessor
slots)`` tile in VMEM:

  1. XLA gathers the predecessor values ``state[srcs]`` (and every other
     per-source quantity P reads) into ``[n_pad, width]`` operands before
     the launch — Mosaic lowers no in-kernel vector gather — with
     frontier-inactive and padding slots already set to the reduction
     identity, so the operands stream through VMEM as plain tiles.  The
     gather reads only the real slots, through the layout's slot list,
     and scatters them into the rectangle (``gather_slots``),
  2. apply the synthesized propagation function P (a jnp-traceable closure
     from repro.core.synthesis — the paper's "kernel function" IS the
     kernel body),
  3. reduce along the slot axis with the reduction monoid R, and
  4. write one candidate per (row, slot-tile) into a lane-dense
     ``[n_pad/128, n_tiles, 128]`` output block that stays resident while
     the grid walks one 128-row group (``_store_lane_dense``).

The per-tile activity bitmap rides in SMEM as the scalar-prefetch operand
of a ``PrefetchScalarGridSpec``; a tile whose bit is 0 skips its body.
Where a layout's live-tile list is shorter than the full grid, it rides
beside the bitmap and the grid walks only the tiles it names
(``live_grid``, ``_tile_call``).
Per-row vectors the push sweep reads (its own row's state, the frontier,
the degrees) stream as ``(1, 1, 128)`` lane rows (``_lane_rows``).

Three sweep entry points:

``fused_ell_sweep`` — the single-pass PULL engine sweep (DESIGN.md §2).  ONE
``pallas_call`` evaluates every plan of the fused round: each tile gathers
each component's state once, applies all propagation functions, performs the
full lexicographic reduction chain on-chip, and emits per-tile candidate
blocks (plus, optionally, the fused has-predecessor probe of the pull−
models).  Cross-tile lexicographic ties are resolved by a short jnp pass
over the ``[n_pad, width/BLOCK_E]`` candidate arrays — no second kernel
launch.  Tiles whose ``tile_act`` bit is 0 (no real slots, or no frontier-
active source) short-circuit via ``pl.when`` and contribute exactly the
reduction identities.

``fused_ell_push_sweep`` — the single-pass PUSH sweep (Defs. 3/4) over the
out-edge (source-keyed successor) layout.  ONE ``pallas_call`` applies every
propagation function across the frontier-active source tiles — state is read
per ROW (no gather), and a sparse frontier skips whole row blocks, which is
what makes BFS/SSSP iteration cost scale with the frontier instead of the
graph — then the dst-keyed lexicographic reduction resolves either through
the default dst-sorted segment-reduction path (``resolution="sorted"``,
DESIGN.md §10: candidates gather through the precomputed dst-major
permutation into the in-rectangle, where each row is one contiguous dst
segment, and a second Pallas tile pass lex-reduces only the tiles whose
candidates came from frontier-active source tiles) or as the reference
full-rectangle scatter pass in plain jnp (``resolution="scatter"``).  Both
feed the same ``plan_merge`` contract as the pull sweep (bit-for-bit
⊥-as-identity, C6).

``ell_level_reduce`` — the original one-launch-per-lex-level pull sweep,
kept as a reference path and for kernel-level tests; later levels recompute
the earlier levels' propagated values and mask to tie slots.

Padding slots and frontier-inactive sources carry the reduction identity
(condition C6 makes that sound).  Tiles default to (8, 128): the VPU lane
layout, and the slot axis a multiple of 128.  ``block_v`` must divide 128
(the lane-dense outputs pack 128 // block_v row tiles per lane row).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graph import segment

BLOCK_V = 8
BLOCK_E = 128
LANES = 128
_INT32_MIN = -(2 ** 31)


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """THE interpret decision of every Pallas call in the repo: an explicit
    ``interpret`` wins; None means compiled (Mosaic) on a TPU backend and
    the Pallas interpreter on any other."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# boolean monoids run as int32 min/max inside the kernel
_INT_OP = {"or": "max", "and": "min"}

# Sweep statistics.  "launches"/"pull_launches"/"push_launches" are
# trace-time counters: each EDGE-SWEEP pallas_call issued during tracing
# increments them exactly once AFTER the call traces successfully (a
# launch whose construction raises must not skew bench launch counts), so
# for a pull- or push-only executor they ARE sweeps-per-iteration; a
# direction-optimized executor traces BOTH branches of its lax.cond, so it
# counts one pull and one push launch per round while executing exactly
# one per iteration.  "resolve_launches" counts the dst-sorted push
# RESOLUTION tile passes separately (one per traced push sweep under
# ``resolution="sorted"``, zero under "scatter"/pull) — they are not edge
# sweeps, so the sweep-launch contract tests stay direction-symmetric.
# What the executed iterations did (direction split, resolution and gather
# work) is per query: ``IterationResult`` / ``ExecStats``.
SWEEP_STATS = {"launches": 0, "pull_launches": 0, "push_launches": 0,
               "resolve_launches": 0}


def reset_sweep_stats():
    for k in SWEEP_STATS:
        SWEEP_STATS[k] = 0


def comps_in_plan_order(plans):
    """Component ids in first-appearance order over the static plan specs
    ((comp, op) lex levels, primary first).  Every layer that walks a fused
    round — both sweeps and the executor's state tuple — derives its
    component ordering from this one function so kernel argument order can
    never desynchronize from the executor's state order."""
    order = []
    for spec in plans:
        for c, _op in spec:
            if c not in order:
                order.append(c)
    return order


def _ident_scalars(comps_order, states, idents):
    """Identities as Python scalars (Pallas kernels may not close over
    traced constants), coerced to the component state dtype's kind."""
    def scalar(c):
        i = idents[c]
        return int(i) if jnp.issubdtype(states[c].dtype, jnp.integer) \
            else float(i)
    return tuple(scalar(c) for c in comps_order)


def _combine(op: str, a, b):
    return {"min": jnp.minimum, "max": jnp.maximum,
            "sum": lambda x, y: x + y, "prod": lambda x, y: x * y}[op](a, b)


def _row_reduce(op: str, x, axis):
    return {"min": jnp.min, "max": jnp.max, "sum": jnp.sum,
            "prod": jnp.prod}[op](x, axis=axis)


def _fold_tile_candidates(plans, plan_specs, ident_scalars, outs):
    """Cross-tile lexicographic resolution: fold the ``plan_merge``
    recurrence over the tile axis of per-tile candidate arrays
    ``outs[level][n_pad, n_tiles]``, in plain jnp.  Shared verbatim by the
    pull sweep and the dst-sorted push resolution so both directions reduce
    with the identical monoid tree (the bitwise pull ≡ push(sorted)
    guarantee of DESIGN.md §10 rests on this).  Returns ({comp: [n_pad]
    reduction}, levels consumed)."""
    red, oi = {}, 0
    with jax.named_scope("grafs.merge"):
        for spec, mapped in zip(plans, plan_specs):
            tie = jnp.ones(outs[oi].shape, bool)
            for (c, _op), (pos, op) in zip(spec, mapped):
                ident = jnp.asarray(ident_scalars[pos], outs[oi].dtype)
                vals = jnp.where(tie, outs[oi], ident)
                best = _row_reduce(op, vals, axis=1)
                red[c] = best
                tie = tie & (vals == best[:, None])
                oi += 1
    return red, oi


# ---------------------------------------------------------------------------
# Operand plumbing shared by every sweep: which env entries P reads, SMEM
# tile activity, lane rows in, lane-dense candidates out.
# ---------------------------------------------------------------------------


class _ReadProbe(dict):
    """Env mapping that records the keys a propagation closure reads."""

    def __init__(self, *args):
        super().__init__(*args)
        self.read = set()

    def __getitem__(self, key):
        self.read.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.read.add(key)
        return super().get(key, default)


def env_keys_read(p_fns, dtypes) -> frozenset:
    """Env entries the synthesized P closures read, found by one abstract
    trace of each closure on a single tile.  The sweeps gather and stream
    only these: each one is an ``[n_pad, width]`` operand per launch."""
    read = set()
    tile = (BLOCK_V, BLOCK_E)
    for fn, dt in zip(p_fns, dtypes):
        def trace(n, f32, i32, fn=fn):
            env = _ReadProbe({"n": n, "w": f32, "c": f32, "esrc": i32,
                              "edst": i32, "outdeg": f32, "wdeg": f32,
                              "nv": jnp.float32(1)})
            out = fn(env)
            read.update(env.read)
            return out
        jax.eval_shape(trace, jax.ShapeDtypeStruct(tile, dt),
                       jax.ShapeDtypeStruct(tile, jnp.float32),
                       jax.ShapeDtypeStruct(tile, jnp.int32))
    return frozenset(read)


def _check_block_v(block_v: int) -> None:
    if LANES % block_v:
        raise ValueError(f"block_v must divide {LANES}, got {block_v}")


def gather_slots(table, fill, shape, slots=None, idx=None, mask=None):
    """``table[idx]`` on every real slot of an ``shape`` = [n_pad, width]
    layout, ``fill`` on the rest.

    ``slots = (pos, sidx)`` is the layout's ``structure.slot_list``: the
    real slots' flat positions (ascending; positions ≥ n_pad·width are
    padding and dropped) and the table index each reads.  XLA then gathers
    E values and scatters them into the rectangle — a TPU gather costs per
    index, and a rectangle padded to the max degree is mostly padding.
    Without ``slots`` it is the plain per-slot gather over ``idx`` under
    ``mask``; both give the same array bit for bit."""
    if slots is None:
        with jax.named_scope("grafs.slot_gather"):
            return jnp.where(mask, table[idx], jnp.asarray(fill, table.dtype))
    pos, sidx = slots
    with jax.named_scope("grafs.slot_gather"):
        vals = table[sidx]
    with jax.named_scope("grafs.slot_scatter"):
        flat = jnp.full((shape[0] * shape[1],), fill, table.dtype)
        flat = flat.at[pos].set(vals, mode="drop", unique_indices=True,
                                indices_are_sorted=True)
        return flat.reshape(shape)


def _bits(x):
    """x as int32 carrying its exact 32-bit pattern (NaNs and -0.0
    included); narrower dtypes widen by value."""
    if jnp.dtype(x.dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)


def _unbits(b, dtype):
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(b, dtype)
    return b.astype(dtype)


def _step_lanes(i, block_v):
    """(lane offset of grid row-step ``i`` inside its 128-row group, the
    (block_v, 128) one-hot "row r ↔ lane off + r" mask)."""
    off = (i % (LANES // block_v)) * block_v
    sub = jax.lax.broadcasted_iota(jnp.int32, (block_v, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_v, LANES), 1)
    return off, lane == off + sub


def _lane_rows(v):
    """[n] per-vertex vector → [ceil(n/128), 1, 128] lane rows: a
    ``(1, 1, 128)`` block carries the values of 128 // block_v row tiles
    (a ``(block_v,)`` block would break the TPU's (8, 128) tiling)."""
    n = v.shape[0]
    n_g = -(-n // LANES)
    return jnp.pad(v, (0, n_g * LANES - n)).reshape(n_g, 1, LANES)


def _lane_row_block(block_v):
    """The ``(1, 1, 128)`` lane-row block of row tile i (``_tile_call``'s
    (block shape, tile → block index) form)."""
    g = LANES // block_v
    return (1, 1, LANES), lambda i, j: (i // g, 0, 0)


def _rows_of_step(ref, i, block_v):
    """The (block_v, 1) values of row tile ``i`` out of its lane-row block,
    moved lanes → sublanes exactly (a one-hot max over the bit patterns)."""
    _off, onehot = _step_lanes(i, block_v)
    col = jnp.max(jnp.where(onehot, _bits(ref[0]), _INT32_MIN), axis=1,
                  keepdims=True)
    return _unbits(col, ref.dtype)


def _lane_dense_block(n_j, block_v):
    """The resident ``(1, n_j, 128)`` lane-dense output block of row tile
    i's 128-row group."""
    g = LANES // block_v
    return (1, n_j, LANES), lambda i, j: (i // g, 0, 0)


def _fill_on_first_visit(out_refs, fills, step):
    """Identity-fill each lane-dense output block when the grid enters its
    128-row group: row tiles that skip keep ⊥ (= the identity, C6)."""
    @pl.when(step.first())
    def _fill():
        for ref, fill in zip(out_refs, fills):
            ref[...] = jnp.full(ref.shape, fill, ref.dtype)


def _store_lane_dense(out_ref, col, i, j, block_v):
    """Write grid step (i, j)'s (block_v, 1) per-row result into the
    resident ``(1, n_j, 128)`` block: sublane j, lanes off .. off+block_v.
    The sublane → lane move is a one-hot max over the bit patterns, so every
    value (NaNs and -0.0 included) lands bit-exact."""
    n_j = out_ref.shape[1]
    off, onehot = _step_lanes(i, block_v)
    row = jnp.max(jnp.where(onehot, _bits(col), _INT32_MIN), axis=0,
                  keepdims=True)
    sub = jax.lax.broadcasted_iota(jnp.int32, (n_j, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_j, LANES), 1)
    hit = (sub == j) & (lane >= off) & (lane < off + block_v)
    out_ref[0] = jnp.where(hit, _unbits(row, out_ref.dtype), out_ref[0])


def _lane_dense_rows(out, n_pad):
    """[n_pad/128, n_j, 128] lane-dense candidates → [n_pad, n_j]."""
    n_g, n_j, _ = out.shape
    return out.transpose(0, 2, 1).reshape(n_g * LANES, n_j)[:n_pad]


def _pack_tile_bits(tile_act):
    """[n_i, n_j] activity bitmap → one bit per tile, 32 tiles per int32
    word (tile t = i·n_j + j at bit t % 32 of word t // 32): SMEM holds
    1 MiB on a v5e, which a word per tile would exhaust at 2^18 tiles."""
    flags = (jnp.asarray(tile_act).reshape(-1) != 0).astype(jnp.uint32)
    n_w = -(-flags.shape[0] // 32)
    flags = jnp.pad(flags, (0, n_w * 32 - flags.shape[0])).reshape(n_w, 32)
    words = jnp.sum(flags << jnp.arange(32, dtype=jnp.uint32), axis=1)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _tile_active(bits_ref, t):
    """Scalar test of flat tile t's bit in the SMEM bitmap."""
    word = bits_ref[t // 32]
    return jax.lax.shift_right_logical(word, t % 32) & 1 != 0


# Live-tile lists longer than this (int32 entries, 256 KiB) keep the full
# grid: the list rides in SMEM (1 MiB on a v5e) beside the packed bitmap.
LIVE_LIST_MAX = 1 << 16


def live_grid(live, n_tiles: int):
    """The grid a sweep over a layout of ``n_tiles`` tiles walks: its
    live-tile list (``structure.live_tiles``) when that is shorter than the
    full grid and fits ``LIVE_LIST_MAX``, else None — the full
    (row tile, slot tile) grid."""
    if live is None or live.shape[0] >= n_tiles \
            or live.shape[0] > LIVE_LIST_MAX:
        return None
    return live


def grid_steps(live, n_tiles: int) -> int:
    """Grid steps of one sweep launch over ``live_grid(live, n_tiles)``."""
    grid = live_grid(live, n_tiles)
    return n_tiles if grid is None else int(grid.shape[0])


class _Step(NamedTuple):
    """The layout tile a grid step sweeps: row tile ``i``, slot tile ``j``,
    and two scalars the kernel traces where it needs them: ``first()``,
    the step enters its 128-row output group, and ``active()``, the tile's
    bit in the SMEM bitmap."""
    i: object
    j: object
    first: Callable
    active: Callable


def _tile_call(kern, tile_act, live, args, in_blocks, out_shapes, out_blocks,
               *, block_v, interpret, name):
    """``pallas_call`` named ``name`` of ``kern(step, *refs)`` (``_Step``)
    over the tiles of an [n_i, n_j] layout, with the packed activity bitmap
    (``_pack_tile_bits``) as the SMEM scalar-prefetch operand.  Blocks are
    (block shape, (i, j) → block index) pairs.

    ``live`` None walks the full (row tile, slot tile) grid.  A live-tile
    list (``live_grid``) is a second scalar-prefetch operand and the grid
    is 1-D over it: step s sweeps tile ``live[s] = i·n_j + j``.  The list
    is ascending, so each 128-row group's steps run contiguously and its
    lane-dense output block is filled on the group's first step, stays
    resident and is written back once."""
    n_i, n_j = tile_act.shape
    g = LANES // block_v
    prefetch = [_pack_tile_bits(tile_act)]
    if live is None:
        grid = (n_i, n_j)

        def spec(block):
            shape, at = block
            return pl.BlockSpec(shape, lambda i, j, *_: at(i, j))

        def body(bits_ref, *refs):
            i = pl.program_id(0)
            j = pl.program_id(1)
            kern(_Step(i, j, lambda: (j == 0) & (i % g == 0),
                       lambda: _tile_active(bits_ref, i * n_j + j)), *refs)
    else:
        grid = (live.shape[0],)
        prefetch.append(live)

        def spec(block):
            shape, at = block
            return pl.BlockSpec(
                shape, lambda s, _bits, lv: at(lv[s] // n_j, lv[s] % n_j))

        def body(bits_ref, live_ref, *refs):
            s = pl.program_id(0)
            t = live_ref[s]

            def first():
                prev = live_ref[jnp.maximum(s - 1, 0)]
                return (s == 0) | (t // (g * n_j) != prev // (g * n_j))
            kern(_Step(t // n_j, t % n_j, first,
                       lambda: _tile_active(bits_ref, t)), *refs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid,
        in_specs=[spec(b) for b in in_blocks],
        out_specs=[spec(b) for b in out_blocks])
    outs = pl.pallas_call(body, grid_spec=grid_spec, out_shape=out_shapes,
                          interpret=interpret, name=name)(*prefetch, *args)
    return list(outs) if isinstance(outs, (tuple, list)) else [outs]


def _tile_lex_chain(props, plan_specs, idents, tie):
    """The per-tile lexicographic reduction chain: per plan, reduce each
    level over the slot axis with ties of the earlier levels masking it to
    the identity.  ``tie`` None means every slot competes (operands already
    carry ⊥ outside the mask).  Returns one (block_v, 1) column per level."""
    cols = []
    for spec in plan_specs:
        t = tie
        for l, (pos, op) in enumerate(spec):
            ident = jnp.asarray(idents[pos], props[pos].dtype)
            vals = props[pos] if t is None else jnp.where(t, props[pos], ident)
            best = _row_reduce(op, vals, axis=1)[:, None]
            cols.append(best)
            if l + 1 < len(spec):
                eq = props[pos] == best
                t = eq if t is None else t & eq
    return cols


# ---------------------------------------------------------------------------
# Fused single-pass sweep: all plans × lex levels (+ has-pred) in one launch.
# ---------------------------------------------------------------------------


def _reduce_kernel(step, *refs, n_comps, env_names, has_act, plan_specs,
                   hp_positions, p_fns, idents, nv, block_v):
    """One (BLOCK_V, BLOCK_E) tile of the pull sweep or of the sorted push
    resolution.

    ``refs`` = ``n_comps`` value tiles (gathered neighbour states, or the
    gathered push candidates; ⊥ outside the mask), the env tiles named by
    ``env_names``, an int32 frontier-mask tile when ``has_act``, then the
    lane-dense outputs: one per plan per lex level, then one has-pred output
    per entry of ``hp_positions``.  ``p_fns`` None means the values are
    already propagated (resolution).  ``plan_specs`` is static: per plan a
    tuple of (state position, monoid) levels, primary first.

    Every (row, slot tile) candidate is written by exactly one grid step —
    no cross-step accumulation — so cross-tile lexicographic resolution can
    run outside the kernel on the [n_pad, n_tiles] candidates."""
    i, j = step.i, step.j
    n_env = len(env_names)
    val_refs = refs[:n_comps]
    env_refs = refs[n_comps:n_comps + n_env]
    act_ref = refs[n_comps + n_env] if has_act else None
    out_refs = refs[n_comps + n_env + int(has_act):]
    fills = [idents[pos] for spec in plan_specs for pos, _op in spec] \
        + [0] * len(hp_positions)
    _fill_on_first_visit(out_refs, fills, step)

    @pl.when(step.active())
    def _tile_body():
        vals = [r[...] for r in val_refs]
        if p_fns is None:
            props = vals
        else:
            env = {name: r[...] for name, r in zip(env_names, env_refs)}
            env["edst"] = i * block_v + jax.lax.broadcasted_iota(
                jnp.int32, vals[0].shape, 0)
            env["nv"] = jnp.float32(nv)
            props = []
            for k, nvals in enumerate(vals):
                p = jnp.asarray(p_fns[k]({"n": nvals, **env}), nvals.dtype)
                props.append(jnp.where(nvals == idents[k], idents[k], p))
        tie = (act_ref[...] != 0) if has_act else None
        cols = _tile_lex_chain(props, plan_specs, idents, tie)
        for pos in hp_positions:                 # fused has-pred probe
            nb = (vals[pos] != idents[pos]).astype(jnp.int32)
            cols.append(jnp.max(nb, axis=1, keepdims=True))
        for out_ref, col in zip(out_refs, cols):
            _store_lane_dense(out_ref, col.astype(out_ref.dtype), i, j,
                              block_v)


def _tile_block(block_v, block_e):
    """The (block_v, block_e) operand tile of layout tile (i, j)."""
    return (block_v, block_e), lambda i, j: (i, j)


def _reduce_sweep(vals, env, act, tile_act, live, *, plan_specs,
                  hp_positions, p_fns, idents, nv, block_v, block_e,
                  interpret, name):
    """Launch ``_reduce_kernel`` (as the kernel ``name``) over [n_pad, width]
    value operands ``vals`` (plus the named env operands and an optional
    frontier mask), on the grid ``live`` names (``_tile_call``), and return
    the per-level (then has-pred) [n_pad, n_tiles] candidate arrays."""
    _check_block_v(block_v)
    n_pad, width = vals[0].shape
    n_j = width // block_e
    env_names = tuple(env)
    args = list(vals) + [env[k] for k in env_names]
    if act is not None:
        args.append(act.astype(jnp.int32))
    out_dtypes = [vals[pos].dtype for spec in plan_specs
                  for pos, _op in spec] + [jnp.int32] * len(hp_positions)
    n_g = -(-n_pad // LANES)
    kern = functools.partial(
        _reduce_kernel, n_comps=len(vals), env_names=env_names,
        has_act=act is not None, plan_specs=plan_specs,
        hp_positions=hp_positions, p_fns=p_fns, idents=idents, nv=nv,
        block_v=block_v)
    outs = _tile_call(
        kern, tile_act, live, args, [_tile_block(block_v, block_e)] * len(args),
        [jax.ShapeDtypeStruct((n_g, n_j, LANES), dt) for dt in out_dtypes],
        [_lane_dense_block(n_j, block_v)] * len(out_dtypes),
        block_v=block_v, interpret=interpret, name=name)
    return [_lane_dense_rows(o, n_pad) for o in outs]


def fused_ell_sweep(srcs, weight, capacity, mask, tile_act, states, active,
                    outdeg, *, plans, idents, p_fns, nv,
                    need_haspred: bool = False, wdeg=None, slots=None,
                    live=None, block_v: int = BLOCK_V, block_e: int = BLOCK_E,
                    interpret: Optional[bool] = None,
                    return_candidates: bool = False):
    """Single-launch fused edge sweep over every plan of a fused round.

    srcs/weight/capacity/mask   [n_pad, width] blocked-ELL arrays
    tile_act  [n_pad/block_v, width/block_e] int32 — 0 short-circuits a tile
    live      the layout's live-tile grid (``live_grid``): the kernel walks
              only those tiles; None walks the full grid.  Either way every
              candidate comes out the same, bit for bit
    states    {comp: [n_pad] value vector}
    active    [n_pad] int32 frontier (1 = source eligible)
    outdeg    [n_pad] float32 (gathered per edge into the P environment)
    wdeg      [n_pad] float32 weighted out-degree (env "wdeg"; None → 1s)
    slots     the layout's (slot_pos, slot_nbr) list: gathers go through it
              (``gather_slots``); None gathers per slot
    plans     static: per plan a tuple of (comp, op) lex levels, primary first
    idents    {comp: identity scalar};  p_fns {comp: propagation closure}

    Returns ``(red, hp)``: ``red[comp]`` is the [n_pad] cross-tile-resolved
    reduction of that level, ``hp[comp]`` the [n_pad] bool has-pred vector
    (empty dict unless ``need_haspred``).  With ``return_candidates`` the raw
    per-tile candidate arrays are appended: ``(red, hp, cands)``.

    The neighbour gathers run in XLA before the launch (``gather_slots``):
    each component's ``state[srcs]`` becomes an [n_pad, width] operand
    holding ⊥ wherever the slot is padding or (for value-only sweeps) its
    source is frontier-inactive, and only the env entries P reads
    (``env_keys_read``) are streamed.  A has-pred sweep masks the values by
    the real slots only and streams the frontier mask separately.
    """
    interpret = resolve_interpret(interpret)
    comps_order = comps_in_plan_order(plans)
    pos_of = {c: k for k, c in enumerate(comps_order)}
    ident_scalars = _ident_scalars(comps_order, states, idents)
    plan_specs = tuple(tuple((pos_of[c], _INT_OP.get(op, op)) for c, op in spec)
                       for spec in plans)
    hp_positions = tuple(range(len(comps_order))) if need_haspred else ()
    fns = tuple(p_fns[c] for c in comps_order)
    keys = env_keys_read(fns, [states[c].dtype for c in comps_order])

    if wdeg is None:
        wdeg = jnp.ones_like(outdeg)
    gather = functools.partial(gather_slots, shape=srcs.shape, slots=slots,
                               idx=srcs, mask=mask)
    active = jnp.asarray(active, jnp.int32)
    act = gather(active, 0) != 0
    # a value-only sweep masks frontier-inactive sources to ⊥ per vertex,
    # before the gather; the has-pred sweep streams the frontier instead
    vals = [gather(states[c] if need_haspred else jnp.where(
                active != 0, states[c],
                jnp.asarray(ident_scalars[k], states[c].dtype)),
                ident_scalars[k])
            for k, c in enumerate(comps_order)]
    per_slot = {"w": lambda: weight, "c": lambda: capacity,
                "esrc": lambda: srcs, "outdeg": lambda: gather(outdeg, 0),
                "wdeg": lambda: gather(wdeg, 0)}
    env = {k: get() for k, get in per_slot.items() if k in keys}
    outs = _reduce_sweep(
        vals, env, act if need_haspred else None, tile_act, live,
        plan_specs=plan_specs, hp_positions=hp_positions, p_fns=fns,
        idents=ident_scalars, nv=float(nv), block_v=block_v,
        block_e=block_e, interpret=interpret, name="grafs_pull_sweep")
    SWEEP_STATS["launches"] += 1
    SWEEP_STATS["pull_launches"] += 1

    # Cross-tile lexicographic resolution (the "short second pass"): a fold
    # of the plan_merge recurrence over the tile axis, in plain jnp — zero
    # extra kernel launches.
    red, oi = _fold_tile_candidates(plans, plan_specs, ident_scalars, outs)
    hp = {}
    if need_haspred:
        for k, c in enumerate(comps_order):
            hp[c] = jnp.max(outs[oi + k], axis=1) > 0
    if return_candidates:
        return red, hp, outs
    return red, hp


def tile_activity(srcs, mask, tile_nnz, active_i32, block_v: int, block_e: int,
                  slots=None):
    """Frontier-aware per-tile activity bitmap: a tile runs iff it has real
    slots AND at least one frontier-active source.  One gather (through
    ``slots`` when given, see ``gather_slots``) + block reduction in XLA —
    far cheaper than the propagation work it skips."""
    n_i, n_j = tile_nnz.shape
    with jax.named_scope("grafs.tile_activity"):
        act = gather_slots(active_i32, 0, srcs.shape, slots, srcs, mask) != 0
        any_act = act.reshape(n_i, block_v, n_j, block_e).any(axis=(1, 3))
        return ((tile_nnz > 0) & any_act).astype(jnp.int32)


def tile_activity_push(tile_nnz, active_i32, block_v: int):
    """Push-side activity bitmap over the out-edge (source-keyed) layout.

    Rows ARE sources, so a tile is active iff its row block contains a
    frontier-active vertex — no gather at all, just a block-any over the
    frontier, and work scales with the number of active *source rows*
    rather than "tiles that happen to contain an active source" (the pull
    criterion, which a sparse frontier of hub predecessors still lights up
    almost everywhere).  This asymmetry is why the push direction wins the
    sparse tail of BFS/SSSP (DESIGN.md §2)."""
    n_i, _n_j = tile_nnz.shape
    with jax.named_scope("grafs.tile_activity"):
        row_act = (active_i32.reshape(n_i, block_v) != 0).any(axis=1)
        return ((tile_nnz > 0) & row_act[:, None]).astype(jnp.int32)


def resolution_tile_activity(res_contrib, push_tile_act, res_tile_nnz):
    """Per-tile activity bitmap of the dst-sorted resolution pass.

    A resolution tile holds candidates gathered from out-layout slots; a
    candidate is non-identity only if its OUT tile ran (``push_tile_act``
    from ``tile_activity_push``), so a resolution tile whose real slots all
    map into skipped out-tiles contains only identities and can skip too.
    ``res_contrib`` is the compact contributing-out-tile table of the live
    resolution tiles (``structure.PushResolution.contrib``): per length
    class, a gather of the out-tile bitmap through the ``[width, tiles]``
    lists (one column per tile, −1 padded), an OR down each column, and a
    scatter of the column results into a zero bitmap at the class's tile
    ids (ids past the end, a sharded stack's padding columns, drop).  The
    reads are the table's entries — O(live pairs), padded by under 2× —
    where a dense ``[n_tiles, c_max]`` table read every tile at the hub
    tiles' width.  The lists run down columns because the TPU compiler
    took minutes over the fixpoint with GAP urand-20's classes stored as
    ``[tiles, width]`` rows and seconds with them as columns.
    Σ res_tile_nnz over the tiles this bitmap keeps IS the resolution edge
    work fusion_bench gates as frontier-proportional."""
    n_i, n_j = res_tile_nnz.shape
    with jax.named_scope("grafs.res_activity"):
        flat_act = push_tile_act.reshape(-1)
        top = flat_act.shape[0] - 1
        any_act = jnp.zeros(n_i * n_j, bool)
        for tile_ids, lists in res_contrib:
            hit = (lists >= 0) & (flat_act[jnp.clip(lists, 0, top)] != 0)
            any_act = any_act.at[tile_ids].set(
                hit.any(axis=0), mode="drop", unique_indices=True,
                indices_are_sorted=True)
        return ((res_tile_nnz > 0) & any_act.reshape(n_i, n_j)) \
            .astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused push sweep: frontier-active source tiles → per-edge candidates →
# dst-keyed lexicographic scatter resolution.
# ---------------------------------------------------------------------------


def _push_kernel(step, mask_ref, *refs, n_comps, edge_names, row_names,
                 p_fns, idents, nv, block_v):
    """One (BLOCK_V sources × BLOCK_E successor slots) tile of the push sweep.

    ``refs`` = the edge tiles named by ``edge_names``, then lane-row blocks
    (``_lane_rows``) of the frontier, of each of the ``n_comps`` component
    states (push reads its OWN row's state, no gather) and of the per-row
    env entries named by ``row_names``, then one [block_v, block_e] per-edge
    candidate output per component.

    The kernel's job is the propagation half of Defs. 3/4: apply every
    synthesized P to the row's state across the row's out-edges, masking
    frontier-inactive sources and padding slots to the reduction identity
    (C6) so the dst-keyed scatter outside absorbs them as no-ops.  Inactive
    tiles short-circuit via ``pl.when`` and emit identities bit-for-bit."""
    i = step.i
    n_e, n_r = len(edge_names), len(row_names)
    edge_refs = refs[:n_e]
    active_ref = refs[n_e]
    state_refs = refs[n_e + 1:n_e + 1 + n_comps]
    row_refs = refs[n_e + 1 + n_comps:n_e + 1 + n_comps + n_r]
    out_refs = refs[n_e + 1 + n_comps + n_r:]

    for k in range(n_comps):
        out_refs[k][...] = jnp.full(out_refs[k].shape, idents[k],
                                    out_refs[k].dtype)

    @pl.when(step.active())
    def _tile_body():
        shape = out_refs[0].shape
        mask = (mask_ref[...] != 0) & \
            (_rows_of_step(active_ref, i, block_v) != 0)
        env = {name: r[...] for name, r in zip(edge_names, edge_refs)}
        env.update({name: jnp.broadcast_to(_rows_of_step(r, i, block_v),
                                           shape)
                    for name, r in zip(row_names, row_refs)})
        env["esrc"] = i * block_v + jax.lax.broadcasted_iota(jnp.int32,
                                                             shape, 0)
        env["nv"] = jnp.float32(nv)
        for k in range(n_comps):
            nvals = jnp.broadcast_to(
                _rows_of_step(state_refs[k], i, block_v), shape)
            ident = jnp.asarray(idents[k], nvals.dtype)
            p = jnp.asarray(p_fns[k]({"n": nvals, **env}), nvals.dtype)
            p = jnp.where(nvals == ident, ident, p)        # C3: ⊥ stays ⊥
            out_refs[k][...] = jnp.where(mask, p, ident).astype(
                out_refs[k].dtype)


def fused_ell_push_sweep(dsts, weight, capacity, mask, tile_act, states,
                         active, outdeg, *, plans, idents, p_fns, nv,
                         need_haspred: bool = False, wdeg=None,
                         resolution: str = "scatter", res=None,
                         res_slots=None, live=None, res_live=None,
                         block_v: int = BLOCK_V, block_e: int = BLOCK_E,
                         interpret: Optional[bool] = None,
                         return_candidates: bool = False):
    """Single-launch fused PUSH edge sweep over every plan of a fused round.

    dsts/weight/capacity/mask  [n_pad, width] out-edge blocked-ELL arrays
                               (``to_blocked_ell(..., direction="out")``:
                               rows are sources, slots hold destinations)
    live / res_live  the live-tile grids (``live_grid``) of the out-layout
              and of the resolution layout; None walks the full grid
    tile_act  [n_pad/block_v, width/block_e] int32 — 0 short-circuits a tile
    states    {comp: [n_pad] value vector}
    active    [n_pad] int32 frontier (1 = source eligible; push+ masks
              inactive sources, push− passes all-ones)
    wdeg      [n_pad] float32 weighted out-degree (env "wdeg"; None → 1s)
    plans     static: per plan a tuple of (comp, op) lex levels, primary first
    idents    {comp: identity scalar};  p_fns {comp: propagation closure}

    Contract (DESIGN.md §2/§10): ONE ``pallas_call`` applies every
    synthesized P over the frontier-active source tiles and emits per-edge
    *candidates* (identity-filled where inactive, per C6).  The dst-keyed
    lexicographic reduction then resolves by ``resolution``:

    ``"sorted"`` — the dst-sorted segment-reduction path.  ``res`` must be
    ``(in2out, valid, res_tile_act)`` from ``structure.PushResolution`` +
    ``resolution_tile_activity`` (in2out and valid may be None when
    ``res_slots``, the resolution's (slot_pos, slot_src) list, carries the
    gather — see ``gather_slots``): a second Pallas tile pass lex-reduces
    only the resolution tiles whose candidates came from frontier-active
    out-tiles, over the candidates XLA gathered through the dst-major
    permutation (row v = the contiguous candidate segment of dst v),
    finishing with the SAME cross-tile fold as the pull sweep — resolution
    work is Σ tile_nnz of processed resolution tiles, and the reduction is
    bit-identical to the pull sweep's tree (even for float sums).

    ``"scatter"`` — the reference full-rectangle scatter pass in plain jnp
    (the original path, kept as fallback and as the equivalence oracle).

    Both produce exactly the identity-initialised reduction that
    ``iterate.plan_merge`` resolves against the old state, so push and pull
    rounds share one merge contract bit-for-bit.

    Over a live-tile grid the kernel never writes the candidate blocks of
    dead tiles.  The sorted resolution reads candidates only at real slots,
    which lie in live tiles; the scatter reference and
    ``return_candidates`` read the whole rectangle, so for them the
    padding slots are set to the identity after the launch.

    Returns ``(red, hp)`` like ``fused_ell_sweep``: ``red[comp]`` is the
    [n_pad] dst-keyed reduction of that level over the candidates, ``hp``
    the has-predecessor vectors of the push− models (from the non-⊥ source
    states — no extra sweep launch).  ``return_candidates`` appends the raw
    [n_pad, width] per-edge candidate arrays (out-layout positions).
    """
    interpret = resolve_interpret(interpret)
    if resolution not in ("scatter", "sorted"):
        raise ValueError(f"resolution must be 'scatter' or 'sorted', "
                         f"got {resolution!r}")
    if resolution == "sorted" and res is None:
        raise ValueError("resolution='sorted' needs res=(in2out, valid, "
                         "res_tile_act) from structure.PushResolution")
    _check_block_v(block_v)
    comps_order = comps_in_plan_order(plans)
    pos_of = {c: k for k, c in enumerate(comps_order)}
    ident_scalars = _ident_scalars(comps_order, states, idents)
    fns = tuple(p_fns[c] for c in comps_order)
    keys = env_keys_read(fns, [states[c].dtype for c in comps_order])

    n_pad, width = dsts.shape

    if wdeg is None:
        wdeg = jnp.ones_like(outdeg)
    edge = {k: a for k, a in (("w", weight), ("c", capacity), ("edst", dsts))
            if k in keys}
    rows = {k: a for k, a in (("outdeg", outdeg), ("wdeg", wdeg))
            if k in keys}
    args = [mask] + list(edge.values()) \
        + [_lane_rows(jnp.asarray(active, jnp.int32))] \
        + [_lane_rows(states[c]) for c in comps_order] \
        + [_lane_rows(a) for a in rows.values()]
    tile = _tile_block(block_v, block_e)
    in_blocks = [tile] * (1 + len(edge)) \
        + [_lane_row_block(block_v)] * (1 + len(comps_order) + len(rows))
    kern = functools.partial(
        _push_kernel, n_comps=len(comps_order), edge_names=tuple(edge),
        row_names=tuple(rows), p_fns=fns, idents=ident_scalars,
        nv=float(nv), block_v=block_v)
    outs = _tile_call(
        kern, tile_act, live, args, in_blocks,
        [jax.ShapeDtypeStruct((n_pad, width), states[c].dtype)
         for c in comps_order],
        [tile] * len(comps_order), block_v=block_v, interpret=interpret,
        name="grafs_push_sweep")
    SWEEP_STATS["launches"] += 1
    SWEEP_STATS["push_launches"] += 1
    if live is not None and (resolution == "scatter" or return_candidates):
        outs = [jnp.where(mask != 0, o, jnp.asarray(ident_scalars[k], o.dtype))
                for k, o in enumerate(outs)]

    if resolution == "sorted":
        in2out, valid, res_tile_act = res
        res_shape = (res_tile_act.shape[0] * block_v,
                     res_tile_act.shape[1] * block_e)
        res_gather = functools.partial(gather_slots, shape=res_shape,
                                       slots=res_slots, idx=in2out,
                                       mask=valid)
        red = _resolve_push_sorted(
            outs, res_gather, res_tile_act, res_live, plans=plans,
            comps_order=comps_order, ident_scalars=ident_scalars,
            block_v=block_v, block_e=block_e, interpret=interpret)
    else:
        # Dst-keyed lexicographic scatter resolution (reference path): the
        # push analogue of the pull sweep's cross-tile fold, over the full
        # out rectangle.  Identity-initialised (NOT onto the old state) so
        # the result obeys the same plan_merge contract as the pull
        # reduction; ties mask the next level to identity exactly like
        # plan_segment_reduce does on the pull side.
        flat_dst = dsts.reshape(-1)
        flat = {c: outs[pos_of[c]].reshape(-1) for c in comps_order}
        red = {}
        for spec in plans:
            tie = jnp.ones_like(flat_dst, dtype=bool)
            for l, (c, op) in enumerate(spec):
                ident = jnp.asarray(ident_scalars[pos_of[c]], flat[c].dtype)
                init = jnp.full((n_pad,), ident, flat[c].dtype)
                vals = jnp.where(tie, flat[c], ident)
                prim = segment.scatter_reduce(op, init, vals, flat_dst)
                red[c] = prim
                if l + 1 < len(spec):
                    tie = tie & (vals == prim[flat_dst])

    hp = {}
    if need_haspred:
        # Def. 4's CPreds ≠ ∅ probe from "source state non-⊥" over real
        # out-edges.  Pure jnp on data already resident — no launch.  The
        # sorted path reads it through the dst-major permutation (the same
        # booleans the pull sweep's fused probe computes); scatter keeps
        # the scatter-OR.
        for c in comps_order:
            ident = jnp.asarray(ident_scalars[pos_of[c]], states[c].dtype)
            nonbot = (mask & (states[c][:, None] != ident)).astype(jnp.int32)
            if resolution == "sorted":
                hp[c] = jnp.any(res_gather(nonbot.reshape(-1), 0) != 0,
                                axis=1)
            else:
                hp[c] = segment.scatter_reduce(
                    "or", jnp.zeros((n_pad,), jnp.int32), nonbot.reshape(-1),
                    dsts.reshape(-1)) > 0
    if return_candidates:
        return red, hp, outs
    return red, hp


def _resolve_push_sorted(cand_outs, res_gather, res_tile_act, res_live, *,
                         plans, comps_order, ident_scalars, block_v, block_e,
                         interpret):
    """Dst-sorted segment-reduction resolution (DESIGN.md §10).

    XLA gathers each component's candidates through the dst-major
    permutation (``res_gather``: ``cand.ravel()[in2out]``, ⊥ on invalid
    slots) into the [n_pad, width_in] rectangle where row v is the
    contiguous candidate segment of dst v; the pull sweep's ``_reduce_kernel`` then lex-reduces
    the resolution tiles ``res_tile_act`` keeps — the same chain, the same
    tie masking, the same lane-dense candidates — and the pull sweep's
    cross-tile fold finishes the job, so the whole reduction tree is
    bit-identical to pull's."""
    pos_of = {c: k for k, c in enumerate(comps_order)}
    plan_specs = tuple(tuple((pos_of[c], _INT_OP.get(op, op)) for c, op in s)
                       for s in plans)
    vals = [res_gather(c.reshape(-1), ident_scalars[k])
            for k, c in enumerate(cand_outs)]
    outs = _reduce_sweep(
        vals, {}, None, res_tile_act, res_live, plan_specs=plan_specs,
        hp_positions=(), p_fns=None, idents=ident_scalars, nv=0.0,
        block_v=block_v, block_e=block_e, interpret=interpret,
        name="grafs_resolve")
    SWEEP_STATS["resolve_launches"] += 1
    red, _ = _fold_tile_candidates(plans, plan_specs, ident_scalars, outs)
    return red


def _level_kernel(srcs_ref, w_ref, c_ref, mask_ref, active_ref, outdeg_ref,
                  wdeg_ref, *state_and_best, out_ref, op, p_fns, idents, bots,
                  n_levels, nv, block_v, mode):
    """One (BLOCK_V, BLOCK_E) tile of one lex level.

    state_and_best = (state_0 .. state_{L-1}, best_0 .. best_{L-2}):
    full per-vertex state vectors for every level plus the already-reduced
    best values of the PRIOR levels (tie masks).  Level L-1 is the one being
    reduced; ``op`` is its monoid.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    srcs = srcs_ref[...]
    mask = mask_ref[...]
    act = active_ref[...][srcs] != 0
    mask = mask & act

    rows = i * block_v + jax.lax.broadcasted_iota(jnp.int32, srcs.shape, 0)
    env_common = {"w": w_ref[...], "c": c_ref[...], "esrc": srcs,
                  "edst": rows, "outdeg": outdeg_ref[...][srcs],
                  "wdeg": wdeg_ref[...][srcs], "nv": jnp.float32(nv)}

    state_refs = state_and_best[:n_levels]
    best_refs = state_and_best[n_levels:]

    def prop(level):
        nvals = state_refs[level][...][srcs]
        p = p_fns[level]({"n": nvals, **env_common})
        p = jnp.asarray(p, dtype=nvals.dtype)
        return jnp.where(nvals == bots[level], idents[level], p), nvals

    # tie masks from the prior levels
    for lvl in range(n_levels - 1):
        pv, _ = prop(lvl)
        mask = mask & (pv == best_refs[lvl][...][:, None])

    pv, nvals = prop(n_levels - 1)
    if mode == "nonbot":                       # has-pred probe (pull− models)
        vals = (nvals != bots[n_levels - 1]).astype(out_ref.dtype)
    else:
        vals = pv.astype(out_ref.dtype)
    ident = jnp.asarray(idents[n_levels - 1], out_ref.dtype) if mode == "value" \
        else jnp.asarray(0, out_ref.dtype)
    red_op = op if mode == "value" else "max"
    vals = jnp.where(mask, vals, ident)
    partial = _row_reduce(red_op, vals, axis=1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, ident, out_ref.dtype)

    out_ref[...] = _combine(red_op, out_ref[...], partial)


def ell_level_reduce(ell, op: str, p_fns: Sequence[Callable],
                     states: Sequence[jnp.ndarray],
                     idents: Sequence, active: jnp.ndarray,
                     outdeg: jnp.ndarray,
                     bests: Sequence[jnp.ndarray] = (),
                     mode: str = "value", wdeg=None,
                     block_v: int = BLOCK_V, block_e: int = BLOCK_E,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Reduce one lex level over the blocked-ELL edges.

    ell       BlockedELL layout (repro.graph.structure.to_blocked_ell)
    op        monoid of the level being reduced
    p_fns     propagation closures, one per level (priors first)
    states    [n_pad] per-vertex value vectors, one per level
    idents    reduction identities (= ⊥ sentinels), one per level
    bests     [n_pad] best values of the PRIOR levels (len = len(states)-1)
    mode      "value" (reduce P values) | "nonbot" (count non-⊥ preds)

    Returns the [n_pad] per-vertex partial reduction.
    """
    interpret = resolve_interpret(interpret)
    n_levels = len(states)
    assert len(bests) == n_levels - 1
    kernel_op = _INT_OP.get(op, op)
    # Pallas kernels may not close over traced constants — identities must be
    # Python scalars.
    idents = tuple(
        (int(i) if jnp.issubdtype(s.dtype, jnp.integer) else float(i))
        for i, s in zip(idents, states))

    out_dtype = states[-1].dtype if mode == "value" else jnp.int32
    n_pad, width = ell.srcs.shape
    grid = (n_pad // block_v, width // block_e)

    tile = pl.BlockSpec((block_v, block_e), lambda i, j: (i, j))
    full = lambda a: pl.BlockSpec(a.shape, lambda i, j: (0,) * a.ndim)
    vrow = pl.BlockSpec((block_v,), lambda i, j: (i,))

    kern = functools.partial(
        _level_kernel, op=kernel_op, p_fns=tuple(p_fns),
        idents=tuple(idents), bots=tuple(idents), n_levels=n_levels,
        nv=float(ell.n), block_v=block_v, mode=mode)

    if wdeg is None:
        wdeg = jnp.ones_like(outdeg)
    args = [ell.srcs, ell.weight, ell.capacity, ell.mask,
            active.astype(jnp.int32), outdeg, wdeg]
    specs = [tile, tile, tile, tile, full(active), full(outdeg), full(wdeg)]
    for s in states:
        args.append(s)
        specs.append(full(s))
    for b in bests:
        args.append(b)
        specs.append(vrow)

    fn = pl.pallas_call(
        lambda *refs: kern(*refs[:-1], out_ref=refs[-1]),
        grid=grid,
        in_specs=specs,
        out_specs=vrow,
        out_shape=jax.ShapeDtypeStruct((n_pad,), out_dtype),
        interpret=interpret,
    )
    out = fn(*args)
    SWEEP_STATS["launches"] += 1
    return out
