"""Per-(architecture × shape) workload construction for pjit.

``build_workload(arch, shape, mesh)`` returns everything the dry-run,
trainer and server need:

  step_fn           the pure function to jit (train_step / serve_step / …)
  abstract_args     ShapeDtypeStruct pytree (weak-type-correct, shardable,
                    never allocated)
  in_shardings / out_shardings   NamedSharding pytrees
  donate            arg indices safe to donate (params/opt/cache)
  meta              roofline bookkeeping (model flops, token counts, …)

Sharding strategy (DESIGN.md §5):
  * params: FSDP rows over "data" × TP columns/heads/experts over "model";
    replicated over "pod" (pure DP on the DCN — gradient all-reduce only).
  * LM batch: global batch over ("pod","data").
  * KV caches: batch over ("pod","data"), sequence over "model"
    (kv-head counts like 8 don't divide a 16-way model axis; the sequence
    axis always does).  long_500k (batch=1) shards the sequence over EVERY
    axis.
  * GNN: edges over the whole mesh (vertex-cut), node features over
    ("pod","data") rows and the feature dim over "model".
  * DLRM: embedding tables row-sharded over "model"; batch over
    ("pod","data").
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.launch.mesh import batch_axes, mesh_devices
from repro.models import dlrm as dlrm_mod
from repro.models import gnn as gnn_mod
from repro.models import transformer as tf
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

F32, I32 = jnp.float32, jnp.int32


@dataclasses.dataclass
class Workload:
    arch: str
    shape: str
    kind: str                   # train | prefill | decode | serve | retrieval
    step_fn: Callable
    abstract_args: tuple
    in_shardings: tuple
    out_shardings: Any
    donate: tuple
    meta: dict


def _sanitize_spec(mesh, spec: P, shape) -> P:
    """Drop (or shorten) per-dim axis assignments that don't divide the dim.

    jit argument shardings must divide evenly; e.g. 24 attention heads can't
    split 16 ways, and a batch of 1 can't split at all.  For tuple
    assignments, fall back to the longest dividing prefix: ("pod","data")
    over batch 32 with pod·data=32 stays, over batch 16 becomes ("pod",).
    """
    new = []
    for i, dim in enumerate(shape):
        ax = spec[i] if i < len(spec) else None
        if ax is None:
            new.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        keep = None
        for k in range(len(axes), 0, -1):
            size = int(np.prod([mesh.shape[a] for a in axes[:k]]))
            if dim % size == 0:
                keep = axes[:k] if k > 1 else axes[0]
                break
        new.append(keep)
    return P(*new)


def _shard_tree(mesh, spec_tree, abs_tree=None):
    """Spec tree → NamedSharding tree, sanitized against the abstract
    shapes when given."""
    if abs_tree is None:
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def one(s, a):
        return NamedSharding(mesh, _sanitize_spec(mesh, s, a.shape))

    flat_a, tdef = jax.tree.flatten(abs_tree)
    flat_s = tdef.flatten_up_to(spec_tree)
    return tdef.unflatten([one(s, a) for s, a in zip(flat_s, flat_a)])


def _abstract(fn, *args):
    return jax.eval_shape(fn, *args)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _opt_specs(param_spec_tree):
    return {"m": param_spec_tree, "v": param_spec_tree, "step": P()}


def _lm_remap(cfg):
    """Production LM configs keep bf16 params/compute; nothing to remap —
    hook kept for per-shape dtype overrides."""
    return cfg


# ---------------------------------------------------------------------------
# LM workloads
# ---------------------------------------------------------------------------

def _lm_workload(arch: str, shape_name: str, shape: dict, mesh,
                 smoke: bool = False, analysis: bool = False,
                 variant: str = "baseline") -> Workload:
    entry = configs.get(arch)
    cfg = entry.smoke() if smoke else _lm_remap(entry.full())
    dp_all = int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))
    cfg = dataclasses.replace(cfg, hint_axes=tuple(mesh.axis_names),
                              moe_groups=dp_all)
    if variant == "kvq" and shape["kind"] in ("decode", "prefill"):
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if analysis:
        # exact-FLOP lowering: unroll layer/KV loops (XLA cost_analysis
        # counts while bodies once), one KV tile (same math/FLOPs), no
        # sharding constraints (lowered single-device, no mesh context)
        cfg = dataclasses.replace(cfg, loop_impl="unroll", kv_chunk=1 << 30,
                                  hint_axes=())
    bat = batch_axes(mesh)
    key = jax.random.PRNGKey(0)
    params_abs = _abstract(lambda k: tf.init_params(cfg, k), key)
    pspec = tf.param_specs(cfg)
    psh = _shard_tree(mesh, pspec, params_abs)
    seq, batch = shape["seq"], shape["batch"]
    if smoke:
        seq, batch = min(seq, 64), min(batch, 4)

    meta = {"params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "tokens": batch * seq if shape["kind"] != "decode" else batch,
            "seq": seq, "batch": batch}

    if shape["kind"] == "train":
        opt_cfg = AdamWConfig(
            state_dtype="bfloat16" if cfg.param_count() > 1e11 else "float32")
        opt_abs = _abstract(lambda p: adamw_init(opt_cfg, p), params_abs)
        osh = _shard_tree(mesh, _opt_specs(pspec), opt_abs)
        batch_abs = {"tokens": _sds((batch, seq), I32),
                     "targets": _sds((batch, seq), I32)}
        bsh = _shard_tree(mesh, {"tokens": P(bat, None),
                                 "targets": P(bat, None)}, batch_abs)

        # microbatching (gradient accumulation): cap the live activation
        # stack at ~8k local tokens per microbatch — the remat stack is the
        # dominant HBM term at 4k×256 (DESIGN.md §Perf).  Analysis mode
        # runs n_micro=1 (same total FLOPs: attention is batch-diagonal).
        dp = int(np.prod([mesh.shape[a] for a in bat]))
        local_b = max(batch // dp, 1)
        n_micro = 1
        if not (smoke or analysis):
            target = max(1, (local_b * seq + 8191) // 8192)
            n_micro = max(d for d in range(1, local_b + 1)
                          if local_b % d == 0 and d <= target)
        meta["n_micro"] = n_micro

        def train_step(params, opt_state, b):
            if n_micro == 1:
                loss, grads = jax.value_and_grad(
                    lambda p: tf.loss_fn(cfg, p, b))(params)
            else:
                # strided split (row i goes to micro i%n) so each device
                # contributes rows to every microbatch — no resharding
                def split(x):
                    y = x.reshape((x.shape[0] // n_micro, n_micro)
                                  + x.shape[1:])
                    y = jnp.swapaxes(y, 0, 1)
                    spec = P(None, bat, *([None] * (y.ndim - 2)))
                    return jax.lax.with_sharding_constraint(
                        y, NamedSharding(mesh, _sanitize_spec(
                            mesh, spec, y.shape)))

                mb = jax.tree.map(split, b)

                def micro(carry, one):
                    gsum, lsum = carry
                    l, g = jax.value_and_grad(
                        lambda p: tf.loss_fn(cfg, p, one))(params)
                    # §Perf A4: pin the raw (bf16) grads to the param
                    # sharding BEFORE the f32 accumulate — the per-micro
                    # cross-"data" grad reduction then runs on bf16
                    # operands (half the bytes of reducing the f32 sum)
                    g = jax.tree.map(
                        lambda x, s: jax.lax.with_sharding_constraint(x, s),
                        g, psh)
                    gsum = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), gsum, g)
                    # keep the f32 accumulator sharded exactly like the
                    # params (unconstrained, GSPMD replicates it)
                    gsum = jax.tree.map(
                        lambda a, s: jax.lax.with_sharding_constraint(a, s),
                        gsum, psh)
                    return (gsum, lsum + l), None

                zeros = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params)
                (gsum, lsum), _ = jax.lax.scan(
                    micro, (zeros, jnp.float32(0.0)), mb)
                grads = jax.tree.map(lambda g: g / n_micro, gsum)
                loss = lsum / n_micro
            params, opt_state, m = adamw_update(opt_cfg, params, grads,
                                                opt_state)
            return params, opt_state, {"loss": loss, **m}

        # MODEL_FLOPS = 6·N_active·D tokens (fwd+bwd)
        meta["model_flops"] = 6 * cfg.active_param_count() * batch * seq
        return Workload(arch, shape_name, "train", train_step,
                        (params_abs, opt_abs, batch_abs),
                        (psh, osh, bsh), (psh, osh, None), (0, 1), meta)

    # serving shapes --------------------------------------------------------
    seq_sharded = batch == 1                       # long_500k: shard the seq
    cache_abs = _abstract(
        lambda: tf.init_cache(cfg, batch, seq))
    csp = _cache_specs(cfg, bat, seq_sharded, cache_abs)
    csh = _shard_tree(mesh, csp, cache_abs)

    if shape["kind"] == "prefill":
        toks_abs = _sds((batch, seq), I32)
        tsh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat, None),
                                                  (batch, seq)))

        def prefill_step(params, tokens, cache):
            return tf.prefill(cfg, params, tokens, cache)

        meta["model_flops"] = 2 * cfg.active_param_count() * batch * seq
        lsh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat, None),
                                                  (batch, cfg.vocab)))
        return Workload(arch, shape_name, "prefill", prefill_step,
                        (params_abs, toks_abs, cache_abs),
                        (psh, tsh, csh), (lsh, csh), (2,), meta)

    # decode: one new token against a seq-long cache
    tok_abs = _sds((batch,), I32)
    tok_sh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat), (batch,)))
    pos_abs = _sds((), I32)

    def serve_step(params, token, pos, cache):
        return tf.decode_step(cfg, params, token, pos, cache)

    meta["model_flops"] = 2 * cfg.active_param_count() * batch
    lsh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat, None),
                                              (batch, cfg.vocab)))
    return Workload(arch, shape_name, "decode", serve_step,
                    (params_abs, tok_abs, pos_abs, cache_abs),
                    (psh, tok_sh, NamedSharding(mesh, P()), csh),
                    (lsh, csh), (3,), meta)


def _cache_specs(cfg, bat, seq_sharded: bool, cache_abs):
    """Cache sharding by leaf rank: [L, B, S, ...] — batch over the data
    axes, sequence over "model" (or over everything for batch=1 streams).
    Rank-driven so int8-quantization scale arrays [L,B,S,H] get the same
    prefix treatment as their [L,B,S,H,D] payloads."""
    all_ax = bat + ("model",)

    def one(leaf):
        nd = leaf.ndim
        if seq_sharded:
            prefix = [None, None, all_ax]
        else:
            prefix = [None, bat, "model"]
        return P(*(prefix + [None] * (nd - 3)))

    return jax.tree.map(one, cache_abs)


# ---------------------------------------------------------------------------
# GNN workloads
# ---------------------------------------------------------------------------

def _gnn_sizes(shape: dict, smoke: bool):
    n, e = shape["n"], shape["e"]
    if shape["kind"] == "sample":
        from repro.graph.sampler import max_nodes_for
        bn, fan = shape["batch_nodes"], shape["fanout"]
        if smoke:
            bn, fan = 8, (3, 2)
        n = max_nodes_for(bn, list(fan))
        e = sum(bn * int(np.prod(fan[:i + 1])) for i in range(len(fan)))
    elif shape["kind"] == "batch":
        n = shape["n"] * shape["batch"]
        e = shape["e"] * shape["batch"]
    if smoke:
        n, e = min(n, 256), min(e, 1024)
    return n, e


def _gnn_batch_abs(kind: str, cfg, shape: dict, n: int, e: int,
                   smoke: bool) -> tuple:
    d_feat = shape.get("d_feat", 16)
    ng = shape.get("batch", 32) if shape["kind"] == "batch" else \
        max(1, n // 30)
    if kind == "gat":
        return ({"x": _sds((n, cfg.d_in), F32), "src": _sds((e,), I32),
                 "dst": _sds((e,), I32), "y": _sds((n,), I32)},
                {"x": P(("data",), None), "src": P(("data",)),
                 "dst": P(("data",)), "y": P(("data",))})
    if kind == "egnn":
        return ({"feats": _sds((n, cfg.d_in), F32),
                 "coords": _sds((n, 3), F32),
                 "src": _sds((e,), I32), "dst": _sds((e,), I32),
                 "graph_id": _sds((n,), I32), "target": _sds((ng,), F32)},
                {"feats": P(("data",), None), "coords": P(("data",), None),
                 "src": P(("data",)), "dst": P(("data",)),
                 "graph_id": P(("data",)), "target": P()})
    if kind == "mgn":
        return ({"node_x": _sds((n, cfg.d_node_in), F32),
                 "edge_x": _sds((e, cfg.d_edge_in), F32),
                 "src": _sds((e,), I32), "dst": _sds((e,), I32),
                 "target": _sds((n, cfg.d_out), F32)},
                {"node_x": P(("data",), None), "edge_x": P(("data",), None),
                 "src": P(("data",)), "dst": P(("data",)),
                 "target": P(("data",), None)})
    if kind == "dimenet":
        avg_deg = max(1, min(e // max(n, 1), 32))
        t = min(e * avg_deg, 2_000_000_000 // 8)          # wedge count
        if smoke:
            t = min(t, 4096)
        return ({"species": _sds((n,), I32), "coords": _sds((n, 3), F32),
                 "src": _sds((e,), I32), "dst": _sds((e,), I32),
                 "t_kj": _sds((t,), I32), "t_ji": _sds((t,), I32),
                 "graph_id": _sds((n,), I32), "target": _sds((ng,), F32)},
                {"species": P(("data",)), "coords": P(("data",), None),
                 "src": P(("data",)), "dst": P(("data",)),
                 "t_kj": P(("data",)), "t_ji": P(("data",)),
                 "graph_id": P(("data",)), "target": P()})
    raise ValueError(kind)


_GNN_LOSS = {"gat": gnn_mod.gat_loss, "egnn": gnn_mod.egnn_loss,
             "mgn": gnn_mod.mgn_loss, "dimenet": gnn_mod.dimenet_loss}
_GNN_INIT = {"gat": gnn_mod.gat_init, "egnn": gnn_mod.egnn_init,
             "mgn": gnn_mod.mgn_init, "dimenet": gnn_mod.dimenet_init}
_GNN_SPECS = {"gat": gnn_mod.gat_specs, "egnn": gnn_mod.egnn_specs,
              "mgn": gnn_mod.mgn_specs, "dimenet": gnn_mod.dimenet_specs}


def _gnn_dist_workload(arch, shape_name, shape, mesh, smoke):
    """Hillclimb B generalized: shard_map dst-block vertex-cut for the
    full-graph GNN cells (models.gnn.{mgn,egnn}_forward_dist) — local
    scatters, one node-state all-gather per layer, gradient psum."""
    entry = configs.get(arch)
    kind = entry.kind
    cfg = entry.smoke() if smoke else entry.full()
    n, e = _gnn_sizes(shape, smoke)
    axes = tuple(mesh.axis_names)
    k = mesh_devices(mesh)
    n_loc = -(-n // k)
    e_pad = max(1, int(math.ceil(e * 1.3 / k)))

    key = jax.random.PRNGKey(0)
    init = {"mgn": gnn_mod.mgn_init, "egnn": gnn_mod.egnn_init}[kind]
    loss = {"mgn": gnn_mod.mgn_loss_dist,
            "egnn": gnn_mod.egnn_loss_dist}[kind]
    params_abs = _abstract(lambda k_: init(cfg, k_), key)
    # params replicated inside shard_map (MLPs are small); grads psum'd
    psh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params_abs)
    opt_cfg = AdamWConfig()
    opt_abs = _abstract(lambda p: adamw_init(opt_cfg, p), params_abs)
    osh = jax.tree.map(lambda _: NamedSharding(mesh, P()), opt_abs)

    batch_abs = {"src": _sds((k * e_pad,), I32),
                 "dst": _sds((k * e_pad,), I32),
                 "emask": _sds((k * e_pad,), jnp.bool_),
                 "nmask": _sds((k * n_loc,), jnp.bool_)}
    if kind == "mgn":
        batch_abs.update(
            node_x=_sds((k * n_loc, cfg.d_node_in), F32),
            edge_x=_sds((k * e_pad, cfg.d_edge_in), F32),
            target=_sds((k * n_loc, cfg.d_out), F32))
    else:
        batch_abs.update(
            feats=_sds((k * n_loc, cfg.d_in), F32),
            coords=_sds((k * n_loc, 3), F32),
            target=_sds((k * n_loc, cfg.d_out), F32))

    def shard_fn(params, opt_state, batch):
        l, grads = jax.value_and_grad(
            lambda p: loss(cfg, p, batch, axes))(params)
        grads = jax.lax.psum(grads, axes)
        params, opt_state, m = adamw_update(opt_cfg, params, grads,
                                            opt_state)
        return params, opt_state, {"loss": l, **m}

    rep = P()
    bspecs = {k_: P(axes, None) if v.ndim == 2 else P(axes)
              for k_, v in batch_abs.items()}
    bsh = {k_: NamedSharding(mesh, sp) for k_, sp in bspecs.items()}
    step = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: rep, params_abs),
                  jax.tree.map(lambda _: rep, opt_abs), bspecs),
        out_specs=(jax.tree.map(lambda _: rep, params_abs),
                   jax.tree.map(lambda _: rep, opt_abs),
                   {"loss": rep, "grad_norm": rep, "lr": rep}),
        check_vma=False)

    meta = {"n": n, "e": e, "variant": "dist",
            "model_flops": _gnn_model_flops(kind, cfg, n, e, batch_abs)}
    return Workload(arch, shape_name, "train", step,
                    (params_abs, opt_abs, batch_abs),
                    (psh, osh, bsh), (psh, osh, None), (0, 1), meta)


def _gnn_workload(arch: str, shape_name: str, shape: dict, mesh,
                  smoke: bool = False) -> Workload:
    entry = configs.get(arch)
    cfg = entry.smoke() if smoke else entry.full()
    kind = entry.kind
    if kind == "gat":
        cfg = dataclasses.replace(cfg, d_in=shape.get("d_feat", cfg.d_in))
    n, e = _gnn_sizes(shape, smoke)
    bat = batch_axes(mesh)

    key = jax.random.PRNGKey(0)
    params_abs = _abstract(lambda k: _GNN_INIT[kind](cfg, k), key)
    pspec = _GNN_SPECS[kind](cfg)
    psh = _shard_tree(mesh, pspec, params_abs)
    opt_cfg = AdamWConfig()
    opt_abs = _abstract(lambda p: adamw_init(opt_cfg, p), params_abs)
    osh = _shard_tree(mesh, _opt_specs(pspec), opt_abs)

    batch_abs, bspec = _gnn_batch_abs(kind, cfg, shape, n, e, smoke)
    # remap the data axis to include the pod axis when present
    bspec = jax.tree.map(
        lambda s: P(*[bat if ax == ("data",) or ax == "data" else ax
                      for ax in s]),
        bspec, is_leaf=lambda x: isinstance(x, P))
    bsh = _shard_tree(mesh, bspec, batch_abs)
    loss = _GNN_LOSS[kind]

    def train_step(params, opt_state, b):
        l, grads = jax.value_and_grad(lambda p: loss(cfg, p, b))(params)
        params, opt_state, m = adamw_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {"loss": l, **m}

    meta = {"n": n, "e": e,
            "model_flops": _gnn_model_flops(kind, cfg, n, e, batch_abs)}
    return Workload(arch, shape_name, "train", train_step,
                    (params_abs, opt_abs, batch_abs),
                    (psh, osh, bsh), (psh, osh, None), (0, 1), meta)


def _gnn_model_flops(kind, cfg, n, e, batch_abs) -> float:
    """Hand-derived useful FLOPs (fwd+bwd ≈ 3× fwd matmul flops)."""
    if kind == "gat":
        total, d_in = 0, cfg.d_in
        for li in range(cfg.n_layers):
            last = li == cfg.n_layers - 1
            h = 1 if last else cfg.n_heads
            d_out = cfg.n_classes if last else cfg.d_hidden
            total += 2 * n * d_in * h * d_out + 6 * e * h
            d_in = d_out if last else h * d_out
        return 3 * total
    if kind == "egnn":
        d = cfg.d_hidden
        per_layer = 2 * e * (2 * d + 1) * d + 2 * e * d * d * 2 + 2 * n * 2 * d * d
        return 3 * cfg.n_layers * per_layer
    if kind == "mgn":
        d = cfg.d_hidden
        per_layer = 2 * e * (3 * d) * d + 2 * e * d * d + 2 * n * (2 * d) * d + 2 * n * d * d
        return 3 * cfg.n_layers * per_layer
    if kind == "dimenet":
        d = cfg.d_hidden
        t = batch_abs["t_kj"].shape[0]
        per_block = (2 * e * d * d                      # w_kj
                     + 2 * t * d * cfg.n_bilinear * d   # bilinear
                     + 2 * e * d * d * 2 + 2 * e * d * d)
        return 3 * cfg.n_blocks * per_block
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# DLRM workloads
# ---------------------------------------------------------------------------

def _dlrm_workload(arch: str, shape_name: str, shape: dict, mesh,
                   smoke: bool = False) -> Workload:
    entry = configs.get(arch)
    cfg = entry.smoke() if smoke else entry.full()
    bat = batch_axes(mesh)
    batch = shape["batch"]
    if smoke:
        batch = min(batch, 32)
    key = jax.random.PRNGKey(0)
    params_abs = _abstract(lambda k: dlrm_mod.dlrm_init(cfg, k), key)
    pspec = dlrm_mod.dlrm_specs(cfg)
    psh = _shard_tree(mesh, pspec, params_abs)

    dense_abs = _sds((batch, cfg.n_dense), F32)
    sparse_abs = _sds((batch, cfg.n_sparse), I32)
    dsh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat, None),
                                             (batch, cfg.n_dense)))
    ssh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat, None),
                                             (batch, cfg.n_sparse)))
    meta = {"params": cfg.param_count(), "batch": batch}

    if shape["kind"] == "train":
        opt_cfg = AdamWConfig()
        opt_abs = _abstract(lambda p: adamw_init(opt_cfg, p), params_abs)
        osh = _shard_tree(mesh, _opt_specs(pspec), opt_abs)
        batch_abs = {"dense": dense_abs, "sparse": sparse_abs,
                     "label": _sds((batch,), F32)}
        bsh = {"dense": dsh, "sparse": ssh,
               "label": NamedSharding(mesh, _sanitize_spec(
                   mesh, P(bat), (batch,)))}

        def train_step(params, opt_state, b):
            l, grads = jax.value_and_grad(
                lambda p: dlrm_mod.dlrm_loss(cfg, p, b))(params)
            params, opt_state, m = adamw_update(opt_cfg, params, grads,
                                                opt_state)
            return params, opt_state, {"loss": l, **m}

        meta["model_flops"] = 3 * batch * _dlrm_dense_flops(cfg)
        return Workload(arch, shape_name, "train", train_step,
                        (params_abs, opt_abs, batch_abs),
                        (psh, osh, bsh), (psh, osh, None), (0, 1), meta)

    if shape["kind"] == "serve":
        def serve_step(params, dense, sparse):
            return dlrm_mod.dlrm_forward(cfg, params, dense, sparse)

        meta["model_flops"] = batch * _dlrm_dense_flops(cfg)
        out_sh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat), (batch,)))
        return Workload(arch, shape_name, "serve", serve_step,
                        (params_abs, dense_abs, sparse_abs),
                        (psh, dsh, ssh), out_sh, (), meta)

    # retrieval: score batch×n_candidates with one matmul
    nc = shape["n_candidates"]
    if smoke:
        nc = min(nc, 1024)
    cand_abs = _sds((nc, cfg.embed_dim), F32)
    csh = NamedSharding(mesh, _sanitize_spec(mesh, P(bat + ("model",), None),
                                             (nc, cfg.embed_dim)))

    def retrieval_step(params, dense, sparse, cand):
        return dlrm_mod.dlrm_retrieval_scores(cfg, params, dense, sparse,
                                              cand)

    meta["model_flops"] = 2 * batch * nc * cfg.embed_dim \
        + batch * _dlrm_dense_flops(cfg)
    meta["n_candidates"] = nc
    out_sh = NamedSharding(mesh, _sanitize_spec(
        mesh, P(None, bat + ("model",)), (batch, nc)))
    return Workload(arch, shape_name, "retrieval", retrieval_step,
                    (params_abs, dense_abs, sparse_abs, cand_abs),
                    (psh, dsh, ssh, csh), out_sh, (), meta)


def _dlrm_dense_flops(cfg) -> float:
    bot = sum(2 * a * b for a, b in zip(cfg.bot_mlp[:-1], cfg.bot_mlp[1:]))
    dims = [cfg.d_interact] + list(cfg.top_mlp_hidden)
    top = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    inter = 2 * cfg.n_feats * cfg.n_feats * cfg.embed_dim
    return bot + top + inter


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def build_workload(arch: str, shape_name: str, mesh,
                   smoke: bool = False, analysis: bool = False,
                   variant: str = "baseline") -> Workload:
    entry = configs.get(arch)
    shape = entry.shapes[shape_name]
    if entry.family == "lm":
        return _lm_workload(arch, shape_name, shape, mesh, smoke, analysis,
                            variant)
    if entry.family == "gnn":
        if variant == "dist" and entry.kind in ("mgn", "egnn") \
                and not analysis:
            return _gnn_dist_workload(arch, shape_name, shape, mesh, smoke)
        return _gnn_workload(arch, shape_name, shape, mesh, smoke)
    if entry.family == "recsys":
        return _dlrm_workload(arch, shape_name, shape, mesh, smoke)
    raise ValueError(f"{arch}: family {entry.family} has no shaped workloads")


def all_cells():
    """The 40 assigned (arch × shape) cells, with skip annotations."""
    cells = []
    for arch in configs.ASSIGNED:
        for shape in configs.get(arch).shapes:
            cells.append((arch, shape, configs.skip_reason(arch, shape)))
    return cells
