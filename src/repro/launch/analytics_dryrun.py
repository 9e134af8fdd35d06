import os
if "--xla" not in str(os.environ.get("XLA_FLAGS", "")):
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run of the PAPER'S OWN workload at production scale: one fused
GraFS iteration (the WSP lexicographic plan — FPNEST's output) over an
ogb_products-scale edge set, vertex-cut across the full mesh, lowered and
compiled on the (16,16) and (2,16,16) meshes.

    PYTHONPATH=src python -m repro.launch.analytics_dryrun [--multi-pod]

This is the shard_map distributed engine (PowerGraph/Gemini analogue) with
abstract inputs: per-shard edge blocks, replicated vertex state, monoid
collectives for the cross-shard lexicographic combine.  Writes
reports/dryrun/<mesh>/grafs-analytics__ogb_scale.json in the same format
as the 40 assigned cells so the roofline table picks it up.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def build_step(mesh, n, e, max_iter=64):
    """One fused WSP (lex min-length → max-capacity) fixpoint under
    shard_map, abstract-shaped."""
    from repro.core import fusion, iterate, usecases as U
    from repro.core.synthesis import synthesize_round
    from repro.graph import segment

    prog = fusion.fuse(U.wsp(0))
    round_ = prog.rounds[0][1]
    synth = synthesize_round(round_)
    comps = iterate.comp_runtimes(
        round_, {k: v for k, v in synth.items() if not isinstance(k, tuple)})
    plans = [leaf.plan for leaf in round_.leaves]
    comps_by_idx = {cr.idx: cr for cr in comps}
    axes = tuple(mesh.axis_names)
    k_shards = int(np.prod(list(mesh.shape.values())))
    e_loc = -(-e // k_shards)

    def shard_fn(src, dst, w, c, mask, out_deg):
        env = {"w": w, "c": c, "esrc": src, "edst": dst,
               "outdeg": out_deg[src], "nv": jnp.float32(n)}

        def cross_plan(plan, red):
            best = segment.psum_like(plan.op, red[plan.comp], axes)
            out = {plan.comp: best}
            if isinstance(plan, fusion.Lex):
                tie = red[plan.comp] == best
                masked = {j: jnp.where(tie, red[j], comps_by_idx[j].ident)
                          for j in iterate._plan_comps(plan.secondary)}
                out.update(cross_plan(plan.secondary, masked))
            return out

        def body(carry):
            state, active, it = carry
            state_d = {cr.idx: state[i] for i, cr in enumerate(comps)}
            evals = iterate._propagate(comps, state, src, env)
            eactive = active[src] & mask
            masked = {i: jnp.where(eactive, evals[i],
                                   comps_by_idx[i].ident) for i in evals}
            red = {}
            for p in plans:
                red.update(iterate.plan_segment_reduce(
                    p, masked, dst, n, comps_by_idx))
            for p in plans:
                red.update(cross_plan(p, red))
            new_d = {}
            for p in plans:
                new_d.update(iterate.plan_merge(p, state_d, red,
                                                comps_by_idx))
            new = tuple(new_d[cr.idx] for cr in comps)
            ch = iterate._changed(comps, new, state, 0.0)
            return new, ch, it + 1

        def cond(carry):
            _, active, it = carry
            return jnp.any(active) & (it < max_iter)

        state0 = iterate._init_state(comps, n)
        state, active, it = jax.lax.while_loop(
            cond, body, (state0, jnp.ones(n, bool), jnp.int32(0)))
        return state, it

    espec = P(axes)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(espec, espec, espec, espec, espec, P()),
        out_specs=(tuple(P() for _ in comps), P()),
        check_vma=False)

    args = (
        jax.ShapeDtypeStruct((k_shards * e_loc,), jnp.int32),   # src
        jax.ShapeDtypeStruct((k_shards * e_loc,), jnp.int32),   # dst
        jax.ShapeDtypeStruct((k_shards * e_loc,), jnp.float32),
        jax.ShapeDtypeStruct((k_shards * e_loc,), jnp.float32),
        jax.ShapeDtypeStruct((k_shards * e_loc,), jnp.bool_),
        jax.ShapeDtypeStruct((n,), jnp.int32),                  # out_deg
    )
    shardings = tuple(NamedSharding(mesh, s) for s in
                      (espec, espec, espec, espec, espec, P()))
    return fn, args, shardings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n", type=int, default=2_449_029)    # ogb_products
    ap.add_argument("--e", type=int, default=61_859_140)
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args(argv)

    from repro.launch.dryrun import _mem_dict, _mesh_tag, collective_bytes
    from repro.launch.mesh import make_production_mesh, mesh_devices

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = _mesh_tag(args.multi_pod)
    t0 = time.perf_counter()
    fn, fargs, shardings = build_step(mesh, args.n, args.e)
    with mesh:
        lowered = jax.jit(fn, in_shardings=shardings).lower(*fargs)
        compiled = lowered.compile()
    rec = {"arch": "grafs-analytics", "shape": "ogb_scale", "mesh": tag,
           "status": "ok", "kind": "analytics",
           "devices": mesh_devices(mesh),
           "compile_s": round(time.perf_counter() - t0, 2),
           "meta": {"n": args.n, "e": args.e,
                    # per fixpoint iteration: each edge does P + R
                    "model_flops": 4.0 * args.e},
           "memory_analysis": _mem_dict(compiled)}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))}
    except Exception as ex:
        rec["cost_analysis"] = {"error": str(ex)}
    rec["analysis_cost"] = dict(rec["cost_analysis"])
    hlo = compiled.as_text()
    rec["collectives"], rec["collective_top_ops"] = collective_bytes(hlo)
    out_dir = os.path.join(args.out, tag)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "grafs-analytics__ogb_scale.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    coll = sum(v["operand_bytes"] for v in rec["collectives"].values())
    print(f"[analytics:{tag}] ok compile={rec['compile_s']}s "
          f"mem={rec['memory_analysis']} coll/chip={coll / 1e9:.2f}GB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
