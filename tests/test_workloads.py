"""Launch-layer tests: workload construction for all 40 cells (abstract
only — instant), plus a reduced-config lower+compile smoke on a small
forced-device mesh in a subprocess."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_all_cells_enumerate():
    import repro.configs as configs
    from repro.launch.workloads import all_cells
    cells = all_cells()
    assert len(cells) == 40
    skips = [c for c in cells if c[2]]
    # 4 pure full-attention LMs skip long_500k (llama3.2/qwen2/yi/deepseek;
    # MLA is compressed-KV FULL attention) — llama4's chunked attn runs it
    assert len(skips) == 4
    assert all(s == "long_500k" for _, s, _ in skips)
    assert not any(a == "llama4-maverick-400b-a17b" for a, _, _ in skips)


def test_skip_reasons():
    import repro.configs as configs
    assert configs.skip_reason("llama3.2-3b", "long_500k") is not None
    assert configs.skip_reason("llama4-maverick-400b-a17b",
                               "long_500k") is None     # chunked attn runs
    assert configs.skip_reason("deepseek-v3-671b", "long_500k") is not None
    assert configs.skip_reason("gat-cora", "molecule") is None


def test_sanitize_spec():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.workloads import _sanitize_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}

    m = FakeMesh()
    # 24 heads can't split 16 ways → dropped
    assert _sanitize_spec(m, P(None, "model", None), (3072, 24, 128)) == \
        P(None, None, None)
    # tuple prefix fallback: batch 32 over pod·data=32 keeps both
    assert _sanitize_spec(m, P(("pod", "data"), None), (32, 128)) == \
        P(("pod", "data"), None)
    # batch 16 over pod·data → falls back to ("pod",)
    got = _sanitize_spec(m, P(("pod", "data"), None), (16, 128))
    assert got == P("pod", None)
    # batch 1 → unsharded
    assert _sanitize_spec(m, P(("pod", "data")), (1,)) == P(None)


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.dryrun import collective_bytes
    hlo = textwrap.dedent("""\
        HloModule test
        %region_body (arg: (s32[], f32[128])) -> (s32[], f32[128]) {
          %p = f32[128]{0} parameter(0)
          %ar = f32[128]{0} all-reduce(%p), replica_groups={}
          ROOT %t = (s32[], f32[128]) tuple(%ar, %ar)
        }
        %region_cond (arg: (s32[], f32[128])) -> pred[] {
          %c = s32[] constant(7)
          ROOT %cmp = pred[] compare(%c, %c), direction=LT
        }
        ENTRY %main (x: f32[128]) -> f32[128] {
          %x = f32[128]{0} parameter(0)
          %ag = f32[256]{0} all-gather(f32[128]{0} %x), dimensions={0}
          %w = (s32[], f32[128]) while(%x), condition=%region_cond, body=%region_body
          ROOT %r = f32[128]{0} get-tuple-element(%w), index=1
        }
    """)
    out, top = collective_bytes(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["operand_bytes"] == 128 * 4
    # the while body's all-reduce is weighted by the trip count 7
    assert out["all-reduce"]["count"] == 7
    assert out["all-reduce"]["operand_bytes"] == 7 * 128 * 4
    assert top and top[0]["kind"] == "all-reduce" and top[0]["trips"] == 7


@pytest.mark.slow
def test_smoke_dryrun_cells_compile():
    """Reduced-config lower+compile for one cell per family on a 4-device
    mesh (subprocess: forced host devices)."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, json
        import repro.configs
        import repro.launch.workloads as W
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 2), ("data", "model"))
        done = {}
        for arch, shape, variant in [
                ("llama3.2-3b", "train_4k", "baseline"),
                ("deepseek-v3-671b", "decode_32k", "baseline"),
                ("qwen2-72b", "decode_32k", "kvq"),
                ("gat-cora", "full_graph_sm", "baseline"),
                ("meshgraphnet", "molecule", "dist"),
                ("egnn", "full_graph_sm", "dist"),
                ("dlrm-rm2", "retrieval_cand", "baseline")]:
            wl = W.build_workload(arch, shape, mesh, smoke=True,
                                  variant=variant)
            with mesh:
                c = jax.jit(wl.step_fn, in_shardings=wl.in_shardings,
                            out_shardings=wl.out_shardings,
                            donate_argnums=wl.donate).lower(
                                *wl.abstract_args).compile()
            ca = c.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            done[f"{arch}:{shape}"] = ca.get("flops", 0) > 0
        print(json.dumps(done))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    done = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(done.values()), done


def test_roofline_derivation_from_record():
    from benchmarks.roofline import derive
    rec = {
        "arch": "x", "shape": "train", "status": "ok", "kind": "train",
        "devices": 256,
        "analysis_cost": {"flops": 1e18, "bytes accessed": 1e15},
        "cost_analysis": {},
        "collectives": {"all-reduce": {"count": 1, "operand_bytes": 50e9}},
        "meta": {"model_flops": 5e17},
        "memory_analysis": {"temp_size_in_bytes": 1} ,
    }
    d = derive(rec)
    assert abs(d["t_compute_s"] - 1e18 / (256 * 197e12)) < 1e-9
    assert abs(d["t_collective_s"] - 1.0) < 1e-9
    assert d["dominant"] == "compute"
    assert 0 < d["roofline_frac"] <= 1
    assert abs(d["useful_flops_frac"] - 0.5) < 1e-9
