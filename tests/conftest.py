import os
import subprocess
import sys
import textwrap

# Tests run single-device (the dry-run sets its own 512-device flag in its
# own process; see src/repro/launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_forced_devices(code: str, device_count: int) -> str:
    """Run ``code`` in a subprocess on ``device_count`` forced host devices
    (device count locks at first jax init, so multi-device suites cannot run
    in the main test process).  JAX_PLATFORMS is pinned to cpu: the forced
    host devices need the cpu platform, and leaving the choice to
    auto-detection stalls on hosts whose TPU plugin probes — and retries —
    instance metadata before falling back.  Shared by test_distributed and
    test_pallas_sharded."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={device_count}"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def norm_inf(x):
    """Collapse every ⊥-ish value to one token before comparing.

    The engines use finite sentinels (±~1e9 int, ±inf float) for ⊥; the
    oracle uses IEEE ±inf/nan.  Arithmetic over unreachable vertices may
    produce any of them (-inf vs nan for -⊥/⊥ etc.) — all mean "undefined"
    in the paper's domain, so they compare equal."""
    v = np.asarray(x, dtype=np.float64)
    return np.where(np.isnan(v) | (np.abs(v) >= 1e8), np.float64(1e9), v)


@pytest.fixture(autouse=True)
def _fresh_program_caches():
    """Drop every compiled-program cache layer after each test so
    ``_EXEC_CACHE`` / ``blocked_ell_cached`` / ``synthesize_round`` state —
    and in particular the id()-reuse hazard of identity-keyed caches when a
    test's graph is garbage-collected — can never leak across tests.  Tests
    that assert warm-cache behaviour do so within a single test body."""
    yield
    from repro.core import engine
    engine.clear_program_caches()


@pytest.fixture(scope="session")
def small_graphs():
    from repro.graph.structure import line_graph, rmat_graph, uniform_graph
    return {
        "uniform": uniform_graph(9, 18, seed=3),
        "uniform2": uniform_graph(12, 30, seed=7),
        "rmat": rmat_graph(16, 48, seed=5),
        "line": line_graph(8, weighted=True, seed=2),
    }


def skewed_graph(seed: int = 3):
    """A power-law graph on which most layout tiles are dead: R-MAT edges on
    256 vertices, placed at ids 0-127 and 256-511 so that rows 128-255 (one
    whole 128-row output group) hold no edge, plus vertex 0 joined both ways
    to 200 others, so its rows span several 128-slot tiles."""
    from repro.graph.structure import from_edges, rmat_graph
    src, dst, w, c = rmat_graph(256, 1500, seed=seed).host_edges()
    spread = lambda v: np.where(v < 128, v, v + 128)
    rng = np.random.default_rng(seed)
    leaves = spread(rng.choice(np.arange(1, 256), 200, replace=False))
    hub = np.zeros_like(leaves)
    ones = np.ones(2 * leaves.size, np.float32)
    return from_edges(512,
                      np.concatenate([spread(src), hub, leaves]).astype(np.int32),
                      np.concatenate([spread(dst), leaves, hub]).astype(np.int32),
                      np.concatenate([w, 1 + 7 * ones]),
                      np.concatenate([c, ones]))
