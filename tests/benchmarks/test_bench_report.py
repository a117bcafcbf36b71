"""bench.py's result shape and its refusal to run without a chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import bench

REPO = Path(__file__).resolve().parents[2]


def test_short_step_summary_shape():
    """The short lane is published through one helper — the schema
    (and steps_per_arm bookkeeping) cannot diverge (review r5)."""
    su = [0.0120, 0.0121, 0.0119]
    st = [0.0123, 0.0124, 0.0122]
    sd = [(t - u) / u * 100.0 for u, t in zip(su, st)]
    out = bench._short_step_summary(su, st, sd, steps_per_arm=128)
    assert set(out) == {
        "untraced_ms", "traced_ms", "median_delta_pct", "ci95_pct",
        "pairs", "steps_per_arm",
    }
    assert out["pairs"] == 3 and out["steps_per_arm"] == 128
    assert out["untraced_ms"] == 12.0
    assert out["ci95_pct"][0] <= out["median_delta_pct"] <= out["ci95_pct"][1]


def test_step_flops_counts_params_not_optimizer_state():
    import jax.numpy as jnp

    state = {
        "params": {"w": jnp.zeros((4, 8))},
        "opt_state": ({"mu": jnp.zeros((4, 8)), "nu": jnp.zeros((4, 8))},),
        "step": jnp.zeros((), jnp.int32),
    }
    assert bench._step_flops(state, [jnp.zeros((2, 16))]) == 6.0 * 32 * 2 * 16


def test_default_path_without_chip_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        assert "value" not in row, row
