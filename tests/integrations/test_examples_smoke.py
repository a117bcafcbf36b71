"""Runnable examples don't rot: each script executes end-to-end on the
virtual CPU mesh in a subprocess (its own interpreter — examples call
init() and own their global state).  Scripts with heavyweight deps
(HF Trainer download, ray) or their own dedicated tests (lightning,
ddp via launcher e2e) are excluded.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

EXAMPLES = [
    "examples/quickstart/flax_minimal.py",
    "examples/quickstart/pytorch_minimal.py",
    "examples/distributed/sharded_llm.py",
    "examples/distributed/ring_attention_demo.py",
    "examples/distributed/moe_pipeline.py",
    "examples/advanced/grad_accum_mfu.py",
]


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script, tmp_path):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": str(REPO),
        # examples default to small loops; keep artifacts out of the repo
        "TRACEML_LOGS_DIR": str(tmp_path),
    })
    proc = subprocess.run(
        [sys.executable, str(REPO / script)],
        env=env, cwd=str(tmp_path), timeout=420,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, (
        f"{script} failed:\n{proc.stdout[-1500:]}\n{proc.stderr[-1500:]}"
    )
