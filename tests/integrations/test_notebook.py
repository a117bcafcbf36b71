"""The quickstart notebook's code cells execute end-to-end and reach
the expected verdict (keeps examples/quickstart.ipynb from rotting)."""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

RUNNER = """
import json, sys
sys.path.insert(0, {repo!r})
nb = json.load(open({nb!r}))
code = "\\n\\n".join(
    "".join(c["source"]) for c in nb["cells"] if c["cell_type"] == "code"
)
g = {{}}
exec(compile(code, "<nb>", "exec"), g)
print("NB-OK")
"""


def test_quickstart_notebook_executes(tmp_path):
    import os

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the notebook's first cell pins cpu
    proc = subprocess.run(
        [sys.executable, "-c", RUNNER.format(
            repo=str(REPO), nb=str(REPO / "examples" / "quickstart.ipynb"))],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "NB-OK" in proc.stdout
    assert "INPUT_BOUND" in proc.stdout  # the designed verdict


def test_diagnosis_walkthrough_notebook_executes(tmp_path):
    """The diagnosis walkthrough runs its full diagnose → fix → compare
    loop: INPUT_BOUND detected, the fix collapses the input share, and
    compare reports a major STEP_TIME_IMPROVEMENT (VERDICT r3 item 9).
    The run-level verdict is allowed to be MIXED on a noisy single-core
    host (per-step overhead is a real residual warning there) — the
    notebook asserts the robust facts, not IMPROVEMENT; do not tighten
    it back, that was a CI flake."""
    import os

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-c", RUNNER.format(
            repo=str(REPO),
            nb=str(REPO / "examples" / "diagnosis_walkthrough.ipynb"))],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "WALKTHROUGH-OK" in proc.stdout
    assert "INPUT_BOUND" in proc.stdout


def test_ray_example_help_runs_without_ray(tmp_path):
    """The Ray example's CLI surface works on machines without ray —
    imports happen after argparse by design."""
    import os

    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / "ray" /
                             "ray_train_minimal.py"), "--help"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ), cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert "--num-workers" in proc.stdout


def test_grad_accum_example_declares_summed_flops(tmp_path):
    """The grad-accum example runs end-to-end and its declared
    (accum-summed) FLOPs reach the final summary's efficiency block."""
    import json
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    env["TRACEML_LOGS_DIR"] = str(tmp_path)
    env["TRACEML_SESSION_ID"] = "ga"
    env["TRACEML_FINALIZE_TIMEOUT_SEC"] = "15"
    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / "advanced" /
                             "grad_accum_mfu.py"),
         "--accum", "2", "--steps", "10"],
        capture_output=True, text=True, timeout=280, env=env,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads((tmp_path / "ga" / "final_summary.json").read_text())
    eff = payload["sections"]["step_time"]["global"]["efficiency"]
    assert eff["flops_source"] == "manual"
    assert eff["flops_per_step"] > 0
    assert eff["achieved_tflops_median"] is not None
