"""torch-xla support path end-to-end against the FAKE torch_xla module
(tests/fakes/torch_xla — VERDICT r2 item 3: this path was dead code in
an image without torch_xla; two BASELINE configs depend on it).

The launcher runs a real torch training script that imports the fake,
calls ``xm.mark_step()`` every step, and samples memory.  Assertions:

* ``patch_mark_step`` engaged via the post-import hook (tracing
  initializes BEFORE the script imports torch_xla) and the barrier time
  landed in the first-class ``collective`` phase;
* ``XlaMemoryBackend`` drove the step-memory section (fake kb_total
  visible as the device limit);
* the run produces a normal final summary (fail-open held throughout).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
FAKES = REPO / "tests" / "fakes"

SCRIPT = """
import numpy as np
import torch
import torch_xla
import torch_xla.core.xla_model as xm
import traceml_tpu

model = torch.nn.Sequential(
    torch.nn.Linear(64, 64), torch.nn.ReLU(), torch.nn.Linear(64, 1)
)
opt = torch.optim.SGD(model.parameters(), lr=0.01)
rng = np.random.default_rng(0)

def batches():
    for _ in range(60):
        yield torch.tensor(rng.normal(size=(16, 64)).astype("float32"))

for x in traceml_tpu.wrap_dataloader(batches()):
    with traceml_tpu.trace_step():
        loss = model(x).pow(2).mean()
        opt.zero_grad()
        loss.backward()
        opt.step()
        xm.mark_step()  # the lazy barrier — patched into `collective`
print("torch-xla fake run done")
"""


def test_torch_xla_fake_e2e(tmp_path):
    script = tmp_path / "train_xla.py"
    script.write_text(SCRIPT)
    logs = tmp_path / "logs"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(REPO), str(FAKES)])
    env["FAKE_XLA_MARK_STEP_MS"] = "40"
    proc = subprocess.run(
        [
            sys.executable, "-m", "traceml_tpu", "run",
            "--mode", "summary", "--logs-dir", str(logs),
            "--run-name", "xla", "--sampler-interval", "0.25",
            "--finalize-timeout", "45", str(script),
        ],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    session = next(p for p in logs.iterdir() if p.is_dir())
    payload = json.loads((session / "final_summary.json").read_text())

    # the mark_step barrier is a first-class collective phase
    st = payload["sections"]["step_time"]
    coll = (st["global"]["phases"] or {}).get("collective")
    assert coll is not None, st["global"]["phases"].keys()
    assert coll["median_ms"] >= 25.0, coll  # 40 ms injected barrier

    # XlaMemoryBackend fed the memory section: the fake 8 GiB HBM limit
    sm = payload["sections"]["step_memory"]
    assert sm["status"] == "OK", sm
    rank0 = sm["global"]["per_rank"]["0"]
    limit = rank0.get("limit_bytes")
    assert limit == 8 * 1024 * 1024 * 1024, rank0


def test_detect_backend_prefers_torch_xla_when_loaded():
    """sys.modules-gated preference: a process that imported torch_xla
    gets the XlaMemoryBackend (lazy tensors never appear in jax's
    live-arrays view); processes that didn't are untouched."""
    sys.path.insert(0, str(FAKES))
    try:
        import torch_xla  # noqa: F401

        from traceml_tpu.utils.step_memory import detect_backend

        backend = detect_backend()
        assert backend.name == "torch_xla"
        rows = backend.sample()
        assert rows and rows[0]["limit_bytes"] == 8 << 30
        assert rows[0]["current_bytes"] > 0
    finally:
        sys.path.remove(str(FAKES))
        for m in [m for m in sys.modules if m.startswith("torch_xla")]:
            del sys.modules[m]
