"""Checkpoint phase: manual wrapper + REAL orbax save through the
auto-patch (orbax is in the image) — a blocking save inside a step must
appear as the first-class ``checkpoint`` phase, not residual."""

import jax.numpy as jnp
import pytest

import traceml_tpu
from traceml_tpu.sdk.state import get_state
from traceml_tpu.utils import timing as T
from traceml_tpu.utils.step_time_window import build_step_time_window


def test_wrap_checkpoint_emits_phase():
    st = get_state()
    captured = []
    st.on_batch_flushed.append(captured.append)
    try:
        saver = traceml_tpu.wrap_checkpoint(lambda tree: len(tree))
        with traceml_tpu.trace_step():
            assert saver({"a": 1, "b": 2}) == 2
    finally:
        st.on_batch_flushed.remove(captured.append)
    names = [e.name for e in captured[-1].events]
    assert T.CHECKPOINT_TIME in names


def test_orbax_save_auto_patched(tmp_path):
    ocp = pytest.importorskip("orbax.checkpoint")
    from traceml_tpu.instrumentation.orbax_patch import patch_orbax

    assert patch_orbax() or getattr(
        ocp.Checkpointer.__dict__.get("save"), "_traceml_wrapped", False
    )
    st = get_state()
    captured = []
    st.on_batch_flushed.append(captured.append)
    try:
        ckptr = ocp.PyTreeCheckpointer()
        tree = {"w": jnp.ones((8, 8)), "step": jnp.asarray(3)}
        with traceml_tpu.trace_step():
            ckptr.save(tmp_path / "ckpt", tree)
        names = [e.name for e in captured[-1].events]
        assert T.CHECKPOINT_TIME in names
        ev = next(e for e in captured[-1].events if e.name == T.CHECKPOINT_TIME)
        assert ev.cpu_ms is not None and ev.cpu_ms > 0
        # the save actually happened
        restored = ocp.PyTreeCheckpointer().restore(tmp_path / "ckpt")
        assert restored["w"].shape == (8, 8)
    finally:
        st.on_batch_flushed.remove(captured.append)
        # the patch is deliberately LEFT applied: unpatching here would
        # drain the module-global patch list and silently un-instrument
        # saves for the rest of the pytest process (auto-patches from an
        # earlier init() share that list); a wrapped save is harmless


def test_orbax_deferred_patch_launcher_order(tmp_path):
    """The LAUNCHER order: init() runs before the user script imports
    orbax — the post-import hook must patch it when the import happens."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = tmp_path / "deferred.py"
    script.write_text("""
import sys
sys.path.insert(0, %r)
import traceml_tpu
traceml_tpu.init(mode="auto")           # BEFORE orbax is imported
assert "orbax.checkpoint" not in sys.modules
import orbax.checkpoint as ocp          # hook fires here
assert getattr(ocp.Checkpointer.__dict__["save"], "_traceml_wrapped", False), \\
    "deferred patch did not apply"
from traceml_tpu.sdk.state import get_state
import jax.numpy as jnp
captured = []
get_state().on_batch_flushed.append(captured.append)
with traceml_tpu.trace_step():
    ocp.PyTreeCheckpointer().save(%r + "/ck", {"w": jnp.ones((4,))})
names = [e.name for e in captured[-1].events]
assert any(n.endswith("checkpoint_time") for n in names), names
print("DEFERRED-OK")
""" % (str(Path(__file__).resolve().parents[2]), str(tmp_path)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=180, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DEFERRED-OK" in proc.stdout


def test_checkpoint_phase_flows_to_window():
    rows = {0: [
        {"step": s, "timestamp": float(s), "clock": "device",
         "events": {
             T.STEP_TIME: {"cpu_ms": 100.0, "device_ms": 100.0, "count": 1},
             T.COMPUTE_TIME: {"cpu_ms": 1.0, "device_ms": 60.0, "count": 1},
             T.CHECKPOINT_TIME: {"cpu_ms": 30.0, "device_ms": None, "count": 1},
         }}
        for s in range(1, 31)
    ]}
    window = build_step_time_window(rows)
    assert "checkpoint" in window.phases_present
    assert window.metric("checkpoint").median_ms == pytest.approx(30.0)
    assert window.share_of_step("checkpoint") == pytest.approx(0.3)
