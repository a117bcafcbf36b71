"""chip_smoke.py rehearsed on the CPU at tiny widths: the real launcher
run, the artifact checks, and the refusals without a chip."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def cpu_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def _stdout_results(stdout: str) -> list:
    rows = []
    for line in stdout.splitlines():
        try:
            rows.append(json.loads(line))
        except ValueError:
            continue
    return rows


@pytest.mark.parametrize("four_chips", [False, True],
                         ids=["one_chip", "four_chips"])
def test_rehearsal_passes_cpu_checks_and_never_passes_as_tpu(
    cpu_env, tmp_path, four_chips
):
    rank_args = ["--four-chips"] if four_chips else []
    assert chip_smoke.run_launcher(tmp_path, rank_args, timeout=240) == 0
    art = chip_smoke.read_session(tmp_path)
    assert chip_smoke.check(art, "cpu", four_chips) == []
    out = chip_smoke.report(art, four_chips)
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert out["numbers"]["compile_s"] > 0
    if four_chips:
        assert out["numbers"]["ici_shape"] == [4, 12]
    # the same artifacts can never pass as a chip run
    problems = chip_smoke.check(art, "tpu", four_chips)
    assert any("platform='cpu'" in p for p in problems)
    assert any("step memory backends ['live_arrays']" in p for p in problems)


def test_check_reports_a_phase_that_yielded_nothing(cpu_env, tmp_path):
    assert chip_smoke.run_launcher(tmp_path, [], timeout=240) == 0
    art = chip_smoke.read_session(tmp_path)
    del art["rank"]["phases"]["kernel"]
    art["rank"]["phases"]["marker"]["ok"] = False
    problems = chip_smoke.check(art, "cpu", False)
    assert "phase kernel yielded nothing" in problems
    assert any(p.startswith("phase marker failed") for p in problems)


def test_without_a_chip_exits_nonzero_and_prints_no_result(cpu_env, tmp_path):
    # a checkout of its own, so the run's artifacts stay in tmp_path
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    (tmp_path / "traceml_tpu").symlink_to(REPO / "traceml_tpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 1
    assert "FAIL: system manifest platform='cpu'" in proc.stderr
    assert _stdout_results(proc.stdout) == []


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2
    assert _stdout_results(proc.stdout) == []


def test_aggregator_env_is_pinned_to_cpu():
    from traceml_tpu.launcher.commands import _aggregator_env

    base = {"JAX_PLATFORMS": "tpu", "TRACEML_MODE": "summary"}
    env = _aggregator_env(base)
    assert env["JAX_PLATFORMS"] == "cpu" and env["TRACEML_MODE"] == "summary"
    assert base["JAX_PLATFORMS"] == "tpu"  # the ranks' env is untouched
