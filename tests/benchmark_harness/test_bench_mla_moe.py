"""The latent-attention, routed-expert family through the harness on
the CPU at a tiny width (``tiny_moonlight_cell.json``, Moonlight's
ratios), and its readers on made-up records.

The tiny cell's limits sit between the program's readings (bf16 against
the float32 reference: loss 9e-4, grad 8.8e-3, delta 2.3e-3 over two
seeds) and the fp8 control's (8.7e-3, 0.060, 4.7e-3).
"""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import discovery, harness
from traceml_tpu.sdk import state as state_mod
from traceml_tpu.utils.self_trace import TimedRing

TINY = json.loads((Path(__file__).parent / "tiny_moonlight_cell.json").read_text())
SPEC = dict(TINY, family=discovery.load_family(TINY["model"]["family"]))
PROGRAM = SPEC["family"].program
SEED = 2**33 + 5


def _run():
    return harness.run("tiny-moonlight", SEED, 1.0, False, time.monotonic(),
                       require_tpu=False, spec=SPEC)


def test_sound_run_is_correct_and_counts_expert_load():
    rings = state_mod.get_state().counter_rings
    before = len(rings["expert_load"].samples()) if "expert_load" in rings else 0
    result, lines = _run()
    assert result["correct"] is True, lines
    loads = [v for _, v in rings["expert_load"].samples()][before:]
    assert len(loads) >= result["attempted"]
    m, load = TINY["model"], TINY["load"]
    tokens = load["batch"] * (load["seq"] - 1)
    for v in loads:
        assert v.shape == (m["num_hidden_layers"] - m["first_k_dense_replace"], 64)
        assert (v.sum(axis=1) == tokens * m["num_experts_per_tok"]).all()


def test_state_left_unchanged_is_not_correct(monkeypatch):
    real = PROGRAM.make_train_step

    def make(model, tx, mesh=None):
        step = real(model, tx, mesh=mesh)
        return lambda state, tokens: (state, step(state, tokens)[1])

    monkeypatch.setattr(PROGRAM, "make_train_step", make)
    result, lines = _run()
    assert result["correct"] is False, lines


def _record(scopes, other_ops, steps=4, model=None):
    return {
        "trace": {"steps": steps, "program": {"scope_s": scopes, "other_ops": other_ops}},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"start": 0.0, "stop": 100.0},
        "spec": {"model": model or json.loads(
            (discovery.HERE / "configs" / "moonlight-16b-a3b.json").read_text())},
    }


SCOPES = {"mla": {"fwd": 0.02, "bwd": 0.04}, "moe_route": {"fwd": 0.004, "bwd": 0.002},
          "moe_combine": {"fwd": 0.002, "bwd": 0.004}, "moe_experts": {"fwd": 0.001, "bwd": 0.003}}
OTHER = [["copy-done f32[2048,1408]", 0.5]]


def test_scope_readers():
    rec = _record(SCOPES, OTHER)
    assert discovery.reader("attn.mla_ms")(rec) == pytest.approx(15.0)
    assert discovery.reader("moe.route_ms")(rec) == pytest.approx(3.0)
    assert discovery.reader("moe.experts_ms")(rec) == pytest.approx(1.0)


def test_readers_find_nothing_where_the_program_has_no_experts(monkeypatch):
    rec = _record({"forward": {"fwd": 1.0, "bwd": 2.0}}, OTHER)
    for name in ("attn.mla_ms", "moe.route_ms", "moe.experts_ms", "moe.experts_roofline_pct"):
        assert discovery.reader(name)(rec) is None, name
    state_mod.reset_state_for_tests()  # a program without the counter
    assert discovery.reader("moe.load_max_over_mean")(_record(SCOPES, OTHER)) is None
    state_mod.reset_state_for_tests()


def test_counter_readers(monkeypatch):
    """The load reader takes the window's steps; the roofline reader the
    profiled steps after it, of which the last is never read."""
    st = state_mod.reset_state_for_tests()
    ring = TimedRing(16)
    even = np.full((4, 64), 384)
    skewed = even.copy()
    skewed[:, 0] += 384 * 3
    skewed[:, 1:4] -= 384
    away = np.zeros((4, 64), int)
    away[:, 8:] = 4 * 384 * 64 // 56  # no pair on a held expert
    for t, load in [(1.0, even), (2.0, skewed), (101.0, away), (102.0, even), (103.0, skewed)]:
        ring.add(t, load)
    st.counter_rings["expert_load"] = ring
    rec = _record(SCOPES, OTHER, steps=3)
    # even: 1; skewed: the busiest 4 x 384 over the same mean, 384
    assert discovery.reader("moe.load_max_over_mean")(rec) == pytest.approx(2.5)
    pct = discovery.reader("moe.experts_roofline_pct")(rec)
    cost = SPEC["family"].reference.routed_expert_cost
    flops, nbytes = cost(rec["spec"]["model"], 8 * 384)
    want = 4 * max(flops / 197e12, nbytes / 819e9) / (0.004 / 3)  # 4 layers over 4 ms / 3 steps
    assert pct == pytest.approx(100 * want)
    state_mod.reset_state_for_tests()


def test_routed_expert_cost_counts_every_pass():
    ref = SPEC["family"].reference
    m = dict(TINY["model"], program={"remat": True})
    flops, _ = ref.routed_expert_cost(m, 100.0)
    one = 2 * 100 * m["hidden_size"] * m["moe_intermediate_size"]
    # 3 matmuls: forward, recomputed forward, and two per matmul backward
    assert flops == 3 * one * 4
    assert ref.routed_expert_cost(dict(m, program={"remat": False}), 100.0)[0] == 3 * one * 3
