"""``trace_program``: name stacks read from a trace's event metadata,
phases from them, and the reduction — phase sums, the idle time a
``traceml/`` span overlaps, and gaps given to the program's spans before
the harness's — on synthetic events, on a TPU v5e trace recorded before
the scopes and spans existed (``small.xplane.pb``) and on one recorded
with them (``scoped.xplane.pb``: 4 annotated steps of the tiny cell,
0.12 s of host sleep with the runtime ticking every 0.05 s, 2 more
steps)."""

from pathlib import Path

import pytest

from benchmark import trace_program as tp
from benchmark import trace_reduce as tr

TRACES = Path(__file__).resolve().parents[2] / "benchmark" / "traces"
SMALL = TRACES / "small.xplane.pb"
SCOPED = TRACES / "scoped.xplane.pb"


@pytest.mark.parametrize(
    "stack,phase",
    [
        ("jit(train_step)/jvp(forward)/DecoderLM/layer_0/attn/wq/dot_general:", "forward"),
        ("jit(train_step)/transpose(jvp(forward))/DecoderLM/lm_head/dot_general", "backward"),
        ("jit(train_step)/optimizer/mul", "optimizer"),
        ("jit(train_step)/transpose(jvp(DecoderLM))/layer_1/attn_norm/div:", "other"),
        ("jit(train_step)/feedforward/add", "other"),
        ("", "other"),
        (None, "other"),
    ],
)
def test_phase_of(stack, phase):
    assert tp.phase_of(stack) == phase


def _events():
    return {
        "devices": {
            "/device:TPU:0": [("f", 10, 40, "forward"), ("b", 40, 70, "backward"),
                              ("o", 80, 90, "optimizer"), ("c", 90, 95, "other")],
            "/device:TPU:1": [("f", 10, 90, "forward")],
        },
        "host": [
            (tr.WINDOW, 0, 100), ("dispatch", 0, 12), ("wait_loss", 70, 100),
            ("traceml/step_enter", 72, 75), ("traceml/sampler_tick", 60, 98),
        ],
    }


def test_reduce_synthetic():
    r = tp.reduce(_events())
    # per chip, mean over the 2 chips: forward (30 + 80) / 2 = 55 ns
    assert r["phase_s"] == pytest.approx(
        {"forward": 55e-9, "backward": 15e-9, "optimizer": 5e-9, "other": 2.5e-9}
    )
    assert r["ops_s"] == pytest.approx(77.5e-9)
    assert r["other_ops"] == [["c", pytest.approx(2.5e-9)]]
    # idle: dev0 [0,10] [70,80] [95,100]; dev1 [0,10] [90,100] -> 45 ns
    assert r["idle_s"] == pytest.approx(22.5e-9)
    # the spans' union [60,98] covers 10 + 3 + 8 of it
    assert r["idle_overlap_pct"] == pytest.approx(100 * 21 / 45)
    # a traceml/ span that overlaps a gap takes it (the one overlapping
    # most: sampler_tick over step_enter in [70,80]); else the harness's
    assert dict(map(tuple, r["idle_gaps"])) == {
        "dispatch": pytest.approx(10e-9), "traceml/sampler_tick": pytest.approx(12.5e-9),
    }


def test_reduce_needs_one_window():
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tp.reduce(ev)


def test_name_stacks_cover_every_operation():
    """Every ``XLA Ops`` event of the scoped trace has event metadata the
    wire reader found; those with no name stack are operations the
    compiler made (async copies, an iota, one fusion)."""
    from jax.profiler import ProfileData

    stacks = tp.name_stacks(SCOPED)
    assert list(stacks) == ["/device:TPU:0"]
    names = [
        ev.name
        for plane in ProfileData.from_file(str(SCOPED)).planes
        if plane.name.startswith(tr.DEVICE_PREFIX)
        for line in plane.lines if line.name == tr.OPS_LINE
        for ev in line.events
    ]
    stack = stacks["/device:TPU:0"]
    assert names and all(n in stack for n in names)
    unnamed = {tr.op_name(n).split()[0] for n in names if not stack[n]}
    assert unnamed <= {"copy-start", "copy-done", "iota", "fusion"}
    assert any(stack[n] for n in names)


def test_old_trace_has_no_scopes_or_spans():
    r = tp.reduce(tp.load(SMALL))
    base = tr.reduce(tr.load(SMALL))
    assert r["phase_s"]["other"] == pytest.approx(r["ops_s"])
    assert r["idle_overlap_pct"] == 0.0
    assert r["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"], rel=1e-3)
    assert {n for n, _ in r["idle_gaps"]} <= set(tr.HOST_SPANS) | {"other"}


def test_scoped_chip_trace():
    """The tiny cell on a TPU v5e with the scopes and spans: every phase
    present, the named phases most of the op time, each ``traceml/``
    span with its arguments, and the idle time split between spans."""
    ev = tp.load(SCOPED)
    r = tp.reduce(ev)
    base = tr.reduce(tr.load(SCOPED))
    assert all(r["phase_s"][p] > 0 for p in tp.PHASES)
    assert r["ops_s"] == pytest.approx(sum(r["phase_s"].values()))
    assert 1 - r["phase_s"]["other"] / r["ops_s"] > 0.7
    assert {n.split()[0] for n, _ in r["other_ops"]} <= {"copy-done", "copy-start", "fusion", "iota"}
    steps = [a["step"] for a in ev["args"]["traceml/step_exit"]]
    assert steps == sorted(steps) and len(steps) == 6
    assert {a["sampler"] for a in ev["args"]["traceml/sample"]} >= {"step_time", "step_memory"}
    assert set(r["spans"]) == {
        "traceml/step_enter", "traceml/wrap", "traceml/step_exit", "traceml/mem_read",
        "traceml/resolver_poll", "traceml/sampler_tick", "traceml/sample", "traceml/publish",
    }
    assert r["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"], rel=1e-6)
    assert 0 < r["idle_overlap_pct"] < 100
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(r["idle_s"], rel=1e-6)
    # the 0.12 s sleep runs under the runtime's ticks: a program span
    # takes it, where the harness spans alone gave it to ``dispatch``
    assert max(gaps, key=gaps.get) == "traceml/sampler_tick"
    assert max(base["idle_gaps"], key=lambda g: g[1])[0] == "dispatch"
