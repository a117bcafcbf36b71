"""The trace reduction: busy union, idle share, top operations and gap
attribution, on synthetic events and on a small trace recorded on a TPU
v5e (``benchmark/traces/small.xplane.pb``: 3 annotated steps of a tiny
DecoderLM through the harness's attached step, PR 22)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

SMALL = Path(__file__).resolve().parents[2] / "benchmark" / "traces" / "small.xplane.pb"


def test_union_merges_and_clips():
    got = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 20)], 1, 15)
    assert got == [(1, 3), (5, 9), (12, 15)]


def test_gaps_fill_the_window():
    assert tr.gaps([(1, 3), (5, 9)], 0, 10) == [(0, 1), (3, 5), (9, 10)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def _events():
    return {
        "devices": {
            "/device:TPU:0": [("fusion.1", 10, 40), ("dot.2", 40, 70), ("fusion.1", 80, 90)],
            "/device:TPU:1": [("fusion.1", 10, 90)],
        },
        "host": [
            (tr.WINDOW, 0, 100),
            ("dispatch", 0, 12),
            ("wait_loss", 70, 95),
        ],
    }


def test_reduce_synthetic():
    r = tr.reduce(_events())
    # device 0 busy 30+30+10 = 70 ns, device 1 80 ns, window 100 ns
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_pct"] == pytest.approx(25.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(60e-9)]
    assert r["device_ops"][1] == ["dot.2", pytest.approx(15e-9)]
    # gaps: dev0 [0,10] dispatch, [70,80] wait_loss, [90,100] wait_loss;
    # dev1 [0,10] dispatch, [90,100] wait_loss
    assert dict(map(tuple, r["idle_gaps"])) == {
        "dispatch": pytest.approx(10e-9), "wait_loss": pytest.approx(15e-9),
    }


@pytest.mark.parametrize(
    "hlo,name",
    [
        ("%fusion.14 = (f32[49152,2048]{1,0:T(8,128)}, f32[2]{0}) fusion(f32[1] %a), kind=kLoop",
         "fusion f32[49152,2048]"),
        ("%convert_element_type.200 = bf16[49152,2048]{1,0:T(8,128)(2,1)} convert(f32[2] %b)",
         "convert_element_type bf16[49152,2048]"),
        ("%convolution_bitcast_fusion = f32[3072,16032]{1,0} fusion(%c)",
         "convolution_bitcast_fusion f32[3072,16032]"),
        ("%async-collective-done.110 = bf16[3072,16032]{1,0} async-collective-done(%d)",
         "async-collective-done bf16[3072,16032]"),
        ("%custom-call.7 = (f32[], s32[]) custom-call()", "custom-call f32[]"),
    ],
)
def test_op_name(hlo, name):
    assert tr.op_name(hlo) == name


def test_reduce_needs_one_window():
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tr.reduce(ev)


def test_recorded_chip_trace():
    r = tr.reduce(tr.load(SMALL))
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_pct"] < 100
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    names = {n for n, _ in r["idle_gaps"]}
    assert names <= set(tr.HOST_SPANS) | {"other"}
    total_gaps = sum(s for _, s in r["idle_gaps"])
    assert total_gaps == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
