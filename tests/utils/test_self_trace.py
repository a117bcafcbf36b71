"""utils/self_trace.py: the pinned span vocabulary, spans that build
nothing while no profiler session records, and the time-stamped rings."""

import pytest

from traceml_tpu.utils import self_trace
from traceml_tpu.utils.self_trace import SPAN_NAMES, TimedRing, span


def test_span_names_pinned():
    assert SPAN_NAMES == (
        "traceml/step_enter",
        "traceml/wrap",
        "traceml/step_exit",
        "traceml/mem_read",
        "traceml/resolver_poll",
        "traceml/sampler_tick",
        "traceml/sample",
        "traceml/publish",
    )


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation."""

    enabled = False
    made = []

    def __init__(self, name, **kwargs):
        type(self).made.append((name, kwargs))

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def counting(monkeypatch):
    _CountingAnnotation.made = []
    _CountingAnnotation.enabled = False
    monkeypatch.setattr(self_trace, "_annotation", _CountingAnnotation)
    return _CountingAnnotation


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_span_builds_nothing_without_a_session(counting, name):
    with span(name, 7, sampler="step_time") as sp:
        assert sp is self_trace._NULL
    assert counting.made == []


def test_span_carries_its_arguments_while_recording(counting):
    counting.enabled = True
    with span("traceml/step_exit", 12):
        pass
    with span("traceml/sample", sampler="system"):
        pass
    with span("traceml/publish"):
        pass
    assert counting.made == [
        ("traceml/step_exit", {"step": 12}),
        ("traceml/sample", {"sampler": "system"}),
        ("traceml/publish", {}),
    ]


def test_real_annotation_is_off_outside_a_session():
    import jax  # noqa: F401  (the helper only looks once jax is imported)

    assert span("traceml/wrap", 1) is self_trace._NULL


def test_timed_ring_windows_and_bounds():
    ring = TimedRing(4)
    for t in range(6):
        ring.add(float(t), 10.0 * t)
    assert ring.samples() == [(2.0, 20.0), (3.0, 30.0), (4.0, 40.0), (5.0, 50.0)]
    assert ring.samples(3.0, 4.0) == [(3.0, 30.0), (4.0, 40.0)]
    assert ring.summary() == {"n": 4, "p50": 35.0, "p95": pytest.approx(48.5)}


@pytest.mark.parametrize(
    "values,want",
    [([], {"n": 0, "p50": None, "p95": None}),
     ([5.0], {"n": 1, "p50": 5.0, "p95": 5.0}),
     ([4.0, 1.0, 3.0, 2.0], {"n": 4, "p50": 2.5, "p95": pytest.approx(3.85)})],
)
def test_ring_summary(values, want):
    ring = TimedRing(8)
    for i, v in enumerate(values):
        ring.add(float(i), v)
    assert ring.summary() == want
