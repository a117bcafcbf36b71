"""The step wrapper's output handling: the arrays of a step's ``counters``
dict are held from dispatch and read to the host, each into the ring of
its name, only once their step has resolved; and the cached marker-leaf
getter follows the output's structure."""

import numpy as np
import pytest

from traceml_tpu.sdk import state as state_mod
from traceml_tpu.sdk.instrumentation import trace_step
from traceml_tpu.sdk.step_fn import wrap_step_fn
from traceml_tpu.utils.timing import GLOBAL_STEP_QUEUE

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_state():
    st = state_mod.reset_state_for_tests()
    from traceml_tpu.utils.step_memory import FakeMemoryBackend, StepMemoryTracker

    st.mem_tracker = StepMemoryTracker(FakeMemoryBackend([[]]))
    GLOBAL_STEP_QUEUE.drain()
    yield st
    GLOBAL_STEP_QUEUE.drain()
    # dispatches outside a step leave events in the buffer: hand the next
    # test a clean state
    state_mod.reset_state_for_tests()


class _InFlight:
    """An array whose step is still running: reading it would block."""

    def __init__(self, value):
        self.value = np.asarray(value)
        self.ready = False
        self.copies = 0

    def copy_to_host_async(self):
        self.copies += 1

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        if not self.ready:
            raise AssertionError("a blocking read of a step still in flight")
        return self.value


def _ring(st, name):
    ring = st.counter_rings.get(name)
    return [] if ring is None else ring.samples()


def test_load_is_read_only_once_its_step_resolved(fresh_state):
    st = fresh_state
    a, b = _InFlight([[3, 1]]), _InFlight([[2, 2]])
    st.note_counters({"expert_load": a})
    st.note_counters({"expert_load": b})
    assert a.copies == b.copies == 1  # the copy to host starts at dispatch
    st.drain_counters(1.0)
    assert _ring(st, "expert_load") == []
    b.ready = True
    st.drain_counters(2.0)  # oldest first: b waits behind a
    assert _ring(st, "expert_load") == []
    a.ready = True
    st.drain_counters(3.0)
    got = _ring(st, "expert_load")
    assert [t for t, _ in got] == [3.0, 3.0]
    assert [v.tolist() for _, v in got] == [[[3, 1]], [[2, 2]]]


def test_each_counter_goes_to_the_ring_of_its_name(fresh_state):
    """A step's counters resolve together: none is read while one of
    them is still in flight."""
    st = fresh_state
    a, b = _InFlight([1, 2]), _InFlight(7)
    st.note_counters({"expert_load": a, "dropped": b})
    a.ready = True
    st.drain_counters(1.0)
    assert st.counter_rings == {}
    b.ready = True
    st.drain_counters(2.0)
    assert [v.tolist() for _, v in _ring(st, "expert_load")] == [[1, 2]]
    assert [v.tolist() for _, v in _ring(st, "dropped")] == [7]


def test_wrapped_step_records_expert_load_at_the_next_step(fresh_state):
    def step(w, x):
        load = jnp.stack([jnp.bincount(x, length=4)]).astype(jnp.int32)
        return w + 1, {"loss": jnp.sum(w), "counters": {"expert_load": load}}

    wrapped = wrap_step_fn(step)
    w = jnp.zeros(())
    feeds = [jnp.array([0, 1, 1, 3]), jnp.array([2, 2, 2, 2]), jnp.array([0, 0, 0, 0])]
    for x in feeds:
        with trace_step() as ts:
            w, m = wrapped(w, x)
            ts.mark(m["loss"])
        float(m["loss"])
    got = [v.tolist() for _, v in _ring(fresh_state, "expert_load")]
    # each step's load is read at the next step's enter: the last one waits
    assert got == [[[1, 2, 0, 1]], [[0, 0, 4, 0]]]


def test_steps_without_the_counter_record_nothing(fresh_state):
    wrapped = wrap_step_fn(lambda w: (w + 1, {"loss": jnp.sum(w), "expert_load": w}))
    w = jnp.zeros(())
    for _ in range(3):
        with trace_step():
            w, m = wrapped(w)
        float(m["loss"])
    # only the ``counters`` dict is read: a metric beside it is not
    assert fresh_state.counter_rings == {}


def test_marker_leaf_follows_a_changed_output_structure(fresh_state):
    """A retrace that changes the output's structure while the old
    getter's path still resolves: the smallest leaf is picked again."""

    def step(w, extra):
        out = {"big": w, "small": jnp.sum(w, keepdims=True) * jnp.ones(8)}
        if extra is not None:
            out["small"] = out["small"] * jnp.ones((4, 8))
            out["tiny"] = extra
        return out

    wrapped = wrap_step_fn(step)
    w = jnp.ones((64,))
    out = wrapped(w, None)
    assert wrapped._pick_handles(out)[0] is out["small"]
    out = wrapped(w, jnp.zeros(()))
    assert wrapped._pick_handles(out)[0] is out["tiny"]
    # the same structure again: the cached getter, no flatten
    out = wrapped(w, jnp.ones(()))
    assert wrapped._pick_handles(out)[0] is out["tiny"]
