"""The readers of the metrics the program counts itself, windowed by the
run's ``[start, stop]``: the tracer's host time per step, the step rows'
emission waits, and the compiles of the window.  Each reads nothing and
returns None from a program that does not keep the counter."""

import time
from types import SimpleNamespace

import pytest

from benchmark import discovery
from traceml_tpu.sdk import state as state_mod

HOST = "tracer.host_us_per_step"
WAIT = "samplers.tick_wait_ms_p95"
COMPILES = "compile.window_compiles"


@pytest.fixture()
def st():
    return state_mod.reset_state_for_tests()


def _rec(start, stop):
    return {"window": {"start": start, "stop": stop, "step_s": []}}


def test_host_time_of_the_window_steps_only(st):
    for t, ns in [(1.0, 900_000), (2.0, 40_000), (3.0, 60_000), (9.0, 700_000)]:
        st.host_ns_ring.add(t, ns)
    assert discovery.reader(HOST)(_rec(1.5, 8.0)) == pytest.approx(50.0)
    assert discovery.reader(HOST)(_rec(4.0, 8.0)) is None


def test_tick_wait_p95_of_the_window(st):
    for i in range(101):
        st.emit_waits.add(10.0 + i, float(i))
    st.emit_waits.add(500.0, 1e6)
    assert discovery.reader(WAIT)(_rec(10.0, 110.0)) == pytest.approx(95.0)


@pytest.mark.parametrize("name", [HOST, WAIT, COMPILES])
def test_program_without_the_counter_reads_nothing(monkeypatch, name):
    monkeypatch.setattr(state_mod, "get_state", lambda: SimpleNamespace())
    assert discovery.reader(name)(_rec(0.0, 1e9)) is None


def test_window_compiles_counts_a_forced_recompile(st):
    import jax
    import jax.numpy as jnp

    from traceml_tpu.instrumentation.compile_tracker import install_compile_tracker

    install_compile_tracker()
    salt = time.time_ns() % 1000  # a program no cache has seen

    @jax.jit
    def f(x):
        for _ in range(8):
            x = jnp.tanh(x @ x.T @ x + salt)
        return x

    small, large = jnp.ones((16, 16)), jnp.ones((24, 24))
    f(small).block_until_ready()
    w0 = time.monotonic()
    f(small).block_until_ready()
    w1 = time.monotonic()
    assert discovery.reader(COMPILES)(_rec(w0, w1)) == 0
    f(large).block_until_ready()  # a new shape: compiles again
    w2 = time.monotonic()
    assert discovery.reader(COMPILES)(_rec(w1, w2)) == 1
