"""A whole run of a tiny cell on the CPU, the chip check skipped: a sound
run is correct, and with the timed path broken underneath it is not."""

import json
import time
from pathlib import Path

import jax
import pytest

from benchmark import harness

SPEC = json.loads((Path(__file__).parent / "tiny_cell.json").read_text())
SPEC4 = dict(SPEC, chips=4, cell=dict(SPEC["cell"], mesh={"data": 1, "fsdp": 2, "tensor": 2}))
SEED = 2**33 + 5
#: weights the program splits over fsdp along dim 1 (models/transformer.py)
ROW_PARALLEL = ("wo", "w_down")


def _broken_step(kind):
    real = harness.make_train_step

    def make(model, tx, mesh=None):
        step = real(model, tx, mesh=mesh)

        def broken(state, tokens):
            if kind == "state_unchanged":
                return state, step(state, tokens)[1]
            if kind == "half_batch":
                return step(state, tokens[: tokens.shape[0] // 2])
            if kind == "token_altered":
                return step(state, tokens.at[:, 7].set((tokens[:, 7] + 1) % 256))
            raise ValueError(kind)

        return broken

    return make


def _no_exchange_step(model, tx, mesh=None):
    """A step whose fsdp halves of each weight are updated from their own
    half of the batch: the gradient exchange between chips left out."""
    import optax

    from traceml_tpu.models.transformer import loss_fn

    def pick(path, a, b):
        if a.ndim < 2:
            return a
        names = [str(getattr(k, "key", k)) for k in path]
        dim = 1 if any(n in ROW_PARALLEL for n in names) else 0
        n = a.shape[dim] // 2
        return jax.numpy.concatenate(
            [jax.lax.slice_in_dim(a, 0, n, axis=dim),
             jax.lax.slice_in_dim(b, n, a.shape[dim], axis=dim)], axis=dim,
        )

    def step(state, tokens):
        half = tokens.shape[0] // 2
        la, ga = jax.value_and_grad(loss_fn)(state["params"], model.apply, tokens[:half])
        lb, gb = jax.value_and_grad(loss_fn)(state["params"], model.apply, tokens[half:])
        grads = jax.tree_util.tree_map_with_path(pick, ga, gb)
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new = {"params": params, "opt_state": opt_state, "step": state["step"] + 1}
        return new, {"loss": 0.5 * (la + lb)}

    return step


def _run(spec=SPEC):
    result, lines = harness.run(
        "tiny", SEED, 1.0, False, time.monotonic(), require_tpu=False, spec=spec
    )
    return result, lines


@pytest.mark.parametrize("spec", [SPEC, SPEC4], ids=["1chip", "4chip"])
def test_sound_run_is_correct(spec):
    result, lines = _run(spec)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "tokens_per_s", "step_ms_p95", "view_lag_ms_p95"}
    assert list(result)[-1] == "check"
    assert [ln.split()[1] for ln in lines if ln.startswith("check ")] == list(result["check"])
    assert lines[-len(result["check"]):] == [ln for ln in lines if ln.startswith("check ")]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "token_altered"])
def test_broken_step_is_not_correct(monkeypatch, kind):
    monkeypatch.setattr(harness, "make_train_step", _broken_step(kind))
    result, lines = _run()
    assert result["correct"] is False, lines


def test_exchange_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(harness, "make_train_step", _no_exchange_step)
    result, lines = _run(SPEC4)
    assert result["device"]["count"] == 4
    assert result["correct"] is False, lines


def test_lost_telemetry_is_not_correct(monkeypatch):
    real = harness.Stack.finish
    monkeypatch.setattr(harness.Stack, "finish", lambda self: set(sorted(real(self))[:-3]))
    result, _ = _run()
    assert result["correct"] is False
    assert result["failed"] >= 1
