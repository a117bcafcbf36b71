"""``make_train_step`` names its phases: the loss runs in ``forward``, its
gradient carries ``transpose(jvp(forward))``, the update ``optimizer``.
The scopes are metadata: without them the compiled program is the same."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from traceml_tpu.models import ModelConfig, init_train_state, make_train_step

CFG = ModelConfig(vocab_size=128, hidden=64, n_layers=2, n_heads=4, n_kv_heads=4,
                  ffn_mult=2.0, max_seq_len=32)


@pytest.fixture(scope="module")
def setup():
    model, state, tx = init_train_state(CFG, jax.random.PRNGKey(0))
    return model, state, tx, jnp.zeros((2, 32), jnp.int32)


def _compiled_text(setup):
    model, state, tx, tokens = setup
    return jax.jit(make_train_step(model, tx)).lower(state, tokens).compile().as_text()


def test_phase_scopes_in_op_names(setup):
    names = re.findall(r'op_name="([^"]+)"', _compiled_text(setup))
    stacks = {"/".join(n.split("/")[:2]) for n in names}
    assert {
        "jit(train_step)/jvp(forward)",
        "jit(train_step)/transpose(jvp(forward))",
        "jit(train_step)/optimizer",
    } <= stacks


def _without_metadata(text):
    text = text.split("\nFileNames")[0]  # the stack-frame tables
    return re.sub(r",?\s*metadata=\{[^}]*\}", "", text)


def test_scopes_leave_the_program_unchanged(setup, monkeypatch):
    scoped = _compiled_text(setup)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_text(setup)
    assert "jvp(forward)" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)
