"""Flash-attention kernel vs the jnp reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from traceml_tpu.ops.attention import causal_attention, causal_attention_reference
from traceml_tpu.ops.pallas_attention import flash_attention


def _qkv(B=2, S=256, H=4, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    return tuple(jax.random.normal(k, shape, dtype) * 0.3 for k in ks)


def test_flash_matches_reference():
    q, k, v = _qkv()
    ref = causal_attention_reference(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_matches_reference_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = causal_attention_reference(q, k, v).astype(jnp.float32)
    out = flash_attention(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2, rtol=3e-2)


def test_flash_is_causal():
    q, k, v = _qkv(B=1, S=128, H=2, D=64)
    out1 = flash_attention(q, k, v)
    # perturb the LAST key/value: only the last positions may change
    k2 = k.at[:, -1].add(1.0)
    v2 = v.at[:, -1].add(1.0)
    out2 = flash_attention(q, k2, v2)
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-5
    )
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


def test_flash_rejects_ragged_seq():
    q, k, v = _qkv(S=100)  # not divisible by block
    with pytest.raises(ValueError):
        flash_attention(q, k, v, blk_q=64, blk_k=64)


def test_dispatcher_uses_flash_for_long_seq(monkeypatch):
    import traceml_tpu.ops.attention as att

    called = {}

    def spy(q, k, v):
        called["flash"] = True
        return att.causal_attention_reference(q, k, v)

    monkeypatch.setattr(
        "traceml_tpu.ops.pallas_attention.flash_attention", spy
    )
    q, k, v = _qkv(B=1, S=1024, H=1, D=64)
    att.causal_attention(q, k, v)
    assert called.get("flash")

    called.clear()
    q, k, v = _qkv(B=1, S=128, H=1, D=64)
    att.causal_attention(q, k, v)
    assert not called.get("flash")  # short seq stays on the fused path


def test_flash_block_sizes_clamped_to_seq():
    """blk larger than S is clamped (single-block path)."""
    q, k, v = _qkv(B=1, S=64, H=2, D=64)
    ref = causal_attention_reference(q, k, v)
    out = flash_attention(q, k, v, blk_q=128, blk_k=128)  # > S
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_uneven_block_pair():
    q, k, v = _qkv(B=1, S=256, H=2, D=64)
    ref = causal_attention_reference(q, k, v)
    out = flash_attention(q, k, v, blk_q=128, blk_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grad_matches_reference():
    """The kernel's VJP is the reference path's, so a train step can
    differentiate through it."""
    q, k, v = _qkv(B=1, S=256, H=2, D=64)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) ** 2).sum()

    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(causal_attention_reference), argnums=(0, 1, 2))(
        q, k, v
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "S, kv_S, expect_flash",
    [(1024, 1024, True), (1023, 1023, False), (1024 + 64, 1024 + 64, False),
     (1024, 2048, False)],
)
def test_flash_eligible_conditions(S, kv_S, expect_flash):
    from traceml_tpu.ops.attention import flash_eligible

    q = jax.ShapeDtypeStruct((1, S, 2, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, kv_S, 2, 64), jnp.float32)
    assert flash_eligible(q, kv, kv) is expect_flash


def test_dispatcher_propagates_kernel_errors(monkeypatch):
    """A kernel that fails to lower raises; no silent reference path."""

    def broken(q, k, v):
        raise RuntimeError("mosaic lowering failed")

    monkeypatch.setattr(
        "traceml_tpu.ops.pallas_attention.flash_attention", broken
    )
    q, k, v = _qkv(B=1, S=1024, H=1, D=64)
    with pytest.raises(RuntimeError, match="mosaic"):
        causal_attention(q, k, v)


def test_flash_refuses_unknown_platform(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    q, k, v = _qkv(B=1, S=128, H=1, D=64)
    with pytest.raises(NotImplementedError, match="gpu"):
        flash_attention(q, k, v)
