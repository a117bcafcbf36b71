"""Latent attention's own entry point (``ops.attention.latent_causal_attention``):
the splash kernels (interpreted on the CPU) against the dense reference at
unequal q/k and v widths, and which path each call takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from traceml_tpu.ops import attention as att
from traceml_tpu.ops.attention import (
    attention_reference,
    latent_causal_attention,
    partitioned_step,
    record_latent_dispatch,
)

DQK, DV = 192, 128  # Moonlight's q/k (128 + 64 RoPE) and v widths


def _qkv(S, dtype=jnp.float32, B=2, H=2):
    kq, kk, kv, kg = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(kq, (B, S, H, DQK)).astype(dtype)
    k = jax.random.normal(kk, (B, S, H, DQK)).astype(dtype)
    v = jax.random.normal(kv, (B, S, H, DV)).astype(dtype)
    return q, k, v, jax.random.normal(kg, (B, S, H, DV))


def _loss(attend, g):
    return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * g)


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


@pytest.fixture
def short_kernel(monkeypatch):
    """The kernel path from 128 tokens on, so interpreted runs stay small."""
    monkeypatch.setattr(att, "_PALLAS_MIN_SEQ", 128)


# bf16: a few units of bf16's 2**-8 against the float32 reference's scale
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("S", [256, 255])  # a block multiple, one short of it
def test_kernel_matches_the_reference(short_kernel, S, dtype, tol):
    q, k, v, g = _qkv(S, dtype)
    with record_latent_dispatch() as calls:
        out = latent_causal_attention(q, k, v)
    assert calls == [("kernel", q.shape, v.shape)]
    assert out.shape == v.shape and out.dtype == dtype
    want = attention_reference(*_f32(q, k, v))
    np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=0,
                               atol=tol * float(jnp.max(jnp.abs(want))))
    got = jax.grad(_loss(latent_causal_attention, g), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(_loss(attention_reference, g), argnums=(0, 1, 2))(*_f32(q, k, v))
    for name, a, b in zip("qkv", got, ref):
        assert a.dtype == dtype, name
        np.testing.assert_allclose(a.astype(jnp.float32), b, rtol=0,
                                   atol=tol * float(jnp.max(jnp.abs(b))), err_msg=name)


def test_kernel_under_checkpoint(short_kernel):
    """As ``nn.remat`` wraps the blocks: the recomputed forward gives the
    same gradients."""
    q, k, v, g = _qkv(255)
    got = jax.grad(_loss(jax.checkpoint(latent_causal_attention), g),
                   argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(_loss(attention_reference, g), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))))


def _path(S):
    """The path one call of Moonlight's shape takes, from a fresh trace
    (JAX reuses the trace of a function it has seen at these shapes)."""
    q = jax.ShapeDtypeStruct((1, S, 16, DQK), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, S, 16, DV), jnp.bfloat16)
    with record_latent_dispatch() as calls:
        jax.eval_shape(lambda *a: latent_causal_attention(*a), q, q, v)
    assert len(calls) == 1
    return calls[0][0]


@pytest.mark.parametrize("S, path", [(2047, "kernel"), (1024, "kernel"), (1023, "dense")])
def test_kernel_from_the_threshold_on(S, path):
    assert _path(S) == path


def test_dense_inside_a_partitioned_step():
    with partitioned_step(jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))):
        assert _path(2047) == "dense"
        with partitioned_step(None):  # an inner step without a mesh stays partitioned
            assert _path(2047) == "dense"
    with partitioned_step(None):
        assert _path(2047) == "kernel"


def test_dense_on_a_backend_without_the_kernel(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert _path(2047) == "dense"


def test_kernel_runs_under_the_mla_kernel_scope(short_kernel):
    """Forward and backward kernels carry the scope in their name stacks,
    which the benchmark's trace reduction counts under ``mla_kernel``."""
    q, k, v, g = _qkv(128, jnp.bfloat16, B=1)

    def lowered(attend):
        return jax.jit(jax.grad(_loss(attend, g))).lower(q, k, v).as_text(debug_info=True)

    text = lowered(latent_causal_attention)
    assert "/jvp(mla_kernel)/vmap(jit(_splash_attention))" in text
    assert "/transpose(jvp(mla_kernel))/vmap(jit(_splash_attention))" in text
    assert "(mla_kernel)" not in lowered(attention_reference)


def test_equal_width_attention_keeps_its_dispatch(monkeypatch):
    """``causal_attention`` (the decoder layers') is not latent attention's
    path: below the flash threshold it is the reference, op for op, and
    it never reaches the splash kernels."""
    q = jax.ShapeDtypeStruct((1, 1023, 2, 64), jnp.bfloat16)
    with record_latent_dispatch() as calls:
        got = jax.make_jaxpr(att.causal_attention)(q, q, q)
    assert calls == []
    assert str(got) == str(jax.make_jaxpr(att.causal_attention_reference)(q, q, q))
    called = []
    monkeypatch.setattr("traceml_tpu.ops.pallas_attention.flash_attention",
                        lambda *a: called.append(1) or a[2])
    att.causal_attention(*(jnp.zeros((1, 1024, 1, 64)),) * 3)
    assert called == [1]
