"""Causal attention: jnp reference path + optional pallas flash kernel.

The reference path is a single einsum-softmax-einsum chain that XLA
fuses and MXU-tiles well at the model sizes the demos/bench use.  The
pallas flash-attention kernel (ops/pallas_attention.py) takes over for
long sequences where the S×S score matrix would blow HBM, wherever its
shape conditions (:func:`flash_eligible`) hold.  A kernel that fails to
lower or compile raises: it never falls back in silence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_PALLAS_MIN_SEQ = 1024  # below this the fused jnp path wins


def attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True
) -> jnp.ndarray:
    """q,k,v: (B, S, H, D) → (B, S, H, D); softmax(QK^T)V, optionally
    causal-masked (decoders) or full (encoders/ViT)."""
    B, S, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
) -> jnp.ndarray:
    return attention_reference(q, k, v, causal=True)


def flash_eligible(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> bool:
    """The flash kernel's conditions: self-attention (q, k and v share
    one shape), S ≥ ``_PALLAS_MIN_SEQ`` and S a multiple of the kernel's
    block size."""
    from traceml_tpu.ops.pallas_attention import BLOCK

    S = q.shape[1]
    return (
        q.shape == k.shape == v.shape
        and S >= _PALLAS_MIN_SEQ
        and S % BLOCK == 0
    )


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    if flash_eligible(q, k, v):
        from traceml_tpu.ops.pallas_attention import flash_attention

        return flash_attention(q, k, v)
    return causal_attention_reference(q, k, v)
