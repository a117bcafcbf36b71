"""Flash attention (causal) as a pallas TPU kernel.

Why: the reference jnp path materializes the (S, S) score matrix per
head — at S=8k, bf16, that is 128 MiB per (batch, head) of pure HBM
traffic.  The flash pattern streams K/V blocks through VMEM with an
online softmax, keeping the working set at O(BLK_Q × S/BLK_K) and the
matmuls MXU-shaped.

Kernel layout (one program per (batch*head, q-block)):

* q block  (BLK_Q, D)  resident in VMEM,
* K and V  (S, D)      resident in VMEM (fits comfortably: 2×S×D×2 B —
  8k×128 bf16 is 2 MiB each against ~16 MiB VMEM),
* ``fori_loop`` over k-blocks with a DYNAMIC trip count — causality
  bounds the loop at the q block's diagonal, so the lower triangle does
  ~half the work instead of masking it away,
* online softmax in f32 (m, l, acc carries), one write of the output
  block at the end.

The kernel is compiled by Mosaic on TPU and run by the Pallas
interpreter on CPU (CI numerics); any other backend is an error.  The
backward pass is the reference path's VJP, recomputed from q, k and v,
so the kernel can sit inside a differentiated train step.
``ops.attention.causal_attention`` selects it where its shape
conditions hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30
#: default q and k block rows; S must be a multiple of the block
BLOCK = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, blk_q: int, blk_k: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (BLK_Q, D)
    d = q.shape[-1]
    q_start = qi * blk_q

    m = jnp.full((blk_q, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((blk_q, 1), jnp.float32)
    acc = jnp.zeros((blk_q, d), jnp.float32)

    # causal bound: last k block that any row of this q block can see
    n_kv = (q_start + blk_q + blk_k - 1) // blk_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BLK_Q, BLK_K)
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_ids = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        s = jnp.where(q_ids >= k_ids, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_q", "blk_k", "interpret"))
def _flash_bhsd(q, k, v, blk_q: int, blk_k: int, interpret: bool):
    """q,k,v: (BH, S, D) → (BH, S, D)."""
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    grid = (BH, S // blk_q)
    kernel = functools.partial(
        _flash_kernel, blk_q=blk_q, blk_k=blk_k, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)


def _interpret() -> bool:
    """Mosaic on TPU, the Pallas interpreter on CPU; nothing else."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"flash_attention has no kernel for platform {platform!r}"
        )
    return platform == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, blk_q: int, blk_k: int):
    B, S, H, D = q.shape

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    out = _flash_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), blk_q, blk_k, _interpret()
    )
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, blk_q, blk_k):
    return _flash(q, k, v, blk_q, blk_k), (q, k, v)


def _flash_bwd(blk_q, blk_k, residuals, g):
    from traceml_tpu.ops.attention import causal_attention_reference

    _, vjp = jax.vjp(causal_attention_reference, *residuals)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    blk_q: int = BLOCK,
    blk_k: int = BLOCK,
) -> jnp.ndarray:
    """Causal flash attention; q,k,v: (B, S, H, D) → (B, S, H, D).

    Constraints (``ops.attention.flash_eligible`` checks them before
    selecting this kernel): S divisible by the block sizes; same S for
    q and k/v (self-attention).
    """
    S = q.shape[1]
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    if S % blk_q or S % blk_k:
        raise ValueError(f"S={S} not divisible by blocks ({blk_q},{blk_k})")
    return _flash(q, k, v, blk_q, blk_k)
