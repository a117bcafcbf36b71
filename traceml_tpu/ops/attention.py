"""Causal attention: jnp reference path + optional pallas flash kernels.

The reference path is a single einsum-softmax-einsum chain that XLA
fuses and MXU-tiles well at the model sizes the demos/bench use.  The
pallas flash-attention kernel (ops/pallas_attention.py) takes over for
long sequences where the S×S score matrix would blow HBM, wherever its
shape conditions (:func:`flash_eligible`) hold.  A kernel that fails to
lower or compile raises: it never falls back in silence.

Latent attention (``models.transformer.LatentAttention``, q/k and v of
unequal widths) has its own entry point, :func:`latent_causal_attention`:
from ``_PALLAS_MIN_SEQ`` on it runs JAX's splash attention kernels, which
keep each score block in VMEM with an f32 online softmax, skip the blocks
above the diagonal, and have fused dq and dk/dv backward kernels, so no
S×S tensor reaches HBM in either pass.  It is kept apart from
:func:`causal_attention`, which the equal-width decoder layers take:
a Pallas call is not partitioned by GSPMD, so inside a step built for a
mesh (:func:`partitioned_step`) latent attention keeps the dense path,
and the decoder layers' dispatch stays what it was.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp

_PALLAS_MIN_SEQ = 1024  # below this the fused jnp path wins
#: q and kv block rows of the latent kernels, forward and backward: at
#: Moonlight's shape (2 x 2047 tokens, 16 heads, 192/128 wide) on a TPU
#: v5e, forward + backward took 7.45 / 2.73 / 2.71 ms at 256 / 512 / 1024,
#: the forward 1.20 / 0.78 / 0.83 ms
_LATENT_BLOCK = 512

_partitioned = contextvars.ContextVar("partitioned_step", default=False)
_dispatches: contextvars.ContextVar = contextvars.ContextVar(
    "latent_dispatches", default=None
)


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


@contextlib.contextmanager
def partitioned_step(mesh) -> Iterator[None]:
    """While open with a mesh (not None), the step being traced is
    partitioned over it, so :func:`latent_causal_attention` keeps the
    dense path; ``make_train_step`` and ``init_train_state`` open it with
    the mesh they were given."""
    token = _partitioned.set(_partitioned.get() or mesh is not None)
    try:
        yield
    finally:
        _partitioned.reset(token)


@contextlib.contextmanager
def record_latent_dispatch() -> Iterator[List[Tuple[str, tuple, tuple]]]:
    """While open, each :func:`latent_causal_attention` call traced appends
    ``(path, q shape, v shape)`` to the list yielded; path is ``"kernel"``
    or ``"dense"``."""
    calls: List[Tuple[str, tuple, tuple]] = []
    token = _dispatches.set(calls)
    try:
        yield calls
    finally:
        _dispatches.reset(token)


def latent_causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
) -> jnp.ndarray:
    """Causal attention for q/k of one width and v of another: q, k (B, S,
    H, Dqk), v (B, S, H, Dv) → (B, S, H, Dv), scaled by 1/√Dqk.  The splash
    kernels (Mosaic on TPU, the Pallas interpreter on CPU) where S ≥
    ``_PALLAS_MIN_SEQ`` and the step is not partitioned, under the
    ``mla_kernel`` scope; else the dense reference path."""
    backend = jax.default_backend()
    fused = (
        q.shape[1] >= _PALLAS_MIN_SEQ
        and backend in ("tpu", "cpu")
        and not _partitioned.get()
    )
    calls = _dispatches.get()
    if calls is not None:
        calls.append(("kernel" if fused else "dense", q.shape, v.shape))
    if not fused:
        return causal_attention_reference(q, k, v)
    with jax.named_scope("mla_kernel"):
        return _splash_causal(q, k, v, interpret=backend == "cpu")


def _splash_causal(q, k, v, interpret: bool):
    """Splash attention over (B, S, H, D) inputs.  q is pre-scaled, as
    splash applies no scale.  S is padded up to a block multiple: the
    causal mask hides the padded keys from every real query, and the
    padded queries' outputs are dropped, so their gradients are 0 and
    add nothing to dk or dv."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    _, S, H, D = q.shape
    block = min(_LATENT_BLOCK, -(-S // 128) * 128)
    padded = -(-S // block) * block
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(D))).astype(q.dtype)

    def heads_major(x):
        x = jnp.pad(x, ((0, 0), (0, padded - S), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3)  # (B, H, S, D)

    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([splash.CausalMask((padded, padded))] * H),
        block_sizes=splash.BlockSizes(
            block_q=block, block_kv=block, block_kv_compute=block,
            block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
            block_q_dq=block, block_kv_dq=block,
        ),
        head_shards=1, q_seq_shards=1, interpret=interpret,
    )
    out = jax.vmap(kernel)(heads_major(q), heads_major(k), heads_major(v))
    return out.transpose(0, 2, 1, 3)[:, :S]
