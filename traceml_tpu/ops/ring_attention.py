"""Ring attention: causal attention over a sequence-sharded mesh axis.

Long-context training shards the SEQUENCE across chips (context
parallelism): each device holds a contiguous S/P slice of Q, K and V.
Full attention still needs every (q, k) pair, so K/V blocks rotate
around the ring via ``jax.lax.ppermute`` over ICI while each device
accumulates its Q block's output with an online softmax — attention at
S×P length for the memory of S, with communication overlapped
block-by-block instead of one giant all-gather.

Usage (inside ``shard_map`` over a mesh with a sequence axis)::

    out = ring_attention(q, k, v, axis_name="context")

where q,k,v are the LOCAL (B, S_local, H, D) shards, sequence-ordered by
mesh position along ``axis_name`` (device p holds positions
[p*S_local, (p+1)*S_local)).  Causality is enforced with global position
ids; blocks entirely in the future contribute nothing (numerically
masked — the rotation is static so every device does P block-steps).

The per-block kernel is the fused jnp path; the pallas flash kernel can
substitute per block for very large S_local.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


_NEG = -1e30


def _block_attend(q, k_blk, v_blk, q_offset, k_offset, scale):
    """Causally-masked score matrix for one (q block × kv block) pair.

    q: (B, Sq, H, D); k_blk: (B, Sk, H, D) → (B, H, Sq, Sk) f32 scores,
    masked by GLOBAL positions (future pairs set to a large negative).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    Sq, Sk = q.shape[1], k_blk.shape[1]
    q_ids = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
    k_ids = k_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
    mask = q_ids >= k_ids
    return jnp.where(mask[None, None, :, :], s, _NEG)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
) -> jnp.ndarray:
    """Causal ring attention over ``axis_name``; q,k,v: local (B,S,H,D)."""
    axis_size = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, S_loc, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q_offset = my_idx * S_loc

    m0 = jnp.full((B, H, S_loc, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, S_loc, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, S_loc, D), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(step, carry):
        m, l, acc, k_blk, v_blk = carry
        # after `step` rotations this device holds the block that
        # originated at ring position (my_idx − step) mod P
        src = jax.lax.rem(my_idx - step + axis_size, axis_size)
        s = _block_attend(q, k_blk, v_blk, q_offset, src * S_loc, scale)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)

        # rotate K/V one hop around the ring (overlappable with the next
        # block's compute by XLA's async collectives); the final
        # iteration skips the dead hop — P−1 rotations suffice
        def rotate(blks):
            return tuple(jax.lax.ppermute(b, axis_name, perm) for b in blks)

        k_blk, v_blk = jax.lax.cond(
            step < axis_size - 1, rotate, lambda blks: blks, (k_blk, v_blk)
        )
        return m_new, l, acc, k_blk, v_blk

    m, l, acc, _, _ = jax.lax.fori_loop(0, axis_size, body, (m0, l0, acc0, k, v))
    # causal rows always include self-attention → l > 0
    out = (acc / l).astype(q.dtype)  # (B, H, S_loc, D)
    return out.transpose(0, 2, 1, 3)


def make_ring_attention(mesh, axis_name: str = "context"):
    """Convenience: a jitted global-array ring attention over ``mesh``.

    Takes GLOBAL (B, S, H, D) arrays sequence-sharded over ``axis_name``
    and returns the globally-correct causal attention output with the
    same sharding.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)

    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name)

    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )
