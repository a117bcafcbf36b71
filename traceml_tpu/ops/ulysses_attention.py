"""Ulysses-style sequence parallelism: all-to-all head scattering.

The second canonical long-context strategy (alongside
``ops/ring_attention.py``): instead of rotating K/V blocks around a
ring, ONE ``all_to_all`` re-shards the activations from
sequence-sharded to head-sharded, every device runs ordinary full
attention for its head slice, and a second ``all_to_all`` restores the
sequence sharding (DeepSpeed-Ulysses recipe; public pattern).

Trade-offs vs ring attention on TPU:

* **Communication**: 2 all-to-alls of the full activations per layer
  (O(S·H·D/P) bytes each, one shot over ICI) vs P−1 ppermute hops of
  K/V.  All-to-all rides the ICI fabric well and needs no per-block
  software pipeline, but cannot overlap with attention math the way
  the ring's hop-per-block does.
* **Memory**: full sequence length is materialized locally for the
  head slice → the S² score matrix exists per head slice.  Ring keeps
  O(S_local²) blocks only.  Ulysses therefore suits moderate S with
  many heads; ring suits extreme S.
* **Constraint**: the head count must divide by the axis size
  (heads-per-device = H/P); ring has no such constraint.

Usage inside ``shard_map`` over a mesh with a sequence axis::

    out = ulysses_attention(q, k, v, axis_name="context")

with q,k,v the LOCAL (B, S_local, H, D) shards, sequence-ordered by
mesh position (same contract as ring_attention).  Causality is exact:
after the first all-to-all each device sees the FULL sequence, so a
standard causal mask applies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _full_causal_attention(q, k, v):
    """Ordinary causal attention on full-sequence local tensors.

    q,k,v: (B, S, h, D) → (B, S, h, D); f32 softmax accumulation.
    After the head-scatter this is PLAIN causal self-attention, so the
    pallas flash kernel applies unchanged on TPU —
    ``ops.attention.causal_attention`` dispatches to it (with the jnp
    reference as the fail-open path) exactly as in the dense model.
    """
    from traceml_tpu.ops.attention import causal_attention

    return causal_attention(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
) -> jnp.ndarray:
    """Causal attention over a sequence-sharded axis via all-to-all.

    q,k,v: local (B, S_local, H, D); H must be divisible by the axis
    size.  Returns the local (B, S_local, H, D) output shard.
    """
    P = jax.lax.axis_size(axis_name)
    B, S_loc, H, D = q.shape
    if H % P != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({H}) divisible by the "
            f"sequence-axis size ({P}); use ring_attention otherwise"
        )

    def seq_to_heads(x):
        # (B, S_loc, H, D) → (B, P·S_loc, H/P, D): trade the sequence
        # shard for a head shard.  split_axis=2 (heads), concat_axis=1
        # (sequence); tiled=True splits/joins in place rather than
        # adding a mesh dimension.
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        # inverse: (B, P·S_loc, H/P, D) → (B, S_loc, H, D)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    q_full = seq_to_heads(q)
    k_full = seq_to_heads(k)
    v_full = seq_to_heads(v)
    out_full = _full_causal_attention(q_full, k_full, v_full)
    return heads_to_seq(out_full).astype(q.dtype)


def make_ulysses_attention(mesh, axis_name: str = "context"):
    """Convenience: a jitted global-array Ulysses attention over ``mesh``.

    Same contract as ``make_ring_attention``: GLOBAL (B, S, H, D)
    arrays sequence-sharded over ``axis_name`` in and out.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)

    def fn(q, k, v):
        return ulysses_attention(q, k, v, axis_name)

    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )
