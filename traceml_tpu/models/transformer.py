"""Flagship decoder-only LM (llama-style), TPU-first.

Design notes (why it looks the way it does):

* **MXU-friendly**: every hot op is a large batched matmul in bf16;
  static shapes everywhere; attention is einsum-based so XLA fuses the
  softmax chain and tiles onto the systolic array.
* **Sharding-native**: `param_shardings` maps every parameter to a
  `PartitionSpec` over the ("data","fsdp","tensor") mesh axes — embed /
  ffn / head dims shard over "tensor", everything shards over "fsdp"
  (ZeRO-style) on its largest remaining dim; XLA inserts the
  all-gathers/reduce-scatters (GSPMD), we never hand-roll collectives.
* **Remat**: optional `jax.checkpoint` over each block trades FLOPs for
  HBM, the standard long-context lever.
* **GQA + RoPE + RMSNorm + SwiGLU**: the contemporary decoder recipe,
  kept minimal and readable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_mult: float = 2.6667  # SwiGLU hidden = mult * hidden (rounded)
    max_seq_len: int = 1024
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # sequence/context parallelism for long sequences: "dense" runs the
    # fused jnp path and lets GSPMD partition it; "ring" / "ulysses"
    # wrap the matching ops/ kernel in shard_map over ``context_axis``
    # of ``mesh`` (set both), sharding attention BY SEQUENCE with exact
    # global causality — see ops/ring_attention.py /
    # ops/ulysses_attention.py for the trade-offs
    attention_impl: str = "dense"
    context_axis: Any = None     # mesh axis name, e.g. "context"
    mesh: Any = None             # jax.sharding.Mesh (shard_map needs it)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    @property
    def ffn_hidden(self) -> int:
        # round to a multiple of 128 for MXU tiling
        h = int(self.hidden * self.ffn_mult)
        return max(128, (h + 127) // 128 * 128)

    @classmethod
    def tiny(cls) -> "ModelConfig":
        return cls(vocab_size=256, hidden=128, n_layers=2, n_heads=4,
                   n_kv_heads=2, max_seq_len=128)

    @classmethod
    def flagship(cls) -> "ModelConfig":
        """The one-chip TPU width of bench.py and chip_smoke.py: ~175M
        params, head_dim 64; trained at batch 16 x 512 tokens."""
        return cls(vocab_size=16384, hidden=1024, n_layers=12, n_heads=16,
                   n_kv_heads=8, max_seq_len=512)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embeddings over the last dim of x: (..., seq, heads, head_dim)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = jnp.exp(
        -jnp.log(theta) * (2.0 * jnp.arange(half, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def seq_parallel_spec(cfg: "ModelConfig", batch_size: Optional[int] = None):
    """PartitionSpec for (B, S, heads, hd) q/k/v under seq parallelism.

    Derived from the mesh instead of hardcoded so no axis is silently
    replicated: batch shards over whichever of ``mesh.BATCH_AXES`` the
    mesh actually has (without this, every data-parallel group would
    all-gather the global batch at the shard_map boundary and
    redundantly compute full-batch attention — advisor r4); heads shard
    over "tensor" when the mesh has one and the head count divides,
    matching the column-parallel wq/wk/wv output layout so the
    shard_map boundary introduces no tensor-axis all-gather either.
    Attention is independent per batch element and per head, so both
    shardings are exact.

    Fallbacks keep previously-valid configs running (review r5): a
    ``batch_size`` not divisible by the batch axes' product (e.g. B=1
    eval on a training mesh) replicates batch as before, and heads stay
    unsharded when the ulysses all-to-all could not redistribute the
    per-shard head count over the context axis.
    """
    from jax.sharding import PartitionSpec as P

    from traceml_tpu.parallel.mesh import BATCH_AXES

    mesh_axes = tuple(cfg.mesh.axis_names)
    batch_axes = tuple(
        ax for ax in BATCH_AXES
        if ax in mesh_axes and ax != cfg.context_axis
    )
    if batch_axes and batch_size is not None:
        # keep the LARGEST dividing subset rather than all-or-nothing
        # (mesh {data:4, fsdp:2} with B=4 still shards over 'data') —
        # exhaustive over the ≤2 batch axes, because a greedy in-order
        # scan lets an earlier small axis block a later larger one
        # (mesh {data:2, fsdp:4} with B=4 must pick fsdp, not data)
        best, best_dp = (), 1
        for mask in range(1, 1 << len(batch_axes)):
            subset = tuple(
                ax for i, ax in enumerate(batch_axes) if mask >> i & 1
            )
            dp = 1
            for ax in subset:
                dp *= cfg.mesh.shape[ax]
            if batch_size % dp == 0 and (
                dp > best_dp or (dp == best_dp and len(subset) > len(best))
            ):
                best, best_dp = subset, dp
        batch_axes = best
    heads_axis = None
    if (
        "tensor" in mesh_axes
        and cfg.context_axis != "tensor"
        and cfg.n_heads % cfg.mesh.shape["tensor"] == 0
    ):
        local_heads = cfg.n_heads // cfg.mesh.shape["tensor"]
        if (
            cfg.attention_impl != "ulysses"
            or local_heads % cfg.mesh.shape[cfg.context_axis] == 0
        ):
            heads_axis = "tensor"
    return P(batch_axes or None, cfg.context_axis, heads_axis, None)


class Attention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        B, S, H = x.shape
        hd = cfg.head_dim
        q = nn.Dense(cfg.n_heads * hd, use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wq")(x)
        k = nn.Dense(cfg.n_kv_heads * hd, use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wk")(x)
        v = nn.Dense(cfg.n_kv_heads * hd, use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wv")(x)
        q = q.reshape(B, S, cfg.n_heads, hd)
        k = k.reshape(B, S, cfg.n_kv_heads, hd)
        v = v.reshape(B, S, cfg.n_kv_heads, hd)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        # GQA: repeat kv heads up to n_heads
        group = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        out = self._attend(q, k, v)  # (B, S, heads, hd)
        out = out.reshape(B, S, cfg.n_heads * hd)
        return nn.Dense(H, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="wo")(out)

    def _attend(self, q, k, v):
        """Attention kernel dispatch per cfg.attention_impl.

        "dense": the fused jnp path — GSPMD partitions it (the pallas
        flash kernel substitutes on TPU).  "ring"/"ulysses": the op
        runs inside shard_map over cfg.context_axis with q/k/v sharded
        BY SEQUENCE (and by batch over the data-parallel axes — see
        seq_parallel_spec); RoPE was already applied on global
        positions, and both ops enforce global causality themselves.
        """
        cfg = self.cfg
        if cfg.attention_impl == "dense":
            from traceml_tpu.ops.attention import causal_attention

            return causal_attention(q, k, v)
        if cfg.attention_impl == "ring":
            from traceml_tpu.ops.ring_attention import ring_attention as op
        elif cfg.attention_impl == "ulysses":
            from traceml_tpu.ops.ulysses_attention import (
                ulysses_attention as op,
            )
        else:
            raise ValueError(
                f"unknown attention_impl {cfg.attention_impl!r} "
                "(dense | ring | ulysses)"
            )
        if cfg.mesh is None or cfg.context_axis is None:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r} requires cfg.mesh "
                "and cfg.context_axis (sequence-parallel attention runs "
                "inside shard_map); use attention_impl='dense' for "
                "single-mesh GSPMD partitioning"
            )
        spec = seq_parallel_spec(cfg, batch_size=q.shape[0])
        return jax.shard_map(
            lambda a, b, c: op(a, b, c, cfg.context_axis),
            mesh=cfg.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)


class MLP(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = nn.Dense(cfg.ffn_hidden, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="w_gate")(x)
        up = nn.Dense(cfg.ffn_hidden, use_bias=False, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="w_up")(x)
        return nn.Dense(cfg.hidden, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="w_down")(
            nn.silu(gate) * up
        )


class Block(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            RMSNorm(dtype=cfg.dtype, name="attn_norm")(x), positions
        )
        x = x + MLP(cfg, name="mlp")(
            RMSNorm(dtype=cfg.dtype, name="mlp_norm")(x)
        )
        return x


class DecoderLM(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        B, S = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=())
        for i in range(cfg.n_layers):
            x = block(cfg, name=f"layer_{i}")(x, positions)
        x = RMSNorm(dtype=cfg.dtype, name="final_norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          param_dtype=cfg.param_dtype, name="lm_head")(x)
        return logits


# -- sharding ------------------------------------------------------------


def param_shardings(params, mesh) -> Any:
    """Map every param leaf to a NamedSharding over (fsdp, tensor).

    Rules (scaling-book style):
    * 2D kernels: shard dim 0 over "fsdp"; dim 1 over "tensor" for
      column-parallel layers (wq/wk/wv/w_gate/w_up/lm_head) and dim 0
      over "tensor" + dim 1 over "fsdp" for row-parallel (wo/w_down).
    * embeddings: vocab over "fsdp", hidden over "tensor".
    * 1D scales: replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    col_parallel = ("wq", "wk", "wv", "w_gate", "w_up", "lm_head")
    row_parallel = ("wo", "w_down")

    def spec_for(path: Tuple[str, ...], leaf) -> Any:
        ndim = getattr(leaf, "ndim", 0)
        names = [p for p in path]
        if ndim <= 1:
            return NamedSharding(mesh, P())
        owner = next((n for n in names if n in col_parallel + row_parallel), None)
        if "embed" in names and ndim == 2:
            return NamedSharding(mesh, P("fsdp", "tensor"))
        if owner in col_parallel:
            return NamedSharding(mesh, P("fsdp", "tensor"))
        if owner in row_parallel:
            return NamedSharding(mesh, P("tensor", "fsdp"))
        return NamedSharding(mesh, P("fsdp"))

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        keys = tuple(
            getattr(k, "key", getattr(k, "name", str(k))) for k in path
        )
        specs.append(spec_for(keys, leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)


# -- training ------------------------------------------------------------


def loss_fn(params, apply_fn, tokens) -> jnp.ndarray:
    """Next-token cross entropy (inputs=tokens[:, :-1], targets=[:, 1:])."""
    logits = apply_fn({"params": params}, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def init_train_state(
    cfg: ModelConfig,
    rng: Optional[jax.Array] = None,
    learning_rate: float = 3e-4,
    mesh=None,
) -> Tuple[Any, Dict[str, Any], Any]:
    """Returns (model, state, optimizer).  state = {params, opt_state, step}.

    With a mesh, params are initialised directly in their
    `param_shardings` layout (each device builds only its shard), and
    the optimizer state inherits it.
    """
    import optax

    model = DecoderLM(cfg)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    tokens = jnp.zeros((2, min(16, cfg.max_seq_len)), dtype=jnp.int32)

    def init(key):
        return model.init(key, tokens)["params"]

    out_shardings = None
    if mesh is not None:
        out_shardings = param_shardings(jax.eval_shape(init, rng), mesh)
    params = jax.jit(init, out_shardings=out_shardings)(rng)
    tx = optax.adamw(learning_rate, weight_decay=0.01)
    opt_state = tx.init(params)
    state = {"params": params, "opt_state": opt_state, "step": jnp.zeros((), jnp.int32)}
    if mesh is not None:
        # every leaf in the layout make_train_step(mesh=mesh) returns it
        # in, so the step's output state re-enters without a recompile
        state = jax.device_put(state, param_shardings(state, mesh))
    return model, state, tx


def make_train_step(model: DecoderLM, tx, mesh=None) -> Any:
    """The (un-jitted) functional train step: (state, tokens) → (state, metrics).

    Callers wrap it with ``traceml_tpu.wrap_step_fn`` (tracing + AOT
    compile attribution) or plain ``jax.jit``; donate state for in-place
    updates.  The loss runs in a ``forward`` name scope, so its
    operations carry ``forward`` and their gradients
    ``transpose(jvp(forward))`` in their names; the update runs in
    ``optimizer``.  Scopes are metadata: the compiled program is the
    same with or without them.  With the ``mesh`` the state was initialised on, the new
    state is held to ``param_shardings``, the layout it came in with:
    left to itself the compiler may lay out optimizer leaves otherwise,
    and the second step would compile again.
    """
    import optax

    def forward(params, apply, tokens):
        with jax.named_scope("forward"):
            return loss_fn(params, apply, tokens)

    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(forward)(
            state["params"], model.apply, tokens
        )
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(
                grads, state["opt_state"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        if mesh is not None:
            new_state = jax.lax.with_sharding_constraint(
                new_state, param_shardings(new_state, mesh)
            )
        return new_state, {"loss": loss}

    return train_step
