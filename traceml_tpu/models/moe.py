"""Mixture-of-Experts layers.

``RoutedExperts`` / ``ExpertBlock``: the DeepSeek-V3 expert layer of a
``DecoderLM`` whose config sets ``moe`` (sigmoid top-k routing with a
selection-only correction bias, shared experts), told which experts it
holds, computing only their part, dropless; see ``RoutedExperts``.

``MoEFFN`` / ``MoEBlock``: a soft-routed expert FFN with expert
parallelism
(SURVEY §2.13 / driver mandate: the ``ep`` axis of tp/pp/dp/sp/ep —
the reference has no MoE; this is the TPU-native design for one).

Design — dense-dispatch MoE, XLA-first:

* routing and dispatch are ONE pair of einsums over a static expert
  dimension — no top-k scatter, no capacity overflow, no dynamic
  shapes.  Every expert sees every token, weighted by its gate
  probability (soft-MoE style).  For the small expert counts the test
  meshes carry this is FLOP-comparable to capacity-based dispatch and
  maps straight onto the MXU; the point here is the SHARDING pattern,
  which is identical to a capacity-based implementation's:
* the expert dimension of ``w_in (E, H, F)`` / ``w_out (E, F, H)`` is
  sharded over the mesh's ``expert`` axis.  Under GSPMD the dispatch
  einsum partitions by expert and the combine einsum inserts the
  reduce over the expert axis automatically — each chip computes only
  its local experts' contributions and the partial sums ride ICI.
* an auxiliary load-balance loss (squared-importance, the
  switch-transformer shape: Σ_e mean_gate_e² · E, minimized by uniform
  routing) keeps the router from collapsing onto one expert.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from traceml_tpu.models.transformer import MLP, ModelConfig, RMSNorm, attention_for


class _Router(nn.Module):
    """The gate: a float32 (hidden, n_experts) kernel and the
    correction bias that selection alone reads."""

    n_experts: int

    @nn.compact
    def __call__(self, h):
        kernel = self.param(
            "kernel", nn.initializers.normal(0.02), (h.shape[-1], self.n_experts), jnp.float32
        )
        bias = self.param(
            "e_score_correction_bias", nn.initializers.zeros, (self.n_experts,), jnp.float32
        )
        return kernel, bias


class _HeldExperts(nn.Module):
    """The SwiGLU weights of the experts this layer holds, expert first."""

    n: int
    ffn: int

    @nn.compact
    def __call__(self, hidden: int):
        init = nn.initializers.normal(0.02)
        return (
            self.param("w_gate", init, (self.n, hidden, self.ffn), jnp.float32),
            self.param("w_up", init, (self.n, hidden, self.ffn), jnp.float32),
            self.param("w_down", init, (self.n, self.ffn, hidden), jnp.float32),
        )


class RoutedExperts(nn.Module):
    """DeepSeek-V3 routed experts, as one chip of an expert-parallel
    group runs them (``cfg.moe``: ``ExpertConfig``).

    Routing covers all ``n_experts``: float32 logits and sigmoid scores
    (``moe_route``); ``top_k`` chosen on score + correction bias, which
    selection alone reads; their weights are the unbiased scores,
    normalised over the chosen and scaled by ``routed_scale``.  Every
    (token, held expert) pair is computed, none dropped: the pairs are
    sorted by expert and run through grouped matmuls over the held
    experts (``grouped_matmul``, ``moe_experts``), then weighted and
    summed per token (``moe_combine``).  The shared SwiGLU sees every
    token (``moe_shared``).  Pairs of experts held elsewhere add
    nothing here: their chips add them.

    Returns ``(out, aux, load)``: the sequence-wise balance loss
    ``aux_alpha * sum_i f_i P_i`` (DeepSeek-V3, over all experts, mean
    over sequences) and the tokens per expert, int32 ``(n_experts,)``.
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, h):
        cfg, m = self.cfg, self.cfg.moe
        B, S, H = h.shape
        T, k, E, n = B * S, m.top_k, m.n_experts, m.n_held
        with jax.named_scope("moe_route"):
            kernel, bias = _Router(E, name="gate")(h)
            flat = h.reshape(T, H)
            scores = jax.nn.sigmoid(
                jnp.dot(flat.astype(jnp.float32), kernel, precision=jax.lax.Precision.HIGHEST)
            )
            idx, weight = route(scores, bias, k, m.routed_scale)
            chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.int32), axis=1)  # (T, E)
            load = jnp.sum(chosen, axis=0)
            aux = m.aux_alpha * _balance(chosen, scores, B, S, k)
            local = (idx - m.expert_offset).reshape(-1)
            held = (local >= 0) & (local < n)
            group = jnp.where(held, local, n)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.sum(jax.nn.one_hot(group, n, dtype=jnp.int32), axis=0)
            rows = flat[order // k]
        with jax.named_scope("moe_experts"):
            w_gate, w_up, w_down = _HeldExperts(n, m.expert_ffn, name="experts")(H)
            # rows past the held pairs belong to no group, and a grouped
            # matmul leaves them unwritten: zero them after every product
            # (and before, so their gradient is zero too)
            valid = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]

            def grouped(x, w):
                out = grouped_matmul(jnp.where(valid, x, 0), w.astype(cfg.dtype), sizes)
                return jnp.where(valid, out, 0)

            y = grouped(jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up), w_down)
        with jax.named_scope("moe_combine"):
            back = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
            w_held = jnp.where(held, weight.reshape(-1), 0.0).astype(cfg.dtype)
            out = jnp.einsum("tk,tkh->th", w_held.reshape(T, k), y[back].reshape(T, k, H))
        with jax.named_scope("moe_shared"):
            shared = MLP(cfg, width=m.n_shared * m.expert_ffn, name="shared")(h)
        return out.reshape(B, S, H) + shared, aux, load


def grouped_matmul(x, w, sizes):
    """Rows of ``x`` (m, k), sorted by group, times their group's ``w``
    (groups, k, n): the first ``sizes[0]`` rows by ``w[0]`` and so on;
    rows past ``sum(sizes)`` are left undefined.  On a TPU the Pallas
    megablox kernels (``gmm`` forward, ``gmm``/``tgmm`` backward) visit
    only the row tiles that hold a group; elsewhere ``jax.lax.ragged_dot``."""
    return jax.lax.platform_dependent(x, w, sizes, tpu=_gmm, default=jax.lax.ragged_dot)


def _tile(d: int) -> int:
    return next((t for t in (512, 256, 128) if d % t == 0), d)


def _gmm(x, w, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = x.shape[0]
    pad = -m % 512
    out = gmm(
        jnp.pad(x, ((0, pad), (0, 0))), w, sizes, preferred_element_type=x.dtype,
        tiling=lambda mm, kk, nn_: (512, _tile(kk), _tile(nn_)),
    )
    return out[:m]


def route(scores, bias, k: int, scale: float):
    """The ``k`` experts with the largest score + ``bias`` and their
    weights: their unbiased scores normalised over the chosen, times
    ``scale``.  ``scores`` (tokens, experts) → (idx, weight), (tokens, k)."""
    _, idx = jax.lax.top_k(scores + bias, k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20) * scale


def _balance(chosen, scores, B: int, S: int, k: int):
    """``sum_i f_i P_i`` per sequence, mean over sequences: ``f_i`` is
    ``n_experts / (k * S)`` times the sequence's tokens that chose
    expert ``i``, ``P_i`` the sequence's mean of expert ``i``'s score
    normalised over all experts."""
    E = scores.shape[-1]
    f = jnp.sum(chosen.reshape(B, S, E), axis=1).astype(jnp.float32) * (E / (k * S))
    p = jnp.mean((scores / jnp.sum(scores, axis=-1, keepdims=True)).reshape(B, S, E), axis=1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


class ExpertBlock(nn.Module):
    """A decoder layer whose feed-forward is ``RoutedExperts``; returns
    ``(x, aux, load)``."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        x = x + attention_for(cfg)(cfg, name="attn")(
            RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="attn_norm")(x), positions
        )
        out, aux, load = RoutedExperts(cfg, name="mlp")(
            RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="mlp_norm")(x)
        )
        return x + out, aux, load


class MoEFFN(nn.Module):
    """Expert-parallel feed-forward block."""

    n_experts: int
    hidden: int
    ffn_hidden: int

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: (batch, seq, hidden) → (out, aux_loss)."""
        e, h, f = self.n_experts, self.hidden, self.ffn_hidden
        router = self.param(
            "router", nn.initializers.normal(0.02), (h, e), jnp.float32
        )
        w_in = self.param(
            "w_in", nn.initializers.normal(0.02), (e, h, f), jnp.float32
        )
        w_out = self.param(
            "w_out", nn.initializers.normal(0.02), (e, f, h), jnp.float32
        )
        # router probabilities per token
        gates = jax.nn.softmax(
            jnp.einsum("bsh,he->bse", x, router), axis=-1
        )  # (B, S, E)
        # dense dispatch: every expert computes, gated combine reduces
        # over the expert dim (GSPMD turns this into a psum over the
        # 'expert' mesh axis when w_in/w_out are expert-sharded)
        inner = jax.nn.silu(jnp.einsum("bsh,ehf->ebsf", x, w_in))
        expert_out = jnp.einsum("ebsf,efh->ebsh", inner, w_out)
        out = jnp.einsum("bse,ebsh->bsh", gates, expert_out)
        # load-balance aux: squared mean gate per expert (switch-style
        # importance loss; uniform routing minimizes it)
        importance = gates.mean(axis=(0, 1))  # (E,)
        aux = (importance**2).sum() * e
        return out, aux


class MoEBlock(nn.Module):
    """Pre-norm residual block around the expert FFN."""

    n_experts: int
    hidden: int
    ffn_hidden: int

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        scale = self.param("norm_scale", nn.initializers.ones, (self.hidden,))
        normed = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6
        ) * scale
        out, aux = MoEFFN(
            n_experts=self.n_experts,
            hidden=self.hidden,
            ffn_hidden=self.ffn_hidden,
        )(normed)
        return x + out, aux


def moe_param_shardings(params, mesh, expert_axis: str = "expert") -> Any:
    """Expert-parallel sharding specs: expert dim over the expert axis,
    inner dims over fsdp/tensor where they exist."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    have_fsdp = "fsdp" in mesh.axis_names
    fsdp = "fsdp" if have_fsdp else None

    def spec_for(path: Tuple[str, ...], leaf) -> Any:
        if "w_in" in path or "w_out" in path:
            return NamedSharding(mesh, P(expert_axis, fsdp, None))
        if "router" in path:
            return NamedSharding(mesh, P(fsdp, None))
        return NamedSharding(mesh, P())

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        keys = tuple(
            getattr(k, "key", getattr(k, "name", str(k))) for k in path
        )
        specs.append(spec_for(keys, leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)


def make_moe_train_step(
    model: MoEBlock, learning_rate: float = 1e-3, aux_weight: float = 0.01
):
    """(params, opt_state, x, y) → (params, opt_state, metrics) — simple
    regression objective over the block, aux-loss regularized."""
    import optax

    tx = optax.adam(learning_rate)

    def loss_fn(params, x, y):
        out, aux = model.apply({"params": params}, x)
        mse = jnp.mean((out - y) ** 2)
        return mse + aux_weight * aux, (mse, aux)

    def train_step(params, opt_state, x, y):
        (loss, (mse, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, y
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "mse": mse, "aux": aux}

    def init(rng, x):
        params = model.init(rng, x)["params"]
        return params, tx.init(params)

    return init, train_step


def init_expert_parallel(
    model: MoEBlock,
    mesh,
    rng: Optional[jax.Array] = None,
    sample: Optional[jnp.ndarray] = None,
    expert_axis: str = "expert",
) -> Dict[str, Any]:
    """Initialize params and place them expert-sharded over the mesh."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if sample is None:
        sample = jnp.zeros((2, 8, model.hidden), jnp.float32)
    params = model.init(rng, sample)["params"]
    shardings = moe_param_shardings(params, mesh, expert_axis)
    params = jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(leaf, s), params, shardings
    )
    return {"params": params, "shardings": shardings}
