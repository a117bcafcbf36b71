"""Pipeline parallelism over a mesh axis
(SURVEY §2.13 / driver mandate: the ``pp`` axis of tp/pp/dp/sp/ep —
the reference's trainer has no pipeline engine; this is the TPU-native
design for one).

GPipe-style schedule as ONE compiled program:

* per-stage parameters are stacked on a leading stage dimension and
  sharded over the mesh's ``stage`` axis (each chip holds its stage
  only);
* inside ``shard_map`` every stage runs the same ``lax.scan`` over
  ``n_microbatches + n_stages − 1`` ticks; activations move stage→stage
  with a single ``lax.ppermute`` per tick (point-to-point over ICI);
* stage 0 injects microbatch ``t`` at tick ``t``; the last stage's
  output of microbatch ``m`` appears at tick ``m + S − 1`` and is
  collected with a static mask — no data-dependent control flow, fully
  jittable;
* ``ppermute`` has a well-defined transpose (the reverse permutation),
  so ``jax.grad`` differentiates straight through the schedule — the
  backward pipeline needs no hand-written schedule.

This runs identically on the 8-device CPU CI mesh and a real slice.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage_params: list) -> Any:
    """[stage0_tree, stage1_tree, …] → one tree with a leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params
    )


def stage_param_shardings(stacked_params, mesh, axis: str = "stage") -> Any:
    """Leading (stage) dim over the pipeline axis, rest replicated within
    the stage group (compose with fsdp/tensor specs for real models)."""

    def spec(leaf):
        ndim = getattr(leaf, "ndim", 1)
        return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))

    return jax.tree_util.tree_map(spec, stacked_params)


def make_pipeline_fn(
    stage_apply: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh,
    n_microbatches: int,
    axis: str = "stage",
):
    """Build the pipelined forward.

    ``stage_apply(stage_params, x) -> x`` is one stage's computation
    (stage_params WITHOUT the leading stage dim).  Returns
    ``pipeline_fn(stacked_params, microbatches)`` with
    ``microbatches: (n_micro, mb, …)`` → ``(n_micro, mb, …)`` outputs
    (valid on every chip after the closing all-gather of the last
    stage's buffer).
    """
    n_stages = mesh.shape[axis]
    total_ticks = n_microbatches + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def _check_stage_dim(stacked_params) -> None:
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != n_stages:
                raise ValueError(
                    f"stacked stage dim {leaf.shape[0]} != mesh {axis} "
                    f"size {n_stages} — each chip must hold exactly one "
                    "stage (a mismatch would silently drop stages)"
                )

    def per_stage(stacked_local, micro_local, stage_index):
        # stacked_local leaves: (1, …) — this chip's stage; drop the dim
        params = jax.tree_util.tree_map(lambda l: l[0], stacked_local)
        mb_shape = micro_local.shape[1:]
        outputs = jnp.zeros((n_microbatches,) + mb_shape, micro_local.dtype)
        inbuf = jnp.zeros(mb_shape, micro_local.dtype)

        def tick(carry, t):
            inbuf, outputs = carry
            # stage 0 injects microbatch t (static gather with clamp;
            # ticks ≥ n_micro re-inject the last microbatch into the
            # bubble — masked out at collection)
            mb_idx = jnp.minimum(t, n_microbatches - 1)
            injected = jax.lax.dynamic_index_in_dim(
                micro_local, mb_idx, axis=0, keepdims=False
            )
            x = jnp.where(stage_index == 0, injected, inbuf)
            y = stage_apply(params, x)
            # collect on the last stage: microbatch m completes at tick
            # m + S − 1
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            valid = (stage_index == n_stages - 1) & (t >= n_stages - 1)
            current = jax.lax.dynamic_index_in_dim(
                outputs, out_idx, axis=0, keepdims=False
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(valid, y, current),
                out_idx,
                axis=0,
            )
            # hand activations to the next stage (ring permute; the
            # wrap-around edge is ignored by stage 0's injection select)
            inbuf = jax.lax.ppermute(y, axis, perm=fwd_perm)
            return (inbuf, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (inbuf, outputs), jnp.arange(total_ticks)
        )
        # every chip returns the LAST stage's collected outputs: psum of
        # stage-masked buffers replicates them across the pipeline
        mask = (stage_index == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    def pipeline_fn(stacked_params, microbatches):
        _check_stage_dim(stacked_params)
        if microbatches.shape[0] != n_microbatches:
            raise ValueError(
                f"got {microbatches.shape[0]} microbatches, schedule was "
                f"built for {n_microbatches} — the clamp in the injection "
                "gather would silently duplicate the last microbatch"
            )

        def wrapped(stacked_local, micro_local):
            stage_index = jax.lax.axis_index(axis)
            return per_stage(stacked_local, micro_local, stage_index)

        n_leaf_specs = jax.tree_util.tree_map(
            lambda _: P(axis), stacked_params
        )
        return jax.shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(n_leaf_specs, P()),
            out_specs=P(),
            check_vma=False,
        )(stacked_params, microbatches)

    return pipeline_fn


def make_pipeline_train_step(
    stage_apply: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh,
    n_microbatches: int,
    axis: str = "stage",
    learning_rate: float = 1e-2,
) -> Tuple[Callable, Callable]:
    """(init, train_step) for a pipelined regression objective —
    gradients flow through the schedule via ppermute's transpose."""
    import optax

    tx = optax.sgd(learning_rate)
    pipeline_fn = make_pipeline_fn(stage_apply, mesh, n_microbatches, axis)

    def loss_fn(stacked_params, micro_x, micro_y):
        out = pipeline_fn(stacked_params, micro_x)
        return jnp.mean((out - micro_y) ** 2)

    def train_step(stacked_params, opt_state, micro_x, micro_y):
        loss, grads = jax.value_and_grad(loss_fn)(
            stacked_params, micro_x, micro_y
        )
        updates, opt_state = tx.update(grads, opt_state, stacked_params)
        stacked_params = optax.apply_updates(stacked_params, updates)
        return stacked_params, opt_state, {"loss": loss}

    def init(stacked_params):
        return tx.init(stacked_params)

    return init, train_step


def linear_stage_apply(params: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    """Reference stage: y = tanh(x @ w + b) — used by tests/dryrun."""
    return jnp.tanh(x @ params["w"] + params["b"])


def init_linear_stages(
    n_stages: int, width: int, rng: jax.Array
) -> list:
    keys = jax.random.split(rng, n_stages)
    return [
        {
            "w": jax.random.normal(k, (width, width), jnp.float32) * 0.3,
            "b": jnp.zeros((width,), jnp.float32),
        }
        for k in keys
    ]
