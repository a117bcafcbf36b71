"""Plain float32 reference of a training step, around a family's model.

The family's ``reference.py`` (``families/<family>/``) gives the model:
its checkpoint layout (``param_shapes``), the layout it computes on
(``pack`` and ``unpack``: stacked layers to scan, for one), its
``forward`` to logits and any extra loss, the dimension of each packed
leaf the program's ``fsdp`` axis splits (``fsdp_dim``) and the FLOPs of
a step. This module adds what every family shares: next-token cross
entropy, AdamW as Loshchilov & Hutter with Adam's bias correction, the
leaf norms that ``check.py`` compares (of the checkpoint layout's
leaves), and the split of state and batch over devices. Every matmul
runs in float32 at ``Precision.HIGHEST``. It imports nothing of
``traceml_tpu``; weights are made by ``weights.init_params`` from the
seed, in the checkpoint layout, and packed.

``variant`` puts a lower-precision or faulty copy in the program's
place, for the control and the planted faults:

* ``f32``: the reference itself;
* ``fp8``: every matmul on per-tensor-scaled float8 operands (e4m3
  forward, e5m2 cotangents), the precision below the program's bf16;
* ``half_batch``: the loss is the mean over half of the tokens only;
* ``labels_shifted``: each target token is its neighbour's;
* ``no_exchange``: each half of a weight, split along the dimension the
  program's ``fsdp`` axis splits, gets the gradient of its own half of
  the batch, as if the reduce-scatter between chips were left out.

Memory: each step is its own jitted call with the state donated, so one
state is live; the change's norms make each start leaf again inside its
own reduction; on several devices every leaf and the batch are split
over them. So a 4-chip cell's reference fits beside nothing else.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.weights import init_params, start_leaf

HIGHEST = jax.lax.Precision.HIGHEST
VARIANTS = ("f32", "fp8", "half_batch", "labels_shifted", "no_exchange")


def leaf_paths(tree) -> List[str]:
    """``a/b/c`` for each leaf, in flatten order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [_path(path) for path, _ in flat]


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in keys)


def leaf_norms(tree) -> jnp.ndarray:
    """Traceable: the float32 2-norm of each leaf, in flatten order."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(tree)
    ])


# -- matmuls -------------------------------------------------------------


def _quant(x, dtype, fmax):
    scale = jnp.max(jnp.abs(x)) / fmax
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _q43(x):
    return _quant(x, jnp.float8_e4m3fn, 448.0)


def _q52(x):
    return _quant(x, jnp.float8_e5m2, 57344.0)


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return _ein(spec, _q43(a), _q43(b))


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _q43(a), _q43(b)
    return _ein(spec, qa, qb), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(functools.partial(_ein, spec), qa, qb)
    return vjp(_q52(g))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


# -- loss and update -----------------------------------------------------


def loss_fn(params, tokens, family, cfg: dict, variant: str = "f32"):
    """Mean next-token cross entropy of ``tokens`` (B, S), plus the
    family's extra loss; ``params`` packed."""
    ein = _ein_fp8 if variant == "fp8" else _ein
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if variant == "labels_shifted":
        targets = jnp.roll(targets, 1, axis=1)
    logits, extra = family.forward(params, inputs, cfg, ein)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if variant == "half_batch":
        B, L = nll.shape
        nll = nll[: B // 2] if B >= 2 else nll[:, : L // 2]
    return nll.mean() + extra


def _no_exchange_grads(params, tokens, family, cfg):
    """Each fsdp half of a weight takes its own half-batch's gradient."""
    half = tokens.shape[0] // 2
    loss_a, g_a = jax.value_and_grad(loss_fn)(params, tokens[:half], family, cfg)
    loss_b, g_b = jax.value_and_grad(loss_fn)(params, tokens[half:], family, cfg)

    def pick(path, a, b):
        dim = family.fsdp_dim(_path(path), a.shape)
        if dim is None:
            return a
        n = a.shape[dim] // 2
        return jnp.concatenate(
            [jax.lax.slice_in_dim(a, 0, n, axis=dim),
             jax.lax.slice_in_dim(b, n, a.shape[dim], axis=dim)],
            axis=dim,
        )

    return 0.5 * (loss_a + loss_b), jax.tree_util.tree_map_with_path(pick, g_a, g_b)


def adamw(p, g, mu, nu, t, opt: dict):
    """One AdamW update at step ``t`` (1-based)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t

    def upd(w, m, n):
        return w - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + wd * w)

    return jax.tree.map(upd, p, mu, nu), mu, nu


def _shardings(family, cfg, shapes, devices):
    """Each checkpoint leaf split over all devices on its first divisible
    dim, packed as the family packs it (the split follows each leaf into
    the packed layout), and the batch on its rows."""
    mesh = Mesh(np.asarray(devices), ("r",))
    n = len(devices)

    def spec(s):
        for d in range(len(s.shape)):
            if s.shape[d] % n == 0:
                return NamedSharding(mesh, P(*([None] * d), "r"))
        return NamedSharding(mesh, P())

    pack = jax.jit(lambda p: family.pack(p, cfg), in_shardings=(jax.tree.map(spec, shapes),))
    return pack.lower(shapes).compile().output_shardings, NamedSharding(mesh, P("r"))


@functools.lru_cache(maxsize=None)
def _programs(family, cfg_json, opt_items, init_std, variant, devices):
    """The jitted init, step and change-norm programs for one variant."""
    cfg, opt = json.loads(cfg_json), dict(opt_items)
    shapes = family.param_shapes(cfg)
    p_sh, tok_sh = _shardings(family, cfg, shapes, devices) if len(devices) > 1 else (None, None)

    def init(key):
        p = family.pack(init_params(key, shapes, init_std), cfg)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, jax.tree.map(jnp.zeros_like, p)

    def step(p, mu, nu, tokens, t):
        if variant == "no_exchange":
            loss, g = _no_exchange_grads(p, tokens, family, cfg)
        else:
            loss, g = jax.value_and_grad(loss_fn)(p, tokens, family, cfg, variant)
        p, mu, nu = adamw(p, g, mu, nu, t, opt)
        return p, mu, nu, loss, leaf_norms(family.unpack(g, cfg))

    def change_norms(p, key):
        """Per checkpoint leaf, the norm of its change from the seeded
        start, each start leaf made again inside its own reduction."""
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x - start_leaf(key, i, x.shape, init_std))))
            for i, x in enumerate(jax.tree_util.tree_leaves(family.unpack(p, cfg)))
        ])

    if p_sh is None:
        return (jax.jit(init), jax.jit(step, donate_argnums=(0, 1, 2)),
                jax.jit(change_norms), leaf_paths(shapes), None)
    three = (p_sh, p_sh, p_sh)
    return (
        jax.jit(init, out_shardings=three),
        jax.jit(step, donate_argnums=(0, 1, 2), in_shardings=(*three, tok_sh, None),
                out_shardings=(*three, None, None)),
        jax.jit(change_norms, in_shardings=(p_sh, None)),
        leaf_paths(shapes),
        tok_sh,
    )


def readings(family, cfg: dict, opt: dict, init_std: float, key, toks: np.ndarray,
             variant: str = "f32", devices: Optional[list] = None) -> Dict:
    """Losses of the first steps, the first gradient's leaf norms and the
    leaf norms of the parameters' change over the steps, from the seeded
    weights (``key``) and the token batches ``toks`` (n, B, S), for the
    model of ``family`` (its ``reference`` module). One jitted step per
    step, state donated, so only one state is live."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    devices = tuple(devices or jax.devices()[:1])
    init, step, change_norms, paths, tok_sh = _programs(
        family, json.dumps(cfg, sort_keys=True), tuple(sorted(opt.items())),
        float(init_std), variant, devices,
    )
    with jax.default_device(devices[0]):
        p, mu, nu = init(key)
        losses = []
        for t, batch in enumerate(toks, start=1):
            batch = jax.device_put(batch, tok_sh or devices[0])
            p, mu, nu, loss, gnorms = step(p, mu, nu, batch, jnp.float32(t))
            losses.append(float(loss))
            if t == 1:
                first = jax.device_get(gnorms)
        mu = nu = None
        dnorms = jax.device_get(change_norms(p, key))
    return {
        "losses": losses,
        "grad_norms": dict(zip(paths, map(float, first))),
        "delta_norms": dict(zip(paths, map(float, dnorms))),
    }
