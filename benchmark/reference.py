"""Plain float32 reference of the DecoderLM training step.

Written from the published decoder recipe, not from the program: token
embedding, per layer an RMSNorm, multi-head causal attention with
rotate-half RoPE and a SwiGLU MLP, each with a residual; a final RMSNorm,
an untied output head, next-token cross entropy; AdamW as Loshchilov &
Hutter with Adam's bias correction. Every matmul runs in float32 at
``Precision.HIGHEST``. It imports nothing of ``traceml_tpu`` and reads
its sizes from the configuration file. Parameters live in the nested
layout of the program's checkpoints (``layer_<i>/attn/wq/kernel`` ...),
made by ``weights.init_params`` from the seed.

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

Memory and compile time: the layers run as one scanned, rematerialised
body; each step is its own jitted call with the state donated, so one
state is live; the change's norms make each start leaf again inside its
own reduction; on several devices every leaf and the batch are split
over them. So a 4-chip cell's reference fits beside nothing else.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.weights import init_params, start_leaf

HIGHEST = jax.lax.Precision.HIGHEST
#: the configuration keys the reference reads
MODEL_KEYS = (
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
    "intermediate_size", "vocab_size", "num_hidden_layers", "rope_theta",
    "rms_norm_eps",
)
VARIANTS = ("f32", "fp8", "half_batch", "labels_shifted", "no_exchange")
#: weights the program splits over ``fsdp`` along dim 1, not dim 0
_ROW_PARALLEL = ("wo", "w_down")


def param_shapes(cfg: dict) -> dict:
    """The checkpoint layout: nested dict of float32 ``ShapeDtypeStruct``."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, v = cfg["intermediate_size"], cfg["vocab_size"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer():
        return {
            "attn_norm": {"scale": s(h)},
            "attn": {
                "wq": {"kernel": s(h, nq * hd)},
                "wk": {"kernel": s(h, nkv * hd)},
                "wv": {"kernel": s(h, nkv * hd)},
                "wo": {"kernel": s(nq * hd, h)},
            },
            "mlp_norm": {"scale": s(h)},
            "mlp": {
                "w_gate": {"kernel": s(h, f)},
                "w_up": {"kernel": s(h, f)},
                "w_down": {"kernel": s(f, h)},
            },
        }

    out = {"embed": {"embedding": s(v, h)}}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer_{i}"] = layer()
    out["final_norm"] = {"scale": s(h)}
    out["lm_head"] = {"kernel": s(h, v)}
    return out


def leaf_paths(tree) -> List[str]:
    """``a/b/c`` for each leaf, in flatten order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [
        "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        for path, _ in flat
    ]


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


# -- model ---------------------------------------------------------------


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over (B, L, heads, hd)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * (2.0 * jnp.arange(half, dtype=jnp.float32) / hd))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg, ein, p, x):
    B, L, _ = x.shape
    hd, nq, nkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    h = _rms(x, p["attn_norm"]["scale"], eps)
    q = _rope(ein("bld,de->ble", h, a["wq"]["kernel"]).reshape(B, L, nq, hd), theta)
    k = _rope(ein("bld,de->ble", h, a["wk"]["kernel"]).reshape(B, L, nkv, hd), theta)
    v = ein("bld,de->ble", h, a["wv"]["kernel"]).reshape(B, L, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = ein("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((L, L), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = ein("bhqk,bkhd->bqhd", probs, v).reshape(B, L, nq * hd)
    x = x + ein("ble,ed->bld", o, a["wo"]["kernel"])
    m = p["mlp"]
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    gate = ein("bld,df->blf", h, m["w_gate"]["kernel"])
    up = ein("bld,df->blf", h, m["w_up"]["kernel"])
    return x + ein("blf,fd->bld", jax.nn.silu(gate) * up, m["w_down"]["kernel"])


def _stack(params: dict, n_layers: int) -> dict:
    """The checkpoint layout with its ``layer_<i>`` subtrees stacked into
    one ``layers`` subtree (layer on axis 0), which the loss scans."""
    out = {k: v for k, v in params.items() if not k.startswith("layer_")}
    out["layers"] = jax.tree.map(
        lambda *ls: jnp.stack(ls), *[params[f"layer_{i}"] for i in range(n_layers)]
    )
    return out


def _in_layers(path) -> bool:
    return str(getattr(path[0], "key", path[0])) == "layers"


def loss_fn(params, tokens, cfg: dict, variant: str = "f32"):
    """Mean next-token cross entropy of ``tokens`` (B, S); ``params`` in
    the stacked layout."""
    ein = _ein_fp8 if variant == "fp8" else _ein
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if variant == "labels_shifted":
        targets = jnp.roll(targets, 1, axis=1)
    x = params["embed"]["embedding"][inputs]
    layer = jax.checkpoint(functools.partial(_layer, cfg, ein))
    x, _ = jax.lax.scan(lambda h, p: (layer(p, h), None), x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = ein("bld,dv->blv", x, params["lm_head"]["kernel"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if variant == "half_batch":
        B, L = nll.shape
        nll = nll[: B // 2] if B >= 2 else nll[:, : L // 2]
    return nll.mean()


def _no_exchange_grads(params, tokens, cfg):
    """Each fsdp half of a weight takes its own half-batch's gradient."""
    half = tokens.shape[0] // 2
    loss_a, g_a = jax.value_and_grad(loss_fn)(params, tokens[:half], cfg)
    loss_b, g_b = jax.value_and_grad(loss_fn)(params, tokens[half:], cfg)

    def pick(path, a, b):
        offset = 1 if _in_layers(path) else 0
        if a.ndim - offset < 2:
            return a
        names = [str(getattr(k, "key", k)) for k in path]
        dim = offset + (1 if any(n in _ROW_PARALLEL for n in names) else 0)
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


def _stacked_norms(tree):
    """Traceable: a scalar per leaf, a vector over layers per stacked leaf."""
    def norm(path, x):
        axes = tuple(range(1 if _in_layers(path) else 0, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))

    return jax.tree_util.tree_map_with_path(norm, tree)


def _unstack(tree, n_layers: int) -> dict:
    """The stacked layout back to the checkpoint layout (flatten order
    as ``param_shapes``)."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    for i in range(n_layers):
        out[f"layer_{i}"] = jax.tree.map(lambda x: x[i], tree["layers"])
    return out


def _shardings(stacked_shapes, devices):
    """Each leaf split over all devices on its first divisible dim after
    the layer axis, and the batch on its rows."""
    mesh = Mesh(np.asarray(devices), ("r",))
    n = len(devices)

    def spec(path, s):
        first = 1 if _in_layers(path) else 0
        for d in range(first, len(s.shape)):
            if s.shape[d] % n == 0:
                return NamedSharding(mesh, P(*([None] * d), "r"))
        return NamedSharding(mesh, P())

    return (jax.tree_util.tree_map_with_path(spec, stacked_shapes),
            NamedSharding(mesh, P("r")))


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, opt_items, init_std, variant, devices):
    """The jitted init, step and change-norm programs for one variant."""
    cfg, opt = dict(cfg_items), dict(opt_items)
    n_layers = cfg["num_hidden_layers"]
    shapes = param_shapes(cfg)
    paths = leaf_paths(shapes)
    stacked = jax.eval_shape(lambda p: _stack(p, n_layers), shapes)
    p_sh, tok_sh = _shardings(stacked, devices) if len(devices) > 1 else (None, None)

    def init(key):
        p = _stack(init_params(key, shapes, init_std), n_layers)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, jax.tree.map(jnp.zeros_like, p)

    def step(p, mu, nu, tokens, t):
        if variant == "no_exchange":
            loss, g = _no_exchange_grads(p, tokens, cfg)
        else:
            loss, g = jax.value_and_grad(loss_fn)(p, tokens, cfg, variant)
        p, mu, nu = adamw(p, g, mu, nu, t, opt)
        return p, mu, nu, loss, _stacked_norms(g)

    def change_norms(p, key):
        """Per checkpoint leaf, the norm of its change from the seeded
        start, each start leaf made again inside its own reduction."""
        leaves = jax.tree_util.tree_leaves(_unstack(p, n_layers))
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x - start_leaf(key, i, x.shape, init_std))))
            for i, x in enumerate(leaves)
        ])

    if p_sh is None:
        return (jax.jit(init), jax.jit(step, donate_argnums=(0, 1, 2)),
                jax.jit(change_norms), paths, None)
    three = (p_sh, p_sh, p_sh)
    return (
        jax.jit(init, out_shardings=three),
        jax.jit(step, donate_argnums=(0, 1, 2), in_shardings=(*three, tok_sh, None),
                out_shardings=(*three, None, None)),
        jax.jit(change_norms, in_shardings=(p_sh, None)),
        paths,
        tok_sh,
    )


def readings(cfg: dict, opt: dict, init_std: float, key, toks: np.ndarray,
             variant: str = "f32", devices: Optional[list] = None) -> Dict:
    """Losses of the first steps, the first gradient's leaf norms and the
    leaf norms of the parameters' change over the steps, from the seeded
    weights (``key``) and the token batches ``toks`` (n, B, S). One
    jitted step per step, state donated, so only one state is live."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    devices = tuple(devices or jax.devices()[:1])
    init, step, change_norms, paths, tok_sh = _programs(
        tuple((k, cfg[k]) for k in MODEL_KEYS), tuple(sorted(opt.items())),
        float(init_std), variant, devices,
    )
    n_layers = cfg["num_hidden_layers"]
    with jax.default_device(devices[0]):
        p, mu, nu = init(key)
        losses = []
        for t, batch in enumerate(toks, start=1):
            batch = jax.device_put(batch, tok_sh or devices[0])
            p, mu, nu, loss, gnorms = step(p, mu, nu, batch, jnp.float32(t))
            losses.append(float(loss))
            if t == 1:
                first = _unstack(jax.device_get(gnorms), n_layers)
        mu = nu = None
        dnorms = jax.device_get(change_norms(p, key))
    return {
        "losses": losses,
        "grad_norms": dict(zip(leaf_paths(first), map(float, jax.tree_util.tree_leaves(first)))),
        "delta_norms": dict(zip(paths, map(float, dnorms))),
    }
