"""Plain float32 reference of the toy tied-embedding family."""

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    return {"embed": {"embedding": s(v, h)}, "mix": {"kernel": s(h, h)}, "norm": {"scale": s(h)}}


def pack(params, cfg):
    return params


def unpack(tree, cfg):
    return tree


def fsdp_dim(path, shape):
    return None if len(shape) < 2 else 0


def forward(params, inputs, cfg, ein):
    emb = params["embed"]["embedding"]
    x = emb[inputs]
    scale = params["norm"]["scale"]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg["rms_norm_eps"]) * scale
    x = x + jax.nn.silu(ein("bld,de->ble", h, params["mix"]["kernel"]))
    return ein("bld,vd->blv", x, emb), 0.0


def train_step_flops(cfg, batch, seq):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return 3.0 * batch * (seq - 1) * 2 * (h * h + h * v)
