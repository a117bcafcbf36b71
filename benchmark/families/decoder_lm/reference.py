"""Plain float32 reference of the dense decoder family, written from the
published decoder recipe, not from the program: token embedding, per
layer an RMSNorm, multi-head causal attention with rotate-half RoPE and
a SwiGLU MLP, each with a residual; a final RMSNorm and an untied output
head. It imports nothing of ``traceml_tpu`` and reads its sizes from the
configuration file.

Parameters are made in the nested layout of the program's checkpoints
(``layer_<i>/attn/wq/kernel`` ...) and computed on packed: the
``layer_<i>`` subtrees stacked into one ``layers`` subtree (layer on
axis 0), which the forward scans as one rematerialised body. So the
step's state is stacked once, not again inside every step, and its
compiled program holds one layer's code, not every layer's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def pack(params: dict, cfg: dict) -> dict:
    """The checkpoint layout with its ``layer_<i>`` subtrees stacked into
    one ``layers`` subtree (layer on axis 0)."""
    n = cfg["num_hidden_layers"]
    out = {k: v for k, v in params.items() if not k.startswith("layer_")}
    out["layers"] = jax.tree.map(lambda *ls: jnp.stack(ls), *[params[f"layer_{i}"] for i in range(n)])
    return out


def unpack(tree: dict, cfg: dict) -> dict:
    """The packed layout back to the checkpoint layout."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer_{i}"] = jax.tree.map(lambda x: x[i], tree["layers"])
    return out


def fsdp_dim(path: str, shape: tuple):
    """The dimension of packed leaf ``path`` that the program's ``fsdp``
    axis splits (after the layer axis of a stacked leaf), or None for a
    leaf it keeps whole."""
    names = path.split("/")
    offset = 1 if names[0] == "layers" else 0
    if len(shape) - offset < 2:
        return None
    return offset + (1 if any(n in _ROW_PARALLEL for n in names) else 0)


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


def forward(params: dict, inputs, cfg: dict, ein):
    """Logits of ``inputs`` (B, L) over the vocabulary, and no extra loss;
    ``params`` packed."""
    x = params["embed"]["embedding"][inputs]
    layer = jax.checkpoint(functools.partial(_layer, cfg, ein))
    x, _ = jax.lax.scan(lambda h, p: (layer(p, h), None), x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return ein("bld,dv->blv", x, params["lm_head"]["kernel"]), 0.0


# -- FLOPs ---------------------------------------------------------------


def forward_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs for one sequence of ``seq_len`` positions.

    Counted: every matmul (q, k, v, o projections, the SwiGLU gate, up
    and down projections, ``lm_head``) and attention's score (QK^T) and
    value (PV) products, two FLOPs per multiply-add. Left out: the
    embedding gather, norms, softmax and other elementwise work. Causal
    convention: attention counts the query-key pairs a causal mask
    keeps, L(L+1)/2 per head and sequence, not the L^2 the dense path
    computes, because those are the operations the model requires.
    """
    h = cfg["hidden_size"]
    hd = cfg["head_dim"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    ffn = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]
    per_token_layer = 2 * (
        h * nq * hd          # q
        + 2 * h * nkv * hd   # k, v
        + nq * hd * h        # o
        + 3 * h * ffn        # gate, up, down
    )
    pairs = seq_len * (seq_len + 1) // 2
    attn_layer = 2 * 2 * nq * hd * pairs  # QK^T and PV
    layers = cfg["num_hidden_layers"] * (per_token_layer * seq_len + attn_layer)
    head = 2 * h * vocab * seq_len
    return float(layers + head)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward (twice the forward) FLOPs of one step on a
    ``batch x seq`` token array; the model sees ``seq - 1`` positions
    (inputs ``[:, :-1]``, targets ``[:, 1:]``). The optimizer and any
    recomputation are left out."""
    return 3.0 * batch * forward_flops_per_sequence(cfg, seq - 1)
