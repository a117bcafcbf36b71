"""Plain float32 reference of the latent-attention, routed-expert decoder
family (DeepSeek-V3, ``model_type`` ``deepseek_v3``; Moonlight-16B-A3B),
written from the published description, not from the program. It
imports nothing of ``traceml_tpu`` and reads its sizes from the
configuration file.

Per layer: an RMSNorm, multi-head latent attention and a residual, an
RMSNorm, a feed-forward and a residual; then a final RMSNorm and an
untied output head.

* Latent attention (``q_lora_rank`` null): q = h W_q, split per head
  into a ``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part;
  [c, k_pe] = h W_kv_a, with the ``kv_lora_rank``-wide latent c
  RMS-normed (``kv_a_layernorm``); [k_nope, v] = c W_kv_b per head. A
  score is q_nope . k_nope + RoPE(q_pe) . RoPE(k_pe), one k_pe shared
  by every head, over sqrt(qk_nope + qk_rope); causal softmax; the
  ``v_head_dim``-wide values; W_o. RoPE follows the published
  modelling code of the checkpoints: each RoPE part is viewed as
  (pairs, 2) and transposed, so its interleaved pairs become halves,
  then rotated by rotate-half.
* The first ``first_k_dense_replace`` layers have a SwiGLU of
  ``intermediate_size``; the others route (``noaux_tc``, one group):
  s = sigmoid(h W_gate) over all experts in float32; the
  ``num_experts_per_tok`` experts with the largest s + b
  (``e_score_correction_bias``) are chosen; their weights are their s,
  normalised over the chosen (``norm_topk_prob``) and times
  ``routed_scaling_factor``. The output is the weighted sum of the
  chosen experts' SwiGLUs (``moe_intermediate_size``) plus a shared
  SwiGLU ``n_shared_experts`` times as wide. Only the experts this chip
  holds (``experts_held``) are in the parameters, and only their part
  is summed: every held expert is computed for every token, weighted by
  its routing weight, zero where not chosen.
* The sequence-wise balance loss (``seq_aux``, DeepSeek-V3 eq. 17-20):
  alpha * sum_i f_i P_i over all experts, per sequence, mean over
  sequences and summed over the routed layers; f_i = E / (K T) times
  the sequence's tokens that chose expert i, P_i the sequence's mean of
  s_i normalised over all experts.

Departures from the published recipe, the program's as well:
``e_score_correction_bias`` is a parameter that selection alone reads;
its gradient is zero and the generic step's AdamW moves it by weight
decay only, where DeepSeek-V3 moves it by a load rule after each step.
Moonlight trains with Muon (arXiv:2502.16982); the benchmark's step is
AdamW. Alpha is assumed (the configuration's ``seq_aux_alpha``).

Parameters are made in the nested layout of the program's checkpoints
(``layer_<i>/attn/q_proj/kernel`` ...) and computed on packed: the
dense layers kept apart, the routed layers stacked into one ``layers``
subtree (layer on axis 0), which the forward scans as one
rematerialised body. Attention runs one head at a time and the held
experts one expert at a time, each rematerialised, so that the step's
activations stay small beside its parameters, gradients and Adam state
on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: weights the program splits over ``fsdp`` along dim 1, not dim 0
_ROW_PARALLEL = ("w_down",)


def _held(cfg: dict):
    lo, hi = cfg["experts_held"]
    return lo, hi - lo


def param_shapes(cfg: dict) -> dict:
    """The checkpoint layout: nested dict of float32 ``ShapeDtypeStruct``."""
    h, nh, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    r, f, fe = cfg["kv_lora_rank"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    n = _held(cfg)[1]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def swiglu(width):
        return {"w_gate": {"kernel": s(h, width)}, "w_up": {"kernel": s(h, width)},
                "w_down": {"kernel": s(width, h)}}

    def layer(i):
        mlp = swiglu(f) if i < cfg["first_k_dense_replace"] else {
            "gate": {"kernel": s(h, cfg["published"]["n_routed_experts"]),
                     "e_score_correction_bias": s(cfg["published"]["n_routed_experts"])},
            "experts": {"w_gate": s(n, h, fe), "w_up": s(n, h, fe), "w_down": s(n, fe, h)},
            "shared": swiglu(fs),
        }
        return {
            "attn_norm": {"scale": s(h)},
            "attn": {
                "q_proj": {"kernel": s(h, nh * (dn + dr))},
                "kv_a_proj_with_mqa": {"kernel": s(h, r + dr)},
                "kv_a_layernorm": {"scale": s(r)},
                "kv_b_proj": {"kernel": s(r, nh * (dn + dv))},
                "o_proj": {"kernel": s(nh * dv, h)},
            },
            "mlp_norm": {"scale": s(h)},
            "mlp": mlp,
        }

    out = {"embed": {"embedding": s(v, h)}}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer_{i}"] = layer(i)
    out["final_norm"] = {"scale": s(h)}
    out["lm_head"] = {"kernel": s(h, v)}
    return out


def _routed(cfg: dict) -> range:
    return range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def pack(params: dict, cfg: dict) -> dict:
    """The checkpoint layout with its routed ``layer_<i>`` subtrees
    stacked into one ``layers`` subtree (layer on axis 0); dense layers
    stay as they are."""
    routed = [f"layer_{i}" for i in _routed(cfg)]
    out = {k: v for k, v in params.items() if k not in routed}
    out["layers"] = jax.tree.map(lambda *ls: jnp.stack(ls), *[params[k] for k in routed])
    return out


def unpack(tree: dict, cfg: dict) -> dict:
    """The packed layout back to the checkpoint layout."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    for j, i in enumerate(_routed(cfg)):
        out[f"layer_{i}"] = jax.tree.map(lambda x: x[j], tree["layers"])
    return out


def fsdp_dim(path: str, shape: tuple):
    """The dimension of packed leaf ``path`` that the program's ``fsdp``
    axis would split (after the layer axis of a stacked leaf), or None
    for a leaf it keeps whole."""
    names = path.split("/")
    offset = 1 if names[0] == "layers" else 0
    if len(shape) - offset < 2:
        return None
    return offset + (1 if any(n in _ROW_PARALLEL for n in names) else 0)


# -- model ---------------------------------------------------------------


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """RoPE of the published checkpoints over (B, L, heads, d): the
    interleaved pairs viewed as (d/2, 2), transposed to halves, then
    rotate-half."""
    B, L, nh, d = x.shape
    x = jnp.swapaxes(x.reshape(B, L, nh, d // 2, 2), -1, -2).reshape(B, L, nh, d)
    half = d // 2
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(cfg, ein, a, x):
    B, L, _ = x.shape
    nh, dn, dr = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r, theta = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rope_theta"]
    q = ein("bld,de->ble", x, a["q_proj"]["kernel"]).reshape(B, L, nh, dn + dr)
    ckv = ein("bld,de->ble", x, a["kv_a_proj_with_mqa"]["kernel"])
    c = _rms(ckv[..., :r], a["kv_a_layernorm"]["scale"], cfg["rms_norm_eps"])
    k_pe = _rope(ckv[:, :, None, r:], theta)[:, :, 0]
    kv = ein("blr,re->ble", c, a["kv_b_proj"]["kernel"]).reshape(B, L, nh, dn + dv)
    q_pe = _rope(q[..., dn:], theta)
    causal = jnp.tril(jnp.ones((L, L), bool))

    @jax.checkpoint
    def head(parts):
        q_nope, q_pe, k_nope, v = parts
        scores = (
            ein("bqd,bkd->bqk", q_nope, k_nope) + ein("bqd,bkd->bqk", q_pe, k_pe)
        ) / jnp.sqrt(jnp.float32(dn + dr))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return ein("bqk,bkd->bqd", probs, v)

    def heads(t):
        return jnp.moveaxis(t, 2, 0)

    o = jax.lax.map(head, (heads(q[..., :dn]), heads(q_pe), heads(kv[..., :dn]),
                           heads(kv[..., dn:])))
    o = jnp.moveaxis(o, 0, 2).reshape(B, L, nh * dv)
    return ein("ble,ed->bld", o, a["o_proj"]["kernel"])


def _swiglu(ein, m, x):
    gate = ein("bld,df->blf", x, m["w_gate"]["kernel"])
    up = ein("bld,df->blf", x, m["w_up"]["kernel"])
    return ein("blf,fd->bld", jax.nn.silu(gate) * up, m["w_down"]["kernel"])


def _experts(cfg, ein, m, x):
    """The held experts' part of the routed output, the shared expert,
    and the balance loss."""
    B, L, _ = x.shape
    E, K = cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"]
    lo, n = _held(cfg)
    g = m["gate"]
    s = jax.nn.sigmoid(ein("bld,de->ble", x, g["kernel"]))
    _, idx = jax.lax.top_k(s + g["e_score_correction_bias"], K)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    # (B, L, held): each held expert's routing weight, zero where not chosen
    mine = jnp.sum(jnp.where(idx[..., None] == lo + jnp.arange(n), w[..., None], 0.0), axis=2)
    e = m["experts"]

    @jax.checkpoint
    def expert(acc, parts):
        w_gate, w_up, w_down, weight = parts
        gate = ein("bld,df->blf", x, w_gate)
        up = ein("bld,df->blf", x, w_up)
        return acc + weight[..., None] * ein("blf,fd->bld", jax.nn.silu(gate) * up, w_down), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(x), (e["w_gate"], e["w_up"], e["w_down"], jnp.moveaxis(mine, -1, 0))
    )
    f = jnp.sum(jax.nn.one_hot(idx, E), axis=(1, 2)) * (E / (K * L))
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
    aux = cfg["seq_aux_alpha"] * jnp.mean(jnp.sum(f * p, axis=-1))
    return routed + _swiglu(ein, m["shared"], x), aux


def _dense_layer(cfg, ein, p, x):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, ein, p["attn"], _rms(x, p["attn_norm"]["scale"], eps))
    return x + _swiglu(ein, p["mlp"], _rms(x, p["mlp_norm"]["scale"], eps))


def _routed_layer(cfg, ein, p, x):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, ein, p["attn"], _rms(x, p["attn_norm"]["scale"], eps))
    out, aux = _experts(cfg, ein, p["mlp"], _rms(x, p["mlp_norm"]["scale"], eps))
    return x + out, aux


def forward(params: dict, inputs, cfg: dict, ein):
    """Logits of ``inputs`` (B, L) over the vocabulary and the balance
    loss summed over the routed layers; ``params`` packed."""
    x = params["embed"]["embedding"][inputs]
    dense = jax.checkpoint(functools.partial(_dense_layer, cfg, ein))
    for i in range(cfg["first_k_dense_replace"]):
        x = dense(params[f"layer_{i}"], x)
    routed = jax.checkpoint(functools.partial(_routed_layer, cfg, ein))

    def body(carry, p):
        x, aux = routed(p, carry[0])
        return (x, carry[1] + aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return ein("bld,dv->blv", x, params["lm_head"]["kernel"]), aux


# -- FLOPs and bytes ------------------------------------------------------


def forward_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs for one sequence of ``seq_len`` positions.

    Counted, two FLOPs per multiply-add: every projection of latent
    attention (``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj``,
    ``o_proj``), its score (q width) and value (``v_head_dim``) products
    at the L(L+1)/2 query-key pairs a causal mask keeps, the dense
    SwiGLUs, the router, the shared experts, the routed experts at the
    expected number of (token, held expert) pairs, L K H / E with H
    experts held of E (K per token, uniform routing), and ``lm_head``.
    Left out: the embedding gather, norms, softmax, sort and other
    elementwise work.
    """
    h, nh, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                     cfg["kv_lora_rank"])
    E, K = cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"]
    fe = cfg["moe_intermediate_size"]
    proj = h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) + nh * dv * h
    pairs = seq_len * (seq_len + 1) // 2
    attn = 2 * proj * seq_len + 2 * nh * (dn + dr + dv) * pairs
    dense = attn + 2 * 3 * h * cfg["intermediate_size"] * seq_len
    held = seq_len * K * _held(cfg)[1] / E
    routed = (attn + 2 * h * E * seq_len
              + 2 * 3 * h * cfg["n_shared_experts"] * fe * seq_len
              + 2 * 3 * h * fe * held)
    n_dense = cfg["first_k_dense_replace"]
    layers = n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * routed
    return float(layers + 2 * h * v * seq_len)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward (twice the forward) FLOPs of one step on a
    ``batch x seq`` token array; the model sees ``seq - 1`` positions.
    The optimizer and any recomputation are left out."""
    return 3.0 * batch * forward_flops_per_sequence(cfg, seq - 1)


def routed_expert_cost(cfg: dict, pairs: float) -> tuple:
    """FLOPs and HBM bytes that one training step's grouped matmuls of
    the held experts need in one routed layer, for ``pairs`` (token,
    held expert) pairs: gate, up and down (pairs x hidden x width each),
    in the forward, again in the recomputation when the program
    rematerialises, and in the backward as two matmuls each (operand and
    weight gradients). Bytes: bf16 operands read once, results written
    once, per matmul."""
    h, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], _held(cfg)[1]
    flops = 2.0 * pairs * h * f

    def mm(a, w, out):  # operand elements read and written by one matmul
        return a + w + out

    x, mid, wt = pairs * h, pairs * f, n * h * f
    fwd = 2 * mm(x, wt, mid) + mm(mid, wt, x)
    bwd = 2 * (mm(mid, wt, x) + mm(x, mid, wt)) + (mm(x, wt, mid) + mm(mid, x, wt))
    passes = 2 if cfg["program"]["remat"] else 1
    return 3 * flops * (passes + 2), 2.0 * (passes * fwd + bwd)
