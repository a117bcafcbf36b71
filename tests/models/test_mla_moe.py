"""The latent-attention, routed-expert decoder (``ModelConfig.mla`` /
``.moe``) against the family's plain float32 reference
(``benchmark/families/mla_moe/reference.py``), on the CPU at a small
width in Moonlight's ratios: hidden 256, 16 heads, q 16 + 8 (RoPE), v 16, latent 64,
dense 5.5x hidden, experts 0.6875x, 2 shared; the published 64 experts,
top 6, 8 held.

The program runs in float32 here (``dtype``), so program and reference
differ by float32 round-off and summation order alone; tolerances say
what each gap is. Weights are drawn at std 0.2, so router scores are
far from flat and no top-6 choice sits within round-off of a tie, and
the correction bias is drawn too, so selection reads it.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import discovery, reference
from benchmark.weights import init_params
from traceml_tpu.models import DecoderLM, init_train_state, make_train_step
from traceml_tpu.models.moe import RoutedExperts, route
from traceml_tpu.models.transformer import loss_and_counters

FAMILY = discovery.load_family("mla_moe")
REF = FAMILY.reference
CFG = {
    "family": "mla_moe", "hidden_size": 256, "num_attention_heads": 16,
    "num_key_value_heads": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 64, "q_lora_rank": None,
    "intermediate_size": 1408, "moe_intermediate_size": 176, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "n_routed_experts": 8, "experts_held": [0, 8],
    "published": {"n_routed_experts": 64}, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "vocab_size": 128, "rope_theta": 50000,
    "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.446, "seq_aux_alpha": 1e-4,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu",
    "program": {"remat": True}, "init_std": 0.2,
    "optimizer": {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
                  "weight_decay": 0.01},
}
B, S = 2, 24


def _program_cfg(cfg=CFG, **moe):
    pcfg = FAMILY.program.model_config({"model": cfg, "load": {"seq": S}})
    return dataclasses.replace(
        pcfg, dtype=jnp.float32, moe=dataclasses.replace(pcfg.moe, **moe)
    )


def _params(cfg=CFG, seed=0):
    """Seeded weights in the checkpoint layout, with a drawn correction
    bias in every routed layer."""
    shapes = REF.param_shapes(cfg)
    p = init_params(jax.random.key(seed), shapes, cfg["init_std"])
    bias = jax.random.normal(jax.random.key(seed + 1), (cfg["published"]["n_routed_experts"],))
    for i in range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]):
        p[f"layer_{i}"]["mlp"]["gate"]["e_score_correction_bias"] = 0.05 * bias * (i + 1)
    return p


def _tokens(seed=3):
    return jax.random.randint(jax.random.key(seed), (B, S + 1), 0, CFG["vocab_size"])


def _ref_loss(p, tokens):
    return reference.loss_fn(REF.pack(p, CFG), tokens, REF, CFG)


def test_layout_is_the_references():
    model = DecoderLM(_program_cfg())
    got = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((B, S), jnp.int32))["params"]
    want = REF.param_shapes(CFG)
    assert reference.leaf_paths(got) == reference.leaf_paths(want)
    assert [s.shape for s in jax.tree.leaves(got)] == [s.shape for s in jax.tree.leaves(want)]


def test_logits_aux_and_loss_match_the_reference():
    p, toks = _params(), _tokens()
    logits, aux, load = DecoderLM(_program_cfg()).apply({"params": p}, toks[:, :-1])
    ref_logits, ref_aux = REF.forward(REF.pack(p, CFG), toks[:, :-1], CFG, reference._ein)
    # float32 round-off over 3 layers: logits are O(1)
    np.testing.assert_allclose(logits, ref_logits, rtol=0, atol=2e-4)
    # aux is a sum of products of the same scores, and a count
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-5)
    assert load.shape == (2, 64) and load.dtype == jnp.int32
    loss, counters = loss_and_counters(p, DecoderLM(_program_cfg()).apply, toks)
    np.testing.assert_allclose(loss, _ref_loss(p, toks), rtol=1e-5)
    assert (counters["expert_load"] == load).all()


def test_first_gradients_match_the_reference():
    p, toks = _params(), _tokens()
    apply = DecoderLM(_program_cfg()).apply
    got = jax.grad(lambda q: loss_and_counters(q, apply, toks)[0])(p)
    want = jax.grad(_ref_loss)(p, toks)
    flat_want = dict(zip(reference.leaf_paths(want), jax.tree.leaves(want)))
    for path, g in zip(reference.leaf_paths(got), jax.tree.leaves(got)):
        w = flat_want[path]
        if path.endswith("e_score_correction_bias"):
            # selection alone reads the bias: no gradient by construction
            assert not np.any(g) and not np.any(w), path
            continue
        # float32 round-off relative to the leaf's own scale
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(w))),
                                   err_msg=path)


def test_three_steps_match_reference_adamw():
    p, opt = _params(), CFG["optimizer"]
    model, state, tx = init_train_state(_program_cfg(), jax.random.key(0),
                                        learning_rate=opt["learning_rate"])
    state["params"] = p
    state["opt_state"] = tx.init(p)
    step = jax.jit(make_train_step(model, tx))
    rp = REF.pack(p, CFG)
    mu = nu = jax.tree.map(jnp.zeros_like, rp)
    for t in range(1, 4):
        toks = _tokens(10 + t)
        state, m = step(state, toks)
        loss, g = jax.value_and_grad(reference.loss_fn)(rp, toks, REF, CFG)
        rp, mu, nu = reference.adamw(rp, g, mu, nu, t, opt)
        # the program reports the loss with its balance loss, as the reference does
        np.testing.assert_allclose(m["loss"], loss, rtol=1e-5)
    got = jax.tree.map(lambda a, b: a - b, state["params"], p)
    want = jax.tree.map(lambda a, b: a - b, REF.unpack(rp, CFG), p)
    for path, d, w in zip(reference.leaf_paths(got), jax.tree.leaves(got), jax.tree.leaves(want)):
        # Adam's first steps move each weight by ~lr whatever its gradient's
        # size, so a gradient within round-off of zero may move its weight
        # the other way (one element in ~30000 here): held on the leaf's
        # change as a whole, 1% of its norm
        gap = float(jnp.linalg.norm(d - w)) / float(jnp.linalg.norm(w))
        assert gap < 1e-2, (path, gap)


def _layer(cfg, p_mlp, h, **moe):
    pcfg = _program_cfg(cfg, **moe)
    return RoutedExperts(pcfg).apply({"params": p_mlp}, h)


def _mlp_params(held, seed=5):
    p = _params(seed=seed)["layer_1"]["mlp"]
    full = init_params(jax.random.key(seed + 7), {
        k: jax.ShapeDtypeStruct((64,) + v.shape[1:], v.dtype) for k, v in p["experts"].items()
    }, CFG["init_std"])
    return p, full


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 8 of the 64 experts and computes their part;
    with the shared expert counted once, the parts add up to what the
    reference gives for the whole layer."""
    p, full = _mlp_params(8)
    h = jax.random.normal(jax.random.key(9), (B, S, CFG["hidden_size"]))
    shared = None
    total = 0.0
    for share in range(8):
        held = {k: v[8 * share: 8 * share + 8] for k, v in full.items()}
        out, _, load = _layer(CFG, dict(p, experts=held), h, expert_offset=8 * share)
        if shared is None:
            # the shared expert is what every chip computes alike
            zero = {k: jnp.zeros_like(v) for k, v in held.items()}
            shared = _layer(CFG, dict(p, experts=zero), h)[0]
        total = total + out - shared
    total = total + shared
    uncut = dict(CFG, experts_held=[0, 64], n_routed_experts=64)
    want, _ = REF._experts(uncut, reference._ein, dict(p, experts=full), h)
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-4)
    assert int(load.sum()) == B * S * CFG["num_experts_per_tok"]


def test_dropless_when_one_expert_takes_every_token():
    """A bias that sends every token to held expert 3 (and most of their
    other choices to held experts too): every pair is computed."""
    p, _ = _mlp_params(8)
    bias = jnp.full((64,), -1.0).at[:5].set(1.0).at[3].set(50.0)
    p = dict(p, gate=dict(p["gate"], e_score_correction_bias=bias))
    h = jax.random.normal(jax.random.key(4), (B, S, CFG["hidden_size"]))
    out, _, load = _layer(CFG, p, h)
    want, _ = REF._experts(CFG, reference._ein, p, h)
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-4)
    assert int(load[3]) == B * S
    assert int(load[:8].sum()) >= 5 * B * S  # 5 of every token's 6 choices are held


def test_tpu_grouped_kernels_match_the_reference(monkeypatch):
    """The TPU path's megablox kernels (interpreted here), which leave the
    rows past the held pairs unwritten (NaN in the interpreter): the
    layer's output and gradients still match the reference."""
    from jax.experimental.pallas.ops.tpu import megablox

    from traceml_tpu.models import moe

    monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    monkeypatch.setattr(moe, "grouped_matmul", moe._gmm)
    p, _ = _mlp_params(8)
    h = jax.random.normal(jax.random.key(8), (B, S, CFG["hidden_size"]))

    def program(q, x):
        return jnp.sum(jnp.sin(_layer(CFG, q, x)[0]))

    def ref(q, x):
        return jnp.sum(jnp.sin(REF._experts(CFG, reference._ein, q, x)[0]))

    out = _layer(CFG, p, h)[0]
    np.testing.assert_allclose(out, REF._experts(CFG, reference._ein, p, h)[0], rtol=0, atol=1e-4)
    got, want = jax.grad(program, argnums=(0, 1))(p, h), jax.grad(ref, argnums=(0, 1))(p, h)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(w)) + 1e-9))


def test_bias_changes_the_choice_not_the_weights():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(2), (16, 64)))
    idx0, w0 = route(scores, jnp.zeros(64), 6, 2.446)
    bias = jnp.zeros(64).at[7].set(10.0)
    idx1, w1 = route(scores, bias, 6, 2.446)
    assert (idx1[:, 0] == 7).all() and not (idx0 == 7).all()
    s = np.asarray(scores)
    for idx, w in ((idx0, w0), (idx1, w1)):
        chosen = np.take_along_axis(s, np.asarray(idx), -1)
        np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True) * 2.446, rtol=1e-6)
    # a bias that keeps the same order changes nothing
    idx2, w2 = route(scores, jnp.full(64, 3.0), 6, 2.446)
    assert (idx2 == idx0).all() and np.allclose(w2, w0)


def test_expert_load_is_the_count_of_choices():
    p, _ = _mlp_params(8)
    h = jax.random.normal(jax.random.key(6), (B, S, CFG["hidden_size"]))
    _, _, load = _layer(CFG, p, h)
    x = np.asarray(h, np.float64).reshape(-1, CFG["hidden_size"])
    s = 1 / (1 + np.exp(-x @ np.asarray(p["gate"]["kernel"], np.float64)))
    top = np.argsort(-(s + np.asarray(p["gate"]["e_score_correction_bias"])), axis=-1)[:, :6]
    np.testing.assert_array_equal(load, np.bincount(top.ravel(), minlength=64))


def test_train_step_returns_expert_load_and_dense_models_do_not():
    model, state, tx = init_train_state(_program_cfg(), jax.random.key(0))
    _, m = jax.jit(make_train_step(model, tx))(state, _tokens())
    assert set(m) == {"loss", "counters"} and set(m["counters"]) == {"expert_load"}
    load = m["counters"]["expert_load"]
    assert load.shape == (2, 64) and load.dtype == jnp.int32
    assert (load.sum(axis=1) == B * S * 6).all()
    from traceml_tpu.models import ModelConfig

    model, state, tx = init_train_state(ModelConfig.tiny(), jax.random.key(0))
    _, m = jax.jit(make_train_step(model, tx))(state, _tokens())
    assert set(m) == {"loss"}


def test_cell_config_is_the_catalog_entry_cut():
    """The benchmark's configuration holds the catalog's published keys,
    changed only where ``reduced`` says, and the program builds it."""
    from pathlib import Path

    path = Path(discovery.HERE) / "configs" / "moonlight-16b-a3b.json"
    m = json.loads(path.read_text())
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert m["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64,
                              "vocab_size": 163840}
    pcfg = FAMILY.program.model_config({"model": m, "load": {"seq": 2048}})
    assert (pcfg.moe.n_experts, pcfg.moe.n_held, pcfg.ffn_hidden) == (64, 8, 11264)
    assert pcfg.remat and pcfg.rms_eps == 1e-5


def _first_step(model, tx, state, mesh=None):
    """One train step: which path each latent attention call took, the
    loss, and the first gradient (Adam's first moment ÷ (1 − b1))."""
    from traceml_tpu.ops.attention import record_latent_dispatch

    with record_latent_dispatch() as calls:
        new, m = jax.jit(make_train_step(model, tx, mesh=mesh))(state, _tokens())
    grads = jax.tree.map(lambda mu: mu / (1 - CFG["optimizer"]["b1"]),
                         new["opt_state"][0].mu)
    return [c[0] for c in calls], m["loss"], grads


def test_latent_kernel_step_matches_the_dense_path(monkeypatch):
    """The train step with latent attention on the splash kernels
    (interpreted; from 16 tokens on here, so S = 24 pads to one block):
    the loss and first gradients of the dense path, within the float32
    round-off the reference tests allow."""
    from traceml_tpu.ops import attention

    model, state, tx = init_train_state(_program_cfg(), jax.random.key(0))
    state["params"] = _params()
    state["opt_state"] = tx.init(state["params"])
    dense_paths, dense_loss, dense_grads = _first_step(model, tx, state)
    monkeypatch.setattr(attention, "_PALLAS_MIN_SEQ", 16)
    paths, loss, grads = _first_step(model, tx, state)
    assert dense_paths == ["dense"] * 3 and paths == ["kernel"] * 3
    np.testing.assert_allclose(loss, dense_loss, rtol=1e-5)
    for path, g, w in zip(reference.leaf_paths(grads), jax.tree.leaves(grads),
                          jax.tree.leaves(dense_grads)):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(w))),
                                   err_msg=path)


def test_latent_attention_stays_dense_in_a_step_built_for_a_mesh(monkeypatch):
    """A Pallas call is not partitioned: with a mesh, ``init_train_state``
    and ``make_train_step`` trace latent attention on its dense path."""
    from traceml_tpu.ops import attention
    from traceml_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(attention, "_PALLAS_MIN_SEQ", 16)
    mesh = make_mesh({"data": 1, "fsdp": 2, "tensor": 1}, devices=jax.devices()[:2])
    for on, want in ((mesh, "dense"), (None, "kernel")):
        with attention.record_latent_dispatch() as calls:
            model, state, tx = init_train_state(_program_cfg(), jax.random.key(0), mesh=on)
        assert [c[0] for c in calls] == [want] * 3
        with attention.record_latent_dispatch() as calls:
            jax.eval_shape(make_train_step(model, tx, mesh=on), state, _tokens())
        assert [c[0] for c in calls] == [want] * 3
