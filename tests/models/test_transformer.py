import jax
import jax.numpy as jnp
import numpy as np
import pytest

from traceml_tpu.models import (
    DecoderLM,
    ModelConfig,
    init_train_state,
    make_train_step,
    param_shardings,
)
from traceml_tpu.parallel import make_mesh, batch_sharding


def test_forward_shapes_and_dtype():
    cfg = ModelConfig.tiny()
    model = DecoderLM(cfg)
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32  # lm_head in fp32 for stable CE


def test_train_step_reduces_loss():
    cfg = ModelConfig.tiny()
    model, state, tx = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, tx), donate_argnums=(0,))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    _, first = step(state, tokens)
    model, state, tx = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, tx), donate_argnums=(0,))
    losses = []
    for _ in range(20):
        state, metrics = step(state, tokens)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9
    assert int(state["step"]) == 20


def test_causal_masking():
    """Changing a future token must not change earlier logits."""
    cfg = ModelConfig.tiny()
    model = DecoderLM(cfg)
    tokens = jnp.zeros((1, 8), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    t1 = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, 7].set(9)
    l1 = model.apply({"params": params}, t1)
    l2 = model.apply({"params": params}, t2)
    np.testing.assert_allclose(l1[0, :7], l2[0, :7], rtol=2e-2, atol=2e-3)


def test_sharded_train_step_on_8_device_mesh():
    """Full sharded step on the virtual CPU mesh: dp×fsdp×tensor."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    cfg = ModelConfig.tiny()
    model, state, tx = init_train_state(cfg, jax.random.PRNGKey(0), mesh=mesh)
    # params actually sharded
    flat = jax.tree_util.tree_leaves(state["params"])
    assert any(
        len(l.sharding.device_set) > 1 for l in flat if hasattr(l, "sharding")
    )
    step = jax.jit(make_train_step(model, tx, mesh=mesh), donate_argnums=(0,))
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
        batch_sharding(mesh),
    )
    for _ in range(3):
        state, metrics = step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))
    # the state re-enters in the layout it left in: one compile
    assert step._cache_size() == 1
    state, metrics2 = step(state, tokens)
    assert float(metrics2["loss"]) < float(metrics["loss"]) + 1.0


def test_param_shardings_cover_all_leaves():
    mesh = make_mesh({"fsdp": 4, "tensor": 2})
    cfg = ModelConfig.tiny()
    model = DecoderLM(cfg)
    tokens = jnp.zeros((1, 8), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    specs = param_shardings(params, mesh)
    n_params = len(jax.tree_util.tree_leaves(params))
    n_specs = len(jax.tree_util.tree_leaves(specs, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_params == n_specs


# -- sequence-parallel attention in the full model (r4) --------------------

def test_model_ring_and_ulysses_match_dense():
    """The flagship decoder produces the same logits whether attention
    runs dense (GSPMD), as ring attention, or as Ulysses all-to-all —
    sequence parallelism is a config switch, not a different model."""
    import dataclasses

    import numpy as np

    from traceml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"context": 4}, devices=jax.devices()[:4])
    base = ModelConfig(vocab_size=128, hidden=64, n_layers=2, n_heads=4,
                       n_kv_heads=4, max_seq_len=64, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 128)

    model = DecoderLM(base)
    params = model.init(jax.random.PRNGKey(1), tokens)
    dense = model.apply(params, tokens)

    for impl in ("ring", "ulysses"):
        cfg = dataclasses.replace(
            base, attention_impl=impl, context_axis="context", mesh=mesh)
        out = DecoderLM(cfg).apply(params, tokens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), atol=2e-4, rtol=2e-4,
            err_msg=impl,
        )


def test_seq_parallel_spec_shards_batch_and_heads():
    """The shard_map spec must shard batch over the data-parallel axes
    (not leave it replicated — advisor r4: replication all-gathers the
    global batch per data group) and heads over tensor when it divides."""
    import dataclasses

    from traceml_tpu.models.transformer import seq_parallel_spec
    from traceml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 2, "tensor": 2, "context": 2})
    cfg = dataclasses.replace(
        ModelConfig.tiny(), attention_impl="ring",
        context_axis="context", mesh=mesh)
    spec = seq_parallel_spec(cfg)
    assert spec[0] == ("data", "fsdp")   # batch sharded, not replicated
    assert spec[1] == "context"
    assert spec[2] == "tensor"           # tiny() n_heads=4 % tensor=2 == 0
    assert spec[3] is None

    # heads NOT divisible by tensor → heads stay unsharded, rest holds
    cfg3 = dataclasses.replace(
        ModelConfig.tiny(), n_heads=3, n_kv_heads=3, hidden=96,
        attention_impl="ring", context_axis="context", mesh=mesh)
    spec3 = seq_parallel_spec(cfg3)
    assert spec3[0] == ("data", "fsdp") and spec3[2] is None

    # batch NOT divisible by the batch axes' product (B=1 eval on a
    # training mesh) → batch replicates as before instead of erroring
    spec_b1 = seq_parallel_spec(cfg, batch_size=1)
    # data (size 2) must drop; size-1 fsdp may stay (no-op shard)
    assert spec_b1[0] in (None, ("fsdp",), "fsdp") and spec_b1[1] == "context"
    spec_b4 = seq_parallel_spec(cfg, batch_size=4)
    assert spec_b4[0] == ("data", "fsdp")
    # partial divisibility keeps the largest dividing subset: mesh
    # {data:2, fsdp:2(implicit 1 here)...} — build one where data=2,
    # fsdp=2 and B=2 shards over 'data' only
    mesh22 = make_mesh({"data": 2, "fsdp": 2, "context": 2})
    cfg22 = dataclasses.replace(
        ModelConfig.tiny(), attention_impl="ring",
        context_axis="context", mesh=mesh22)
    assert seq_parallel_spec(cfg22, batch_size=2)[0] in (("data",), "data")
    # ...and is truly the LARGEST subset, not a greedy prefix: with
    # data=2, fsdp=4 and B=4, fsdp alone (4-way) beats data (2-way)
    mesh24 = make_mesh({"data": 2, "fsdp": 4})
    cfg24 = dataclasses.replace(
        ModelConfig.tiny(), attention_impl="ring",
        context_axis="context", mesh=mesh24)
    assert seq_parallel_spec(cfg24, batch_size=4)[0] in (("fsdp",), "fsdp")

    # ulysses: heads shard over tensor ONLY if the per-shard head count
    # still divides the context axis (the all-to-all redistributes
    # heads) — n_heads=8, tensor=4, context=4 → local heads 2 % 4 != 0
    mesh44 = make_mesh({"tensor": 4, "context": 2})
    cfg_u = dataclasses.replace(
        ModelConfig.tiny(), n_heads=8, n_kv_heads=8, hidden=128,
        attention_impl="ulysses", context_axis="context", mesh=mesh44)
    assert seq_parallel_spec(cfg_u)[2] == "tensor"  # 8/4=2 % 2 == 0
    mesh44b = make_mesh({"tensor": 2, "context": 4})
    cfg_u2 = dataclasses.replace(cfg_u, mesh=mesh44b)
    assert seq_parallel_spec(cfg_u2)[2] == "tensor"  # 8/2=4 % 4 == 0
    cfg_u3 = dataclasses.replace(
        cfg_u, n_heads=4, n_kv_heads=4, hidden=64, mesh=mesh44b)
    assert seq_parallel_spec(cfg_u3)[2] is None      # 4/2=2 % 4 != 0
    # ring has no head all-to-all: same shape shards fine
    assert seq_parallel_spec(
        dataclasses.replace(cfg_u3, attention_impl="ring"))[2] == "tensor"


def test_model_seq_parallel_train_step_on_data_context_mesh():
    """Full train step with ring attention on a data×context mesh where
    BOTH axes are >1 — the regime the advisor flagged (batch must shard
    over 'data' inside the shard_map, not be redundantly recomputed)."""
    import dataclasses

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = make_mesh({"data": 2, "context": 2}, devices=jax.devices()[:4])
    cfg = dataclasses.replace(
        ModelConfig.tiny(), attention_impl="ring",
        context_axis="context", mesh=mesh)
    model, state, tx = init_train_state(cfg, jax.random.PRNGKey(0), mesh=mesh)
    step = jax.jit(make_train_step(model, tx), donate_argnums=(0,))
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 33)), jnp.int32),
        batch_sharding(mesh),
    )
    state, metrics = step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))


def test_model_parallel_impl_without_mesh_raises():
    """ring/ulysses without a mesh must raise, not silently fall back to
    dense (advisor r4: silent fallback hides the misconfiguration until
    the long-context run OOMs)."""
    import dataclasses

    import pytest as _pytest

    cfg = dataclasses.replace(ModelConfig.tiny(), attention_impl="ring")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 16), 0, 256)
    with _pytest.raises(Exception, match="requires cfg.mesh"):
        DecoderLM(cfg).init(jax.random.PRNGKey(1), tokens)


def test_model_unknown_attention_impl_raises():
    import dataclasses

    import pytest as _pytest

    from traceml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"context": 2}, devices=jax.devices()[:2])
    cfg = dataclasses.replace(
        ModelConfig.tiny(), attention_impl="flashinfer",
        context_axis="context", mesh=mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 128), 0, 256)
    model = DecoderLM(cfg)
    with _pytest.raises(Exception, match="attention_impl"):
        model.init(jax.random.PRNGKey(1), tokens)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_model_gqa_seq_parallel_matches_dense(impl):
    """GQA (n_kv_heads < n_heads) through BOTH sequence-parallel paths:
    the kv-repeat happens BEFORE the shard_map boundary, so grouped
    heads must produce identical logits to the dense path — ulysses is
    the riskier interaction (its all-to-all redistributes the repeated
    heads across devices)."""
    import dataclasses

    mesh = make_mesh({"context": 4}, devices=jax.devices()[:4])
    base = ModelConfig(vocab_size=128, hidden=64, n_layers=1, n_heads=4,
                       n_kv_heads=2, max_seq_len=64, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 128)
    model = DecoderLM(base)
    params = model.init(jax.random.PRNGKey(6), tokens)
    dense = model.apply(params, tokens)
    cfg = dataclasses.replace(
        base, attention_impl=impl, context_axis="context", mesh=mesh)
    out = DecoderLM(cfg).apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), atol=2e-4, rtol=2e-4,
        err_msg=impl)
