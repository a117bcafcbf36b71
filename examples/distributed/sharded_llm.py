"""Sharded LLM training over a device mesh (dp × fsdp × tensor) with
full tracing — the flagship configuration.

Run on an N-device host (or CPU with
XLA_FLAGS=--xla_force_host_platform_device_count=8):

    traceml-tpu run --mode summary examples/distributed/sharded_llm.py
"""

import jax
import numpy as np

import traceml_tpu
from traceml_tpu.models import ModelConfig, init_train_state, make_train_step
from traceml_tpu.parallel import IciStatAggregator, StatVector, batch_sharding, make_mesh

traceml_tpu.init(mode="auto")

n = len(jax.devices())
tensor = 2 if n % 2 == 0 else 1
mesh = make_mesh({"tensor": tensor, "fsdp": -1})
print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)))

cfg = ModelConfig(vocab_size=8192, hidden=512, n_layers=4, n_heads=8,
                  n_kv_heads=4, max_seq_len=512)
model, state, tx = init_train_state(cfg, jax.random.PRNGKey(0), mesh=mesh)
step = traceml_tpu.wrap_step_fn(
    make_train_step(model, tx, mesh=mesh), donate_argnums=(0,)
)

ici = IciStatAggregator(mesh)
rng = np.random.default_rng(0)
for i in range(30):
    with traceml_tpu.trace_step() as ts:
        # host array → each device receives only its batch shard
        tokens = jax.device_put(
            rng.integers(0, cfg.vocab_size, (8, 256)).astype(np.int32),
            batch_sharding(mesh),
        )
        state, metrics = step(state, tokens)
        ts.mark(metrics["loss"])
    if i % 10 == 9:
        gathered = ici.aggregate(
            StatVector({"step": i, "step_ms": float(metrics["loss"])})
        )
        print(f"step {i + 1}: ici gather {gathered.shape}")

print("final loss:", float(metrics["loss"]))
