"""The training script ``chip_smoke.py`` runs under the launcher.

One rank process owns the chip(s).  It trains the flagship decoder LM
through the public SDK (``init`` / ``wrap_step_fn`` / ``trace_step``)
for a few traced steps after warm-up, then runs the checks only a real
chip can answer, and writes what it saw to ``--out`` as JSON.  Each
phase returns a dict with ``ok``; ``chip_smoke.py`` reads the file and
the session's artifacts and fails unless every phase is there and ok.

* default: the flagship at its one-chip width (``ModelConfig.flagship``,
  batch 16 x 512), the Pallas flash kernel compiled vs the reference at
  the model's head_dim 64 and S >= 1024, and async marker resolution;
* ``--four-chips``: the sharded configuration of
  ``examples/distributed/sharded_llm.py`` on a data x fsdp x tensor
  mesh over 4 chips, the ICI stat all-gather, and the sharded loss vs
  the same seed's step on a 1-device mesh.

Off a TPU every phase runs at tiny widths, as a rehearsal for the
tests: ``chip_smoke.py`` refuses any platform but ``tpu``, so such a
run never passes as a chip run, and no full-size model is ever built
on a CPU.  Weights and tokens are random, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import traceml_tpu
from traceml_tpu.models import ModelConfig, init_train_state, make_train_step

WARMUP_STEPS = 2
TRACED_STEPS = 8
#: bf16 compute over a different sharding reorders the reductions
LOSS_RTOL = 2e-2
#: the kernel and the reference both run f32 matmuls at the MXU's
#: default (bf16-pass) precision; 3e-2 bounds their divergence at D=64
KERNEL_ATOL = 3e-2


def _train(step, state, batches):
    """Warm-up plus traced steps; host-clock step times (each step ends
    in a host read of the loss, so it waits for the device)."""
    losses, times = [], []
    for i in range(WARMUP_STEPS + TRACED_STEPS):
        t0 = time.perf_counter()
        with traceml_tpu.trace_step() as ts:
            state, metrics = step(state, batches[i % len(batches)])
            ts.mark(metrics["loss"])
        losses.append(float(metrics["loss"]))
        times.append(time.perf_counter() - t0)
    return state, losses, times


def _tokens(cfg, batch, seq, seed, n):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        for _ in range(n)
    ]


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def train_phase(tiny: bool, seed: int) -> dict:
    cfg = ModelConfig.tiny() if tiny else ModelConfig.flagship()
    batch, seq = (4, 64) if tiny else (16, 512)
    model, state, tx = init_train_state(cfg, jax.random.PRNGKey(seed))
    step = traceml_tpu.wrap_step_fn(
        make_train_step(model, tx), donate_argnums=(0,)
    )
    batches = [jax.device_put(t) for t in _tokens(cfg, batch, seq, seed, 4)]
    _, losses, times = _train(step, state, batches)
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(state["params"])
    )
    return {
        "ok": all(np.isfinite(losses)),
        "n_params": int(n_params),
        "batch": [batch, seq],
        "losses": losses,
        "first_step_s": times[0],
        "step_ms_median": statistics.median(times[WARMUP_STEPS:]) * 1e3,
        "peak_bytes": _peak_bytes(jax.devices()[0]),
    }


def kernel_phase(tiny: bool, seed: int) -> dict:
    """Pallas flash kernel vs the reference at the model's head_dim."""
    from traceml_tpu.ops.attention import causal_attention_reference
    from traceml_tpu.ops.pallas_attention import flash_attention

    cases = []
    for S in ((256,) if tiny else (1024, 4096)):
        q, k, v = (
            jax.random.normal(key, (2, S, 4, 64), jnp.float32)
            for key in jax.random.split(jax.random.PRNGKey(seed), 3)
        )
        lowered = jax.jit(flash_attention).lower(q, k, v).as_text()
        err = float(jnp.max(jnp.abs(
            flash_attention(q, k, v) - causal_attention_reference(q, k, v)
        )))
        cases.append({
            "S": S, "head_dim": 64, "max_abs_err": err,
            "tpu_custom_call": "tpu_custom_call" in lowered,
        })
    on_tpu = jax.default_backend() == "tpu"
    return {
        "ok": all(
            c["max_abs_err"] < KERNEL_ATOL
            and (c["tpu_custom_call"] or not on_tpu)
            for c in cases
        ),
        "cases": cases,
        "max_abs_err": max(c["max_abs_err"] for c in cases),
    }


def marker_phase() -> dict:
    """Device markers resolve by polling alone, with no host sync."""
    from traceml_tpu.utils.timing import DeviceMarker, smallest_leaf

    x = jnp.ones((2048, 2048), jnp.float32)
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    jax.block_until_ready(f(x))  # warm
    t0 = time.perf_counter()
    marker = DeviceMarker(smallest_leaf(f(x)))
    dispatch_s = time.perf_counter() - t0
    deadline = t0 + 5.0
    ready_after_s = None
    while time.perf_counter() < deadline:
        if marker.poll():
            ready_after_s = time.perf_counter() - t0
            break
        time.sleep(0.001)
    return {
        "ok": bool(marker.resolved) and dispatch_s < 0.5,
        "dispatch_s": dispatch_s,
        "ready_after_s": ready_after_s,
    }


def sharded_phase(tiny: bool, seed: int) -> dict:
    """The flagship sharded configuration over 4 chips vs 1 device."""
    from traceml_tpu.parallel import IciStatAggregator, StatVector
    from traceml_tpu.parallel.ici_stats import N_FIELDS, STAT_FIELDS
    from traceml_tpu.parallel.mesh import batch_sharding, make_mesh

    devices = jax.devices()[:4]
    if len(devices) != 4:
        raise RuntimeError(f"need 4 devices, have {len(jax.devices())}")
    if tiny:
        cfg, batch, seq = ModelConfig.tiny(), 8, 64
    else:  # examples/distributed/sharded_llm.py
        cfg = ModelConfig(vocab_size=8192, hidden=512, n_layers=4,
                          n_heads=8, n_kv_heads=4, max_seq_len=512)
        batch, seq = 8, 256
    tokens = _tokens(cfg, batch, seq, seed, 4)

    mesh = make_mesh({"data": 1, "fsdp": 2, "tensor": 2}, devices=devices)
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    model, state, tx = init_train_state(
        cfg, jax.random.PRNGKey(seed), mesh=mesh
    )
    step = traceml_tpu.wrap_step_fn(
        make_train_step(model, tx, mesh=mesh), donate_argnums=(0,)
    )
    batches = [jax.device_put(t, batch_sharding(mesh)) for t in tokens]
    state, losses, times = _train(step, state, batches)
    embed = state["params"]["embed"]["embedding"]
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    ]

    # the same seed's steps on a 1-device mesh (plain jit, untraced)
    one = make_mesh({"fsdp": 1}, devices=devices[:1])
    model1, state1, tx1 = init_train_state(
        cfg, jax.random.PRNGKey(seed), mesh=one
    )
    step1 = jax.jit(
        make_train_step(model1, tx1, mesh=one), donate_argnums=(0,)
    )
    ref_losses = []
    for i in range(2):
        state1, metrics = step1(
            state1, jax.device_put(tokens[i], batch_sharding(one))
        )
        ref_losses.append(float(metrics["loss"]))
    loss_ok = bool(np.allclose(losses[:2], ref_losses, rtol=LOSS_RTOL))

    # ICI all-gather of distinct per-device stat vectors
    vectors = [
        StatVector({f: float(10 * r + j) for j, f in enumerate(STAT_FIELDS)})
        for r in range(4)
    ]
    gathered = IciStatAggregator(mesh).aggregate_many(vectors)
    want = np.stack([v.to_array() for v in vectors])
    ici_ok = gathered.shape == (4, N_FIELDS) and np.array_equal(gathered, want)

    return {
        "ok": bool(
            len(set(mesh_ids)) == 4
            and len(embed.sharding.device_set) == 4
            and all(np.isfinite(losses))
            and loss_ok and ici_ok
        ),
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "mesh_device_ids": mesh_ids,
        "embed_shard_devices": len(embed.sharding.device_set),
        "bytes_in_use_per_device": in_use,
        "losses": losses,
        "ref_losses_1dev": ref_losses,
        "loss_ok": loss_ok,
        "ici_shape": list(gathered.shape),
        "ici_ok": bool(ici_ok),
        "step_ms_median": statistics.median(times[WARMUP_STEPS:]) * 1e3,
        "first_step_s": times[0],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    parser.add_argument("--four-chips", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    device = jax.devices()[0]
    tiny = device.platform != "tpu"
    traceml_tpu.init(mode="auto")
    result = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "phases": {},
    }
    if args.four_chips:
        phases = {"sharded": lambda: sharded_phase(tiny, args.seed)}
    else:
        phases = {
            "train": lambda: train_phase(tiny, args.seed),
            "kernel": lambda: kernel_phase(tiny, args.seed),
            "marker": marker_phase,
        }
    for name, run in phases.items():
        result["phases"][name] = run()
    tmp = args.out + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(result, fh)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
