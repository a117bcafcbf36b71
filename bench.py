"""Tracer-overhead benchmark — the headline metric.

Measures the cost of the FULL observability stack (``init(auto)`` patches,
``wrap_step_fn`` compile attribution, ``trace_step`` envelopes, step-memory
edges, the runtime agent's sampler thread, telemetry over a real TCP socket
to an in-process aggregator sink) against a plain ``jax.jit`` loop on the
flagship decoder LM.

Methodology: one process owns the chip and runs both arms in
alternating paired rounds (the traced stack's threads paused while the
untraced arm is timed); each round's statistic is the min over its
steps, and the reported value is the median per-round delta with a
bootstrap 95% CI printed alongside.  A short-step lane (~ms steps, where
tracer overhead is proportionally largest) is reported beside it.

There is no CPU fallback: without a TPU the default path exits
non-zero and prints no result.  ``--pair`` runs one untraced-then-traced
pair of the CPU proxy model in this process and writes ``{"u", "t"}``
(seconds per step) to ``--out``; it is a rehearsal of the traced stack,
not a device measurement.  JAX's persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<repo>/.jax_cache``.

Prints ONE JSON line::

    {"metric": "tracer_step_overhead_pct", "value": <pct>, "unit": "%",
     "vs_baseline": <pct / 1.0>}

``vs_baseline`` is the ratio against the reference's published claim of
"under 1% overhead" (reference README.md:44); the driver target is <2%
(BASELINE.md).  Lower is better; <1.0 beats the reference's claim.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

WARMUP_STEPS = 6
ROUNDS = 10
STEPS_PER_ROUND = 16


# --------------------------------------------------------------------------
# model / loop (shared by both arms)
# --------------------------------------------------------------------------

def _build(short: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from traceml_tpu.models import ModelConfig, init_train_state, make_train_step

    platform = jax.default_backend()
    if short:
        # the short-step lane: tracer overhead is proportionally
        # largest on very short steps (ref architecture.md:73,89)
        cfg = ModelConfig(
            vocab_size=1024, hidden=128, n_layers=2, n_heads=4,
            n_kv_heads=2, max_seq_len=64,
        )
        batch, seq = 2, 64
    elif platform == "tpu":
        # sized so one fwd+bwd+opt step is ~7 TFLOP — tens of ms on a
        # real single chip, comfortably above the tracer's µs-scale
        # per-step cost and the measurement noise floor
        cfg = ModelConfig.flagship()
        batch, seq = 16, 512
    else:  # CPU proxy: big enough that steps are ≥100 ms (noise floor)
        cfg = ModelConfig(
            vocab_size=2048, hidden=256, n_layers=2, n_heads=4,
            n_kv_heads=2, max_seq_len=256,
        )
        batch, seq = 4, 128

    model, state, tx = init_train_state(cfg, jax.random.PRNGKey(0))
    train_step = make_train_step(model, tx)
    rng = np.random.default_rng(0)
    batches = [
        jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        for _ in range(8)
    ]
    return model, state, tx, train_step, batches


def _step_flops(state, batches) -> float:
    """~6·params·tokens FLOPs per training step (fwd 2 + bwd 4); the
    optimizer state's leaves are not parameters."""
    import jax

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state["params"]))
    tokens = batches[0].shape[0] * batches[0].shape[1]
    return 6.0 * float(n_params) * float(tokens)


def _run_loop(step_fn, state, batches, n_steps, bracket=None, stat=None):
    """Time n_steps; returns (stat(step_s), final_state).
    ``stat`` defaults to the median; the measured arms pass ``min``."""
    import jax

    times = []
    for i in range(n_steps):
        tokens = batches[i % len(batches)]
        t0 = time.perf_counter()
        if bracket is not None:
            with bracket():
                state, metrics = step_fn(state, tokens)
        else:
            state, metrics = step_fn(state, tokens)
        # per-step sync: measures true per-step cost including device
        # time; identical in both arms so the delta is tracer overhead
        jax.block_until_ready(metrics["loss"])
        times.append(time.perf_counter() - t0)
    return (stat or statistics.median)(times), state


# --------------------------------------------------------------------------
# arms
# --------------------------------------------------------------------------


def _start_traced_stack():
    """Bring up the FULL traced stack exactly as the product deploys it:
    the aggregator in its OWN process (the launcher always spawns it
    standalone — launcher/commands.py), the per-rank runtime agent +
    auto patches in this one.  Returns (traceml_tpu module, runtime,
    stop callable).  Shared by every live bench mode so they all
    measure the same configuration.

    The aggregator must NOT share the training process here: its event
    loop / sqlite writer / TCP drain threads are infrastructure that the
    launcher architecture puts out of the training process, and hosting
    them in-process inflates the measured per-step cost with GIL
    contention the product never pays (visible on the short-step lane:
    ~1 ms/step on a 1-core host).
    """
    import tempfile

    import traceml_tpu
    from traceml_tpu.launcher.process import wait_for_ready_file
    from traceml_tpu.runtime.identity import RuntimeIdentity
    from traceml_tpu.runtime.runtime import TraceMLRuntime
    from traceml_tpu.runtime.settings import (
        AggregatorEndpoint,
        TraceMLSettings,
        settings_to_env,
    )

    tmp = Path(tempfile.mkdtemp(prefix="traceml_bench_"))
    agg_settings = TraceMLSettings(
        session_id="bench", logs_dir=tmp, mode="summary",
        aggregator=AggregatorEndpoint(port=0), expected_world_size=1,
        finalize_timeout_sec=10.0,
    )
    env = dict(os.environ)
    # the same env contract the launcher uses for its aggregator spawn
    # (launcher/commands.py) — hand-rolled keys would silently drift
    env.update(settings_to_env(agg_settings))
    # the aggregator child must never touch the device backend
    env["JAX_PLATFORMS"] = "cpu"
    agg_proc = subprocess.Popen(
        [sys.executable, "-m", "traceml_tpu.aggregator.aggregator_main"],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    session_dir = tmp / "bench"
    ready = wait_for_ready_file(
        session_dir / "aggregator_ready.json", timeout=30.0
    )
    if ready is None:
        agg_proc.kill()
        raise RuntimeError("bench aggregator failed to become ready")
    runtime = TraceMLRuntime(
        TraceMLSettings(
            session_id="bench", logs_dir=tmp, mode="summary",
            aggregator=AggregatorEndpoint(port=int(ready["port"])),
            sampler_interval_sec=1.0,
        ),
        RuntimeIdentity(global_rank=0),
    )
    runtime.start()
    traceml_tpu.init(mode="auto")

    def stop():
        runtime.stop()
        agg_proc.terminate()
        try:
            agg_proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            agg_proc.kill()

    return traceml_tpu, runtime, stop


def _pair_child(steps: int, out_path: Path, short: bool = False) -> int:
    """One FULL pair in one process, untraced arm first (the ``--pair``
    CPU rehearsal).

    Isolation holds because no tracer component is initialized until
    the untraced measurement is done — the baseline runs with zero
    tracer threads (only traceml's model library gets imported, which
    starts nothing).
    """
    import jax

    # enforce the strongest checkable precondition: the bench process
    # reached this point without anything preloading traceml
    assert "traceml_tpu" not in sys.modules
    model, state, tx, train_step, batches = _build(short)
    plain = jax.jit(train_step, donate_argnums=(0,))
    _, state = _run_loop(plain, state, batches, WARMUP_STEPS)
    u, state = _run_loop(plain, state, batches, steps, stat=min)

    traceml_tpu, runtime, stop = _start_traced_stack()
    model2, state2, tx2, train_step2, batches2 = _build(short)
    traced = traceml_tpu.wrap_step_fn(train_step2, donate_argnums=(0,))
    _, state2 = _run_loop(
        traced, state2, batches2, WARMUP_STEPS, bracket=traceml_tpu.trace_step
    )
    t, state2 = _run_loop(
        traced, state2, batches2, steps,
        bracket=traceml_tpu.trace_step, stat=min,
    )
    stop()

    tmp_out = out_path.with_suffix(".tmp")
    tmp_out.write_text(json.dumps({"u": u, "t": t}))
    os.replace(tmp_out, out_path)
    return 0


def _short_step_summary(su_all, st_all, sd_all, steps_per_arm: int) -> dict:
    """The short-lane block of the result (one shape, one site)."""
    lo, hi = _bootstrap_ci(sd_all)
    return {
        "untraced_ms": round(statistics.median(su_all) * 1000, 3),
        "traced_ms": round(statistics.median(st_all) * 1000, 3),
        "median_delta_pct": round(statistics.median(sd_all), 3),
        "ci95_pct": [round(lo, 3), round(hi, 3)],
        "pairs": len(sd_all),
        "steps_per_arm": steps_per_arm,
    }


def _bootstrap_ci(deltas, n=2000, seed=0):
    import random

    rng = random.Random(seed)
    meds = sorted(
        statistics.median(rng.choices(deltas, k=len(deltas))) for _ in range(n)
    )
    return meds[int(0.025 * n)], meds[int(0.975 * n)]


def _report(u_all, t_all, deltas, backend: str,
            steps: int = STEPS_PER_ROUND, extra: dict | None = None) -> int:
    lo, hi = _bootstrap_ci(deltas)
    overhead_pct = max(0.0, statistics.median(deltas))
    print(
        f"[bench] untraced {statistics.median(u_all) * 1000:.2f} ms/step, "
        f"traced {statistics.median(t_all) * 1000:.2f} ms/step on "
        f"{backend} — median delta "
        f"{statistics.median(deltas):+.2f}% (95% CI [{lo:+.2f}, {hi:+.2f}], "
        f"{len(deltas)} paired rounds × {steps} steps; per-round: "
        f"{[round(d, 1) for d in deltas]})",
        file=sys.stderr,
    )
    payload = {
        "metric": "tracer_step_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "vs_baseline": round(overhead_pct / 1.0, 3),
    }
    payload.update(extra or {})
    print(json.dumps(payload))
    return 0


def _run_interleaved(rounds: int = ROUNDS, steps: int = STEPS_PER_ROUND) -> int:
    """Single-process paired rounds on the chip: one process owns it,
    so both arms share this process.  Arm ORDER alternates per round so
    monotone drift cancels in the cross-round median, and the per-round
    statistic is the min over steps (runtime hiccups are one-sided)."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"[bench] no TPU: JAX found {device.platform!r}; the CPU "
            "pair lane is `bench.py --pair --out FILE`",
            file=sys.stderr,
        )
        return 1

    model, state, tx, train_step, batches = _build()
    plain = jax.jit(train_step, donate_argnums=(0,))
    _, state = _run_loop(plain, state, batches, WARMUP_STEPS)

    traceml_tpu, runtime, stop = _start_traced_stack()

    model2, state2, tx2, train_step2, batches2 = _build()
    traced = traceml_tpu.wrap_step_fn(train_step2, donate_argnums=(0,))
    _, state2 = _run_loop(
        traced, state2, batches2, WARMUP_STEPS, bracket=traceml_tpu.trace_step
    )

    def _untraced():
        # quiesce the traced stack's background threads while timing the
        # untraced arm — the arms share one process on device-exclusive
        # backends, and the sampler must not perturb the baseline
        nonlocal state
        runtime.pause()
        u, state = _run_loop(plain, state, batches, steps, stat=min)
        runtime.resume()
        return u

    def _traced():
        nonlocal state2
        t, state2 = _run_loop(
            traced, state2, batches2, steps,
            bracket=traceml_tpu.trace_step, stat=min,
        )
        return t

    u_all, t_all, deltas = [], [], []
    for r in range(rounds):
        if r % 2 == 0:
            u, t = _untraced(), _traced()
        else:
            t, u = _traced(), _untraced()
        u_all.append(u)
        t_all.append(t)
        deltas.append((t - u) / u * 100.0)

    # short-step lane: same alternating-arm schedule on the short
    # model, reported beside the headline
    short_err: str | None = None
    su_all, st_all, sd_all = [], [], []
    # default schedule: more steps per arm (short steps are cheap, the
    # noise floor isn't) — but an EXPLICIT --steps sizes this lane too;
    # an explicit value that EQUALS the default gets the long lane
    short_steps = steps if steps != STEPS_PER_ROUND else 64
    try:
        s_model, s_state, s_tx, s_step_fn, s_batches = _build(short=True)
        s_plain = jax.jit(s_step_fn, donate_argnums=(0,))
        _, s_state = _run_loop(s_plain, s_state, s_batches, WARMUP_STEPS)
        s_model2, s_state2, s_tx2, s_step2, s_batches2 = _build(short=True)
        s_traced = traceml_tpu.wrap_step_fn(s_step2, donate_argnums=(0,))
        _, s_state2 = _run_loop(
            s_traced, s_state2, s_batches2, WARMUP_STEPS,
            bracket=traceml_tpu.trace_step,
        )
        for r in range(rounds):
            if r % 2 == 0:
                runtime.pause()
                su, s_state = _run_loop(
                    s_plain, s_state, s_batches, short_steps, stat=min
                )
                runtime.resume()
                st_, s_state2 = _run_loop(
                    s_traced, s_state2, s_batches2, short_steps,
                    bracket=traceml_tpu.trace_step, stat=min,
                )
            else:
                st_, s_state2 = _run_loop(
                    s_traced, s_state2, s_batches2, short_steps,
                    bracket=traceml_tpu.trace_step, stat=min,
                )
                runtime.pause()
                su, s_state = _run_loop(
                    s_plain, s_state, s_batches, short_steps, stat=min
                )
                runtime.resume()
            su_all.append(su)
            st_all.append(st_)
            sd_all.append((st_ - su) / su * 100.0)
    except Exception as exc:  # evidence lane, not the contract
        short_err = str(exc)
    stop()
    flops = _step_flops(state, batches)
    extra: dict = {"backend": device.platform}
    if sd_all:
        extra["short_step"] = _short_step_summary(
            su_all, st_all, sd_all, short_steps
        )
        if short_err is not None:
            # partial lane: an exception ended it early — say so
            # instead of reporting a clean-looking smaller sample
            extra["short_step"]["error"] = short_err
            print(f"[bench] short-step lane partial: {short_err}",
                  file=sys.stderr)
    elif short_err is not None:
        extra["short_step"] = {"error": short_err}
        print(f"[bench] short-step lane failed: {short_err}",
              file=sys.stderr)
    # on-chip provenance: device kind, achieved model FLOP/s on the
    # untraced arm, and MFU against the chip's published peak
    from traceml_tpu.utils.chip_specs import peak_flops_for

    achieved = flops / min(u_all)
    extra["device_kind"] = device.device_kind
    extra["device_count"] = len(jax.devices())
    extra["achieved_tflops"] = round(achieved / 1e12, 2)
    peak = peak_flops_for(device.device_kind)
    if peak:
        extra["mfu"] = round(achieved / peak, 4)
    return _report(u_all, t_all, deltas, device.platform, steps, extra=extra)


def _run_micro_benches() -> int:
    """The slow-marker micro-bench lane (tests/benchmarks/bench_*.py):
    aggregator/read-path component benches with built-in golden
    comparisons — live tick, window compute, codec, TCP drain, the
    high-rank ingest write path (watermark retention vs the seed
    windowed prune), the serving tier (delta protocol + shared
    payload cache under 8 sessions × 32 viewers), the topology
    attribution pass (mesh axis reductions + η² scoring), and the
    end-to-end tick pipeline (vectorized diagnosis + per-version
    caches vs the scalar legacy arm, with per-stage TICK_STAGES
    profile lines).  They run
    under pytest so their assertions (speedup floors, payload equality)
    gate the same way CI's slow lane runs them; ``-s`` keeps the
    bench_common JSON lines on stdout for collection into BENCH_LOCAL_*
    records."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never needs the chip
    return subprocess.run(
        [
            sys.executable, "-m", "pytest", str(REPO / "tests" / "benchmarks"),
            "-m", "slow", "-q", "-s", "-p", "no:cacheprovider",
        ],
        env=env,
    ).returncode


#: sizing dimensions that distinguish same-metric rows within one record
#: (e.g. window_compute at 256 vs 1024 ranks) — folded into the label
_TREND_DIM_KEYS = (
    "ranks", "steps", "rows", "sessions", "viewers", "world", "tiers",
    "arm", "domain", "stage",
)


def _trend_rows(payload) -> list:
    """Normalize one BENCH_LOCAL ``result`` payload to
    ``[(bench, metric, dims, unit, value), …]``.  Handles both shapes in
    the repo's history: a list of bench_common JSON lines (r07+) and a
    single headline dict (r05/r06/r10)."""
    rows = []
    if isinstance(payload, list):
        for r in payload:
            if not isinstance(r, dict) or "value" not in r:
                continue
            dims = tuple(
                (k, r[k]) for k in _TREND_DIM_KEYS if k in r
            )
            rows.append((
                str(r.get("bench", "?")), str(r.get("metric", "?")),
                dims, str(r.get("unit", "")), r["value"],
            ))
    elif isinstance(payload, dict):
        if "metric" in payload and "value" in payload:
            rows.append((
                str(payload.get("bench", "headline")),
                str(payload["metric"]), (),
                str(payload.get("unit", "")), payload["value"],
            ))
        else:  # flat metric→value dict (r10)
            for k, v in sorted(payload.items()):
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    rows.append(("headline", str(k), (), "", v))
    return rows


def _print_trend() -> int:
    """Consolidate the repo's BENCH_LOCAL_r*.json records into one
    printed trajectory table: bench → metric → per-round values.  Most
    metrics live in one or two rounds (each round benchmarks what it
    built); metrics re-measured across rounds show their trajectory on
    a single line."""
    import re

    records = []
    for path in sorted(REPO.glob("BENCH_LOCAL_r*.json")):
        m = re.search(r"r(\d+)", path.name)
        if not m:
            continue
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError) as exc:
            print(f"[trend] skipping {path.name}: {exc}", file=sys.stderr)
            continue
        records.append((int(m.group(1)), data.get("result")))
    if not records:
        print("[trend] no BENCH_LOCAL_r*.json records found")
        return 1
    records.sort()
    table: dict = {}
    for rnd, payload in records:
        for bench, metric, dims, unit, value in _trend_rows(payload):
            table.setdefault((bench, metric, dims, unit), {})[rnd] = value
    rounds = [rnd for rnd, _ in records]
    print(
        f"[trend] BENCH_LOCAL trajectory — {len(records)} rounds "
        f"(r{rounds[0]:02d}–r{rounds[-1]:02d}), {len(table)} metrics"
    )
    width_b = max(len(k[0]) for k in table)
    labels = {}
    for key in table:
        bench, metric, dims, unit = key
        qual = (
            "{" + ",".join(f"{k}={v}" for k, v in dims) + "}" if dims else ""
        )
        labels[key] = (metric + qual, unit)
    width_m = max(len(lbl) for lbl, _ in labels.values())
    for key in sorted(table):
        bench = key[0]
        lbl, unit = labels[key]
        cells = "  ".join(
            f"r{rnd:02d}={_trend_fmt(v)}" for rnd, v in sorted(table[key].items())
        )
        print(f"{bench:<{width_b}}  {lbl:<{width_m}}  {unit:<6} {cells}")
    return 0


def _trend_fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--pair", action="store_true",
        help="one untraced+traced pair of the CPU proxy in this process; "
        "writes {u, t} to --out (a rehearsal, not a device number)",
    )
    parser.add_argument("--short", action="store_true")
    parser.add_argument(
        "--micro", action="store_true",
        help="run the slow-marker component benches (tests/benchmarks) "
        "instead of the tracer-overhead measurement",
    )
    parser.add_argument(
        "--trend", action="store_true",
        help="print the consolidated BENCH_LOCAL_r* trajectory table "
        "(bench → metric → per-round values) and exit",
    )
    # None = lane defaults; explicit values size BOTH lanes
    parser.add_argument("--rounds", type=int, default=None)
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--out", type=str)
    args = parser.parse_args()
    if args.pair and not args.out:
        parser.error("--pair needs --out")

    if args.trend:
        return _print_trend()
    if args.micro:
        return _run_micro_benches()
    # no code sets another cache: an outside JAX_COMPILATION_CACHE_DIR
    # wins, else one fixed path in the checkout (children inherit it)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(REPO / ".jax_cache"))
    if args.pair:
        return _pair_child(
            STEPS_PER_ROUND if args.steps is None else args.steps,
            Path(args.out), short=args.short
        )
    return _run_interleaved(
        ROUNDS if args.rounds is None else args.rounds,
        STEPS_PER_ROUND if args.steps is None else args.steps,
    )


if __name__ == "__main__":
    sys.exit(main())
