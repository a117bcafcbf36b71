"""One run of one cell, from process start to the result line.

The program under test is driven as a user's training script drives it:
the aggregator in a process of its own (``dashboard`` mode, through the
launcher's env contract), the runtime agent and ``init(mode="auto")`` in
this process, ``wrap_step_fn(make_train_step(...), donate_argnums=(0,))``
called inside ``trace_step`` with the loss read on the host every step.
A poller process reads ``/api/live`` throughout.

Set-up builds the one step object and its state, replaces the program's
initial weights with the benchmark's own (``weights.py``), and drives it
through its first steps with the window's own call and feed; the first
three are what the reference follows. The window is a closed loop for
``--seconds``. A traced run then alternates bare and attached chunks for
the tracer's share, and profiles a short sub-window. After the window:
the device's peak memory, the runtime's final flush, the aggregator's
step rows, then the reference, once the program's state is freed.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import signal
import sqlite3
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax

import traceml_tpu
from traceml_tpu.models import ModelConfig, init_train_state, make_train_step
from traceml_tpu.parallel.mesh import batch_sharding, make_mesh

from benchmark import check, discovery, reference, trace_reduce
from benchmark.flops import train_step_flops
from benchmark.peaks import peaks_for
from benchmark.weights import init_params, make_params, seed_key, token_pool

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SESSION = "bench"
CHECK_STEPS = 3     # the first steps, which the reference follows
WARM_STEPS = 2      # further set-up steps before the window
POOL = 64           # distinct token batches the feed cycles through
OVERHEAD_PAIRS = 16  # bare/attached chunk pairs in a traced run
CHUNK_S = 0.5       # about this long each, and at least MIN_CHUNK steps
MIN_CHUNK = 4
PROFILE_S = 2.0     # profiled sub-window, at least PROFILE_STEPS steps
PROFILE_STEPS = 5
READY_TIMEOUT_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


# -- the stack around the rank ---------------------------------------------


class Stack:
    """Aggregator process, runtime agent, and (later) the poller."""

    def __init__(self, logs: Path, interval: float) -> None:
        from traceml_tpu.launcher.process import wait_for_ready_file
        from traceml_tpu.runtime.settings import (
            AggregatorEndpoint,
            TraceMLSettings,
            settings_to_env,
        )

        self.logs = logs
        self.poller: Optional[subprocess.Popen] = None
        settings = TraceMLSettings(
            session_id=SESSION, logs_dir=logs, mode="dashboard",
            aggregator=AggregatorEndpoint(port=0), expected_world_size=1,
            sampler_interval_sec=interval, finalize_timeout_sec=30.0,
        )
        env = dict(os.environ, **settings_to_env(settings), JAX_PLATFORMS="cpu")
        t0 = time.monotonic()
        self._agg_log = open(logs / "aggregator.log", "wb")
        self.aggregator = subprocess.Popen(
            [sys.executable, "-m", "traceml_tpu.aggregator.aggregator_main"],
            env=env, cwd=ROOT, stdout=self._agg_log, stderr=subprocess.STDOUT,
        )
        try:
            ready = wait_for_ready_file(
                settings.session_dir / "aggregator_ready.json", timeout=READY_TIMEOUT_S
            )
            self.ready_s = time.monotonic() - t0
            if ready is None or not ready.get("display_port"):
                raise RuntimeError(f"aggregator not ready with a dashboard: {ready}")
            self._start_runtime(logs, interval, int(ready["port"]))
        except BaseException:
            self.close()
            raise
        self.live_url = f"http://127.0.0.1:{ready['display_port']}/api/live"
        self.db = settings.session_dir / "telemetry.sqlite"

    def _start_runtime(self, logs: Path, interval: float, port: int) -> None:
        from traceml_tpu.runtime.identity import RuntimeIdentity
        from traceml_tpu.runtime.runtime import TraceMLRuntime
        from traceml_tpu.runtime.settings import AggregatorEndpoint, TraceMLSettings

        self.runtime = TraceMLRuntime(
            TraceMLSettings(
                session_id=SESSION, logs_dir=logs, mode="dashboard",
                aggregator=AggregatorEndpoint(port=port),
                sampler_interval_sec=interval,
            ),
            RuntimeIdentity(global_rank=0),
        )
        self.runtime.start()
        traceml_tpu.init(mode="auto")

    def start_poller(self, interval: float, seed: int) -> None:
        out = self.logs / "polls.jsonl"
        self.poll_file = out
        self.poller = subprocess.Popen(
            [sys.executable, str(HERE / "poller.py"), "--url", self.live_url,
             "--interval", str(interval), "--out", str(out), "--seed", str(seed)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
            stdout=subprocess.DEVNULL,
        )
        ready = Path(str(out) + ".ready")
        deadline = time.monotonic() + READY_TIMEOUT_S
        while not ready.exists():
            if time.monotonic() > deadline or self.poller.poll() is not None:
                raise RuntimeError(f"{self.live_url} does not answer with a step_time view")
            time.sleep(0.05)

    def stop_poller(self) -> List[list]:
        self.poller.send_signal(signal.SIGTERM)
        self.poller.wait(timeout=30)
        self.poller = None
        return [json.loads(line) for line in self.poll_file.read_text().splitlines()]

    def sampler_us(self) -> int:
        """Collect + encode + flush microseconds, summed over samplers."""
        stats = self.runtime.publisher.stats()["samplers"]
        return sum(s["collect_us"] + s["encode_us"] + s["flush_us"] for s in stats.values())

    def finish(self) -> set:
        """Final flush, aggregator finalize; the steps rank 0 has rows for."""
        self.runtime.stop()
        self.aggregator.send_signal(signal.SIGTERM)
        self.aggregator.wait(timeout=120)
        with sqlite3.connect(f"file:{self.db}?mode=ro", uri=True) as conn:
            rows = conn.execute(
                "SELECT DISTINCT step FROM step_time_samples WHERE global_rank = 0"
            ).fetchall()
        return {r[0] for r in rows}

    def close(self) -> None:
        for proc in (self.poller, getattr(self, "aggregator", None)):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        self._agg_log.close()


# -- the program -------------------------------------------------------------


def model_config(spec: dict) -> ModelConfig:
    """The program's ModelConfig for the cell, checked against the widths
    the configuration file states."""
    m = spec["model"]
    cfg = ModelConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], ffn_mult=m["program"]["ffn_mult"],
        max_seq_len=spec["load"]["seq"], rope_theta=float(m["rope_theta"]),
    )
    if (cfg.head_dim, cfg.ffn_hidden) != (m["head_dim"], m["intermediate_size"]):
        raise ValueError(
            f"ModelConfig gives head_dim {cfg.head_dim}, ffn {cfg.ffn_hidden}; "
            f"the configuration states {m['head_dim']}, {m['intermediate_size']}"
        )
    return cfg


def _first_moment(opt_state):
    """Adam's first moment, wherever the optimizer keeps it."""
    found = [
        s.mu for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0]


class Program:
    """The one step object, its state and its feed."""

    def __init__(self, spec: dict, seed: int, devices: list) -> None:
        m, load, cell = spec["model"], spec["load"], spec["cell"]
        self.spec, self.seed, self.devices = spec, seed, devices
        cfg = model_config(spec)
        self.mesh = make_mesh(cell["mesh"], devices=devices) if cell.get("mesh") else None
        key = jax.random.key_data(seed_key(seed))
        model, state, tx = init_train_state(
            cfg, key, learning_rate=m["optimizer"]["learning_rate"], mesh=self.mesh
        )
        params = state["params"]
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        want = reference.param_shapes(m)
        if reference.leaf_paths(shapes) != reference.leaf_paths(want) or [
            (s.shape, s.dtype) for s in jax.tree.leaves(shapes)
        ] != [(s.shape, s.dtype) for s in jax.tree.leaves(want)]:
            raise ValueError("the program's parameters are not the reference's layout")
        self.shapes = shapes
        shardings = jax.tree.map(lambda a: a.sharding, params)
        state["params"] = params = None
        state["params"] = make_params(seed, shapes, m["init_std"], shardings)
        self.state = state
        self.paths = reference.leaf_paths(shapes)

        self.pool_np = token_pool(seed, POOL, load["batch"], load["seq"], m["vocab_size"])
        sharding = batch_sharding(self.mesh) if self.mesh is not None else devices[0]
        self.pool = [jax.device_put(b, sharding) for b in self.pool_np]
        fn = make_train_step(model, tx, mesh=self.mesh)
        self.step = traceml_tpu.wrap_step_fn(fn, donate_argnums=(0,))
        self._fn = fn
        self.bare_step = None
        self.i = 0
        self.completions: List[tuple] = []  # (sdk step, monotonic s at loss)

    def attached(self) -> float:
        """One step as the window runs it; its wall seconds."""
        toks = self.pool[self.i % POOL]
        self.i += 1
        t0 = time.monotonic()
        with traceml_tpu.trace_step() as ts:
            self.state, m = self.step(self.state, toks)
            ts.mark(m["loss"])
        self.last_loss = float(m["loss"])
        t1 = time.monotonic()
        self.completions.append((ts.step, t1))
        return t1 - t0

    def attached_annotated(self) -> None:
        """The same step, its host phases as profiler spans."""
        from jax.profiler import TraceAnnotation

        toks = self.pool[self.i % POOL]
        self.i += 1
        ts = traceml_tpu.trace_step()
        with TraceAnnotation("trace_step_enter"):
            ts.__enter__()
        with TraceAnnotation("dispatch"):
            self.state, m = self.step(self.state, toks)
            ts.mark(m["loss"])
        with TraceAnnotation("trace_step_exit"):
            ts.__exit__(None, None, None)
        with TraceAnnotation("wait_loss"):
            self.last_loss = float(m["loss"])

    def bare(self) -> float:
        """The same step through a plain ``jax.jit``, no tracer."""
        if self.bare_step is None:
            self.bare_step = jax.jit(self._fn, donate_argnums=(0,))
        toks = self.pool[self.i % POOL]
        self.i += 1
        t0 = time.monotonic()
        self.state, m = self.bare_step(self.state, toks)
        self.last_loss = float(m["loss"])
        return time.monotonic() - t0

    def check_steps(self) -> tuple:
        """The first CHECK_STEPS steps and the program's readings; returns
        (readings, seconds of the first step)."""
        b1 = self.spec["model"]["optimizer"]["b1"]
        first_s = self.attached()
        losses = [self.last_loss]
        mu = _first_moment(self.state["opt_state"])
        gnorms = jax.device_get(jax.jit(reference.leaf_norms)(mu)) / (1 - b1)
        for _ in range(CHECK_STEPS - 1):
            self.attached()
            losses.append(self.last_loss)
        std = self.spec["model"]["init_std"]
        shapes = self.shapes

        def delta_norms(params, key):
            return reference.leaf_norms(
                jax.tree.map(lambda p, p0: p - p0, params, init_params(key, shapes, std))
            )

        dnorms = jax.device_get(jax.jit(delta_norms)(self.state["params"], seed_key(self.seed)))
        readings = {
            "losses": losses,
            "grad_norms": dict(zip(self.paths, map(float, gnorms))),
            "delta_norms": dict(zip(self.paths, map(float, dnorms))),
        }
        return readings, first_s

    def free(self) -> None:
        self.state = self.step = self.bare_step = self._fn = self.pool = None
        gc.collect()


def reference_readings(spec: dict, program: Program, devices: list, variant="f32") -> Dict:
    m = spec["model"]
    return reference.readings(
        m, m["optimizer"], m["init_std"], seed_key(program.seed),
        program.pool_np[:CHECK_STEPS], variant, devices,
    )


def peak_bytes(devices: list) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


# -- one run ----------------------------------------------------------------


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, spec: Optional[dict] = None) -> tuple:
    """Returns (result dict, check lines for stderr)."""
    marks = [("imports", time.monotonic())]
    spec = spec or discovery.load_cell(name)
    devices = devices_for(spec["chips"], require_tpu)
    marks.append(("backend_init", time.monotonic()))
    load = spec["load"]
    logs = Path(tempfile.mkdtemp(prefix="bench_"))
    stack = None
    try:
        stack = Stack(logs, load["sampler_interval_s"])
        marks.append(("aggregator_and_runtime", time.monotonic()))
        prog = Program(spec, seed, devices)
        marks.append(("init_and_weights", time.monotonic()))
        readings, first_step_s = prog.check_steps()
        marks.append(("check_steps", time.monotonic()))
        for _ in range(WARM_STEPS):
            prog.attached()
        if trace:
            prog.bare()
        marks.append(("warm_steps", time.monotonic()))
        stack.start_poller(load["poll_interval_s"], seed)
        marks.append(("poller_ready", time.monotonic()))
        setup_s = time.monotonic() - t_start
        split = {
            name: t - prev for (name, t), prev in zip(marks, [t_start] + [t for _, t in marks])
        }
        split["first_step_of_check_steps"] = first_step_s

        us0 = stack.sampler_us()
        w0 = time.monotonic()
        first = len(prog.completions)
        step_s = []
        while time.monotonic() - w0 < seconds:
            step_s.append(prog.attached())
        w1 = time.monotonic()
        us1 = stack.sampler_us()
        window_steps = [s for s, _ in prog.completions[first:]]

        record = {
            "chips": len(devices), "seconds": seconds,
            "setup_s": setup_s, "aggregator_ready_s": stack.ready_s,
            "first_step_s": first_step_s, "setup_split": split,
            "window": {"start": w0, "stop": w1, "step_s": step_s},
            "tokens_per_step": load["batch"] * (load["seq"] - 1),
            "flops_per_step": train_step_flops(spec["model"], load["batch"], load["seq"]),
            "sampler_us": us1 - us0,
        }
        breakdown = None
        if trace:
            record["overhead"] = _overhead(prog, stack, statistics.median(step_s))
            record["trace"] = _profile(prog, logs, statistics.median(step_s))
            breakdown = {k: record["trace"][k] for k in ("device_ops", "idle_gaps")}
        record["polls"] = stack.stop_poller()
        record["completions"] = prog.completions
        memory_peak = peak_bytes(devices)
        stored = stack.finish()
        missing = sum(1 for s in window_steps if s not in stored)
        prog.free()
        ref = reference_readings(spec, prog, devices)
    finally:
        if stack is not None:
            stack.close()
        shutil.rmtree(logs, ignore_errors=True)

    numbers = dict(check.gaps(readings, ref), missing_steps=missing)
    limits = dict(spec["cell"]["limits"], missing_steps=0)
    kind = devices[0].device_kind
    record["peak_flops"] = peaks_for(kind)["bf16_flops"] if require_tpu else None
    metrics = discovery.read_metrics(discovery.metrics_for(name, trace), record)
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    if trace:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    result = {
        "correct": check.judge(numbers, limits),
        "attempted": len(window_steps),
        "failed": missing,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    lines = [f"setup_split {record['setup_split']}"]
    if trace:
        lines.append(f"overhead_chunks_ms [arm, first step, mean of the rest] {record['overhead']['chunks_ms']}")
    lines += [f"check {k} {numbers[k]!r} limit {limits[k]!r}" for k in limits]
    return result, lines


def _overhead(prog: Program, stack: Stack, step_s: float) -> Dict:
    """Alternating chunks of bare and attached steps, order flipped each
    pair; the runtime agent is paused during bare chunks."""
    k = max(MIN_CHUNK, round(CHUNK_S / step_s))
    attached, bare, chunks = [], [], []
    for r in range(OVERHEAD_PAIRS):
        for arm in (("a", "b") if r % 2 == 0 else ("b", "a")):
            if arm == "a":
                t = [prog.attached() for _ in range(k)]
                attached += t
            else:
                stack.runtime.pause()
                t = [prog.bare() for _ in range(k)]
                stack.runtime.resume()
                bare += t
            chunks.append([arm, 1e3 * t[0], 1e3 * statistics.fmean(t[1:])])
    return {"attached_s": attached, "bare_s": bare, "chunks_ms": chunks}


def _profile(prog: Program, logs: Path, step_s: float) -> Dict:
    n = max(PROFILE_STEPS, round(PROFILE_S / step_s))
    trace_dir = logs / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for _ in range(n):
                prog.attached_annotated()
    finally:
        jax.profiler.stop_trace()
    reduced = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    reduced["steps"] = n
    return reduced
