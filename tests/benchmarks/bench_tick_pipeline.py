"""End-to-end tick pipeline: vectorized diagnosis arm vs scalar legacy.

The r20 diagnosis layer must pay for itself on the FULL serving-tier
tick — store refresh → incremental window build → diagnosis →
attribution → views → fragment serialization — not just on a rule
microbench.  Two ``SessionPublisher`` pipelines run over the SAME
session DB, one with ``TRACEML_VECTOR_DIAGNOSIS=1`` (vectorized gates +
per-(domain, version) diagnosis cache) and one with ``=0`` (the scalar
pre-change reference arm).  Interleaved min-of-N warm ticks, golden
byte-comparison of the served payload between arms BEFORE any timing:

* steady-state warm tick (heartbeat: a model_stats-only ingest
  re-dirties the step_time payload without advancing any diagnosis
  input — the serving tier's dominant tick shape between step bursts)
  at 1024 ranks × 240 steps: vectorized arm ≥ 3× faster than the
  scalar arm, the diagnosis cache hits, and ZERO rules evaluate;
* step-burst tick (one new step per rank lands between polls) is
  reported per arm as an informational metric — both arms share the
  irreducible refresh + ring-buffer-append + json.dumps floor there,
  so it is not the gated number;
* the per-stage tick profile (``TICK_STAGES``) for the vectorized arm
  is emitted as bench_common lines at the gate size.

The fixture is a clean straggler at scale (rank 0 slow in residual,
every other rank inflated by sync wait) so the straggler rules fire and
the scalar arm pays the per-rank window materialization the vector
gates avoid.  Results print as bench_common JSON lines (collected into
BENCH_LOCAL_r20.json at the repo root).
"""

import json
import os
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))
import bench_common  # noqa: E402

from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter  # noqa: E402
from traceml_tpu.renderers.serving import SessionPublisher  # noqa: E402
from traceml_tpu.samplers.serving_sampler import pack_floats  # noqa: E402
from traceml_tpu.telemetry.envelope import (  # noqa: E402
    SenderIdentity,
    build_telemetry_envelope,
)
from traceml_tpu.utils import timing as T  # noqa: E402

pytestmark = pytest.mark.slow

BENCH = "tick_pipeline"
FLAG = "TRACEML_VECTOR_DIAGNOSIS"
WINDOW = 240
STEPS = 240
RANKS_PER_NODE = 8
SERVING_RANKS = 8
REPS = 5

ARMS = (("vector", "1"), ("legacy", "0"))


# -- synthetic session -----------------------------------------------------


def _ident(rank, world):
    node = rank // RANKS_PER_NODE
    return SenderIdentity(
        session_id="bench",
        global_rank=rank,
        local_rank=rank % RANKS_PER_NODE,
        world_size=world,
        node_rank=node,
        hostname=f"host-{node}",
        pid=1000 + rank,
    )


def _step_rows(rank, start, n):
    """Clean-straggler fixture at scale: rank 0 is slow in residual
    (backward small, unexplained time large), every other rank's step is
    inflated by sync wait (backward swallows the gap).  Straggler rules
    fire, so the scalar arm runs the component-delta attribution over
    every rank's materialized window."""
    rows = []
    slow = rank == 0
    for s in range(start, start + n):
        base = 200.0 + (s % 7) * 0.05 + (rank % 5) * 0.01
        backward = 60.0 if slow else 156.0 + (s % 3) * 0.01
        rows.append({
            "step": s,
            "timestamp": float(s),
            "clock": "device",
            "events": {
                T.STEP_TIME: {"cpu_ms": base, "device_ms": base, "count": 1},
                T.DATALOADER_NEXT: {
                    "cpu_ms": 4.0, "device_ms": None, "count": 1,
                },
                T.BACKWARD_TIME: {
                    "cpu_ms": backward, "device_ms": backward, "count": 1,
                },
            },
        })
    return rows


def _model_row(ts):
    return {
        "timestamp": ts, "flops_per_step": 1.2e12,
        "flops_source": "estimated", "device_kind": "tpu",
        "peak_flops": 1.97e14, "device_count": 1,
        "tokens_per_step": 4096.0,
    }


def _mem_rows(start, n):
    return [
        {"step": s, "timestamp": float(s), "device_id": 0,
         "device_kind": "tpu", "current_bytes": 1 << 30,
         "peak_bytes": (1 << 30) + s, "step_peak_bytes": 1 << 30,
         "limit_bytes": 16 << 30, "backend": "fake"}
        for s in range(start, start + n)
    ]


def _coll_rows(rank, start, n):
    """One poorly-overlapped all_reduce every 4th step — enough volume
    to keep the collectives rules honest without doubling the DB."""
    rows = []
    for s in range(start, start + n):
        if s % 4:
            continue
        dur = 12.0 + (rank % 7) * 0.25
        rows.append({
            "step": s, "timestamp": float(s), "op": "all_reduce",
            "dtype": "float32", "count": 2, "bytes": 1 << 22,
            "group_size": RANKS_PER_NODE, "duration_ms": dur,
            "exposed_ms": dur * 0.8,
        })
    return rows


def _srv_rows(rank, start, n):
    rows = []
    for s in range(start, start + n):
        if s % 4:
            continue
        rows.append({
            "step": s, "timestamp": float(s),
            "requests_enqueued": 4, "requests_completed": 3,
            "requests_active": 2, "queue_depth": 6 + (rank % 3),
            "decode_tokens": 128, "prefill_ms": 18.0,
            "decode_ms": 90.0 + rank, "tokens_per_s": 240.0 - rank,
            "batch_occupancy": 0.5,
            "kv_bytes": 1 << 30, "kv_limit_bytes": 2 << 30,
            "kv_headroom": 0.5,
            "ttft_ms_list": pack_floats([40.0, 55.0, 70.0]),
            "e2e_ms_list": pack_floats([200.0, 260.0, 320.0]),
            "tokens_list": "16,16,16",
        })
    return rows


def _seed_db(db, ranks, steps):
    w = SQLiteWriter(db)
    w.start()
    for rank in range(ranks):
        ident = _ident(rank, ranks)
        w.ingest(build_telemetry_envelope(
            "step_time",
            {
                "step_time": _step_rows(rank, 1, steps),
                "model_stats": [_model_row(1.0)],
            },
            ident,
        ))
        w.ingest(build_telemetry_envelope(
            "step_memory",
            {"step_memory": _mem_rows(max(1, steps - 59), min(steps, 60))},
            ident,
        ))
        w.ingest(build_telemetry_envelope(
            "collectives",
            {"collectives": _coll_rows(rank, 1, steps)},
            ident,
        ))
        if rank < SERVING_RANKS:
            w.ingest(build_telemetry_envelope(
                "serving", {"serving": _srv_rows(rank, 1, steps)}, ident,
            ))
        if rank % RANKS_PER_NODE == 0:
            w.ingest(build_telemetry_envelope(
                "system",
                {"system": [
                    {"timestamp": float(i), "cpu_pct": 30.0,
                     "memory_used_bytes": 8 << 30,
                     "memory_total_bytes": 32 << 30, "memory_pct": 25.0}
                    for i in range(4)
                ]},
                ident,
            ))
    assert w.force_flush()
    return w


# -- golden comparison -----------------------------------------------------


def _payload_bytes(pub):
    """Served payload canonicalized for cross-arm comparison: drop the
    wall-clock stamp — every OTHER byte must match."""
    obj = pub.full_payload_dict()
    obj.pop("ts", None)
    return json.dumps(obj, sort_keys=True).encode()


def _golden_compare(pubs):
    blobs = {}
    for name, flag in ARMS:
        os.environ[FLAG] = flag
        blobs[name] = _payload_bytes(pubs[name])
    assert blobs["vector"] == blobs["legacy"], (
        "vectorized arm changed served payload bytes"
    )


# -- timing ----------------------------------------------------------------


def _timed_poll(pub):
    t0 = time.perf_counter()
    pub.poll(force=True)
    return (time.perf_counter() - t0) * 1000.0


def _run_case(tmp_path, ranks, steps, emit_stages=False):
    saved = os.environ.get(FLAG)
    db = tmp_path / f"bench_{ranks}.sqlite"
    w = _seed_db(db, ranks, steps)
    pubs, cold_ms = {}, {}
    extra = {"ranks": ranks, "steps": steps, "window": WINDOW}
    try:
        for name, flag in ARMS:
            os.environ[FLAG] = flag
            pub = SessionPublisher(db, "bench", window_steps=WINDOW)
            pub.min_poll_interval = 0.0
            cold_ms[name] = _timed_poll(pub)
            pubs[name] = pub

        # identical served bytes before ANY timing is trusted
        _golden_compare(pubs)

        # step-burst ticks: one new step per rank lands, then each arm
        # polls the same dirty store (order alternates per rep) —
        # informational, both arms share the refresh/append/json floor
        burst = {name: [] for name, _ in ARMS}
        next_step = steps + 1
        for rep in range(REPS):
            for rank in range(ranks):
                w.ingest(build_telemetry_envelope(
                    "step_time",
                    {"step_time": _step_rows(rank, next_step, 1)},
                    _ident(rank, ranks),
                ))
            assert w.force_flush()
            order = ARMS if rep % 2 == 0 else ARMS[::-1]
            for name, flag in order:
                os.environ[FLAG] = flag
                burst[name].append(_timed_poll(pubs[name]))
            next_step += 1
        _golden_compare(pubs)  # arms still byte-identical after warmup

        # warm steady-state (heartbeat) ticks — the GATED number: a
        # model_stats-only ingest re-dirties the step_time payload (MFU
        # block) without advancing any diagnosis input.  The legacy arm
        # re-runs build → rules → views → dataclasses.asdict over all
        # ranks; the vectorized arm rides the window/table/diagnosis
        # caches and only rebuilds the MFU block + serialization
        times = {name: [] for name, _ in ARMS}
        prof = pubs["vector"]._computer.store.tick_profile
        hits0 = prof.counters.get("diag_cache_hits", 0)
        evals0 = prof.counters.get("rule_evals", 0)
        for rep in range(REPS):
            w.ingest(build_telemetry_envelope(
                "step_time",
                {"model_stats": [_model_row(1000.0 + rep)]},
                _ident(0, ranks),
            ))
            assert w.force_flush()
            order = ARMS if rep % 2 == 0 else ARMS[::-1]
            for name, flag in order:
                os.environ[FLAG] = flag
                times[name].append(_timed_poll(pubs[name]))
        _golden_compare(pubs)
        vec_ms = min(times["vector"])
        leg_ms = min(times["legacy"])
        # every vector-arm heartbeat tick must have hit the diagnosis
        # cache and evaluated ZERO rules (the ISSUE acceptance)
        hit_ticks = prof.counters.get("diag_cache_hits", 0) - hits0
        rule_evals = prof.counters.get("rule_evals", 0) - evals0
        assert hit_ticks >= REPS, prof.counters
        assert rule_evals == 0, prof.counters

        for name, _ in ARMS:
            bench_common.emit(
                BENCH, "cold_tick", cold_ms[name], "ms", arm=name, **extra
            )
            bench_common.emit(
                BENCH, "step_burst_tick", min(burst[name]), "ms",
                arm=name, **extra,
            )
            bench_common.emit(
                BENCH, "warm_tick", min(times[name]), "ms",
                arm=name, **extra,
            )
        speedup = leg_ms / max(vec_ms, 1e-6)
        burst_speedup = min(burst["legacy"]) / max(min(burst["vector"]), 1e-6)
        bench_common.emit(BENCH, "speedup_warm_tick", speedup, "x", **extra)
        bench_common.emit(
            BENCH, "speedup_step_burst", burst_speedup, "x", **extra
        )

        if emit_stages:
            snap = prof.snapshot()
            ticks = max(1, snap["ticks"])
            for domain in sorted(snap["stage_ns"]):
                for stage, ns in sorted(snap["stage_ns"][domain].items()):
                    bench_common.emit(
                        BENCH, "stage_ms", ns / ticks / 1e6, "ms",
                        domain=domain, stage=stage, **extra,
                    )
            for key in ("diag_cache_hits", "diag_cache_misses", "rule_evals"):
                bench_common.emit(
                    BENCH, key, snap["counters"].get(key, 0), "count", **extra
                )
        return {"vector_ms": vec_ms, "legacy_ms": leg_ms,
                "burst": burst, "speedup": speedup}
    finally:
        if saved is None:
            os.environ.pop(FLAG, None)
        else:
            os.environ[FLAG] = saved
        for pub in pubs.values():
            pub.close()
        w.finalize()


@pytest.mark.parametrize("ranks", [128, 1024])
def test_tick_pipeline_bench(tmp_path, ranks):
    res = _run_case(tmp_path, ranks, STEPS, emit_stages=(ranks == 1024))
    if ranks == 1024:
        # the acceptance floor (ISSUE r20): total warm pipeline tick,
        # vectorized arm ≥ 3× the scalar pre-change arm
        assert res["speedup"] >= 3.0, res


if __name__ == "__main__":
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        for ranks in (128, 1024):
            _run_case(
                Path(d), ranks, STEPS, emit_stages=(ranks == 1024)
            )
