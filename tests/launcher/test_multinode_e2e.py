"""Two-'node' run on localhost: two launcher invocations with explicit
port — exercises the bind/connect split, cross-node aggregation, and
the node-0 finalize barrier over real sockets."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SCRIPT = """
import os, time
import numpy as np
import jax, jax.numpy as jnp
import traceml_tpu

rank = int(os.environ.get("RANK", 0))

def step_fn(w, x):
    return w - 0.01 * jax.grad(lambda w, x: jnp.sum((x @ w) ** 2))(w, x)

step = traceml_tpu.wrap_step_fn(step_fn)
w = jnp.ones((32, 32)) * 0.01
rng = np.random.default_rng(rank)

def batches():
    for i in range(60):
        if rank == 1:
            # node-1 rank has the slow input pipeline.  0.12 s (toward
            # the reference demo's 0.18 s) keeps the injected skew far
            # above full-suite host-contention noise — 0.03 s was
            # under-margined and flaked INPUT_STRAGGLER → INPUT_BOUND
            # when 2 launchers × (aggregator + rank) timeshared cores
            time.sleep(0.12)
        yield rng.normal(size=(8, 32)).astype(np.float32)

for x in traceml_tpu.wrap_dataloader(batches()):
    with traceml_tpu.trace_step():
        x = jax.device_put(x)
        w = step(w, x)
print("rank", rank, "done")
"""


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_node_localhost(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(SCRIPT)
    logs = tmp_path / "logs"
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    common = [
        sys.executable, "-m", "traceml_tpu", "run",
        "--mode", "summary", "--logs-dir", str(logs),
        "--run-name", "mn",
        "--nnodes", "2", "--nprocs", "1",
        "--aggregator-host", "127.0.0.1",
        "--aggregator-port", str(port),
        "--sampler-interval", "0.25", "--finalize-timeout", "40",
    ]
    # both launchers must share the session id: pin it via env
    env["TRACEML_SESSION_ID"] = "mn-shared"
    node0 = subprocess.Popen(
        common + ["--node-rank", "0", str(script)],
        env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    time.sleep(2.0)  # let node 0 bind the port
    node1 = subprocess.Popen(
        common + ["--node-rank", "1", str(script)],
        env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    out1, _ = node1.communicate(timeout=240)
    out0, _ = node0.communicate(timeout=240)
    assert node0.returncode == 0, out0[-3000:]
    assert node1.returncode == 0, out1[-3000:]
    session = next(p for p in logs.iterdir() if p.name.startswith("mn"))
    payload = json.loads((session / "final_summary.json").read_text())
    topo = payload["meta"]["topology"]
    assert topo["world_size"] == 2
    assert sorted(topo["ranks_seen"]) == [0, 1]
    assert topo["mode"] == "multi_node"
    primary = payload["primary_diagnosis"]
    assert primary["kind"] == "INPUT_STRAGGLER", primary
    assert primary["ranks"] == [1]


def test_two_node_two_rank_distinct_hosts(tmp_path):
    """2 nodes × 2 ranks with genuinely separated 'hosts' (VERDICT r4
    item 6): distinct working roots, distinct logs dirs, distinct env
    universes, and a connect address (127.0.0.2) different from the
    bind address (multi-node default 0.0.0.0) — the VIP/tunnel shape.
    Asserts worker-0 ownership (summary exists ONLY on node 0) and
    per-node identity in the topology block."""
    port = _free_port()

    def _node_env(root: Path) -> dict:
        env = {
            k: v for k, v in os.environ.items()
            # a fresh env universe: no inherited TRACEML_*/RANK state
            if not k.startswith(("TRACEML_", "RANK", "WORLD_", "LOCAL_R"))
        }
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(REPO)
        env["TMPDIR"] = str(root / "tmp")
        (root / "tmp").mkdir(parents=True, exist_ok=True)
        # the ONE thing multi-node launchers must agree on
        env["TRACEML_SESSION_ID"] = "mn2-shared"
        return env

    nodes = {}
    for node_rank in (0, 1):
        root = tmp_path / f"host{node_rank}"
        root.mkdir()
        script = root / "train.py"
        script.write_text(SCRIPT)
        nodes[node_rank] = (root, script, _node_env(root))

    def _argv(node_rank: int, root: Path, script: Path):
        return [
            sys.executable, "-m", "traceml_tpu", "run",
            "--mode", "summary", "--logs-dir", str(root / "logs"),
            "--run-name", "mn2",
            "--nnodes", "2", "--nprocs", "2",
            "--node-rank", str(node_rank),
            # connect address differs from the bind address on purpose:
            # node 0 binds 0.0.0.0 (multi-node default), everyone
            # CONNECTS via the 127.0.0.2 loopback alias
            "--aggregator-host", "127.0.0.2",
            "--aggregator-port", str(port),
            "--sampler-interval", "0.25", "--finalize-timeout", "60",
            str(script),
        ]

    root0, script0, env0 = nodes[0]
    node0 = subprocess.Popen(
        _argv(0, root0, script0), env=env0, cwd=str(root0),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    time.sleep(2.0)  # let node 0 bind the port
    root1, script1, env1 = nodes[1]
    node1 = subprocess.Popen(
        _argv(1, root1, script1), env=env1, cwd=str(root1),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    out1, _ = node1.communicate(timeout=300)
    out0, _ = node0.communicate(timeout=300)
    assert node0.returncode == 0, out0[-3000:]
    assert node1.returncode == 0, out1[-3000:]

    # worker-0 ownership: the final summary exists ONLY on node 0
    session0 = next(p for p in (root0 / "logs").iterdir()
                    if p.name.startswith("mn2"))
    assert (session0 / "final_summary.json").exists()
    node1_sessions = list((root1 / "logs").iterdir())
    assert not any(
        (p / "final_summary.json").exists() for p in node1_sessions
    ), "non-owner node must not write the final summary"

    payload = json.loads((session0 / "final_summary.json").read_text())
    topo = payload["meta"]["topology"]
    assert topo["world_size"] == 4
    assert sorted(topo["ranks_seen"]) == [0, 1, 2, 3]
    assert topo["mode"] == "multi_node"
    assert topo["nodes"] == 2

    # per-node identity: ranks 0-1 on node 0, ranks 2-3 on node 1
    # (identity blocks ride the per-rank cards, SCHEMA.md contract)
    cards = payload["sections"]["step_time"]["global"]["per_rank"]
    node_of = {
        int(r): int(card["identity"]["node_rank"])
        for r, card in cards.items()
        if card.get("identity")
    }
    assert node_of == {0: 0, 1: 0, 2: 1, 3: 1}, node_of

    # the injected straggler is global rank 1 (node 0, local rank 1)
    primary = payload["primary_diagnosis"]
    assert primary["kind"] == "INPUT_STRAGGLER", primary
    assert primary["ranks"] == [1]
