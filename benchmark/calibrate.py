"""Readings that set a cell's limits of ``correct``, in one process.

    python benchmark/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 --out F

For each seed: the program's first steps through the attached step, as a
run's set-up drives them, against the float32 reference. For the first
``--control-seeds`` seeds also the control (the reference at fp8 in the
program's place) and each planted fault the cell can have, each against
the reference. Writes every gap to ``--out`` as JSON and prints, per
number, the largest program reading and the smallest control and fault
readings. It needs the chip, like a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path[0] = str(ROOT)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import traceml_tpu
    from benchmark import check, discovery, harness

    spec = discovery.load_cell(args.workload)
    devices = harness.devices_for(spec["chips"])
    traceml_tpu.init(mode="auto")
    faults = ["half_batch", "labels_shifted"] + (["no_exchange"] if spec["chips"] > 1 else [])
    rows = []
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        t0 = time.monotonic()
        prog = harness.Program(spec, seed, devices)
        got, _ = prog.check_steps()
        prog.free()
        t1 = time.monotonic()
        ref = harness.reference_readings(spec, prog, devices)
        t2 = time.monotonic()
        row = {"seed": seed, "program": check.gaps(got, ref),
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "still_leaves": check.still_leaves(ref)}
        if n < args.control_seeds:
            for variant in ["fp8"] + faults:
                r = harness.reference_readings(spec, prog, devices, variant)
                row[variant] = check.gaps(r, ref)
            row["state_unchanged"] = check.gaps(check.state_unchanged(ref), ref)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for k in check.NUMBERS:
        summary[k] = {"program_max": max(r["program"][k] for r in rows)}
        for v in ["fp8"] + faults + ["state_unchanged"]:
            summary[k][v + "_min"] = min(r[v][k] for r in rows if v in r)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({"rows": rows, "summary": summary}, indent=1))
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
