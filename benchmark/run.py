"""The benchmark's command: one run of one cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the numbers that decide ``correct`` beside their limits as the
last lines of standard error, and one JSON object as the last line of
standard output. Without a TPU, or with fewer chips than the cell asks
for, it exits 1 and prints no result. JAX's persistent compilation cache
is ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``,
and every compile is written to it, so only a cell's first run compiles.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    # the checkout root, not this directory, so no module here shadows one
    sys.path[0] = str(ROOT)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from benchmark import harness

    try:
        result, lines = harness.run(
            args.workload, args.seed, args.seconds, bool(args.trace), T_START
        )
    except harness.NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
