"""The control and the planted faults, at a size a test run holds: the
reference in the program's place, at fp8 or broken, fails the limits
that the program passes (``test_bench_harness.py``). On the chip the
same readings come from ``benchmark/calibrate.py`` at each cell's size."""

import json
from pathlib import Path

import jax
import pytest

from benchmark import check, reference
from benchmark.weights import seed_key, token_pool

SPEC = json.loads((Path(__file__).parent / "tiny_cell.json").read_text())
M, LOAD = SPEC["model"], SPEC["load"]
LIMITS = SPEC["cell"]["limits"]
SEEDS = (11, 2**31 + 3, 2**34 + 9)


def _readings(seed, variant, n_devices=1):
    toks = token_pool(seed, 3, LOAD["batch"], LOAD["seq"], M["vocab_size"])
    return reference.readings(
        M, M["optimizer"], M["init_std"], seed_key(seed), toks, variant,
        jax.devices()[:n_devices],
    )


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize(
    "variant,n_devices",
    [("fp8", 1), ("half_batch", 1), ("labels_shifted", 1), ("no_exchange", 4)],
)
def test_control_and_faults_fail(seed, variant, n_devices):
    ref = _readings(seed, "f32")
    numbers = check.gaps(_readings(seed, variant, n_devices), ref)
    assert not check.judge(numbers, LIMITS), numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_itself_across_devices(seed):
    numbers = check.gaps(_readings(seed, "f32", 4), _readings(seed, "f32"))
    assert all(v < 1e-4 for v in numbers.values()), numbers


def test_state_unchanged_reads_one():
    ref = _readings(SEEDS[0], "f32")
    numbers = check.gaps(check.state_unchanged(ref), ref)
    assert numbers["grad_gap"] == 1.0 and numbers["delta_gap"] == 1.0
