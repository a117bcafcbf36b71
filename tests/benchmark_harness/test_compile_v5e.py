"""Each cell's attached train step, compiled at its real size for a TPU
v5e that is described, not attached: the chip's compiler refuses here
what it would refuse there, and the fullest device's memory has to fit
the HBM in ``benchmark/peaks.json``.

Every TPU-topology call sits in a fixture of this one file: a call made
while a module is imported would load libtpu in every test worker.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _compile(spec, devices):
    """The attached step's program (``wrap_step_fn`` jits exactly this),
    built by the cell's family as the harness builds it, from shapes. On
    a mesh ``eval_shape`` keeps no shardings: the compiler takes the
    state's layout from the step's own sharding constraints (the same
    bytes per device as ``param_shardings`` gives, to 0.01%)."""
    from traceml_tpu.parallel.mesh import batch_sharding, make_mesh

    mesh = make_mesh(spec["cell"]["mesh"], devices=devices) if spec["cell"].get("mesh") else None
    made = {}

    def init(key):
        made["step"], state = spec["family"].program.build(spec, key, mesh)
        return state

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    if mesh is None:
        one = SingleDeviceSharding(devices[0])
        shapes, tok_sh = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes
        ), one
    else:
        tok_sh = batch_sharding(mesh)
    load = spec["load"]
    tokens = jax.ShapeDtypeStruct((load["batch"], load["seq"]), jnp.int32, sharding=tok_sh)
    step = jax.jit(made["step"], donate_argnums=(0,))
    return step.lower(shapes, tokens).compile()


def _total(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reference_step_compiles_and_fits(topo, cell):
    """The float32 reference's step at the cell's size and chips, as a
    run's check builds it once the program is freed: it fits the HBM,
    and its code is one scanned layer's, not one copy per layer (phi's
    reference with its layers unrolled compiled to 878 MB of code)."""
    from benchmark import reference
    from benchmark.discovery import load_cell
    from benchmark.peaks import peaks_for

    spec = load_cell(cell)
    m, load = spec["model"], spec["load"]
    devices = tuple(topo.devices)[: spec["chips"]]
    init, step, _, _, tok_sh = reference._programs(
        spec["family"].reference, json.dumps(m, sort_keys=True),
        tuple(sorted(m["optimizer"].items())), float(m["init_std"]), "f32", devices,
    )
    state = jax.eval_shape(init, jax.random.key(0))
    t_sh = None
    if tok_sh is None:  # one chip: the step's arguments say which
        tok_sh = t_sh = SingleDeviceSharding(devices[0])
        state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=t_sh), state)
    tokens = jax.ShapeDtypeStruct((load["batch"], load["seq"]), jnp.int32, sharding=tok_sh)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=t_sh)
    mem = step.lower(*state, tokens, t).compile().memory_analysis()
    print(f"{cell} reference: {_total(mem) / 1e9:.3f} GB, code {mem.generated_code_size_in_bytes / 1e6:.1f} MB")
    assert _total(mem) <= peaks_for(devices[0].device_kind)["hbm_bytes"]
    assert mem.generated_code_size_in_bytes < 200e6


@pytest.mark.parametrize("cell", CELLS)
def test_cell_step_compiles_and_fits(topo, cell):
    from benchmark.discovery import load_cell
    from benchmark.peaks import peaks_for

    spec = load_cell(cell)
    devices = list(topo.devices)[: spec["chips"]]
    compiled = _compile(spec, devices)
    mem = compiled.memory_analysis()
    total = _total(mem)
    hbm = peaks_for(devices[0].device_kind)["hbm_bytes"]
    print(f"{cell}: {total / 1e9:.3f} GB of {hbm / 1e9:.0f} GB ({mem})")
    assert total <= hbm
