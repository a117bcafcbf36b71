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
    """The attached step's program (``wrap_step_fn`` jits exactly this)."""
    from benchmark.harness import model_config
    from traceml_tpu.models import init_train_state, make_train_step, param_shardings
    from traceml_tpu.parallel.mesh import batch_sharding, make_mesh

    cfg = model_config(spec)
    made = {}

    def init(key):
        made["model"], state, made["tx"] = init_train_state(
            cfg, key, learning_rate=spec["model"]["optimizer"]["learning_rate"]
        )
        return state

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    mesh = None
    if spec["cell"].get("mesh"):
        mesh = make_mesh(spec["cell"]["mesh"], devices=devices)
        state_sh, tok_sh = param_shardings(shapes, mesh), batch_sharding(mesh)
    else:
        one = SingleDeviceSharding(devices[0])
        state_sh, tok_sh = jax.tree.map(lambda _: one, shapes), one
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, state_sh
    )
    load = spec["load"]
    tokens = jax.ShapeDtypeStruct((load["batch"], load["seq"]), jnp.int32, sharding=tok_sh)
    step = jax.jit(make_train_step(made["model"], made["tx"], mesh=mesh), donate_argnums=(0,))
    return step.lower(state, tokens).compile()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_step_compiles_and_fits(topo, cell):
    from benchmark.discovery import load_cell
    from benchmark.peaks import peaks_for

    spec = load_cell(cell)
    devices = list(topo.devices)[: spec["chips"]]
    compiled = _compile(spec, devices)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    hbm = peaks_for(devices[0].device_kind)["hbm_bytes"]
    print(f"{cell}: {total / 1e9:.3f} GB of {hbm / 1e9:.0f} GB ({mem})")
    assert total <= hbm
