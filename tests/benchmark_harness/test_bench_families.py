"""The seam between generic benchmark code and an architecture's family.

* Parity: the dense decoder family, behind the seam, gives the readings
  and the checkpoint layout that the reference gave before the seam
  (``golden_decoder_lm.json``: the tiny cell, 3 seeds, every variant,
  recorded from that code). They agreed bit for bit on the CPU where
  they were recorded; the test allows float32 round-off, since the CPU
  backend's code follows the host's vector width.
* Guard: the generic modules name no leaf of the decoder and no program
  model, and a family's reference imports nothing of the program.
"""

import ast
import json
import re
from pathlib import Path

import jax
import pytest

from benchmark import discovery, reference
from benchmark.weights import seed_key, token_pool

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmark"
SPEC = json.loads((HERE / "tiny_cell.json").read_text())
M, LOAD = SPEC["model"], SPEC["load"]
GOLDEN = json.loads((HERE / "golden_decoder_lm.json").read_text())
FAMILY = discovery.load_family("decoder_lm").reference
GENERIC = ["harness.py", "check.py", "calibrate.py", "weights.py", "reference.py"]
ARCHITECTURE = re.compile(
    r"\b(wq|wk|wv|wo|w_gate|w_up|w_down|lm_head|attn_norm|mlp_norm|final_norm|embedding"
    r"|head_dim|num_attention_heads|num_key_value_heads|intermediate_size|num_hidden_layers"
    r"|ModelConfig|DecoderLM|init_train_state|make_train_step|traceml_tpu\.models)\b"
)


def test_layout_unchanged():
    shapes = FAMILY.param_shapes(M)
    got = [[p, list(s.shape)] for p, s in zip(reference.leaf_paths(shapes), jax.tree.leaves(shapes))]
    assert got == GOLDEN["param_shapes"]


@pytest.mark.parametrize(
    "gold", GOLDEN["readings"], ids=lambda g: f"{g['variant']}-{g['seed']}-{g['devices']}dev"
)
def test_readings_unchanged(gold):
    toks = token_pool(gold["seed"], 3, LOAD["batch"], LOAD["seq"], M["vocab_size"])
    got = reference.readings(
        FAMILY, M, M["optimizer"], M["init_std"], seed_key(gold["seed"]), toks,
        gold["variant"], jax.devices()[: gold["devices"]],
    )
    assert got["losses"] == pytest.approx(gold["losses"], rel=1e-6, abs=0)
    for k in ("grad_norms", "delta_norms"):
        assert got[k] == pytest.approx(gold[k], rel=1e-6, abs=0), k


@pytest.mark.parametrize("name", GENERIC)
def test_generic_code_names_no_architecture(name):
    found = ARCHITECTURE.findall((BENCH / name).read_text())
    assert not found, f"{name} names {sorted(set(found))}"


@pytest.mark.parametrize("path", sorted(BENCH.glob("families/*/reference.py")),
                         ids=lambda p: p.parent.name)
def test_family_reference_imports_nothing_of_the_program(path):
    tree = ast.parse(path.read_text())
    modules = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    modules += [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [m for m in modules if m.split(".")[0] == "traceml_tpu"], modules


def test_every_family_gives_the_seam():
    for d in sorted(p for p in (BENCH / "families").iterdir()
                    if p.is_dir() and p.name != "__pycache__"):
        fam = discovery.load_family(d.name)
        assert callable(fam.program.build)
        for fn in ("param_shapes", "pack", "unpack", "forward", "fsdp_dim", "train_step_flops"):
            assert callable(getattr(fam.reference, fn)), (d.name, fn)
