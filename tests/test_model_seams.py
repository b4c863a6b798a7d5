"""Where the seams of ``lakesoul_tpu/models`` lie, held without compiling a
step: the masked-LM process loads no Pallas and no causal-LM stack; the
attention module and the head-and-loss loop know no family, no BERT and not
the stack that calls them; ``on_tpu`` has one home; and the names
``benchmarks/chip`` imports are where it takes them from."""

from __future__ import annotations

import ast
import glob
import importlib
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = "lakesoul_tpu.models."
FAMILIES = ("qwen3_next", "lfm2_moe", "glm4_moe_lite", "afmoe", "ouro")


def _imported(path: str) -> set[str]:
    """Every module a file imports, anywhere in it, and for ``from m import
    a`` also ``m.a`` (a submodule imported by name reads so)."""
    with open(os.path.join(REPO, path)) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            found |= {node.module} | {f"{node.module}.{a.name}" for a in node.names}
    return found


@pytest.mark.parametrize("module", ["lakesoul_tpu.models.train", "lakesoul_tpu.models.bert"])
def test_the_masked_lm_process_loads_no_pallas_and_no_causal_lm_stack(module):
    """``setup_s`` of the BERT cells pays for every import of their process:
    the tile loop they share with the causal LMs (``models/head_loss.py``)
    brings neither the kernels nor the stack with it."""
    listed = subprocess.run(
        [sys.executable, "-c", f"import sys, {module}; print('\\n'.join(sorted(sys.modules)))"],
        capture_output=True, text=True, check=True, cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    ).stdout.split()
    assert MODELS + "head_loss" in listed
    assert [m for m in listed if m.startswith("jax.experimental.pallas") or "jax._src.pallas" in m] == []
    assert [m for m in listed if m in {MODELS + n for n in ("causal_lm", "attention", "loss_tile", *FAMILIES)}] == []


STACK = ("causal_lm", "bert", "train", *FAMILIES)


@pytest.mark.parametrize("module, unknown", [
    ("attention", STACK), ("head_loss", (*STACK, "loss_tile")),  # the loop takes its kernel as an argument
    ("norms", (*STACK, "attention", "head_loss", "loss_tile")),
    *((module, ("bert",)) for module in ("causal_lm", "loss_tile", *FAMILIES)),
])
def test_what_a_module_of_the_lm_step_does_not_know(module, unknown):
    """The borrowed code knows no family, no BERT and not the stack that calls
    it; no causal-LM module imports a function of ``bert.py``."""
    imports = _imported(f"lakesoul_tpu/models/{module}.py")
    assert [m for m in imports if m.startswith(tuple(MODELS + name for name in unknown))] == []
    if module in ("head_loss", "norms"):
        assert [m for m in imports if "pallas" in m] == []


def test_on_tpu_has_one_home():
    """``utils/platform.py`` defines it; no other module defines its own, keeps
    the old name or asks the vector index's kernel file for it."""
    for path in glob.glob(os.path.join(REPO, "lakesoul_tpu", "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, REPO)
        if rel == "lakesoul_tpu/utils/platform.py":
            continue
        with open(path) as f:
            source = f.read()
        assert "def on_tpu" not in source and "_on_tpu" not in source, rel
        asked = {"lakesoul_tpu.vector.kernels." + name for name in ("on_tpu", "platform")}
        assert not asked & _imported(rel), rel


@pytest.mark.parametrize("module, names", [
    ("bert", ("BertConfig", "bert_mlm_loss", "labelled_nll")),
    ("causal_lm", ("head_params", "lm_head", "lm_hidden", "mtp_hidden", "mtp_head_params", "exit_loss", "loop_hidden")),
    ("qwen3_next", ("Qwen3NextConfig", "lm_head", "lm_hidden")),
    ("train", ("make_bert_train_state", "make_bert_train_step", "make_lm_train_state", "make_lm_train_step")),
    ("lfm2_moe", ("Lfm2MoeConfig",)), ("glm4_moe_lite", ("Glm4MoeLiteConfig",)), ("afmoe", ("AfmoeConfig",)),
    ("ouro", ("OuroConfig",)),
])
def test_what_the_benchmark_imports_is_where_it_takes_it_from(module, names):
    """``benchmarks/chip/consumers/*.py`` is not edited with the program: a
    name it imports stays importable from the module it names."""
    loaded = importlib.import_module(MODELS + module)
    assert [name for name in names if not callable(getattr(loaded, name, None))] == []
    asked = set()
    for path in glob.glob(os.path.join(REPO, "benchmarks", "chip", "consumers", "*.py")):
        asked |= {m for m in _imported(os.path.relpath(path, REPO)) if m.startswith(MODELS + module + ".")}
    assert asked <= {f"{MODELS}{module}.{name}" for name in names}  # the list above is the whole of it
