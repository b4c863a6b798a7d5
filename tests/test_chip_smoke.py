"""chip_smoke.py: no CPU mode for the script, tiny CPU dry runs for its
stage functions, and the compile-cache helper it shares with every other
entry point."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from lakesoul_tpu.models.bert import BertConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_script_refuses_a_cpu(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero at once,
    names the platform it found and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode not in (0, None)
    assert "platform is 'cpu', not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_result_line_is_exactly_ok_and_device(chip_smoke, monkeypatch, capsys):
    """The last stdout line carries ``ok`` and ``device`` (platform, kind,
    count) and nothing else; the report is the line before it.  The stages
    are stubbed: only ``main``'s framing is under test."""
    from lakesoul_tpu import native
    from lakesoul_tpu.utils import compile_cache

    class FakeChip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [FakeChip()])
    monkeypatch.setattr(native, "available", lambda: True)
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "/nowhere")
    for stage in ("stage_trainer", "stage_ann_server", "stage_kernels"):
        monkeypatch.setattr(chip_smoke, stage, lambda *a, **k: {})

    assert chip_smoke.main() == 0
    report, result = (json.loads(line) for line in capsys.readouterr().out.splitlines()[-2:])
    assert result == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert isinstance(result["device"]["count"], int)
    report = report["report"]
    assert report["claim"] is None
    assert report["multichip"] == "not run: 1 device(s)"
    assert {name: s["status"] for name, s in report["stages"].items()} == {
        "trainer": "pass", "ann_server": "pass", "kernels": "pass",
    }


def test_trainer_stage_tiny(chip_smoke):
    """All three loader modes into one step function on a one-device mesh:
    placement, finiteness, the float32 reference and exactly one compile."""
    out = chip_smoke.stage_trainer(
        jax.devices()[:1], cfg=BertConfig.tiny(), batch=4, seq=128, steps=2,
        loader_modes=("default", "sharded", "replay"),
    )
    assert out["optimizer_steps"] == 2 * 4  # default, sharded, replay fill + replay
    assert out["train_step_lowerings"] == 1
    assert out["modes"] == {"default": 1, "sharded": 0, "replay": 0}
    assert out["reference_gap"] <= chip_smoke.REFERENCE_LOSS_TOL


def test_multichip_stage_tiny(chip_smoke):
    """The four-device trainer (dp2 x tp2, batch under P('dp', 'sp')) and
    the register's collective shapes, on the CPU mesh."""
    out = chip_smoke.stage_multichip(
        jax.devices()[:4], cfg=BertConfig.tiny(), batch=8, seq=128, steps=2,
    )
    trainer = out["trainer"]
    assert trainer["mesh"] == {"dp": 2, "tp": 2, "sp": 1}
    assert trainer["tp_param_devices"] == 4
    assert trainer["train_step_lowerings"] == 1
    assert set(out["collectives"]) == {
        "annplane.cross_chip_topk", "parallel.mesh_pipeline",
    }


def test_ann_stage_tiny_interpreted(chip_smoke):
    """The serving stage end to end with the ragged kernel in the Pallas
    interpreter: build, open, endpoint under threads, recall floor."""
    out = chip_smoke.stage_ann_server(
        rows=6_000, dim=32, nlist=8, queries=16, nprobes=(6, 8, 12, 16),
        rerank_depth=80, interpret=True,
    )
    assert out["plane_recall_at_10"] >= chip_smoke.RECALL_FLOOR
    assert out["index_recall_at_10"] >= chip_smoke.RECALL_FLOOR
    assert out["endpoint_batches"] >= 1


def test_kernel_stage_tiny_interpreted(chip_smoke):
    from lakesoul_tpu.tensorplane.smoke import TINY, smoke_cases

    out = chip_smoke.stage_kernels(
        dims=(64, 128), sizes_for=lambda d: dataclasses.replace(TINY, d=d),
        interpret=True,
    )
    assert set(out) == {"d64", "d128", "tensorplane"}
    for group in out.values():
        assert all(case["status"] == "pass" for case in group.values())
    assert len(out["d128"]) == sum(case.kind == "pallas" for case in smoke_cases())


_CACHE_PROBE = (
    "import jax, json;"
    "from lakesoul_tpu.utils.compile_cache import configure_compile_cache;"
    "r = configure_compile_cache();"
    "print(json.dumps([r, jax.config.jax_compilation_cache_dir]))"
)


def _cache_probe(env_overrides: dict) -> list:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_overrides, PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd="/",
        capture_output=True, text=True, timeout=120, check=True,
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_the_environment(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    assert _cache_probe({"JAX_COMPILATION_CACHE_DIR": placed}) == [placed, placed]


def test_compile_cache_defaults_to_the_checkout():
    want = str(ROOT / ".jax_cache")
    assert _cache_probe({}) == [want, want]
