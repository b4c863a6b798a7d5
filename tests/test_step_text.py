"""``tools/step_text.py`` kept working: the cells it finds, and at a tiny
Trinity-Mini configuration (a head of 128, rows of four key tiles: the shapes
every attention kernel takes) the text it hashes: the adaptor's own step,
lowered for the ``tpu`` platform, kernels in, file names out.  Nothing is
compiled."""

from __future__ import annotations

import base64
import importlib.util
import json
import os
import re

from jax._src import tpu_custom_call

from lakesoul_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location("step_text", os.path.join(REPO, "tools", "step_text.py"))
tool = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tool)

TINY = dict(
    vocab_size=256, hidden_size=256, intermediate_size=256, moe_intermediate_size=128, num_hidden_layers=2,
    layer_types=["sliding_attention", "full_attention"], num_dense_layers=1, num_attention_heads=2,
    num_key_value_heads=1, sliding_window=128, num_experts=16, num_experts_per_tok=4, num_experts_held=4,
)


def _kernel_bodies(text: str) -> list[bytes]:
    return [base64.b64decode(body) for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)]


def test_eight_steps_stand_behind_the_nine_trainer_cells():
    steps = tool.steps_of([])
    assert len(steps) == 8 and len({config for config, _, _ in steps.values()}) == 7
    assert tool.steps_of(["bert_base_mlm_pk.dp4_mor_stream"]) == {"bert_base_mlm_pk.dp4.rows64": ("bert_base_mlm_pk", 4, 64)}


def test_a_steps_text_holds_its_kernels_and_no_file_name(monkeypatch):
    with open(os.path.join(tool.BENCH, "configs", "trinity_mini_clm_pk.json")) as f:
        config = json.load(f)
    config["model"] |= TINY
    config["table"]["seq"] = 512
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", tpu_custom_call._lower_mosaic_module_to_asm)
    tool._kernel_bodies_without_locations()  # undone with the patch above
    text = tool.step_text(config, 1, 1)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd", "attn_operands_fwd", "attn_operands_bwd"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    bodies = _kernel_bodies(text)
    assert len(bodies) >= 4 and not [body for body in bodies if b".py" in body]
    monkeypatch.undo()  # the control: as JAX serializes them, the bodies name their source
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert [body for body in _kernel_bodies(tool.step_text(config, 1, 1)) if b"attention.py" in body]
