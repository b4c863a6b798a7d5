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

import pytest
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
    # the routed layer's experts are whole lane tiles ([256, 128] at a tile of 512): both passes through the grouped
    # kernels, which move their own rows, and the weight-gradient sums a segment at a time
    for kernel in ("flash_attention_fwd", "flash_attention_bwd", "attn_operands_fwd", "attn_operands_bwd",
                   "experts_fwd", "experts_bwd", "expert_dw"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    assert 'kernel_name = "take_rows"' not in text and 'kernel_name = "put_tiles"' not in text
    bodies = _kernel_bodies(text)
    assert len(bodies) >= 7 and not [body for body in bodies if b".py" in body]
    monkeypatch.undo()  # the control: as JAX serializes them, the bodies name their source
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert [body for body in _kernel_bodies(tool.step_text(config, 1, 1)) if b"attention.py" in body]


# one row of each head size the flash kernels take, a value as wide as the head: key-value heads, query heads each
# serves, head size, window (LFM2's layer; Trinity-Mini's window layer, token-major; the GLM cell's latent heads)
FLASH_SHAPES = {"head-64": (2, 4, 64, None), "head-128": (2, 8, 128, 200), "head-256": (2, 1, 256, None)}
FLASH_TEXTS = os.path.join(REPO, "tests", "fixtures", "flash_kernels_pr50")


def flash_kernel_texts(case: str) -> dict[str, str]:
    """{kernel: the call lowered for the ``tpu`` platform} of the flash pair
    at ``FLASH_SHAPES[case]`` over 512 tokens, Mosaic bodies without
    locations (the caller patches the serializer).  What
    ``tests/fixtures/flash_kernels_pr50/`` holds, written from a checkout of
    PR 50's commit by this function."""
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.models import attention

    hkv, groups, d, window = FLASH_SHAPES[case]
    t, bf16 = 512, jnp.bfloat16
    tiles = dict(zip(("bq", "bk"), attention._flash_tiles(t, groups, d), strict=True))
    batch = 1 if attention._token_major(t, groups, d) else None
    q, k = jax.ShapeDtypeStruct((hkv, groups, t, d), bf16), jax.ShapeDtypeStruct((hkv, t, d), bf16)
    o = q if batch is None else jax.ShapeDtypeStruct((1, t, hkv * groups * d), bf16)
    lse = jax.ShapeDtypeStruct((hkv, groups, 1, t), jnp.float32)
    forward = jax.jit(lambda q, k, v: attention._flash_forward(q, k, v, **tiles, window=window, batch=batch, interpret=False))
    backward = jax.jit(lambda *a: attention._flash_backward(*a, **tiles, window=window, interpret=False))
    return {
        "flash_attention_fwd": forward.trace(q, k, k).lower(lowering_platforms=("tpu",)).as_text(),
        "flash_attention_bwd": backward.trace(q, k, k, o, lse, o).lower(lowering_platforms=("tpu",)).as_text(),
    }


@pytest.mark.parametrize("kernel", ["flash_attention_fwd", "flash_attention_bwd"])
@pytest.mark.parametrize("case", sorted(FLASH_SHAPES))
def test_a_value_as_wide_as_the_head_runs_the_kernels_pr50_emitted(monkeypatch, case, kernel):
    """The flash kernels read the value's width off ``v`` (PR 51); where it
    is the head's, each ``pallas_call`` (grid, block specs, scratch) and each
    kernel body is byte for byte the one PR 50's commit emitted, at a head of
    64, of 128 (under a window, token-major) and of 256: the five other LM
    cells' steps are the parent's programs."""
    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", tpu_custom_call._lower_mosaic_module_to_asm)
    tool._kernel_bodies_without_locations()  # undone with the patch above
    text = flash_kernel_texts(case)[kernel]
    assert f'kernel_name = "{kernel}"' in text and len(_kernel_bodies(text)) == 1
    with open(os.path.join(FLASH_TEXTS, f"{case}.{kernel}.txt")) as f:
        assert text == f.read()
