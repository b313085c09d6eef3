"""What the files that hold Wan2.2-T2V-A14B to its plain reference share
(``test_wan_reference.py``: the denoiser, the LoRA and the tower;
``test_wan_reference_decoder.py``; ``test_wan_reference_graph.py``: the whole
graph and the residency rule). ``twins.py`` writes the twin's six files and
tokenizer tables once a file of tests; one set serves the float32 and the
bfloat16 program alike.

The reference (``benchmark/yardstick/reference_wan.py``) is the benchmark's;
``benchmark/tests`` walks the whole command with it, these tests hold the
program to it inside tier-1."""

import jax.numpy as jnp
import pytest
from twins import _twin
from yardstick import synth

CELL = "wan22-t2v-a14b-tiny.closed"


@pytest.fixture
def tiny(twin_files, monkeypatch):
    """The twin under the program's presets at its sizes, in float32."""
    return _twin(twin_files, monkeypatch, CELL, jnp.float32)


@pytest.fixture
def tiny_bf16(twin_files, monkeypatch):
    """The same files under the type the presets give: bfloat16 compute."""
    return _twin(twin_files, monkeypatch, CELL, None)


@pytest.fixture
def fresh_residency(monkeypatch):
    """The loader's rule with no budget and no history, so that one test's
    models never meet another's."""
    from comfyui_parallelanything_tpu.models import loader

    rule = loader.Residency(budget_bytes=0)
    monkeypatch.setattr(loader, "residency", rule)
    return rule


def _file(cell, ref_kw, index):
    return ref_kw["files"][synth.checkpoint_files(cell["config_data"])[index]["file"]]
