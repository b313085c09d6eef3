"""``python -m pytest benchmark/tests`` — the benchmark's own tests; they are not
part of ``tests/``. Everything runs on the CPU at tiny widths."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture
def restorable(monkeypatch, tmp_path):
    """``run.main()`` swaps the program's preset factories for a rehearsal
    twin's and sets ``PA_*`` variables: register the originals so they come
    back, and give the run a work directory and compile cache of its own."""
    import glob
    import importlib
    import json

    import run

    for path in glob.glob(os.path.join(HERE, "configs", "*.json")):
        with open(path) as f:
            presets = json.load(f).get("program_presets", ())
        for target in presets:
            mod_name, name = target.split(":")
            mod = importlib.import_module(mod_name)
            monkeypatch.setattr(mod, name, getattr(mod, name))
    for var in ("PA_MODELS_DIR", "PA_OUTPUT_DIR", "PA_CLIP_VOCAB", "PA_CLIP_MERGES",
                "PA_COMPILE_CACHE_MIN_S", "PA_T5_TOKENIZER_JSON"):
        monkeypatch.setenv(var, os.environ.get(var, ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(run, "WORK", str(tmp_path / "work"))
    return tmp_path
