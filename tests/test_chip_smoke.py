"""chip_smoke.py rehearsed on the CPU: its checkpoint/tokenizer synthesis and
its serve phase run at tiny widths (the script itself has no mode that ends
``ok`` without a chip — these tests import its phase functions instead), and
its refusals are pinned: no TPU → non-zero exit, no ``"ok": true``."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from comfyui_parallelanything_tpu.models.vae import VAEConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_VAE = VAEConfig(z_channels=4, base_channels=32, channel_mult=(1, 2),
                     num_res_blocks=1, norm_groups=8, dtype=jnp.float32)


@pytest.fixture
def tiny_widths(monkeypatch, tmp_path):
    """The published configs swapped for tiny ones (the
    tests/test_stock_nodes.py recipe) and the script's work directory moved
    under ``tmp_path``."""
    import comfyui_parallelanything_tpu.models as models_pkg
    import comfyui_parallelanything_tpu.models.text_encoders as te_mod

    real_sd15, real_clip = models_pkg.sd15_config, te_mod.clip_l_config
    clip = real_clip(vocab_size=700, hidden_size=64, num_layers=2,
                     num_heads=4, max_len=16, eos_id=699, dtype=jnp.float32)
    monkeypatch.setattr(models_pkg, "sd15_config", lambda: real_sd15(
        model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
        attention_levels=(0, 1), context_dim=clip.hidden_size, num_heads=4,
        norm_groups=8, dtype=jnp.float32,
    ))
    monkeypatch.setattr(models_pkg, "sd_vae_config", lambda: TINY_VAE)
    monkeypatch.setattr(te_mod, "clip_l_config", lambda: clip)
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    for var in ("PA_MODELS_DIR", "PA_OUTPUT_DIR", "PA_CLIP_VOCAB",
                "PA_CLIP_MERGES", "PA_TOKENIZER_JSON"):
        monkeypatch.setenv(var, "")  # synthesize() overwrites; undone after
    return clip


class TestSynthesis:
    def test_published_widths_have_the_published_parameter_counts(self):
        # The smoke's claim to "full width": the shapes it draws weights for
        # are the published models', to the parameter (SD1.5 UNet / kl-f8
        # VAE / CLIP ViT-L/14 text tower).
        from comfyui_parallelanything_tpu import models
        from comfyui_parallelanything_tpu.models.text_encoders import (
            CLIPTextModel, clip_l_config,
        )
        from comfyui_parallelanything_tpu.models.unet import UNet2D
        from comfyui_parallelanything_tpu.models.vae import AutoencoderKL

        def count(fn):
            return sum(int(np.prod(l.shape))
                       for l in jax.tree.leaves(jax.eval_shape(fn)))

        key = jax.random.key(0)
        assert count(lambda: UNet2D(models.sd15_config()).init(
            key, jnp.zeros((1, 8, 8, 4)), jnp.zeros((1,)),
            jnp.zeros((1, 77, 768)))) == 859_520_964
        assert count(lambda: AutoencoderKL(models.sd_vae_config()).init(
            key, jnp.zeros((1, 64, 64, 3)))) == 83_653_863
        assert count(lambda: CLIPTextModel(clip_l_config()).init(
            key, jnp.zeros((1, 77), jnp.int32))) == 123_060_480

    def test_checkpoint_loads_through_the_stock_loader(self, tiny_widths):
        from safetensors.numpy import load_file

        from comfyui_parallelanything_tpu.models.loader import (
            sniff_model_family,
        )
        from comfyui_parallelanything_tpu.nodes_compat import (
            CheckpointLoaderSimple,
        )

        paths = chip_smoke.synthesize(5)
        sd = load_file(paths["ckpt"])
        assert sniff_model_family(sd) == "sd15"
        assert all(v.dtype == np.float16 for v in sd.values())
        # Same seed → the same bytes; another seed → other weights.
        again = chip_smoke.synthesize(5)
        sd2 = load_file(again["ckpt"])
        assert all(np.array_equal(sd[k], sd2[k]) for k in sd)
        model, clip, vae = CheckpointLoaderSimple().load(chip_smoke.CKPT_NAME)
        assert clip["tokenizer"] is not None and vae is not None
        ids, mask = clip["tokenizer"]("a watercolor lighthouse at dawn")
        assert ids.shape == (1, tiny_widths.max_len)
        assert ids[0, 0] == tiny_widths.vocab_size - 2          # BOS
        assert ids[0, mask[0].sum() - 1] == tiny_widths.eos_id  # EOS
        assert ids.max() < tiny_widths.vocab_size

    def test_tokenizer_table_has_the_clip_layout(self, tmp_path):
        vocab_path, merges_path = chip_smoke.write_tokenizer(
            str(tmp_path), seed=1, vocab_size=2000
        )
        vocab = json.load(open(vocab_path))
        assert len(vocab) == 2000 and sorted(vocab.values()) == list(range(2000))
        assert vocab["<|startoftext|>"] == 1998
        assert vocab["<|endoftext|>"] == 1999
        merges = open(merges_path).read().splitlines()[1:]
        assert len(merges) == 2000 - 512 - 2
        assert all("".join(m.split()) in vocab for m in merges)


def test_serve_phase_on_cpu_at_tiny_widths(tiny_widths, no_compile_cache):
    # (the first prompt's compile events are counted: no hits in the run's cache)
    chip_smoke.synthesize(3)
    graph = chip_smoke.stock_graph(width=32, height=32, batch=2, steps=3)
    # The tiny VAE has one upsampling level (×2), the published one three.
    summary = chip_smoke.serve_phase(graph, want_device=None, vae_factor=2)
    assert summary["prompts"] == 3 and summary["all_success"]
    assert [r["seed"] for r in summary["runs"]] == [42, 7, 42]
    assert summary["runs"][0]["compiles"] > 0
    assert all(r["compiles"] == 0 for r in summary["runs"][1:])
    assert summary["pa_degradation_total"] == 0
    assert summary["devices"][-1] == "cpu"


def test_chain_phase_on_four_virtual_devices(tiny_widths, cpu_devices,
                                             monkeypatch):
    """The ``--chips 4`` path's control flow on the virtual mesh: the chain
    shards the sampler output over four distinct devices and agrees with one
    device. (CPU devices report no memory stats: live-array accounting stands
    in for ``bytes_in_use``.)"""
    from comfyui_parallelanything_tpu.devices.memory import device_memory_stats

    monkeypatch.setattr(
        chip_smoke, "_bytes_in_use",
        lambda d: device_memory_stats(d)["bytes_in_use"],
    )
    chip_smoke.synthesize(3)
    graph = chip_smoke.stock_graph(width=32, height=32, batch=8, steps=3)
    chip_smoke.chain_phase(cpu_devices[:4], graph)


def _run_script(*args, cwd=REPO, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=300,
    )


class TestRefusals:
    def test_no_tpu_exits_nonzero_and_prints_no_ok(self):
        proc = _run_script()
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "no TPU" in proc.stderr
        # Nothing was synthesized: the refusal comes first.
        assert '"synthesize"' not in proc.stdout

    def test_main_raises_systemexit_on_cpu_backend(self, capsys):
        with pytest.raises(SystemExit) as exc:
            chip_smoke.main([])
        assert exc.value.code not in (0, None)
        assert '"ok"' not in capsys.readouterr().out

    def test_script_alone_without_the_program_fails(self, tmp_path):
        import shutil

        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], env=env, cwd=str(tmp_path),
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode != 0 and '"ok"' not in proc.stdout

    def test_a_raising_phase_reaches_the_exit_code(self, monkeypatch, capsys):
        # Past the device phase nothing is caught and passed over.
        monkeypatch.setattr(chip_smoke, "device_phase",
                            lambda chips: jax.devices("cpu")[:1])
        monkeypatch.setattr(chip_smoke, "synthesize",
                            lambda seed: {"ckpt": "unused"})

        def boom(seed):
            raise RuntimeError("kernel phase failed")

        monkeypatch.setattr(chip_smoke, "kernel_phase", boom)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           jax.config.jax_compilation_cache_dir or "")
        with pytest.raises(RuntimeError, match="kernel phase failed"):
            chip_smoke.main([])
        assert '"ok"' not in capsys.readouterr().out


class TestCompileCachePlacement:
    def test_env_dir_wins_else_checkout_jax_cache(self, monkeypatch, tmp_path):
        from comfyui_parallelanything_tpu.utils import enable_compilation_cache

        prev = jax.config.jax_compilation_cache_dir
        made = not os.path.exists(os.path.join(REPO, ".jax_cache"))
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
            assert enable_compilation_cache() == str(tmp_path / "c")
            assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            want = os.path.join(REPO, ".jax_cache")
            assert enable_compilation_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            if made:
                os.rmdir(os.path.join(REPO, ".jax_cache"))


def test_fleet_router_never_initialises_a_jax_backend():
    """N backends on one host need one chip each; the router in front of
    them must not take one — importing and serving never touches a backend."""
    code = r"""
import threading, urllib.request
from comfyui_parallelanything_tpu.fleet import router
from jax._src import xla_bridge
srv, _ = router.make_router(port=0)
threading.Thread(target=srv.serve_forever, daemon=True).start()
base = f"http://127.0.0.1:{srv.server_address[1]}"
for path in ("/health", "/metrics", "/fleet/metrics"):
    urllib.request.urlopen(base + path, timeout=30).read()
srv.shutdown()
assert not xla_bridge.backends_are_initialized()
print("router-stayed-off-jax")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "router-stayed-off-jax" in proc.stdout
