"""Stock-ComfyUI node-name shims (nodes_compat.py), the SD3 stock surface:
TripleCLIPLoader, DualCLIPLoader(type=sd3) and the stock SD3 template chain on
tiny clip_l / clip_g / t5xxl tower files written once for this file.
``test_stock_nodes.py`` has the stock graph itself and the helpers."""

import numpy as np
import pytest

from tests.test_stock_nodes import _word_level_tokenizer


def _synthetic_sd3_towers(tmp_path, monkeypatch):
    """Tiny clip_l / clip_g / t5xxl tower files in the stock SD3 template
    naming, with tokenizer env vars wired and the tiny configs pinned. The
    widths are coupled the way the real family's are: T5 d_model (128) is the
    context width the CLIP L⊕G joint (64+64) pads to; pooled = 64+64."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import torch
    import transformers
    from safetensors.numpy import save_file

    import comfyui_parallelanything_tpu.models as models_pkg
    import comfyui_parallelanything_tpu.models.text_encoders as te_mod
    from comfyui_parallelanything_tpu.models.text_encoders import (
        build_clip_text,
        open_clip_g_config,
    )
    from tests.test_text_encoders import (
        TINY_CLIP,
        TINY_T5,
        TestOpenCLIPConversion,
        _hf_clip,
    )

    l_cfg = dataclasses.replace(TINY_CLIP, max_len=77)
    monkeypatch.setattr(te_mod, "clip_l_config", lambda: l_cfg)
    g_cfg = open_clip_g_config(
        vocab_size=100, hidden_size=64, num_layers=2, num_heads=4,
        max_len=77, projection_dim=64, dtype=jnp.float32,
    )
    monkeypatch.setattr(models_pkg, "open_clip_g_config", lambda: g_cfg)
    monkeypatch.setattr(te_mod, "open_clip_g_config", lambda: g_cfg)
    t5_cfg = dataclasses.replace(TINY_T5, d_model=128)
    monkeypatch.setattr(te_mod, "t5_xxl_config", lambda: t5_cfg)

    hf_l = _hf_clip(l_cfg, "quick_gelu")
    l_path = tmp_path / "clip_l.safetensors"
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in hf_l.state_dict().items()},
        str(l_path),
    )

    g_enc = build_clip_text(g_cfg, rng=jax.random.key(2))
    g_path = tmp_path / "clip_g.safetensors"
    save_file(
        {k: np.ascontiguousarray(v)
         for k, v in TestOpenCLIPConversion._openclip_layout(
             g_cfg, g_enc.params
         ).items()},
        str(g_path),
    )

    hf_cfg = transformers.T5Config(
        vocab_size=t5_cfg.vocab_size, d_model=t5_cfg.d_model,
        d_kv=t5_cfg.d_kv, d_ff=t5_cfg.d_ff, num_layers=t5_cfg.num_layers,
        num_heads=t5_cfg.num_heads,
        relative_attention_num_buckets=t5_cfg.relative_buckets,
        relative_attention_max_distance=t5_cfg.relative_max_distance,
        feed_forward_proj="gated-gelu", dropout_rate=0.0,
    )
    torch.manual_seed(3)
    hf_t5 = transformers.T5EncoderModel(hf_cfg).eval()
    t5_path = tmp_path / "t5xxl_fp16.safetensors"
    save_file(
        {k: np.ascontiguousarray(v.detach().numpy())
         for k, v in hf_t5.state_dict().items()},
        str(t5_path),
    )

    _word_level_tokenizer(tmp_path, monkeypatch)  # PA_TOKENIZER_JSON
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {"[UNK]": 0, "</s>": 1, "a": 5, "watercolor": 6, "lighthouse": 7,
             "at": 8, "dawn": 9, "blurry": 10}
    t = tokenizers.Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = Whitespace()
    t5_tok = tmp_path / "t5_tokenizer.json"
    t.save(str(t5_tok))
    monkeypatch.setenv("PA_T5_TOKENIZER_JSON", str(t5_tok))

    return {"l": str(l_path), "g": str(g_path), "t5": str(t5_path)}


@pytest.fixture(scope="module")
def sd3_towers(tmp_path_factory):
    """``_synthetic_sd3_towers`` once for this file. Read-only."""
    with pytest.MonkeyPatch.context() as mp:
        yield _synthetic_sd3_towers(tmp_path_factory.mktemp("sd3_towers"), mp)


class TestTripleCLIPLoader:
    def test_loads_and_encodes_sd3_conditioning(self, sd3_towers):
        from comfyui_parallelanything_tpu.nodes import TPUTextEncode
        from comfyui_parallelanything_tpu.nodes_compat import TripleCLIPLoader

        paths = sd3_towers
        # Scrambled widget order: classification is by name/keys, not slot.
        (clip,) = TripleCLIPLoader().load(paths["t5"], paths["g"], paths["l"])
        assert clip["type"] == "sd3-triple"
        assert clip["t5"] is not None

        (cond,) = TPUTextEncode().encode(clip, "a watercolor lighthouse")
        # context: CLIP joint (77 tokens, padded 64+64→128) ‖ T5 (77, 128)
        assert cond["context"].shape == (1, 154, 128)
        assert cond["pooled"].shape == (1, 128)
        assert np.isfinite(np.asarray(cond["context"])).all()
        # The T5 half must be the live stream, not padding.
        assert float(np.abs(np.asarray(cond["context"][:, 77:])).max()) > 0

    def test_key_signature_classification(self, sd3_towers, tmp_path):
        """Files with no name markers classify off the safetensors keys."""
        import shutil

        from comfyui_parallelanything_tpu.nodes_compat import (
            TripleCLIPLoader,
            _classify_text_tower,
        )

        paths = sd3_towers
        a = tmp_path / "towerA.safetensors"  # t5 keys
        b = tmp_path / "towerB.safetensors"  # open-clip keys
        c = tmp_path / "towerC.safetensors"  # HF CLIP keys, width 64
        shutil.copy(paths["t5"], a)
        shutil.copy(paths["g"], b)
        shutil.copy(paths["l"], c)
        assert _classify_text_tower(str(a), str(a)) == "t5"
        assert _classify_text_tower(str(b), str(b)) == "open-clip-g"
        assert _classify_text_tower(str(c), str(c)) == "clip-l"
        (clip,) = TripleCLIPLoader().load(str(b), str(c), str(a))
        assert clip["type"] == "sd3-triple" and clip["t5"] is not None

    def test_duplicate_and_missing_towers_raise(self, sd3_towers):
        from comfyui_parallelanything_tpu.nodes_compat import TripleCLIPLoader

        paths = sd3_towers
        with pytest.raises(ValueError, match="two t5 files"):
            TripleCLIPLoader().load(paths["t5"], paths["t5"], paths["l"])

    def test_dual_clip_loader_sd3_two_tower_form(self, sd3_towers):
        """DualCLIPLoader(type=sd3): CLIP-L + G, no T5 — context is the
        padded joint alone; a clip_g file in slot 1 corrects swapped wiring."""
        from comfyui_parallelanything_tpu.nodes import TPUTextEncode
        from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader

        paths = sd3_towers
        (clip,) = DualCLIPLoader().load(paths["g"], paths["l"], type="sd3")
        assert clip["type"] == "sd3-triple" and clip["t5"] is None
        (cond,) = TPUTextEncode().encode(clip, "a watercolor lighthouse")
        # No T5 stream: the joint pads to the real family's 4096.
        assert cond["context"].shape == (1, 77, 4096)
        assert cond["pooled"].shape == (1, 128)

    def test_dual_clip_loader_sd3_clip_plus_t5_pairings(self, sd3_towers):
        """DualCLIPLoader(type=sd3) with the common clip+t5xxl pairings:
        stock classifies the two files from their contents, so the T5 file
        must land on the t5 slot (not mis-load as a CLIP tower) and the
        missing CLIP tower zero-fills at encode."""
        from comfyui_parallelanything_tpu.nodes import TPUTextEncode
        from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader

        paths = sd3_towers
        # clip_l + t5xxl (either order): g stays None.
        (clip,) = DualCLIPLoader().load(paths["t5"], paths["l"], type="sd3")
        assert clip["type"] == "sd3-triple"
        assert clip["g"] is None
        assert clip["l"] is not None and clip["t5"] is not None
        (cond,) = TPUTextEncode().encode(clip, "a watercolor lighthouse")
        # CLIP joint (L only, padded to the tiny T5's 128) ‖ T5 stream.
        assert cond["context"].shape == (1, 154, 128)
        # Missing G pooled zero-fills at the canonical 1280: 64 + 1280.
        assert cond["pooled"].shape == (1, 1344)
        assert float(np.abs(np.asarray(cond["pooled"][:, 64:])).max()) == 0.0
        # The T5 half must be the live stream, not padding.
        assert float(np.abs(np.asarray(cond["context"][:, 77:])).max()) > 0
        # clip_g + t5xxl: l stays None, pooled = zeros(768) ⊕ G's 64.
        (clip2,) = DualCLIPLoader().load(paths["g"], paths["t5"], type="sd3")
        assert clip2["l"] is None and clip2["t5"] is not None
        (cond2,) = TPUTextEncode().encode(clip2, "a watercolor lighthouse")
        assert cond2["pooled"].shape == (1, 832)
        assert float(np.abs(np.asarray(cond2["pooled"][:, :768])).max()) == 0.0
        # ALIGNMENT: the missing L still occupies its LEADING joint slot as
        # zeros (canonical 768, clamped to the tiny geometry: min(768,
        # 128−64) = 64), so G's live features keep their trained offset
        # instead of shifting to column 0.
        assert cond2["context"].shape == (1, 154, 128)
        clip_rows = np.asarray(cond2["context"][:, :77])
        assert float(np.abs(clip_rows[..., :64]).max()) == 0.0
        assert float(np.abs(clip_rows[..., 64:]).max()) > 0

    def test_dual_clip_loader_sd3_duplicate_towers_raise(self, sd3_towers):
        import pytest

        from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader

        paths = sd3_towers
        with pytest.raises(ValueError, match="two t5 files"):
            DualCLIPLoader().load(paths["t5"], paths["t5"], type="sd3")


class TestStockSD3Template:
    def test_sd3_template_chain(self, sd3_towers, tmp_path, monkeypatch):
        """The stock SD3 template node chain — UNETLoader (MMDiT file sniffed
        sd3-medium) + TripleCLIPLoader + CLIPTextEncode ×2 + ModelSamplingSD3
        + EmptySD3LatentImage + KSampler — runs with stock names/inputs."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        from safetensors.numpy import save_file

        import comfyui_parallelanything_tpu.models as models_pkg
        from comfyui_parallelanything_tpu import nodes_compat
        from comfyui_parallelanything_tpu.models.mmdit import (
            MMDiTConfig,
            build_mmdit,
        )
        from tests.test_mmdit import _official_layout_sd

        paths = sd3_towers
        mcfg = MMDiTConfig(
            in_channels=16, depth=2, context_in_dim=128, pooled_dim=128,
            pos_embed_max=16, qk_norm=True, dtype=jnp.float32,
        )
        mm = build_mmdit(
            mcfg, jax.random.key(0), sample_shape=(1, 8, 8, 16), txt_len=6
        )
        mm_path = tmp_path / "sd3_tiny.safetensors"
        save_file(
            {k: np.ascontiguousarray(v)
             for k, v in _official_layout_sd(mcfg, mm.params).items()},
            str(mm_path),
        )
        monkeypatch.setattr(models_pkg, "sd3_medium_config", lambda: mcfg)

        n = nodes_compat.stock_node_mappings()
        (model,) = n["UNETLoader"]().load_unet(str(mm_path))
        (clip,) = n["TripleCLIPLoader"]().load(
            paths["l"], paths["g"], paths["t5"]
        )
        (pos,) = n["CLIPTextEncode"]().run(
            clip=clip, text="a watercolor lighthouse at dawn"
        )
        (neg,) = n["CLIPTextEncode"]().run(clip=clip, text="blurry")
        (model,) = n["ModelSamplingSD3"]().patch(model, shift=3.0)
        (lat,) = n["EmptySD3LatentImage"]().generate(64, 64, 1)
        assert lat["samples"].shape == (1, 8, 8, 16)
        (out,) = n["KSampler"]().run(
            model=model, positive=pos, negative=neg, latent_image=lat,
            seed=0, steps=2, cfg=3.0, sampler_name="euler",
            scheduler="normal",
        )
        assert out["samples"].shape == (1, 8, 8, 16)
        assert np.isfinite(np.asarray(out["samples"])).all()
