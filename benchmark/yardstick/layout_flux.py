"""The FLUX.1 file layouts, written out from the published descriptions:
black-forest-labs/flux ``src/flux/model.py`` and ``modules/layers.py`` (the
``Flux`` transformer as ``flux1-schnell.safetensors`` holds it, bare keys),
and — imported, not copied — the HF ``T5EncoderModel`` of ``t5xxl_fp16``, the
HF ``CLIPTextModel`` of ``clip_l`` and the 16-channel ``AutoEncoder`` of
``ae.safetensors`` (``layout_mmdit`` / ``layout``). Each function returns
``[(key, shape, kind)]`` like ``layout.py``'s; ``synth.write_checkpoint``
finds them through the configuration's ``checkpoint.layouts``. The sizes are
``util.py``'s ``configs["flux-schnell"].params`` under their own names
(``depth``, ``depth_single_blocks``, ``hidden_size``, ...), so a depth cut is
two numbers in the configuration. Nothing here imports the program."""

from __future__ import annotations

from .layout import _lin, clip_hf_layout
from .layout_mmdit import t5_layout, vae16_layout

__all__ = ["flux_layout", "t5_layout", "clip_hf_layout", "vae16_layout",
           "head_dim", "mlp_hidden"]


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_heads"]


def mlp_hidden(m: dict) -> int:
    return int(m["hidden_size"] * m["mlp_ratio"])


def _mlp_embedder(out, key, i, h):
    _lin(out, f"{key}.in_layer", i, h)
    _lin(out, f"{key}.out_layer", h, h)


def _qk_norm(out, key, d):
    out.append((f"{key}.query_norm.scale", (d,), "norm"))
    out.append((f"{key}.key_norm.scale", (d,), "norm"))


def flux_layout(m: dict) -> list[tuple]:
    """``Flux``: ``img_in`` / ``txt_in``, the timestep and pooled-vector
    ``MLPEmbedder``s (a guidance one only with ``guidance_embed``), ``depth``
    ``DoubleStreamBlock``s (each stream: a modulation of six vectors, fused
    qkv with bias, q/k RMS-norm scales per head dim, the attention's output
    projection, a two-layer MLP), ``depth_single_blocks``
    ``SingleStreamBlock``s (a modulation of three vectors, ``linear1`` to
    q, k, v and the MLP's hidden width at once, ``linear2`` back from
    attention ⊕ MLP, the norm scales), the ``LastLayer``."""
    out: list[tuple] = []
    h, d, mlp = m["hidden_size"], head_dim(m), mlp_hidden(m)
    _lin(out, "img_in", m["in_channels"], h)
    _mlp_embedder(out, "time_in", 256, h)
    _mlp_embedder(out, "vector_in", m["vec_in_dim"], h)
    if m.get("guidance_embed"):
        _mlp_embedder(out, "guidance_in", 256, h)
    _lin(out, "txt_in", m["context_in_dim"], h)
    for i in range(m["depth"]):
        for s in ("img", "txt"):
            b = f"double_blocks.{i}.{s}"
            _lin(out, f"{b}_mod.lin", h, 6 * h)
            _lin(out, f"{b}_attn.qkv", h, 3 * h, bias=bool(m.get("qkv_bias", True)))
            _qk_norm(out, f"{b}_attn.norm", d)
            _lin(out, f"{b}_attn.proj", h, h)
            _lin(out, f"{b}_mlp.0", h, mlp)
            _lin(out, f"{b}_mlp.2", mlp, h)
    for i in range(m["depth_single_blocks"]):
        b = f"single_blocks.{i}"
        _lin(out, f"{b}.linear1", h, 3 * h + mlp)
        _lin(out, f"{b}.linear2", h + mlp, h)
        _qk_norm(out, f"{b}.norm", d)
        _lin(out, f"{b}.modulation.lin", h, 3 * h)
    _lin(out, "final_layer.linear", h, m["in_channels"])
    _lin(out, "final_layer.adaLN_modulation.1", h, 2 * h)
    return out
