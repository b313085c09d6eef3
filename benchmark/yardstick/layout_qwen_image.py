"""The Qwen-Image file layouts, written out from the published descriptions:
``transformer_qwenimage.py`` of huggingface/diffusers
(``QwenImageTransformer2DModel`` as ``transformer/`` of Qwen/Qwen-Image holds
it and ComfyUI's ``qwen_image_bf16.safetensors`` spells it, bare keys), the
language model of HF ``Qwen2_5_VLForConditionalGeneration``
(Qwen2.5-VL-7B-Instruct: ``Qwen3ForCausalLM``'s keys with biases on q / k / v
and without the q/k norms; no ``visual.*`` tower and no ``lm_head``, which
text-to-image never runs — ``assumed`` in the configuration),
``autoencoder_kl_qwenimage.py`` (``AutoencoderKLQwenImage`` is the Wan2.1
causal 3-D autoencoder: ``layout_wan.wan_vae_layout`` under ``base_dim``,
imported, in the Wan key spelling ComfyUI's ``qwen_image_vae.safetensors``
keeps) and the rank-64 LoRA of ``lightx2v/Qwen-Image-Lightning``
(``assumed``). Each function returns ``[(key, shape, kind)]`` like
``layout.py``'s; ``synth.write_checkpoint`` finds them through the
configuration's ``checkpoint.layouts``. The sizes are the published
``config.json`` keys (``num_layers``, ``num_attention_heads``,
``attention_head_dim``, ``joint_attention_dim``, …), so the depth cut is one
number in the configuration. Nothing here imports the program.

Counts at the published sizes (``benchmark/tests/test_qwen_image.py`` pins
them): a block 339,831,296 parameters; what the transformer holds besides its
blocks 40,523,328; whole (60 blocks) 20,430,401,088, at the cut (8)
2,759,173,696; the tower's language model 7,070,619,136."""

from __future__ import annotations

from .layout import _lin
from .layout_wan import wan_vae_layout

__all__ = ["qwen_image_layout", "qwen25vl_layout", "qwen_image_vae_layout",
           "qwen_image_lora_layout", "LORA_TARGETS", "inner_dim", "mlp_hidden"]

TIME_FREQUENCIES = 256   # Timesteps(num_channels=256)
MLP_RATIO = 4            # FeedForward(dim, dim_out=dim): inner 4 x dim

# The twelve linears of a block that carry a LoRA delta, by (in, out) kind.
LORA_TARGETS = (
    "attn.to_q", "attn.to_k", "attn.to_v", "attn.to_out.0",
    "attn.add_q_proj", "attn.add_k_proj", "attn.add_v_proj", "attn.to_add_out",
    "img_mlp.net.0.proj", "img_mlp.net.2", "txt_mlp.net.0.proj", "txt_mlp.net.2")


def inner_dim(m: dict) -> int:
    return m["num_attention_heads"] * m["attention_head_dim"]


def mlp_hidden(m: dict) -> int:
    return MLP_RATIO * inner_dim(m)


def _rms(out, key, n):
    out.append((f"{key}.weight", (n,), "norm"))


def qwen_image_layout(m: dict) -> list[tuple]:
    """``QwenImageTransformer2DModel``: ``img_in`` (the 2 x 2 patch of
    ``out_channels`` latent channels → inner), ``txt_norm`` (RMS) and
    ``txt_in`` (``joint_attention_dim`` → inner), the timestep embedder
    (256 → inner → inner; ``guidance_embeds`` false: nothing else),
    ``num_layers`` ``QwenImageTransformerBlock``s — each stream a modulation
    (``img_mod.1`` / ``txt_mod.1``: inner → 6 x inner), biased q / k / v
    (``to_*`` for the image, ``add_*_proj`` for the text), RMS scales over the
    head dim for both streams' q and k, the two output projections, a
    two-layer tanh-GELU feed-forward — ``norm_out.linear`` (inner → 2 x
    inner) and ``proj_out``."""
    out: list[tuple] = []
    d, hd, ff = inner_dim(m), m["attention_head_dim"], mlp_hidden(m)
    patch = m["patch_size"] ** 2 * m["out_channels"]
    if patch != m["in_channels"]:
        raise ValueError("in_channels is not patch_size² x out_channels")
    _lin(out, "img_in", m["in_channels"], d)
    _rms(out, "txt_norm", m["joint_attention_dim"])
    _lin(out, "txt_in", m["joint_attention_dim"], d)
    _lin(out, "time_text_embed.timestep_embedder.linear_1", TIME_FREQUENCIES, d)
    _lin(out, "time_text_embed.timestep_embedder.linear_2", d, d)
    for i in range(m["num_layers"]):
        b = f"transformer_blocks.{i}"
        _lin(out, f"{b}.img_mod.1", d, 6 * d)
        _lin(out, f"{b}.txt_mod.1", d, 6 * d)
        for n in ("to_q", "to_k", "to_v", "add_q_proj", "add_k_proj", "add_v_proj"):
            _lin(out, f"{b}.attn.{n}", d, d)
        for n in ("norm_q", "norm_k", "norm_added_q", "norm_added_k"):
            _rms(out, f"{b}.attn.{n}", hd)
        _lin(out, f"{b}.attn.to_out.0", d, d)
        _lin(out, f"{b}.attn.to_add_out", d, d)
        for s in ("img", "txt"):
            _lin(out, f"{b}.{s}_mlp.net.0.proj", d, ff)
            _lin(out, f"{b}.{s}_mlp.net.2", ff, d)
    _lin(out, "norm_out.linear", d, 2 * d)
    _lin(out, "proj_out", d, patch)
    return out


def qwen25vl_layout(t: dict) -> list[tuple]:
    """The language model of ``Qwen2_5_VLForConditionalGeneration``:
    ``model.embed_tokens``, per layer q (``num_attention_heads`` x 128), k and
    v (``num_key_value_heads`` x 128) WITH bias, o without, the bias-free
    SwiGLU and two RMS norms; ``model.norm``. The embedding is drawn at unit
    variance (a kernel of fan-in 1), as ``layout_zimage.qwen3_layout`` says."""
    out: list[tuple] = []
    h, ff = t["hidden_size"], t["intermediate_size"]
    hd = h // t["num_attention_heads"]
    q, kv = t["num_attention_heads"] * hd, t["num_key_value_heads"] * hd
    out.append(("model.embed_tokens.weight", (t["vocab_size"], h), "w:1"))
    for i in range(t["num_hidden_layers"]):
        b = f"model.layers.{i}"
        _lin(out, f"{b}.self_attn.q_proj", h, q)
        _lin(out, f"{b}.self_attn.k_proj", h, kv)
        _lin(out, f"{b}.self_attn.v_proj", h, kv)
        _lin(out, f"{b}.self_attn.o_proj", q, h, bias=False)
        _lin(out, f"{b}.mlp.gate_proj", h, ff, bias=False)
        _lin(out, f"{b}.mlp.up_proj", h, ff, bias=False)
        _lin(out, f"{b}.mlp.down_proj", ff, h, bias=False)
        _rms(out, f"{b}.input_layernorm", h)
        _rms(out, f"{b}.post_attention_layernorm", h)
    _rms(out, "model.norm", h)
    return out


def qwen_image_vae_layout(v: dict) -> list[tuple]:
    """``AutoencoderKLQwenImage`` = ``WanVAE_`` with ``base_dim`` for ``dim``."""
    return wan_vae_layout(dict(v, dim=v["base_dim"]))


def qwen_image_lora_layout(l: dict) -> list[tuple]:
    """A rank-``rank`` LoRA over LORA_TARGETS of every block
    (``transformer_blocks.N.<linear>.lora_down.weight`` (rank, in),
    ``.lora_up.weight`` (out, rank), ``.alpha`` a scalar), drawn as
    ``layout_wan.wan_lora_layout`` draws: ``(alpha / rank) · up @ down`` is an
    eighth of the base kernel's own spread, so a bake that is left out, or
    scaled wrongly, moves the image far past the comparison's limit."""
    out: list[tuple] = []
    d, r = l["dim"], l["rank"]
    ff = MLP_RATIO * d
    for i in range(l["num_layers"]):
        for name in LORA_TARGETS:
            i_dim = ff if name.endswith("mlp.net.2") else d
            o_dim = ff if name.endswith("mlp.net.0.proj") else d
            b = f"transformer_blocks.{i}.{name}"
            out.append((f"{b}.lora_down.weight", (r, i_dim), f"w:{i_dim}"))
            out.append((f"{b}.lora_up.weight", (o_dim, r), "w:1"))
            out.append((f"{b}.alpha", (), "norm"))
    return out
