"""Text encoders (CLIP-L / OpenCLIP-G / T5 / Qwen3) — flax.linen, TPU-first.

The reference receives ready-made conditioning tensors from its host app (its
forward convention is ``forward(x, timesteps, context, **kwargs)`` with ``context``
already encoded, any_device_parallel.py:1287); standalone, this framework encodes
prompts itself. These are fresh implementations of the three encoder families the
supported checkpoints condition on:

- **CLIP-L** (SD1.5 context; SDXL & FLUX pooled vector): 12-layer pre-LN causal
  transformer, quick-gelu, 77-token window.
- **OpenCLIP-G** (SDXL context + pooled): 32-layer, gelu, penultimate-layer output.
- **T5 encoder** (FLUX/WAN context): RMSNorm, relative-position-bucket attention
  bias, gated-gelu FFN, bidirectional.
- **Qwen3** (Z-Image context): the first decoder-only tower — causal,
  grouped-query heads (32 on 8), per-head q/k RMS norm, half-split rotary
  positions, SwiGLU; hands on the state before its last layer, at a bucketed
  length of which the caller keeps the valid tokens.

All take int32 token ids — tokenization is in utils/tokenizer.py (BPE/unigram
tables load from user-supplied files; this image ships none and has no egress).
Sequence lengths are static per call site (77 / 256 / 512), so every encode is a
single fixed-shape XLA program; attention masks are additive f32 biases fused into
the softmax, and matmuls run in the config compute dtype (bf16 on TPU) with f32
softmax/normalization.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# CLIP text towers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CLIPTextConfig:
    vocab_size: int = 49408
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_len: int = 77
    intermediate_size: int | None = None  # default 4*hidden
    act: str = "quick_gelu"  # "quick_gelu" (CLIP-L) | "gelu" (OpenCLIP-G)
    eos_id: int = 49407
    projection_dim: int | None = None  # text_projection for pooled (OpenCLIP / SDXL)
    # SD2's FrozenOpenCLIPEmbedder applies ln_final to the penultimate stream;
    # SDXL consumes it raw. Config-carried so consumers need no side channel.
    penultimate_ln: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def d_ff(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size


def clip_l_config(**overrides) -> CLIPTextConfig:
    """OpenAI CLIP ViT-L/14 text tower (SD1.5 context encoder; SDXL/FLUX 'clip_l')."""
    return dataclasses.replace(CLIPTextConfig(), **overrides)


def open_clip_h_config(**overrides) -> CLIPTextConfig:
    """OpenCLIP ViT-H/14 text tower (SD2.x context encoder): 1024 wide, 24
    layers, plain gelu; SD2.x conditions on the penultimate layer."""
    base = CLIPTextConfig(
        hidden_size=1024, num_layers=24, num_heads=16, act="gelu",
        projection_dim=1024, penultimate_ln=True,
    )
    return dataclasses.replace(base, **overrides)


def open_clip_g_config(**overrides) -> CLIPTextConfig:
    """OpenCLIP bigG/14 text tower (SDXL's second encoder)."""
    base = CLIPTextConfig(
        hidden_size=1280,
        num_layers=32,
        num_heads=20,
        act="gelu",
        projection_dim=1280,
    )
    return dataclasses.replace(base, **overrides)


def _act(name: str):
    if name == "quick_gelu":
        return lambda x: x * nn.sigmoid(1.702 * x)
    if name == "gelu":
        return lambda x: nn.gelu(x, approximate=False)  # HF/OpenCLIP "gelu" is exact erf
    raise ValueError(f"unknown activation {name!r}")


class _CLIPBlock(nn.Module):
    cfg: CLIPTextConfig

    @nn.compact
    def __call__(self, x, bias):
        cfg = self.cfg
        H = cfg.num_heads
        D = cfg.hidden_size // H
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln1")(x)
        qkv = {
            n: nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name=n)(h) for n in "qkv"
        }
        B, S, _ = h.shape
        q, k, v = (qkv[n].reshape(B, S, H, D) for n in "qkv")
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D**-0.5)
        probs = jax.nn.softmax(logits.astype(jnp.float32) + bias, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        x = x + nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="out")(
            attn.reshape(B, S, cfg.hidden_size)
        )
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln2")(x)
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="fc1")(h)
        h = _act(self.cfg.act)(h)
        return x + nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="fc2")(h)


class CLIPTextModel(nn.Module):
    """Returns (last_hidden, penultimate_hidden, pooled). ``last_hidden`` has the
    final LayerNorm applied; ``penultimate_hidden`` is the raw layer-(N-1) stream
    (SDXL consumes exactly that, un-normed) unless ``cfg.penultimate_ln`` (SD2's
    OpenCLIP-H convention: ln_final applied). ``pooled`` reads the first-EOS
    position of the final-LN stream, projected when cfg.projection_dim is set."""

    cfg: CLIPTextConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        B, S = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="tok_emb")(
            tokens
        )
        pos = self.param(
            "pos_emb", nn.initializers.normal(0.01), (cfg.max_len, cfg.hidden_size)
        )
        x = x + pos[None, :S].astype(cfg.dtype)
        causal = jnp.where(
            jnp.tril(jnp.ones((S, S), bool)), 0.0, -jnp.inf
        ).astype(jnp.float32)[None, None]
        penultimate = None
        for i in range(cfg.num_layers):
            if i == cfg.num_layers - 1:
                penultimate = x
            x = _CLIPBlock(cfg, name=f"layers_{i}")(x, causal)
        final_ln = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="final_ln")
        last = final_ln(x)
        if cfg.penultimate_ln:
            penultimate = final_ln(penultimate)
        eos_pos = jnp.argmax((tokens == cfg.eos_id).astype(jnp.int32), axis=-1)
        pooled = jnp.take_along_axis(last, eos_pos[:, None, None], axis=1)[:, 0]
        if cfg.projection_dim is not None:
            pooled = nn.Dense(
                cfg.projection_dim, use_bias=False, dtype=cfg.dtype, name="text_proj"
            )(pooled)
        return last, penultimate, pooled


# ---------------------------------------------------------------------------
# T5 encoder
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 4096
    num_layers: int = 24
    num_heads: int = 64
    d_kv: int = 64
    d_ff: int = 10240
    relative_buckets: int = 32
    relative_max_distance: int = 128
    # UMT5 gives every layer its own relative-position bias table; classic T5
    # shares layer 0's.
    per_layer_bias: bool = False
    dtype: Any = jnp.bfloat16


def t5_xxl_config(**overrides) -> T5Config:
    """google/t5-v1_1-xxl encoder — the FLUX 't5xxl' conditioning tower."""
    return dataclasses.replace(T5Config(), **overrides)


def umt5_xxl_config(**overrides) -> T5Config:
    """google/umt5-xxl encoder — the WAN conditioning tower (multilingual
    256k-token vocab, per-layer relative bias; otherwise the XXL geometry)."""
    base = T5Config(vocab_size=256384, per_layer_bias=True)
    return dataclasses.replace(base, **overrides)


def _t5_relative_buckets(rel_pos, num_buckets: int, max_distance: int):
    """Bidirectional T5 bucket scheme: sign split, then exact small distances,
    log-spaced large ones."""
    num_buckets //= 2
    ret = jnp.where(rel_pos > 0, num_buckets, 0)
    n = jnp.abs(rel_pos)
    max_exact = num_buckets // 2
    large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(n < max_exact, n, large)


class _T5RMSNorm(nn.Module):
    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


class _T5Block(nn.Module):
    cfg: T5Config

    @nn.compact
    def __call__(self, x, bias):
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.d_kv
        inner = H * D
        h = _T5RMSNorm(name="ln1")(x)
        q = nn.Dense(inner, use_bias=False, dtype=cfg.dtype, name="q")(h)
        k = nn.Dense(inner, use_bias=False, dtype=cfg.dtype, name="k")(h)
        v = nn.Dense(inner, use_bias=False, dtype=cfg.dtype, name="v")(h)
        B, S, _ = h.shape
        q, k, v = (t.reshape(B, S, H, D) for t in (q, k, v))
        # T5 uses unscaled dot products (the 1/sqrt(d) is folded into init).
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) + bias
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, inner)
        x = x + nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="o")(attn)
        h = _T5RMSNorm(name="ln2")(x)
        wi0 = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="wi_0")(h)
        wi1 = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="wi_1")(h)
        h = nn.gelu(wi0, approximate=True) * wi1
        return x + nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="wo")(h)


class T5Encoder(nn.Module):
    """Bidirectional T5 v1.1 / UMT5 encoder stack; returns the final RMS-normed
    stream. The relative-position bias table lives on layer 0 and is shared by
    all layers (T5 convention) unless ``cfg.per_layer_bias`` (UMT5: one table
    per layer); ``mask`` (B, S) of 0/1 marks real tokens."""

    cfg: T5Config

    @nn.compact
    def __call__(self, tokens, mask=None):
        cfg = self.cfg
        B, S = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="tok_emb")(
            tokens
        )
        pos = jnp.arange(S)
        buckets = _t5_relative_buckets(
            pos[None, :] - pos[:, None],
            cfg.relative_buckets,
            cfg.relative_max_distance,
        )
        mask_bias = 0.0
        if mask is not None:
            mask_bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -jnp.inf)

        def layer_bias(name: str):
            table = self.param(
                name,
                nn.initializers.normal(1.0),
                (cfg.relative_buckets, cfg.num_heads),
            )
            return table[buckets].transpose(2, 0, 1)[None].astype(jnp.float32) + mask_bias

        bias = None if cfg.per_layer_bias else layer_bias("rel_bias")
        for i in range(cfg.num_layers):
            b = layer_bias(f"rel_bias_{i}") if cfg.per_layer_bias else bias
            x = _T5Block(cfg, name=f"blocks_{i}")(x, b)
        return _T5RMSNorm(name="final_ln")(x)


# ---------------------------------------------------------------------------
# Qwen3 (decoder-only, as a conditioning tower)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Qwen3Config:
    vocab_size: int = 151936
    hidden_size: int = 2560
    intermediate_size: int = 9728
    num_layers: int = 36
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # What tells the causal towers of this one class apart: Qwen3 norms q and
    # k per head and has no biases; Qwen2 / Qwen2.5(-VL)'s language model has
    # biases on q, k and v and no such norms. ``final_norm``: which state is
    # handed on (``output_layers``).
    qkv_bias: bool = False
    qk_norm: bool = True
    final_norm: bool = False

    @property
    def output_layers(self) -> int:
        """How many of the layers run. Without ``final_norm`` the tower hands
        on ``hidden_states[-2]``, the state BEFORE the last layer, so the last
        layer and the final norm are neither run nor kept resident; with it,
        the last layer's state after ``model.norm`` (``hidden_states[-1]``)."""
        return self.num_layers if self.final_norm else self.num_layers - 1


def qwen3_4b_config(**overrides) -> Qwen3Config:
    """Qwen/Qwen3-4B ``config.json`` — Z-Image's text tower."""
    return dataclasses.replace(Qwen3Config(), **overrides)


def qwen25_vl_7b_config(**overrides) -> Qwen3Config:
    """The language model of Qwen/Qwen2.5-VL-7B-Instruct (``config.json``) —
    Qwen-Image's text tower: 7.07 B parameters, the last layer's normed
    states. Text alone: the three ``mrope`` components are the token's
    position, which is the plain rotary."""
    base = Qwen3Config(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        qkv_bias=True, qk_norm=False, final_norm=True)
    return dataclasses.replace(base, **overrides)


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        from ..ops.basic import rms_normalize

        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_normalize(x, scale, self.eps)


class _Qwen3Layer(nn.Module):
    cfg: Qwen3Config

    @nn.compact
    def __call__(self, x, rope):
        from ..ops.attention import grouped_causal_attention
        from ..ops.rope import apply_rope_halves

        cfg = self.cfg
        B, S, _ = x.shape
        H, Hk, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

        def dense(width, name, bias=False):
            return nn.Dense(width, use_bias=bias, dtype=cfg.dtype, name=name)

        h = _RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        q = dense(H * D, "q_proj", cfg.qkv_bias)(h).reshape(B, S, H, D)
        k = dense(Hk * D, "k_proj", cfg.qkv_bias)(h).reshape(B, S, Hk, D)
        v = dense(Hk * D, "v_proj", cfg.qkv_bias)(h).reshape(B, S, Hk, D)
        if cfg.qk_norm:
            q = _RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
            k = _RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
        cos, sin = rope
        a = grouped_causal_attention(
            apply_rope_halves(q, cos, sin), apply_rope_halves(k, cos, sin), v)
        x = x + dense(cfg.hidden_size, "o_proj")(a.reshape(B, S, H * D))
        h = _RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        h = nn.silu(dense(cfg.intermediate_size, "gate_proj")(h)) * dense(
            cfg.intermediate_size, "up_proj")(h)
        return x + dense(cfg.hidden_size, "down_proj")(h)


class Qwen3Model(nn.Module):
    """The causal Qwen stack as a conditioning tower: token ids (B, S) → the
    residual stream after ``cfg.output_layers`` layers — un-normed (HF's
    ``hidden_states[-2]`` at 35 of 36: Qwen3-4B for Z-Image) or, with
    ``cfg.final_norm``, the last layer's through ``model.norm`` (Qwen2.5-VL
    for Qwen-Image). Causal, so a token's state does not depend on what
    follows it: padding a prompt to a bucket leaves the valid states what
    they were, and the caller drops the rest."""

    cfg: Qwen3Config

    @nn.compact
    def __call__(self, tokens):
        from ..ops.rope import half_rope_freqs

        cfg = self.cfg
        B, S = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(tokens)
        rope = half_rope_freqs(
            jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)),
            cfg.head_dim, cfg.rope_theta)
        for i in range(cfg.output_layers):
            x = _Qwen3Layer(cfg, name=f"layers_{i}")(x, rope)
        if cfg.final_norm:
            x = _RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        return x


# ---------------------------------------------------------------------------
# Builders (mirror build_flux/build_unet: params= skips init)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TextEncoder:
    """Encoder as data: jit-cached apply + weights (same shape as DiffusionModel)."""

    module: Any
    cfg: Any
    params: Any

    def _jitted(self):
        if not hasattr(self, "_jit_cache"):
            def encode(p, *a, **kw):
                return self.module.apply({"params": p}, *a, **kw)

            # The XLA module is named for the tower (``jit_text_encode_
            # T5Encoder`` / ``..._CLIPTextModel``), so a device trace tells a
            # text tower's runs from the VAE's ``jit__lambda``.
            encode.__name__ = encode.__qualname__ = (
                f"text_encode_{type(self.module).__name__}"
            )
            object.__setattr__(self, "_jit_cache", jax.jit(encode))
        return self._jit_cache

    def __call__(self, tokens, **kw):
        from .loader import residency

        residency.ensure(self.params)  # back on the chip if it was sent out
        return self._jitted()(self.params, tokens, **kw)


def build_clip_text(cfg: CLIPTextConfig, rng=None, params=None) -> TextEncoder:
    module = CLIPTextModel(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        params = module.init(rng, jnp.zeros((1, cfg.max_len), jnp.int32))["params"]
    return TextEncoder(module=module, cfg=cfg, params=params)


def build_t5_encoder(cfg: T5Config, rng=None, params=None, sample_len=64) -> TextEncoder:
    module = T5Encoder(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        params = module.init(rng, jnp.zeros((1, sample_len), jnp.int32))["params"]
    return TextEncoder(module=module, cfg=cfg, params=params)


def build_qwen3(cfg: Qwen3Config, rng=None, params=None, sample_len=32) -> TextEncoder:
    module = Qwen3Model(cfg)
    if params is None:
        if rng is None:
            raise ValueError("need rng to initialize (or pass params=)")
        params = module.init(rng, jnp.zeros((1, sample_len), jnp.int32))["params"]
    return TextEncoder(module=module, cfg=cfg, params=params)


def sdxl_text_conditioning(
    l_penultimate, g_penultimate, g_pooled, width: int, height: int,
    crop_x: int = 0, crop_y: int = 0, target_width: int | None = None,
    target_height: int | None = None,
):
    """Assemble SDXL's (context, y) pair: context = CLIP-L ⊕ OpenCLIP-G penultimate
    streams (…, 768+1280=2048); y = G pooled (1280) ⊕ six sinusoidal size/crop
    embeddings (256 each → 2816 = the UNet's adm_in_channels)."""
    from ..ops.basic import timestep_embedding

    context = jnp.concatenate(
        [l_penultimate.astype(jnp.float32), g_penultimate.astype(jnp.float32)], axis=-1
    )
    B = g_pooled.shape[0]
    sizes = [
        height, width, crop_y, crop_x,
        target_height or height, target_width or width,
    ]
    embs = [
        timestep_embedding(jnp.full((B,), float(s), jnp.float32), 256) for s in sizes
    ]
    y = jnp.concatenate([g_pooled.astype(jnp.float32)] + embs, axis=-1)
    return context, y


def sdxl_refiner_text_conditioning(g_penultimate, g_pooled, width: int,
                                   height: int, ascore: float,
                                   crop_x: int = 0, crop_y: int = 0):
    """Assemble the SDXL-REFINER (context, y) pair: context = the OpenCLIP-G
    penultimate stream alone (1280-wide — the refiner has no CLIP-L tower);
    y = G pooled (1280) ⊕ five sinusoidal embeddings (256 each) in the
    refiner embedder's order — height, width, crop_y, crop_x, aesthetic
    score — totalling 2560 = the refiner UNet's adm_in_channels."""
    from ..ops.basic import timestep_embedding

    context = g_penultimate.astype(jnp.float32)
    B = g_pooled.shape[0]
    vals = [height, width, crop_y, crop_x, ascore]
    embs = [
        timestep_embedding(jnp.full((B,), float(v), jnp.float32), 256)
        for v in vals
    ]
    y = jnp.concatenate([g_pooled.astype(jnp.float32)] + embs, axis=-1)
    return context, y


def sd3_text_conditioning(l_penultimate, g_penultimate, l_pooled, g_pooled,
                          t5_context=None, context_dim: int = 4096):
    """Assemble SD3's (context, y): the CLIP joint stream (L ⊕ G penultimate,
    768+1280) zero-padded to ``context_dim`` and concatenated along the SEQUENCE
    axis with the T5 stream; y = L pooled ⊕ G pooled (2048)."""
    clip_joint = jnp.concatenate(
        [l_penultimate.astype(jnp.float32), g_penultimate.astype(jnp.float32)],
        axis=-1,
    )
    pad = context_dim - clip_joint.shape[-1]
    if pad < 0:
        raise ValueError(
            f"CLIP joint width {clip_joint.shape[-1]} exceeds {context_dim}"
        )
    clip_joint = jnp.pad(clip_joint, ((0, 0), (0, 0), (0, pad)))
    context = (
        jnp.concatenate([clip_joint, t5_context.astype(jnp.float32)], axis=1)
        if t5_context is not None
        else clip_joint
    )
    y = jnp.concatenate(
        [l_pooled.astype(jnp.float32), g_pooled.astype(jnp.float32)], axis=-1
    )
    return context, y
