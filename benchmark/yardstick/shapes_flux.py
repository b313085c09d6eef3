"""Operations and bytes of one FLUX forward, of its one attention class and of
the two text towers, from shapes alone: the work the published model needs
at the cell's latent shape, whatever the program emits. Counted as
``shapes_sd`` counts (its ``_Cost``): multiply-adds as two operations,
attention as QK^T and PV, no normalisation, activation, rotary or softmax;
bytes are every parameter once at the compute type's width and every
contraction's input and output activations once. The sampler runs at CFG 1.0,
so a step is one row a latent: nothing is doubled."""

from __future__ import annotations

from .layout_flux import head_dim, mlp_hidden
from .shapes_sd import _Cost


def _t5_length(config: dict) -> int:
    return int(next(t["max_length"] for t in config["tokenizers"] if t["name"] == "t5"))


def _sizes(config: dict, mix: dict, chips: int) -> tuple[dict, int, int, int]:
    """(flux sizes, rows a chip computes, image tokens, text tokens): the
    latent batch split over the chips of a chain; a token is a 2 x 2 patch of
    the 8x-downsampled latent."""
    m, lat = config["flux"], mix["latent"]
    rows = -(-int(lat["batch_size"]) // chips)
    tokens = (int(lat["height"]) // 16) * (int(lat["width"]) // 16)
    return m, rows, tokens, _t5_length(config)


def flux_forward(m: dict, batch: int, tokens: int, txt_len: int,
                 act_bytes: int = 2, param_bytes: int = 2) -> dict:
    """One ``Flux.forward`` on ``batch`` rows of ``tokens`` image tokens and
    ``txt_len`` text tokens."""
    c = _Cost(batch, act_bytes)
    h, mlp, seq = m["hidden_size"], mlp_hidden(m), tokens + txt_len
    qk_norm = 2 * head_dim(m)
    c.linear(m["in_channels"], h, tokens)
    c.linear(m["context_in_dim"], h, txt_len)
    for width in (256, m["vec_in_dim"]) + ((256,) if m.get("guidance_embed") else ()):
        c.linear(width, h, 1)
        c.linear(h, h, 1)
    for _ in range(m["depth"]):
        for n in (tokens, txt_len):
            c.linear(h, 6 * h, 1)
            c.linear(h, 3 * h, n)
            c.linear(h, h, n)
            c.linear(h, mlp, n)
            c.linear(mlp, h, n)
            c.params += qk_norm
        c.attention(seq, seq, h)
    for _ in range(m["depth_single_blocks"]):
        c.linear(h, 3 * h, 1)
        c.linear(h, 3 * h + mlp, seq)
        c.attention(seq, seq, h)
        c.linear(h + mlp, h, seq)
        c.params += qk_norm
    c.linear(h, 2 * h, 1)
    c.linear(h, m["in_channels"], tokens)
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def denoiser_step(config: dict, mix: dict, chips: int) -> dict:
    """One denoiser forward as the cell's sampler step asks for it."""
    m, rows, tokens, txt_len = _sizes(config, mix, chips)
    return flux_forward(m, rows, tokens, txt_len)


def joint_attention(config: dict, mix: dict, chips: int) -> dict:
    """One attention over text ⊕ image tokens (every block has one)."""
    m, rows, tokens, txt_len = _sizes(config, mix, chips)
    c = _Cost(rows, 2)
    c.attention(tokens + txt_len, tokens + txt_len, m["hidden_size"])
    return {"flops": c.flops, "bytes": c.act * 2}


def t5_forward(t: dict, batch: int, length: int, act_bytes: int = 2,
               param_bytes: int = 2) -> dict:
    """One T5 v1.1 encoder pass over ``length`` tokens (the embedding is a
    lookup: its rows count as bytes)."""
    c = _Cost(batch, act_bytes)
    d, inner = t["d_model"], t["num_heads"] * t["d_kv"]
    c.act += length * d
    for _ in range(t["num_layers"]):
        for _ in "qkv":
            c.linear(d, inner, length, bias=False)
        c.attention(length, length, inner)
        c.linear(inner, d, length, bias=False)
        c.linear(d, t["d_ff"], length, bias=False)
        c.linear(d, t["d_ff"], length, bias=False)
        c.linear(t["d_ff"], d, length, bias=False)
        c.params += 2 * d
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def clip_forward(t: dict, batch: int, act_bytes: int = 2, param_bytes: int = 2) -> dict:
    """One CLIP text tower pass over its ``max_position_embeddings`` tokens."""
    c = _Cost(batch, act_bytes)
    h, ff, n = t["hidden_size"], t["intermediate_size"], t["max_position_embeddings"]
    c.act += n * h
    for _ in range(t["num_hidden_layers"]):
        for _ in range(4):
            c.linear(h, h, n)
        c.attention(n, n, h)
        c.linear(h, ff, n)
        c.linear(ff, h, n)
        c.params += 4 * h
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def text_towers(config: dict, mix: dict, chips: int) -> dict:
    """Both towers once, for one prompt: what a request with a new text pays
    before its first step (T5 over its padded length, CLIP-L over 77)."""
    t5 = t5_forward(config["text_t5"], 1, _t5_length(config))
    clip = clip_forward(config["text"], 1)
    return {"flops": t5["flops"] + clip["flops"], "bytes": t5["bytes"] + clip["bytes"]}
