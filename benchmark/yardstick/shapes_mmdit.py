"""Operations and bytes of one MMDiT-X forward and of its two attention
classes, from shapes alone: the work the published model needs at the cell's
latent shape, whatever the program emits. Counted as ``shapes_sd`` counts
(its ``_Cost``): multiply-adds as two operations, attention as QK^T and PV,
no normalisation, activation or softmax; bytes are every parameter once at
the compute type's width and every contraction's input and output
activations once. The stored position table is read, not multiplied: its
cropped rows count as bytes only."""

from __future__ import annotations

from .layout_mmdit import dual_layers, hidden_size
from .shapes_sd import _Cost


def _sizes(config: dict, mix: dict, chips: int) -> tuple[dict, int, int, int]:
    """(mmdit sizes, rows a chip computes, image tokens, text tokens): the
    latent batch doubled by classifier-free guidance and split over the
    chips of a chain, as ``shapes_sd.denoiser_step`` has it."""
    m, lat = config["mmdit"], mix["latent"]
    rows = -(-2 * int(lat["batch_size"]) // chips)
    side = 8 * m["patch_size"]
    tokens = (int(lat["height"]) // side) * (int(lat["width"]) // side)
    return m, rows, tokens, int(config["text"]["max_position_embeddings"])


def mmdit_forward(m: dict, batch: int, tokens: int, ctx_len: int,
                  act_bytes: int = 2, param_bytes: int = 2) -> dict:
    """One ``MMDiTX.forward`` on ``batch`` rows of ``tokens`` image tokens
    and ``ctx_len`` text tokens."""
    c = _Cost(batch, act_bytes)
    h = hidden_size(m)
    mlp = int(h * m.get("mlp_ratio", 4.0))
    qk_norm = 2 * m["attention_head_dim"] if m.get("qk_norm") else 0
    c.linear(m["patch_size"] ** 2 * m["in_channels"], h, tokens)
    c.act += tokens * h  # the cropped position rows, read once a row
    c.linear(m["joint_attention_dim"], h, ctx_len)
    for width in (m["frequency_embedding_size"], m["pooled_projection_dim"]):
        c.linear(width, h, 1)
        c.linear(h, h, 1)
    n = m["num_layers"]
    for i in range(n):
        last, dual = i == n - 1, i in dual_layers(m)
        c.linear(h, (2 if last else 6) * h, 1)
        c.linear(h, (9 if dual else 6) * h, 1)
        c.linear(h, 3 * h, ctx_len)
        c.linear(h, 3 * h, tokens)
        c.attention(ctx_len + tokens, ctx_len + tokens, h)
        c.linear(h, h, tokens)
        c.params += 2 * qk_norm
        if dual:
            c.linear(h, 3 * h, tokens)
            c.attention(tokens, tokens, h)
            c.linear(h, h, tokens)
            c.params += qk_norm
        c.linear(h, mlp, tokens)
        c.linear(mlp, h, tokens)
        if not last:
            c.linear(h, h, ctx_len)
            c.linear(h, mlp, ctx_len)
            c.linear(mlp, h, ctx_len)
    c.linear(h, 2 * h, 1)
    c.linear(h, m["patch_size"] ** 2 * m["out_channels"], tokens)
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def denoiser_step(config: dict, mix: dict, chips: int) -> dict:
    """One denoiser forward as the cell's sampler step asks for it."""
    m, rows, tokens, ctx_len = _sizes(config, mix, chips)
    return mmdit_forward(m, rows, tokens, ctx_len)


def _attention_call(m: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    c = _Cost(rows, act_bytes)
    c.attention(seq, seq, hidden_size(m))
    return {"flops": c.flops, "bytes": c.act * act_bytes}


def joint_attention(config: dict, mix: dict, chips: int) -> dict:
    """One joint attention over text ⊕ image tokens (every block has one)."""
    m, rows, tokens, ctx_len = _sizes(config, mix, chips)
    return _attention_call(m, rows, ctx_len + tokens)


def dual_attention(config: dict, mix: dict, chips: int) -> dict:
    """One second attention over the image tokens alone (the blocks in
    ``dual_attention_layers``)."""
    m, rows, tokens, _ = _sizes(config, mix, chips)
    return _attention_call(m, rows, tokens)
