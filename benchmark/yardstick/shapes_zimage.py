"""Operations and bytes of one Z-Image forward, of its two attention classes
and of the Qwen3 text tower, from shapes alone: the work the published model
needs at the cell's latent shape, whatever the program emits. Counted as
``shapes_sd`` counts (its ``_Cost``): multiply-adds as two operations,
attention as QK^T and PV, no normalisation, activation, rotary or softmax;
bytes are every parameter once at the compute type's width and every
contraction's input and output activations once. The sampler runs at CFG 1.0,
so a step is one row a latent: nothing is doubled. The caption's length is
the traffic's: the mix's words through the configuration's tokenizer
(``tokenizer_bpe``: one token a word) and the chat template's eight tokens,
padded to the model's multiple of 32."""

from __future__ import annotations

from .layout_zimage import adaln_dim, ffn_hidden, head_dim
from .shapes_sd import _Cost

TEMPLATE_TOKENS = 8   # <|im_start|> user \n … <|im_end|> \n <|im_start|> assistant \n
SEQ_MULTI_OF = 32


def _padded(n: int) -> int:
    return -(-n // SEQ_MULTI_OF) * SEQ_MULTI_OF


def caption_tokens(mix: dict) -> int:
    """Valid tokens of one request's templated prompt: a token a word."""
    prompt = mix["draws"]["prompt"]
    words = (len(prompt["text"].split()) if prompt["kind"] == "fixed"
             else int(prompt.get("words", 8)))
    return words + TEMPLATE_TOKENS


def _sizes(config: dict, mix: dict, chips: int) -> tuple[dict, int, int, int]:
    """(denoiser sizes, rows a chip computes, image tokens, caption tokens),
    both counts padded: the latent batch split over the chips of a chain; a
    token is a 2 x 2 patch of the 8x-downsampled latent."""
    m, lat = config["zimage"], mix["latent"]
    rows = -(-int(lat["batch_size"]) // chips)
    tokens = (int(lat["height"]) // 16) * (int(lat["width"]) // 16)
    return m, rows, _padded(tokens), _padded(caption_tokens(mix))


def _block(c: _Cost, m: dict, seq: int, modulated: bool) -> None:
    d, ff = m["dim"], ffn_hidden(m)
    if modulated:
        c.linear(adaln_dim(m), 4 * d, 1)
    for _ in range(4):  # q, k, v, out
        c.linear(d, d, seq, bias=False)
    c.attention(seq, seq, d)
    c.linear(d, ff, seq, bias=False)
    c.linear(d, ff, seq, bias=False)
    c.linear(ff, d, seq, bias=False)
    c.params += 4 * d + 2 * head_dim(m)  # four stream norms, q and k norms


def zimage_forward(m: dict, batch: int, tokens: int, cap_len: int,
                   act_bytes: int = 2, param_bytes: int = 2) -> dict:
    """One ``ZImageTransformer2DModel.forward`` on ``batch`` rows of
    ``tokens`` image tokens and ``cap_len`` caption tokens (padded counts)."""
    c = _Cost(batch, act_bytes)
    d = m["dim"]
    patch = m["in_channels"] * m["all_patch_size"][0] ** 2
    c.linear(patch, d, tokens)
    c.linear(m["cap_feat_dim"], d, cap_len)
    c.linear(256, 1024, 1)
    c.linear(1024, adaln_dim(m), 1)
    c.params += m["cap_feat_dim"] + 2 * d  # caption norm, two pad tokens
    for _ in range(m["n_refiner_layers"]):
        _block(c, m, tokens, True)
        _block(c, m, cap_len, False)
    for _ in range(m["n_layers"]):
        _block(c, m, tokens + cap_len, True)
    c.linear(adaln_dim(m), d, 1)
    c.linear(d, patch, tokens)
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def denoiser_step(config: dict, mix: dict, chips: int) -> dict:
    """One denoiser forward as the cell's sampler step asks for it."""
    m, rows, tokens, cap_len = _sizes(config, mix, chips)
    return zimage_forward(m, rows, tokens, cap_len)


def _attention(config, mix, chips, with_caption: bool) -> dict:
    m, rows, tokens, cap_len = _sizes(config, mix, chips)
    seq = tokens + (cap_len if with_caption else 0)
    c = _Cost(rows, 2)
    c.attention(seq, seq, m["dim"])
    return {"flops": c.flops, "bytes": c.act * 2}


def joint_attention(config: dict, mix: dict, chips: int) -> dict:
    """One attention over image ⊕ caption tokens (every main layer has one)."""
    return _attention(config, mix, chips, True)


def refiner_attention(config: dict, mix: dict, chips: int) -> dict:
    """One attention over the image tokens alone (every noise-refiner layer)."""
    return _attention(config, mix, chips, False)


def qwen3_forward(t: dict, batch: int, length: int, layers: int | None = None,
                  act_bytes: int = 2, param_bytes: int = 2) -> dict:
    """``layers`` layers of the Qwen3 stack (all but the last unless given:
    the state before the last layer is what is taken) over ``length`` tokens.
    The embedding is a lookup: the rows read count as bytes, the table's
    other rows are not touched."""
    c = _Cost(batch, act_bytes)
    h, hd, ff = t["hidden_size"], t["head_dim"], t["intermediate_size"]
    q, kv = t["num_attention_heads"] * hd, t["num_key_value_heads"] * hd
    c.act += length * h
    for _ in range(t["num_hidden_layers"] - 1 if layers is None else layers):
        c.linear(h, q, length, bias=False)
        c.linear(h, kv, length, bias=False)
        c.linear(h, kv, length, bias=False)
        c.attention(length, length, q)
        c.linear(q, h, length, bias=False)
        c.linear(h, ff, length, bias=False)
        c.linear(h, ff, length, bias=False)
        c.linear(ff, h, length, bias=False)
        c.params += 2 * h + 2 * hd
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def text_tower(config: dict, mix: dict, chips: int) -> dict:
    """The tower once, for one prompt, at the bucket the program runs it at
    (the valid tokens padded to 32): what a request with a new text pays
    before its first step. Memory-bound: the layers' weights read once."""
    out = qwen3_forward(config["text"], 1, _padded(caption_tokens(mix)))
    return {"flops": out["flops"], "bytes": out["bytes"]}
