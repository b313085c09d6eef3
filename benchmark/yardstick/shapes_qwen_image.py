"""Operations and bytes of one Qwen-Image forward, of its joint attention
class and of the one-frame decode, from shapes alone: the work the published
model needs at the cell's image shape, whatever the program emits. Counted as
``shapes_sd`` counts (its ``_Cost``): multiply-adds as two operations,
attention as QK^T and PV, no normalisation, activation, rotary or softmax;
bytes are every parameter once at the compute type's width and every
contraction's input and output activations once. The sampler runs at CFG 1.0,
so a step is one forward on one row: nothing is doubled. The text's length is
a fact of the mix's fixed prompt under the seeded table: one token a word of
``traffic/words.txt`` and the template's five after it (``text_tokens``)."""

from __future__ import annotations

from .layout_qwen_image import TIME_FREQUENCIES, inner_dim, mlp_hidden
from .shapes_sd import _Cost
from .shapes_wan import decode_frames

# ``<|im_end|>``, a newline, ``<|im_start|>``, ``assistant``, a newline: what
# follows the user's text inside the states the pipeline keeps.
TEMPLATE_TAIL_TOKENS = 5


def text_tokens(mix: dict) -> int:
    return len(mix["draws"]["prompt"]["text"].split()) + TEMPLATE_TAIL_TOKENS


def image_tokens(config: dict, mix: dict) -> int:
    lat, p = mix["latent"], config["transformer"]["patch_size"]
    return (int(lat["height"]) // 8 // p) * (int(lat["width"]) // 8 // p)


def forward(m: dict, n_img: int, n_txt: int, act_bytes: int = 2,
            param_bytes: int = 2) -> dict:
    """One ``QwenImageTransformer2DModel.forward`` on one row of ``n_img``
    image tokens and ``n_txt`` text tokens."""
    c = _Cost(1, act_bytes)
    d, ff = inner_dim(m), mlp_hidden(m)
    c.linear(m["in_channels"], d, n_img)
    c.linear(m["joint_attention_dim"], d, n_txt)
    c.linear(TIME_FREQUENCIES, d, 1)
    c.linear(d, d, 1)
    c.params += m["joint_attention_dim"]
    for _ in range(m["num_layers"]):
        c.linear(d, 6 * d, 1)
        c.linear(d, 6 * d, 1)
        for n in (n_img, n_txt):
            for _ in range(4):  # q, k, v, out
                c.linear(d, d, n)
            c.linear(d, ff, n)
            c.linear(ff, d, n)
        c.attention(n_img + n_txt, n_img + n_txt, d)
        c.params += 4 * m["attention_head_dim"]
    c.linear(d, 2 * d, 1)
    c.linear(d, m["in_channels"], n_img)
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def denoiser_step(config: dict, mix: dict, chips: int) -> dict:
    """The forward as a sampler step of the cell asks for it."""
    return forward(config["transformer"], image_tokens(config, mix), text_tokens(mix))


def joint_attention(config: dict, mix: dict, chips: int) -> dict:
    """One attention over text ⊕ image tokens (a block has one)."""
    n = image_tokens(config, mix) + text_tokens(mix)
    c = _Cost(1, 2)
    c.attention(n, n, inner_dim(config["transformer"]))
    return {"flops": c.flops, "bytes": c.act * 2}


def decode_image(config: dict, mix: dict, chips: int) -> dict:
    """One image through the 3-D decoder's first-frame path: ``shapes_wan``'s
    count at ONE latent frame, which is never doubled in time."""
    lat, v = mix["latent"], config["vae"]
    return decode_frames(dict(v, dim=v["base_dim"]), 1, int(lat["height"]) // 8,
                         int(lat["width"]) // 8, first=True)
