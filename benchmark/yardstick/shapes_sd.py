"""Operations and bytes of one denoiser forward, from shapes alone: the work
the published UNet needs at the cell's latent shape, whatever the program
emits. Multiply-adds count as two operations; attention counts QK^T and PV;
normalisations, activations and softmax are not counted (they are not what a
peak FLOP/s figure is about). Bytes: every parameter once at the compute
type's width, every contraction's input and output activations once."""

from __future__ import annotations

from .layout import unet_attention_levels, unet_depths


class _Cost:
    def __init__(self, batch: int, act_bytes: int):
        self.flops = 0
        self.act = 0
        self.params = 0
        self.b, self.ab = batch, act_bytes

    def conv(self, cin, cout, k, h, w, stride=1):
        ho, wo = h // stride, w // stride
        self.flops += 2 * k * k * cin * cout * ho * wo * self.b
        self.act += (cin * h * w + cout * ho * wo) * self.b
        self.params += k * k * cin * cout + cout

    def linear(self, cin, cout, tokens, bias=True):
        self.flops += 2 * cin * cout * tokens * self.b
        self.act += (cin + cout) * tokens * self.b
        self.params += cin * cout + (cout if bias else 0)

    def attention(self, sq, sk, c):
        self.flops += 4 * sq * sk * c * self.b
        self.act += (2 * sq + 2 * sk) * c * self.b


def unet_forward(u: dict, batch: int, h: int, w: int, ctx_len: int,
                 act_bytes: int = 2, param_bytes: int = 2) -> dict:
    """One ``UNetModel.forward`` on ``batch`` latents of ``h`` x ``w``."""
    c = _Cost(batch, act_bytes)
    mc, emb, ctx = u["model_channels"], u["model_channels"] * 4, u["context_dim"]
    depths, attn = unet_depths(u), unet_attention_levels(u)

    def res(cin, cout, hh, ww):
        c.conv(cin, cout, 3, hh, ww)
        c.linear(emb, cout, 1)
        c.conv(cout, cout, 3, hh, ww)
        if cin != cout:
            c.conv(cin, cout, 1, hh, ww)
        c.params += 2 * (cin + cout)  # two GroupNorms

    def transformer(ch, depth, hh, ww):
        s = hh * ww
        c.linear(ch, ch, s)
        c.linear(ch, ch, s)
        c.params += 2 * ch
        for _ in range(depth):
            for kv, sk in ((ch, s), (ctx, ctx_len)):
                c.linear(ch, ch, s, bias=False)
                c.linear(kv, ch, sk, bias=False)
                c.linear(kv, ch, sk, bias=False)
                c.attention(s, sk, ch)
                c.linear(ch, ch, s)
            c.linear(ch, ch * 8, s)
            c.linear(ch * 4, ch, s)
            c.params += 6 * ch

    c.linear(mc, emb, 1)
    c.linear(emb, emb, 1)
    if u.get("adm_in_channels"):
        c.linear(u["adm_in_channels"], emb, 1)
        c.linear(emb, emb, 1)
    c.conv(u["in_channels"], mc, 3, h, w)
    levels = range(len(u["channel_mult"]))
    ch, skips, hh, ww = mc, [mc], h, w
    for lvl in levels:
        o = mc * u["channel_mult"][lvl]
        for _ in range(u["num_res_blocks"]):
            res(ch, o, hh, ww)
            ch = o
            if lvl in attn and depths[lvl] > 0:
                transformer(ch, depths[lvl], hh, ww)
            skips.append(ch)
        if lvl != levels[-1]:
            c.conv(ch, ch, 3, hh, ww, stride=2)
            hh, ww = hh // 2, ww // 2
            skips.append(ch)
    res(ch, ch, hh, ww)
    transformer(ch, depths[-1] or 1, hh, ww)
    res(ch, ch, hh, ww)
    for lvl in reversed(levels):
        o = mc * u["channel_mult"][lvl]
        for i in range(u["num_res_blocks"] + 1):
            res(ch + skips.pop(), o, hh, ww)
            ch = o
            if lvl in attn and depths[lvl] > 0:
                transformer(ch, depths[lvl], hh, ww)
            if lvl and i == u["num_res_blocks"]:
                hh, ww = hh * 2, ww * 2
                c.conv(ch, ch, 3, hh, ww)
    c.params += 2 * ch
    c.conv(ch, u["out_channels"], 3, hh, ww)
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def denoiser_step(config: dict, mix: dict, chips: int) -> dict:
    """One denoiser forward as the cell's sampler step asks for it: the
    latent batch doubled by classifier-free guidance, split over the chips of
    a chain (each chip holds a replica and computes its share)."""
    lat = mix["latent"]
    batch = 2 * int(lat["batch_size"])
    per_chip = -(-batch // chips)
    return unet_forward(config["unet"], per_chip, int(lat["height"]) // 8,
                        int(lat["width"]) // 8,
                        int(config["text"]["max_position_embeddings"]))
