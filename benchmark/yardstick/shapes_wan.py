"""Operations and bytes of one Wan2.2 expert's forward, of its two attention
classes and of the video decoder, from shapes alone: the work the published
model needs at the cell's clip shape, whatever the program emits. Counted as
``shapes_sd`` counts (its ``_Cost``): multiply-adds as two operations,
attention as QK^T and PV, no normalisation, activation, rotary or softmax;
bytes are every parameter once at the compute type's width and every
contraction's input and output activations once. The samplers run at CFG 1.0,
so a step is one forward of one expert on one clip: nothing is doubled. The
mix's ``batch_size`` is the clip's pixel FRAMES (its graph slot points at the
latent node's ``length``); a request is one clip."""

from __future__ import annotations

from .layout_wan import vae_dims
from .shapes_sd import _Cost


def _text_len(config: dict) -> int:
    return int(next(t["max_length"] for t in config["tokenizers"] if t["name"] == "t5"))


def clip_shape(config: dict, mix: dict) -> tuple[int, int, int]:
    """(latent frames, latent rows, latent columns) of the cell's clip."""
    lat, v = mix["latent"], config["vae"]
    st, sh, sw = v["stride"]
    return ((int(lat["batch_size"]) - 1) // st + 1, int(lat["height"]) // sh,
            int(lat["width"]) // sw)


def tokens(config: dict, mix: dict) -> int:
    f, h, w = clip_shape(config, mix)
    pt, ph, pw = config["wan"]["patch_size"]
    return (f // pt) * (h // ph) * (w // pw)


def wan_forward(m: dict, n_tokens: int, txt_len: int, act_bytes: int = 2,
                param_bytes: int = 2) -> dict:
    """One ``WanModel.forward`` on one clip of ``n_tokens`` space-time tokens
    and ``txt_len`` text rows."""
    c = _Cost(1, act_bytes)
    d, ff = m["dim"], m["ffn_dim"]
    pt, ph, pw = m["patch_size"]
    c.linear(m["in_dim"] * pt * ph * pw, d, n_tokens)
    c.linear(m["text_dim"], d, txt_len)
    c.linear(d, d, txt_len)
    c.linear(m["freq_dim"], d, 1)
    c.linear(d, d, 1)
    c.linear(d, 6 * d, 1)
    for _ in range(m["num_layers"]):
        for _ in range(4):  # self-attention q, k, v, o
            c.linear(d, d, n_tokens)
        c.attention(n_tokens, n_tokens, d)
        c.linear(d, d, n_tokens)  # cross-attention q
        c.linear(d, d, txt_len)
        c.linear(d, d, txt_len)
        c.attention(n_tokens, txt_len, d)
        c.linear(d, d, n_tokens)  # cross-attention o
        c.linear(d, ff, n_tokens)
        c.linear(ff, d, n_tokens)
        c.params += 4 * d + 2 * d + 6 * d  # q/k norm scales, norm3, modulation
    c.linear(d, m["out_dim"] * pt * ph * pw, n_tokens)
    c.params += 2 * d
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def denoiser_step(config: dict, mix: dict, chips: int) -> dict:
    """One expert's forward as a sampler step of the cell asks for it (both
    experts have one shape)."""
    return wan_forward(config["wan"], tokens(config, mix), _text_len(config))


def self_attention(config: dict, mix: dict, chips: int) -> dict:
    """One self-attention over the clip's space-time tokens (a block has one)."""
    n = tokens(config, mix)
    c = _Cost(1, 2)
    c.attention(n, n, config["wan"]["dim"])
    return {"flops": c.flops, "bytes": c.act * 2}


def cross_attention(config: dict, mix: dict, chips: int) -> dict:
    """One cross-attention of the clip's tokens on the text rows."""
    c = _Cost(1, 2)
    c.attention(tokens(config, mix), _text_len(config), config["wan"]["dim"])
    return {"flops": c.flops, "bytes": c.act * 2}


class _Cost3(_Cost):
    def conv3(self, cin, cout, k, frames, h, w):
        """A (kt, kh, kw) convolution over ``frames`` output frames."""
        kt, kh, kw = k
        self.flops += 2 * kt * kh * kw * cin * cout * frames * h * w
        self.act += (cin + cout) * frames * h * w
        self.params += kt * kh * kw * cin * cout + cout


def _stage_frames(v: dict, latent_frames: int, first: bool) -> list[int]:
    """Frames passing each decoder stage for ``latent_frames`` latent frames,
    of which the clip's first (if among them) is never doubled."""
    out, n = [latent_frames], latent_frames
    for t in list(v["temperal_downsample"])[::-1]:
        if t:
            n = 2 * n - (1 if first else 0)
        out.append(n)
    return out


def decode_frames(v: dict, latent_frames: int, h: int, w: int, first: bool = True,
                  act_bytes: int = 2, param_bytes: int = 4) -> dict:
    """The Wan2.1 decoder's convolutions and its middle attention on
    ``latent_frames`` latent frames of ``h`` x ``w`` positions; ``first``:
    the clip's first frame is among them (it passes the temporal up-samplers
    as it is, without their ``time_conv``). The autoencoder is resident in
    float32."""
    c = _Cost3(1, act_bytes)
    dims = vae_dims(v)
    blocks = v["num_res_blocks"] + 1
    up_t = list(v["temperal_downsample"])[::-1]
    frames = _stage_frames(v, latent_frames, first)
    f = frames[0]
    c.conv3(v["z_dim"], v["z_dim"], (1, 1, 1), f, h, w)
    c.conv3(v["z_dim"], dims[0], (3, 3, 3), f, h, w)

    def res(i, o, n):
        c.conv3(i, o, (3, 3, 3), n, h, w)
        c.conv3(o, o, (3, 3, 3), n, h, w)
        if i != o:
            c.conv3(i, o, (1, 1, 1), n, h, w)
        c.params += i + o

    res(dims[0], dims[0], f)
    c.conv3(dims[0], 3 * dims[0], (1, 1, 1), f, h, w)
    for _ in range(f):
        c.attention(h * w, h * w, dims[0])
    c.conv3(dims[0], dims[0], (1, 1, 1), f, h, w)
    res(dims[0], dims[0], f)
    for s, (i, o) in enumerate(zip(dims[:-1], dims[1:])):
        n = frames[s]
        if s:
            i //= 2
        for _ in range(blocks):
            res(i, o, n)
            i = o
        if s != len(dims) - 2:
            if up_t[s]:
                c.conv3(o, 2 * o, (3, 1, 1), n - (1 if first else 0), h, w)
            h, w = 2 * h, 2 * w
            c.conv3(o, o // 2, (1, 3, 3), frames[s + 1], h, w)
    c.conv3(dims[-1], 3, (3, 3, 3), frames[-1], h, w)
    return {"flops": c.flops, "params": c.params,
            "bytes": c.params * param_bytes + c.act * act_bytes}


def decode_clip(config: dict, mix: dict, chips: int) -> dict:
    """The whole clip through the decoder: what one request's ``VAEDecode``
    needs (49 frames of 832 x 480: 13 latent frames, 1 + 12 x 4 pixel
    frames). The program's one decode program (``jit_video_decode``) holds
    all of it: the first latent frame, then a scan over the others."""
    f, h, w = clip_shape(config, mix)
    return decode_frames(config["vae"], f, h, w, first=True)
