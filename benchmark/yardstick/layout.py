"""The public single-file checkpoint layouts, written out from their published
descriptions: the ldm ``UNetModel`` (``model.diffusion_model.*``), the kl-f8
``AutoencoderKL`` (``first_stage_model.*``), the HF CLIP text model and the
OpenCLIP text tower. Each function returns ``[(key, shape, kind)]`` in file
order; ``kind`` says how a synthetic tensor is drawn: ``w:<fan_in>`` (a
kernel, N(0, 1/fan_in)), ``norm`` (scale near 1), ``bias`` / ``emb`` (small).

The configuration files under ``benchmark/configs`` carry the yaml-style keys
these functions read; nothing here imports the program."""

from __future__ import annotations

import importlib


def _lin(out, key, i, o, bias=True):
    out.append((f"{key}.weight", (o, i), f"w:{i}"))
    if bias:
        out.append((f"{key}.bias", (o,), "bias"))


def _conv(out, key, i, o, k):
    out.append((f"{key}.weight", (o, i, k, k), f"w:{i * k * k}"))
    out.append((f"{key}.bias", (o,), "bias"))


def _norm(out, key, c):
    out.append((f"{key}.weight", (c,), "norm"))
    out.append((f"{key}.bias", (c,), "bias"))


def unet_depths(u: dict) -> list[int]:
    """Transformer depth per level; a scalar in the yaml means every level."""
    d = u["transformer_depth"]
    return [d] * len(u["channel_mult"]) if isinstance(d, int) else list(d)


def unet_attention_levels(u: dict) -> list[int]:
    """Levels whose downsampling factor is in ``attention_resolutions``."""
    return [lvl for lvl in range(len(u["channel_mult"]))
            if 2 ** lvl in u["attention_resolutions"]]


def unet_heads(u: dict, ch: int) -> int:
    if u.get("num_head_channels"):
        return ch // u["num_head_channels"]
    return u["num_heads"]


def unet_layout(u: dict) -> list[tuple]:
    out: list[tuple] = []
    mc, emb = u["model_channels"], u["model_channels"] * 4
    ctx = u["context_dim"]
    linear = bool(u.get("use_linear_in_transformer"))
    depths, attn = unet_depths(u), unet_attention_levels(u)

    def res(key, i, o):
        _norm(out, f"{key}.in_layers.0", i)
        _conv(out, f"{key}.in_layers.2", i, o, 3)
        _lin(out, f"{key}.emb_layers.1", emb, o)
        _norm(out, f"{key}.out_layers.0", o)
        _conv(out, f"{key}.out_layers.3", o, o, 3)
        if i != o:
            _conv(out, f"{key}.skip_connection", i, o, 1)

    def transformer(key, c, depth):
        _norm(out, f"{key}.norm", c)
        for proj in ("proj_in", "proj_out"):
            if linear:
                _lin(out, f"{key}.{proj}", c, c)
            else:
                _conv(out, f"{key}.{proj}", c, c, 1)
        for d in range(depth):
            b = f"{key}.transformer_blocks.{d}"
            for n in (1, 2, 3):
                _norm(out, f"{b}.norm{n}", c)
            for a, kv in (("attn1", c), ("attn2", ctx)):
                _lin(out, f"{b}.{a}.to_q", c, c, bias=False)
                _lin(out, f"{b}.{a}.to_k", kv, c, bias=False)
                _lin(out, f"{b}.{a}.to_v", kv, c, bias=False)
                _lin(out, f"{b}.{a}.to_out.0", c, c)
            _lin(out, f"{b}.ff.net.0.proj", c, c * 8)
            _lin(out, f"{b}.ff.net.2", c * 4, c)

    _lin(out, "time_embed.0", mc, emb)
    _lin(out, "time_embed.2", emb, emb)
    if u.get("adm_in_channels"):
        _lin(out, "label_emb.0.0", u["adm_in_channels"], emb)
        _lin(out, "label_emb.0.2", emb, emb)
    _conv(out, "input_blocks.0.0", u["in_channels"], mc, 3)
    levels = range(len(u["channel_mult"]))
    ch, idx, skips = mc, 1, [mc]
    for lvl in levels:
        o = mc * u["channel_mult"][lvl]
        for _ in range(u["num_res_blocks"]):
            res(f"input_blocks.{idx}.0", ch, o)
            ch = o
            if lvl in attn and depths[lvl] > 0:
                transformer(f"input_blocks.{idx}.1", ch, depths[lvl])
            skips.append(ch)
            idx += 1
        if lvl != levels[-1]:
            _conv(out, f"input_blocks.{idx}.0.op", ch, ch, 3)
            skips.append(ch)
            idx += 1
    res("middle_block.0", ch, ch)
    transformer("middle_block.1", ch, depths[-1] or 1)
    res("middle_block.2", ch, ch)
    idx = 0
    for lvl in reversed(levels):
        o = mc * u["channel_mult"][lvl]
        for i in range(u["num_res_blocks"] + 1):
            res(f"output_blocks.{idx}.0", ch + skips.pop(), o)
            ch, sub = o, 1
            if lvl in attn and depths[lvl] > 0:
                transformer(f"output_blocks.{idx}.1", ch, depths[lvl])
                sub = 2
            if lvl and i == u["num_res_blocks"]:
                _conv(out, f"output_blocks.{idx}.{sub}.conv", ch, ch, 3)
            idx += 1
    _norm(out, "out.0", ch)
    _conv(out, "out.2", ch, u["out_channels"], 3)
    return out


def vae_layout(v: dict) -> list[tuple]:
    out: list[tuple] = []
    base, mult, nrb = v["ch"], v["ch_mult"], v["num_res_blocks"]
    z = v["z_channels"]

    def res(key, i, o):
        _norm(out, f"{key}.norm1", i)
        _conv(out, f"{key}.conv1", i, o, 3)
        _norm(out, f"{key}.norm2", o)
        _conv(out, f"{key}.conv2", o, o, 3)
        if i != o:
            _conv(out, f"{key}.nin_shortcut", i, o, 1)

    def mid(side, c):
        res(f"{side}.mid.block_1", c, c)
        _norm(out, f"{side}.mid.attn_1.norm", c)
        for n in ("q", "k", "v", "proj_out"):
            _conv(out, f"{side}.mid.attn_1.{n}", c, c, 1)
        res(f"{side}.mid.block_2", c, c)

    levels = range(len(mult))
    _conv(out, "encoder.conv_in", v["in_channels"], base, 3)
    ch = base
    for lvl in levels:
        for i in range(nrb):
            res(f"encoder.down.{lvl}.block.{i}", ch, base * mult[lvl])
            ch = base * mult[lvl]
        if lvl != levels[-1]:
            _conv(out, f"encoder.down.{lvl}.downsample.conv", ch, ch, 3)
    mid("encoder", ch)
    _norm(out, "encoder.norm_out", ch)
    _conv(out, "encoder.conv_out", ch, 2 * z, 3)
    ch = base * mult[-1]
    _conv(out, "decoder.conv_in", z, ch, 3)
    mid("decoder", ch)
    for lvl in reversed(levels):
        for i in range(nrb + 1):
            res(f"decoder.up.{lvl}.block.{i}", ch, base * mult[lvl])
            ch = base * mult[lvl]
        if lvl != 0:
            _conv(out, f"decoder.up.{lvl}.upsample.conv", ch, ch, 3)
    _norm(out, "decoder.norm_out", ch)
    _conv(out, "decoder.conv_out", ch, v["out_ch"], 3)
    _conv(out, "quant_conv", 2 * z, 2 * v["embed_dim"], 1)
    _conv(out, "post_quant_conv", v["embed_dim"], z, 1)
    return out


def clip_hf_layout(c: dict) -> list[tuple]:
    """HF ``CLIPTextModel`` keys (under ``text_model.``)."""
    out: list[tuple] = []
    h, ff = c["hidden_size"], c["intermediate_size"]
    e = "text_model.embeddings"
    out.append((f"{e}.token_embedding.weight", (c["vocab_size"], h), "emb"))
    out.append((f"{e}.position_embedding.weight",
                (c["max_position_embeddings"], h), "emb"))
    for i in range(c["num_hidden_layers"]):
        b = f"text_model.encoder.layers.{i}"
        _norm(out, f"{b}.layer_norm1", h)
        for n in ("q_proj", "k_proj", "v_proj", "out_proj"):
            _lin(out, f"{b}.self_attn.{n}", h, h)
        _norm(out, f"{b}.layer_norm2", h)
        _lin(out, f"{b}.mlp.fc1", h, ff)
        _lin(out, f"{b}.mlp.fc2", ff, h)
    _norm(out, "text_model.final_layer_norm", h)
    return out


def open_clip_layout(c: dict) -> list[tuple]:
    """OpenCLIP text tower keys (under ``model.``): fused ``in_proj``,
    ``resblocks``, a bare ``text_projection`` matrix (hidden, proj)."""
    out: list[tuple] = []
    h, ff = c["hidden_size"], c["intermediate_size"]
    out.append(("token_embedding.weight", (c["vocab_size"], h), "emb"))
    out.append(("positional_embedding", (c["max_position_embeddings"], h), "emb"))
    for i in range(c["num_hidden_layers"]):
        b = f"transformer.resblocks.{i}"
        _norm(out, f"{b}.ln_1", h)
        out.append((f"{b}.attn.in_proj_weight", (3 * h, h), f"w:{h}"))
        out.append((f"{b}.attn.in_proj_bias", (3 * h,), "bias"))
        _lin(out, f"{b}.attn.out_proj", h, h)
        _norm(out, f"{b}.ln_2", h)
        _lin(out, f"{b}.mlp.c_fc", h, ff)
        _lin(out, f"{b}.mlp.c_proj", ff, h)
    _norm(out, "ln_final", h)
    out.append(("text_projection", (h, c["projection_dim"]), f"w:{h}"))
    return out


def checkpoint_layout(config: dict, parts: list | None = None) -> list[tuple]:
    """Every tensor of one weight file: its ``parts`` (the configuration's
    single file's unless given) name each part, its key prefix, which layout
    function describes it (``<name>_layout`` of the module
    ``yardstick.<layouts>`` the configuration names, so a new family brings
    its own module) and which group of sizes it reads."""
    mod = importlib.import_module(f"yardstick.{config['checkpoint']['layouts']}")
    out = []
    for part in config["checkpoint"]["parts"] if parts is None else parts:
        sizes = config[part["sizes"]]
        out += [(part["prefix"] + k, s, kind)
                for k, s, kind in getattr(mod, part["layout"] + "_layout")(sizes)]
    return out


def count(entries) -> int:
    n = 0
    for _, shape, _ in entries:
        m = 1
        for d in shape:
            m *= d
        n += m
    return n
