#!/usr/bin/env python3
"""Proof that the main path starts and is right on the chip.

Serves full-width SD1.5 (published UNet / VAE / CLIP-L widths, random fp16
weights drawn from ``--seed``) through ``comfyui_parallelanything_tpu.server``
the way a user would: a single-file checkpoint in the public SD1.x layout, the
stock graph ``examples/workflow_stock_sd15_txt2img.json``, ``POST /prompt`` →
``/history`` → ``/view``. Phases, each printing one JSON line:

- *device*   ``jax.devices()`` must be TPU, else exit 1 at once;
- *kernel*   the in-repo flash kernel (compiled, never interpreted) against
  the plain XLA attention at the FLUX-dev 1024² joint-attention shape;
- *numerics* one full-width UNet forward on the chip against the same
  parameters on the host CPU;
- *serve*    the server in THIS process (one process owns the chip), three
  prompts over HTTP, images checked, no degradation, no recompile.

``--chips 4`` runs only the cross-chip path: the same graph with
``ParallelDeviceList`` + ``ParallelAnything`` over ``tpu:0..3`` against the
same graph on ``tpu:0`` alone.

Any phase that raises ends the process non-zero. The last line printed is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import importlib
import io
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # listed in .gitignore
GRAPH = os.path.join(HERE, "examples", "workflow_stock_sd15_txt2img.json")
CKPT_NAME = "v1-5-pruned-emaonly.safetensors"

# The stock graph asks for 4 × 1024². Its untiled VAE decode of four 1024²
# images is a 12.3 GiB program (the chip compiler's memory_analysis) beside
# 3.7 GiB of resident UNet and CLIP weights — more than the chip's 16 GiB —
# so the resolution is cut to SD1.5's native 512². Batch, steps, sampler, CFG and
# every width stay the stock graph's.
WIDTH = HEIGHT = 512
CHAIN_BATCH = 8  # --chips 4: two images per chip


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- synthesis: a checkpoint and tokenizer tables a user would otherwise download


def _random_params(shapes, rng: np.random.Generator):
    """A well-conditioned random tree for a ``jax.eval_shape`` result: norm
    scales near 1, biases near 0, kernels at 1/sqrt(fan_in), embeddings at
    0.02 — activations stay O(1) through every block."""
    import jax

    def leaf(path, s):
        name = getattr(path[-1], "key", str(path[-1]))
        owner = getattr(path[-2], "key", "") if len(path) > 1 else ""
        if name == "scale":
            x = 1.0 + 0.05 * rng.standard_normal(s.shape, np.float32)
        elif name == "bias":
            x = 0.02 * rng.standard_normal(s.shape, np.float32)
        elif name == "kernel":
            # q/k/v projections are (C, heads, head_dim); everything else
            # contracts over all but the last axis.
            qkv = len(s.shape) == 3 and not owner.endswith("_o")
            fan_in = s.shape[0] if qkv else int(np.prod(s.shape[:-1]))
            x = rng.standard_normal(s.shape, np.float32) / np.sqrt(fan_in)
        else:  # embedding tables, positional embeddings
            x = 0.02 * rng.standard_normal(s.shape, np.float32)
        return x.astype(np.float16)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _put(sd, key, p, layout):
    """One flax leaf-dict → torch-layout entries (the converters' inverse)."""
    if "kernel" in p:
        k = p["kernel"]
        if layout == "conv":      # (kh, kw, in, out) → (out, in, kh, kw)
            k = k.transpose(3, 2, 0, 1)
        elif layout == "dense":   # (in, out) → (out, in)
            k = k.T
        elif layout == "qkv":     # (C, H, D) → (H·D, C)
            k = k.transpose(1, 2, 0).reshape(-1, k.shape[0])
        elif layout == "attn_o":  # (H, D, C) → (C, H·D)
            k = k.reshape(-1, k.shape[-1]).T
        sd[f"{key}.weight"] = k
    if "scale" in p:
        sd[f"{key}.weight"] = p["scale"]
    if "bias" in p:
        sd[f"{key}.bias"] = p["bias"]


def ldm_unet_state_dict(cfg, params) -> dict:
    """UNet params → the ldm ``model.diffusion_model`` layout (inverse of
    models/convert_unet.convert_sd_unet_checkpoint)."""
    from comfyui_parallelanything_tpu.models.unet import middle_depth

    sd: dict = {}

    def res(p, t):
        _put(sd, f"{t}.in_layers.0", p["GroupNorm_0"], "norm")
        _put(sd, f"{t}.in_layers.2", p["Conv_0"], "conv")
        _put(sd, f"{t}.emb_layers.1", p["Dense_0"], "dense")
        _put(sd, f"{t}.out_layers.0", p["GroupNorm_1"], "norm")
        _put(sd, f"{t}.out_layers.3", p["Conv_1"], "conv")
        if "Conv_2" in p:
            _put(sd, f"{t}.skip_connection", p["Conv_2"], "conv")

    def transformer(p, t, depth):
        _put(sd, f"{t}.norm", p["GroupNorm_0"], "norm")
        _put(sd, f"{t}.proj_in", p["proj_in"], "conv")
        _put(sd, f"{t}.proj_out", p["proj_out"], "conv")
        for d in range(depth):
            blk, b = p[f"block_{d}"], f"{t}.transformer_blocks.{d}"
            for i in range(3):
                _put(sd, f"{b}.norm{i + 1}", blk[f"LayerNorm_{i}"], "norm")
            _put(sd, f"{b}.ff.net.0.proj", blk["ff_in"], "dense")
            _put(sd, f"{b}.ff.net.2", blk["ff_out"], "dense")
            for a in ("attn1", "attn2"):
                for n in "qkv":
                    _put(sd, f"{b}.{a}.to_{n}", blk[f"{a}_{n}"], "qkv")
                _put(sd, f"{b}.{a}.to_out.0", blk[f"{a}_o"], "attn_o")

    def attn_at(level):
        return (level in cfg.attention_levels
                and cfg.transformer_depth[level] > 0)

    _put(sd, "time_embed.0", params["time_embed_0"], "dense")
    _put(sd, "time_embed.2", params["time_embed_2"], "dense")
    if cfg.adm_in_channels is not None:  # SDXL-family vector conditioning
        _put(sd, "label_emb.0.0", params["label_embed_0"], "dense")
        _put(sd, "label_emb.0.2", params["label_embed_2"], "dense")
    _put(sd, "input_blocks.0.0", params["input_conv"], "conv")
    levels = range(len(cfg.channel_mult))
    idx = 1
    for level in levels:
        for i in range(cfg.num_res_blocks):
            res(params[f"in_{level}_{i}_res"], f"input_blocks.{idx}.0")
            if attn_at(level):
                transformer(params[f"in_{level}_{i}_attn"],
                            f"input_blocks.{idx}.1",
                            cfg.transformer_depth[level])
            idx += 1
        if level != levels[-1]:
            _put(sd, f"input_blocks.{idx}.0.op",
                 params[f"down_{level}"]["Conv_0"], "conv")
            idx += 1
    res(params["mid_res1"], "middle_block.0")
    if middle_depth(cfg) > 0:
        transformer(params["mid_attn"], "middle_block.1", middle_depth(cfg))
        res(params["mid_res2"], "middle_block.2")
    else:
        res(params["mid_res2"], "middle_block.1")
    idx = 0
    for level in reversed(levels):
        for i in range(cfg.num_res_blocks + 1):
            res(params[f"out_{level}_{i}_res"], f"output_blocks.{idx}.0")
            sub = 1
            if attn_at(level):
                transformer(params[f"out_{level}_{i}_attn"],
                            f"output_blocks.{idx}.{sub}",
                            cfg.transformer_depth[level])
                sub += 1
            if i == cfg.num_res_blocks and level != 0:
                _put(sd, f"output_blocks.{idx}.{sub}.conv",
                     params[f"up_{level}"]["Conv_0"], "conv")
            idx += 1
    _put(sd, "out.0", params["out_norm"], "norm")
    _put(sd, "out.2", params["out_conv"], "conv")
    return sd


def ldm_vae_state_dict(cfg, params) -> dict:
    """VAE params → the ldm ``first_stage_model`` layout (inverse of
    models/convert_vae.convert_vae_checkpoint)."""
    sd: dict = {}

    def res(p, t):
        for n in ("norm1", "norm2"):
            _put(sd, f"{t}.{n}", p[n], "norm")
        for n in ("conv1", "conv2", "nin_shortcut"):
            if n in p:
                _put(sd, f"{t}.{n}", p[n], "conv")

    def attn(p, t):
        _put(sd, f"{t}.norm", p["norm"], "norm")
        for n in ("q", "k", "v", "proj_out"):
            _put(sd, f"{t}.{n}", p[n], "conv")

    levels = range(len(cfg.channel_mult))
    for side, n_blocks in (("encoder", cfg.num_res_blocks),
                           ("decoder", cfg.num_res_blocks + 1)):
        p = params[side]
        _put(sd, f"{side}.conv_in", p["conv_in"], "conv")
        res(p["mid_block_1"], f"{side}.mid.block_1")
        attn(p["mid_attn_1"], f"{side}.mid.attn_1")
        res(p["mid_block_2"], f"{side}.mid.block_2")
        _put(sd, f"{side}.norm_out", p["norm_out"], "norm")
        _put(sd, f"{side}.conv_out", p["conv_out"], "conv")
        way, sample = (("down", "downsample") if side == "encoder"
                       else ("up", "upsample"))
        for lvl in levels:
            for i in range(n_blocks):
                res(p[f"{way}_{lvl}_block_{i}"], f"{side}.{way}.{lvl}.block.{i}")
            if lvl != (levels[-1] if side == "encoder" else 0):
                _put(sd, f"{side}.{way}.{lvl}.{sample}.conv",
                     p[f"{way}_{lvl}_{sample}"]["conv"], "conv")
    if cfg.use_quant_conv:
        _put(sd, "quant_conv", params["quant_conv"], "conv")
        _put(sd, "post_quant_conv", params["post_quant_conv"], "conv")
    return sd


def hf_clip_state_dict(cfg, params) -> dict:
    """CLIP text params → the HF ``text_model`` layout SD1.x bundles under
    ``cond_stage_model.transformer`` (inverse of
    models/convert_text.convert_clip_text_checkpoint)."""
    e = "text_model.embeddings"
    sd = {
        f"{e}.token_embedding.weight": params["tok_emb"]["embedding"],
        f"{e}.position_embedding.weight": params["pos_emb"],
    }
    _put(sd, "text_model.final_layer_norm", params["final_ln"], "norm")
    names = {"ln1": "layer_norm1", "ln2": "layer_norm2",
             "q": "self_attn.q_proj", "k": "self_attn.k_proj",
             "v": "self_attn.v_proj", "out": "self_attn.out_proj",
             "fc1": "mlp.fc1", "fc2": "mlp.fc2"}
    for i in range(cfg.num_layers):
        for ours, theirs in names.items():
            _put(sd, f"text_model.encoder.layers.{i}.{theirs}",
                 params[f"layers_{i}"][ours], "dense")
    return sd


def write_checkpoint(path: str, seed: int, unet_cfg, vae_cfg, clip_cfg) -> int:
    """Write a single-file SD1.x checkpoint (UNet + VAE + bundled CLIP tower)
    of fp16 tensors drawn from ``seed``; returns the parameter count."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    from comfyui_parallelanything_tpu.models.text_encoders import CLIPTextModel
    from comfyui_parallelanything_tpu.models.unet import UNet2D
    from comfyui_parallelanything_tpu.models.vae import AutoencoderKL

    key = jax.random.key(0)
    hw = 8 * 2 ** (len(vae_cfg.channel_mult) - 1)
    shapes = {
        "unet": jax.eval_shape(lambda: UNet2D(unet_cfg).init(
            key, jnp.zeros((1, 8, 8, unet_cfg.in_channels)), jnp.zeros((1,)),
            jnp.zeros((1, clip_cfg.max_len, unet_cfg.context_dim)))["params"]),
        "vae": jax.eval_shape(lambda: AutoencoderKL(vae_cfg).init(
            key, jnp.zeros((1, hw, hw, vae_cfg.in_channels)))["params"]),
        "clip": jax.eval_shape(lambda: CLIPTextModel(clip_cfg).init(
            key, jnp.zeros((1, clip_cfg.max_len), jnp.int32))["params"]),
    }
    p = _random_params(shapes, np.random.default_rng(seed))
    sd = {}
    for prefix, part in (
        ("model.diffusion_model.", ldm_unet_state_dict(unet_cfg, p["unet"])),
        ("first_stage_model.", ldm_vae_state_dict(vae_cfg, p["vae"])),
        ("cond_stage_model.transformer.", hf_clip_state_dict(clip_cfg, p["clip"])),
    ):
        # safetensors writes the raw buffer: transposed views must be copied.
        sd.update({prefix + k: np.ascontiguousarray(v) for k, v in part.items()})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    save_file(sd, path)
    return sum(int(v.size) for v in sd.values())


def write_tokenizer(dirname: str, seed: int, vocab_size: int) -> tuple[str, str]:
    """A CLIP byte-BPE table pair (``vocab.json`` + ``merges.txt``) of the
    published size and id layout — 256 byte symbols, their ``</w>`` forms,
    merges, then BOS/EOS as the last two ids — with merges drawn from
    ``seed``. Any text tokenizes: every byte symbol is in the vocab."""
    from comfyui_parallelanything_tpu.utils.tokenizer import _bytes_to_unicode

    rng = np.random.default_rng(seed)
    alphabet = list(_bytes_to_unicode().values())
    vocab = alphabet + [c + "</w>" for c in alphabet]
    seen = set(vocab)
    merges: list[tuple[str, str]] = []
    letters = [c for c in alphabet if c.isalpha() and c.isascii()]
    while len(vocab) < vocab_size - 2:
        # Left parts never carry the end-of-word marker; short pieces keep the
        # table shaped like a real one (most merges join letters).
        a = vocab[int(rng.integers(len(vocab)))] if rng.random() < 0.5 \
            else letters[int(rng.integers(len(letters)))]
        b = vocab[int(rng.integers(len(vocab)))]
        if a.endswith("</w>") or len(a) + len(b) > 12 or a + b in seen:
            continue
        seen.add(a + b)
        vocab.append(a + b)
        merges.append((a, b))
    vocab += ["<|startoftext|>", "<|endoftext|>"]
    os.makedirs(dirname, exist_ok=True)
    vocab_path = os.path.join(dirname, "vocab.json")
    merges_path = os.path.join(dirname, "merges.txt")
    with open(vocab_path, "w", encoding="utf-8") as f:
        json.dump({tok: i for i, tok in enumerate(vocab)}, f)
    with open(merges_path, "w", encoding="utf-8") as f:
        f.write("#version: 0.2\n")
        f.writelines(f"{a} {b}\n" for a, b in merges)
    return vocab_path, merges_path


def synthesize(seed: int, work: str = WORK) -> dict:
    """Everything the server reads from disk, under ``work``, wired through
    the environment variables a user would set."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import text_encoders

    t0 = time.monotonic()
    ckpt = os.path.join(work, "models", "checkpoints", CKPT_NAME)
    clip_cfg = text_encoders.clip_l_config()
    n = write_checkpoint(ckpt, seed, models.sd15_config(),
                         models.sd_vae_config(), clip_cfg)
    vocab, merges = write_tokenizer(
        os.path.join(work, "tokenizer"), seed, clip_cfg.vocab_size
    )
    os.environ["PA_MODELS_DIR"] = os.path.join(work, "models")
    os.environ["PA_OUTPUT_DIR"] = os.path.join(work, "output")
    os.environ["PA_CLIP_VOCAB"] = vocab
    os.environ["PA_CLIP_MERGES"] = merges
    os.environ.pop("PA_TOKENIZER_JSON", None)
    emit("synthesize", seed=seed, params=n,
         checkpoint_bytes=os.path.getsize(ckpt),
         seconds=round(time.monotonic() - t0, 2))
    return {"ckpt": ckpt, "vocab": vocab, "merges": merges}


# -- phases


def device_phase(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (devices: {devs}); nothing was run"
        )
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but only {len(devs)} found")
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), jax=jax.__version__)
    return devs


def _peak_hbm(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _bytes_in_use(dev) -> int:
    return dev.memory_stats()["bytes_in_use"]


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def kernel_phase(seed: int) -> None:
    """The in-repo flash kernel, compiled by the chip's compiler with the
    blocks ``route`` names, against the plain XLA attention at FLUX-dev's
    1024² joint-attention shape."""
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )
    from comfyui_parallelanything_tpu.ops.pallas.tuning import route

    # ops/__init__ exports a function named ``attention`` that shadows the
    # module attribute.
    xla = importlib.import_module(
        "comfyui_parallelanything_tpu.ops.attention")._xla_attention
    shape = (1, 4608, 24, 128)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in jax.random.split(jax.random.key(seed), 3))
    scale = shape[-1] ** -0.5
    chosen = route(shape[1], shape[1], shape[3], shape[0] * shape[2],
                   on_tpu=True, chunk_threshold=2**27)
    t0 = time.monotonic()
    got = jax.block_until_ready(flash_attention(
        q, k, v, block_q=chosen.block_q, block_k=chosen.block_k,
        interpret=False))
    want = jax.block_until_ready(jax.jit(xla, static_argnums=3)(q, k, v, scale))
    err = _rel_err(got, want)
    emit("kernel", shape=shape, dtype="bfloat16", rule=chosen.rule,
         blocks=(chosen.block_q, chosen.block_k), rel_err=err,
         finite=bool(np.isfinite(np.asarray(got, np.float32)).all()),
         seconds=round(time.monotonic() - t0, 2))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert err < 2e-2, f"flash kernel off the XLA reference: rel err {err}"


def numerics_phase(ckpt: str, seed: int) -> None:
    """One full-width UNet forward on the chip against the same parameters on
    the host CPU, both at the model's own dtype policy (f32 params, bf16
    compute)."""
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.ops.attention import (
        get_attention_backend,
        set_attention_backend,
    )

    cfg = models.sd15_config()
    model = models.load_sd_unet_checkpoint(ckpt, cfg)
    kx, kc = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (2, 64, 64, cfg.in_channels), jnp.float32)
    t = jnp.array([801.0, 201.0], jnp.float32)
    ctx = jax.random.normal(kc, (2, 77, cfg.context_dim), jnp.float32)
    t0 = time.monotonic()
    got = np.asarray(model(x, t, ctx), np.float32)
    t_chip = time.monotonic() - t0
    cpu = jax.devices("cpu")[0]
    on_cpu = jax.device_put((model.params, x, t, ctx), cpu)
    t0 = time.monotonic()
    # The shape rule sends this forward's 4096-token self-attention to the
    # fused kernel because the process holds a TPU; the reference runs on the
    # host, where Mosaic cannot lower, and is the plain XLA path by intent.
    # The route is taken while tracing, and jit would hand back the trace the
    # chip forward made of ``model.apply``: a function of its own is traced anew.
    routed = get_attention_backend()
    set_attention_backend("xla")
    try:
        reference = jax.jit(lambda *args: model.apply(*args))
        want = np.asarray(reference(*on_cpu), np.float32)
    finally:
        set_attention_backend(routed)
    err = _rel_err(got, want)
    emit("numerics", model="sd15-unet", params=model.n_params(),
         out_shape=got.shape, rel_err=err,
         chip_seconds_with_compile=round(t_chip, 2),
         cpu_seconds_with_compile=round(time.monotonic() - t0, 2))
    assert got.shape == x.shape and np.isfinite(got).all()
    assert err < 5e-2, f"UNet on the chip off the CPU reference: rel err {err}"


def _http(base: str, path: str, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        base + path, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST",
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        body, ctype = r.read(), r.headers.get("Content-Type", "")
    return json.loads(body) if "json" in ctype else body


def _metric_total(text: str, family: str) -> float:
    """Sum of every sample of a Prometheus family in an exposition text."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(family) and line[len(family):][:1] in ("{", " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def stock_graph(width: int, height: int, batch: int | None = None,
                steps: int | None = None) -> dict:
    """The stock example graph at another resolution (and, for tests and the
    chain, another batch size / step count)."""
    with open(GRAPH) as f:
        wf = json.load(f)
    wf["5"]["inputs"].update(width=width, height=height)
    if batch is not None:
        wf["5"]["inputs"]["batch_size"] = batch
    if steps is not None:
        wf["3"]["inputs"]["steps"] = steps
    return wf


def serve_phase(graph: dict, seeds=(42, 7, 42), want_device: str | None = "tpu:0",
                vae_factor: int = 8, timeout_s: float = 900.0) -> dict:
    """Start the server in this process, submit one prompt per seed over
    HTTP, fetch the images back through /view, and check them. Latents are
    1/8 of the asked size; ``vae_factor`` is what the VAE scales them by (8 at
    the published widths)."""
    from PIL import Image

    from comfyui_parallelanything_tpu.server import make_server
    from comfyui_parallelanything_tpu.utils.telemetry import compile_snapshot

    srv, q = make_server(port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    def scrape():
        text = _http(base, "/metrics")
        return text.decode() if isinstance(text, bytes) else text

    try:
        # The registry is the process's: what this phase degraded is the
        # increase over it (a test process has run other phases before).
        metrics0 = scrape()
        runs = []
        for seed in seeds:
            wf = json.loads(json.dumps(graph))
            wf["3"]["inputs"]["seed"] = seed
            before = compile_snapshot()
            t0 = time.monotonic()
            pid = _http(base, "/prompt", {"prompt": wf})["prompt_id"]
            while pid not in (hist := _http(base, f"/history/{pid}")):
                if time.monotonic() - t0 > timeout_s:
                    raise TimeoutError(f"prompt {pid} (seed {seed}) never finished")
                time.sleep(0.25)
            wall = time.monotonic() - t0
            entry = hist[pid]
            assert entry["status"]["status_str"] == "success", entry["status"]
            images = []
            for ref in entry["outputs"]["9"]["images"]:
                png = _http(base, f"/view?filename={ref['filename']}"
                                  f"&subfolder={ref['subfolder']}")
                images.append(np.asarray(Image.open(io.BytesIO(png)).convert("RGB")))
            after = compile_snapshot()
            runs.append({
                "seed": seed, "wall_s": round(wall, 3), "images": images,
                **{k: after[k] - before[k] for k in
                   ("compiles", "compile_time_s", "cache_hits", "cache_misses")},
            })
        stats = _http(base, "/system_stats")
        metrics = scrape()
    finally:
        srv.shutdown()
        srv.server_close()
        q.shutdown()
        thread.join(timeout=30)

    wf_in = graph["5"]["inputs"]
    shape = (wf_in["height"] // 8 * vae_factor,
             wf_in["width"] // 8 * vae_factor, 3)
    for r in runs:
        assert len(r["images"]) == wf_in["batch_size"], len(r["images"])
        for img in r["images"]:
            assert img.shape == shape, (img.shape, shape)
            assert img.min() != img.max(), f"seed {r['seed']}: constant image"
    by_seed: dict[int, list] = {}
    for r in runs:
        by_seed.setdefault(r["seed"], []).append(np.stack(r["images"]))
    stacks = [v[0] for v in by_seed.values()]
    for same in by_seed.values():
        assert all(np.array_equal(same[0], s) for s in same[1:]), \
            "equal seeds gave different images"
    assert all(not np.array_equal(a, b)
               for i, a in enumerate(stacks) for b in stacks[i + 1:]), \
        "different seeds gave equal images"
    if want_device is not None:
        assert want_device in stats["devices"], stats
    degraded = {fam: _metric_total(metrics, fam) - _metric_total(metrics0, fam)
                for fam in ("pa_degradation_total", "pa_serving_inline_fallback_total")}
    assert not any(degraded.values()), degraded
    for r in runs[1:]:
        assert r["compiles"] == 0, \
            f"prompt with seed {r['seed']} compiled {r['compiles']} programs"
    summary = {
        "prompts": len(runs), "all_success": True,
        "image_shape": shape, "images_per_prompt": wf_in["batch_size"],
        "steps": graph["3"]["inputs"]["steps"],
        "sampler": graph["3"]["inputs"]["sampler_name"],
        "cfg": graph["3"]["inputs"]["cfg"],
        "runs": [{k: v for k, v in r.items() if k != "images"} for r in runs],
        "cold_wall_s": runs[0]["wall_s"],
        "warm_wall_s": min(r["wall_s"] for r in runs[1:]),
        "devices": stats["devices"], **degraded,
    }
    emit("serve", **summary)
    return summary


def _saved_files(images, paths) -> dict:
    """Where the save node's filter program ran (it takes the images where
    they are: a sharded batch is not gathered first), and whether the files
    decode to numpy's quantise of the fetched floats, to the bit."""
    from PIL import Image

    from comfyui_parallelanything_tpu.utils.png_encode import filter_program

    want = (np.clip(np.asarray(images), 0.0, 1.0) * 255.0 + 0.5).astype(np.uint8)
    return {
        "image_devices": sorted(d.id for d in images.sharding.device_set),
        "filtered_devices": sorted(
            d.id for d in filter_program(images)[1].sharding.device_set),
        "files_exact": len(paths) == len(want) and all(
            np.array_equal(np.asarray(Image.open(p)), w)
            for p, w in zip(paths, want)),
    }


def chain_phase(devs, graph: dict | None = None) -> None:
    """The cross-chip path and what it is compared with: the stock graph with
    a ParallelDeviceList + ParallelAnything chain over four chips against the
    same graph on the first chip alone."""
    import comfyui_parallelanything_tpu as pa

    single = graph or stock_graph(WIDTH, HEIGHT, batch=CHAIN_BATCH)
    chained = json.loads(json.dumps(single))
    n = len(devs)
    chained["30"] = {"class_type": "ParallelDeviceList", "inputs": {
        **{f"device_{i + 1}": f"{d.platform}:{d.id}"
           for i, d in enumerate(devs)},
        **{f"percentage_{i + 1}": 100.0 / n for i in range(n)},
    }}
    chained["31"] = {"class_type": "ParallelAnything", "inputs": {
        "model": ["20", 0], "parallel_devices": ["30", 0],
        "workload_split": True, "auto_vram_balance": False,
        "purge_cache": True, "purge_models": False,
    }}
    chained["3"]["inputs"]["model"] = ["31", 0]

    before = [_bytes_in_use(d) for d in devs]
    results = {}
    for name, wf in (("chain", chained), ("single", single)):
        t0 = time.monotonic()
        out = pa.run_workflow(wf)
        latent = out["3"][0]["samples"]
        images = out["8"][0]
        results[name] = {
            "latent": np.asarray(latent, np.float32),
            "images": np.asarray(images, np.float32),
            "devices": sorted(d.id for d in latent.sharding.device_set),
            "wall_s": round(time.monotonic() - t0, 2),
            **_saved_files(images, out["9"][0]),
        }
        if name == "chain":
            in_use = [_bytes_in_use(d) for d in devs]
    chain, one = results["chain"], results["single"]
    lat_err = _rel_err(chain["latent"], one["latent"])
    img_err = float(np.abs(chain["images"] - one["images"]).max())
    emit("chain", batch=single["5"]["inputs"]["batch_size"],
         chain_devices=chain["devices"],
         single_devices=one["devices"], bytes_in_use_before=before,
         bytes_in_use_after=in_use, latent_rel_err=lat_err,
         image_max_abs_err=img_err, chain_wall_s=chain["wall_s"],
         single_wall_s=one["wall_s"],
         **{f"{name}_{k}": results[name][k] for name in results
            for k in ("image_devices", "filtered_devices", "files_exact")})
    assert chain["devices"] == sorted(d.id for d in devs), chain["devices"]
    for r in results.values():
        assert r["filtered_devices"] == r["image_devices"], r
        assert r["files_exact"], "a saved PNG is not the quantised floats"
    assert all(a > b for a, b in zip(in_use, before)), (before, in_use)
    assert np.isfinite(chain["latent"]).all()
    assert lat_err < 5e-2, f"chain off the single chip: rel err {lat_err}"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic weights and tokenizer tables")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip path and its comparison")
    args = ap.parse_args(argv)

    devs = device_phase(args.chips)[: args.chips]
    from comfyui_parallelanything_tpu.utils import enable_compilation_cache
    from comfyui_parallelanything_tpu.utils.telemetry import compile_snapshot

    cache_dir = enable_compilation_cache()
    paths = synthesize(args.seed)
    if args.chips == 4:
        chain_phase(devs)
    else:
        kernel_phase(args.seed)
        numerics_phase(paths["ckpt"], args.seed)
        graph = stock_graph(WIDTH, HEIGHT)
        emit("reduced", graph=os.path.relpath(GRAPH, HERE), width=WIDTH,
             height=HEIGHT, batch_size=graph["5"]["inputs"]["batch_size"],
             steps=graph["3"]["inputs"]["steps"],
             why="resolution only: the untiled VAE decode of 4 x 1024^2 is "
                 "a 12.3 GiB program beside 3.7 GiB of resident weights, "
                 "over the chip's 16 GiB; batch, steps and all widths are the "
                 "stock graph's and the published ones")
        serve_phase(graph)
    comp = compile_snapshot()
    emit("totals", compile_cache_dir=cache_dir,
         **{k: comp[k] for k in
            ("compiles", "compile_time_s", "cache_hits", "cache_misses")},
         peak_hbm_bytes=[_peak_hbm(d) for d in devs])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)


if __name__ == "__main__":
    main()
