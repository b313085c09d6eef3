"""Flash-attention kernel benchmark: the fused pallas kernel vs the XLA
family ``auto`` would otherwise pick, with a block-size sweep.

Needs the chip and runs in ONE process (a chip belongs to one process at a
time); without a TPU it exits non-zero and measures nothing:

    python scripts/bench_kernels.py            # measure, append KERNEL_BENCH.json
    python scripts/bench_kernels.py --shape sd15-b8-512.self4096,sdxl-b1-1024.self4096
    python scripts/bench_kernels.py --shape flux-schnell-b1-1024.joint4352 \
        --blocks 256x4352 --chunk-k 1536   # one combination, 1536-key tiles
    KERNEL_SWEEP=0 python scripts/bench_kernels.py   # default blocks only
    python scripts/bench_kernels.py --prologue       # the q/k prologue's rows
    python scripts/bench_kernels.py --upsample       # upsample + 3x3 conv forms

Shapes cover the rungs that matter: the benchmark cells' UNet self-attention
classes (named ``<cell>.self<tokens>``: what ops/pallas/tuning.py's shape rule
was set from) and SD3.5-medium's joint and image-only classes, FLUX joint
attention at 1024² (4.6k tokens, 24 heads × 128) and
WAN-video lengths (16k/32k tokens) where the streamed-K/V layout is what keeps
VMEM bounded. Operands are made as (B, S, H·D) and split into heads inside the
timed program, as a model's projections hand them over. The sweep tries
block_q over {128, 256, 512} × block_k over {256, 1024, 4096} per shape (at a
ragged length the padded row as one key block, ``_combos``); each
cell is the mean of 10 chained timed calls after compile+warmup (see
``_time_fn`` for why chained). Appends JSON lines to
``<evidence dir>/KERNEL_BENCH.json`` (not tracked); the lines the shape rule
rests on are quoted beside its constants in ops/pallas/tuning.py and in
PERF.md §6 (PRs 25, 26). A new threshold or block size goes into ``route()``
there, with its lines.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# (label, batch, seq, heads, head_dim[, keys]): self-attention unless the row
# names its keys.
SHAPES = [
    ("flux_1024_joint", 1, 4608, 24, 128),
    ("flux_b4", 4, 4608, 24, 128),
    ("wan_480p_16f", 1, 16384, 12, 128),
    ("wan_long_32k", 1, 32768, 12, 128),
    # UNet-family heads: the kernel runs these zero-padded to 128 lanes
    # (flash_attention pads internally). A measured win here lets the auto
    # backend route SD-class 1024² attention through the fused kernel; a
    # loss keeps chunked XLA.
    ("sd15_1024_d40", 16, 16384, 8, 40),
    ("sdxl_1024_d64", 8, 4096, 10, 64),
    # The benchmark cells' self-attention classes (BENCHMARK.json; CFG doubles
    # the batch): the shapes the rule in ops/pallas/tuning.py was set from.
    ("sd15-b8-512.self4096", 16, 4096, 8, 40),
    ("sd15-b8-512.self1024", 16, 1024, 8, 80),
    ("sdxl-b1-1024.self4096", 2, 4096, 10, 64),
    ("sdxl-b1-1024.self1024", 2, 1024, 20, 64),
    # Their neighbours: one image of SD1.5 (the least B·H a UNet sends) and
    # the 16,384 tokens of SD1.5 at 2 x 1024² (a cell for a later issue).
    ("sd15-b1-512.self4096", 2, 4096, 8, 40),
    ("sd15-b2-1024.self16384", 4, 16384, 8, 40),
    # SD3.5-medium's two attention classes at 1 x 1024² (24 joint attentions
    # over 77 text + 4096 image tokens and 13 over the image tokens alone a
    # step) and the joint class of 512²: what the ragged-length rule in
    # ops/pallas/tuning.py was set from.
    ("sd35m-b1-1024.joint4173", 2, 4173, 24, 64),
    ("sd35m-b1-1024.self4096", 2, 4096, 24, 64),
    ("sd35m-b1-512.joint1101", 2, 1101, 24, 64),
    # FLUX.1-schnell's one attention class at 1 x 1024² and CFG 1.0: 256 T5
    # tokens + 4096 image tokens = 34 x 128, 24 heads of 128 — the
    # ``lane-aligned`` row of ``route`` serves all 36 calls of a 4-step prompt
    # at the cut's 9 blocks.
    ("flux-schnell-b1-1024.joint4352", 1, 4352, 24, 128),
    # The other lane-aligned classes the rule's table in tuning.py names (PR
    # 33): FLUX at 512² (256 + 1024 tokens), the VAE decoder's mid-block
    # attention — one 512-wide head — for 8 images of 512² (sd15's cell) and
    # one of 1024² (the other three cells), and a short row of keys under
    # 128-wide heads (WAN's cross-attention over 512 text tokens).
    ("flux-schnell-b1-512.joint1280", 1, 1280, 24, 128),
    ("vae-b8-512.mid4096", 8, 4096, 1, 512),
    ("vae-b1-1024.mid16384", 1, 16384, 1, 512),
    ("wan_480p_16f.cross512", 1, 16384, 12, 128, 512),
    # Z-Image-Turbo's two attention classes at 1 x 1024² and CFG 1.0 (PR 34),
    # 30 heads of 128: the main layers' over 4096 image + 32 caption tokens
    # (16 valid, padded to the model's multiple of 32) — not a 128-multiple,
    # so the ``ragged`` row takes it, a row measured at 64-wide heads only —
    # and the noise refiner's over the image tokens alone (``lane-aligned``).
    # The third row is the first as ``lane-aligned`` would run it if the
    # CALLER padded it to 4224 (and masked nothing: 96 more keys take part,
    # so it times the kernel, it is not the model's arithmetic).
    ("zimage-b1-1024.joint4128", 1, 4128, 30, 128),
    ("zimage-b1-1024.refine4096", 1, 4096, 30, 128),
    ("zimage-b1-1024.joint4128-as-4224", 1, 4224, 30, 128),
    # Wan2.2-T2V-A14B at 49 frames of 832 x 480 (PR 39): 13 x 30 x 52 = 20,280
    # space-time tokens, 40 heads of 128. The self-attention is ragged and
    # past RAGGED_ONE_BLOCK, so the `ragged` row streams 4096-key blocks and
    # masks the last; the cross-attention's 512 text rows are under
    # PADDED_DIM_MIN_KEYS and stay with the XLA family (in query chunks: 415 M
    # logits a call).
    ("wan22-480p.self20280", 1, 20280, 40, 128),
    ("wan22-480p.cross512", 1, 20280, 40, 128, 512),
    # Qwen-Image at 1 x 1328² and CFG 1.0 (PR 42): 83 x 83 image tokens + the
    # fixed text's 10 (ISSUE 42 reckoned 12: the seeded table gives 10), 24
    # heads of 128. Ragged, and its 6,912 padded keys are past
    # RAGGED_ONE_BLOCK, so the `ragged` row streams two 4096-key blocks, the
    # second a third real keys — between the 4,352 keys the one-block rule was
    # measured to and the video cell's 20,280.
    ("qwen-image-b1-1328.joint6899", 1, 6899, 24, 128),
]

# Shapes whose sweep is not the grid below: 4352 = 17 x 256, so only 128- and
# 256-wide blocks divide it; the wider query blocks (what PR 25 read as the
# winners at FLUX's class) pad the last one, and the whole row as one key
# block stands beside the shipped 256 keys.
COMBOS = {
    "flux-schnell-b1-1024.joint4352": [
        (256, 256), (512, 256), (1024, 256),
        (256, 4352), (512, 4352), (1024, 4352),
    ],
    # The lane-aligned table of tuning.py (PR 33): as shipped before it (256 x
    # 256), the row as one key block where a head's K and V can be, and the
    # streamed blocks on both sides of the rule's choice.
    "flux_1024_joint": [(256, 256), (256, 2304), (256, 4608), (512, 4608)],
    "flux_b4": [(256, 256), (256, 4608), (512, 4608)],
    "flux-schnell-b1-512.joint1280": [
        (256, 256), (128, 1280), (256, 1280), (640, 1280),
    ],
    "wan_480p_16f": [
        (256, 256), (256, 2048), (256, 4096), (512, 4096), (256, 8192),
        (256, 16384), (512, 16384),
    ],
    "wan_long_32k": [
        (256, 256), (256, 4096), (512, 4096), (256, 8192), (256, 16384),
        (256, 32768),
    ],
    "vae-b8-512.mid4096": [
        (256, 256), (512, 256), (256, 1024), (512, 1024), (256, 2048),
        (128, 4096), (256, 4096), (512, 4096), (1024, 4096),
    ],
    "vae-b1-1024.mid16384": [
        (256, 256), (256, 2048), (512, 2048), (256, 4096), (512, 4096),
        (1024, 4096), (256, 8192), (256, 16384),
    ],
    "wan_480p_16f.cross512": [(256, 256), (256, 512), (512, 512)],
    "zimage-b1-1024.refine4096": [(256, 256), (256, 4096), (512, 4096)],
    "zimage-b1-1024.joint4128-as-4224": [(256, 4224), (384, 4224)],
    # as routed (256 x 4096), other query and key blocks beside it, and the
    # whole padded row as one key block (5.2 MB of K a head)
    "wan22-480p.self20280": [(256, 4096), (384, 4096), (512, 4096), (256, 2048),
                             (256, 8192), (256, 20352)],
    "wan22-480p.cross512": [(256, 512), (512, 512), (1024, 512)],
    # as routed (384 x 4096: 6912 = 18 x 384), 256 queries beside it, and the
    # whole padded row as ONE key block (1.8 MB of K a head), for the issue
    # that will set RAGGED_ONE_BLOCK by bytes
    "qwen-image-b1-1328.joint6899": [(384, 4096), (256, 4096), (384, 6912),
                                     (256, 6912), (384, 3456)],
}

BLOCKS_Q = (128, 256, 512)
BLOCKS_K = (256, 1024, 4096)


def _combos(s: int) -> list[tuple[int, int]]:
    """The (block_q, block_k) pairs tried at ``s`` tokens. A ragged length
    is padded and masked by the kernel: its row as one key block (the next
    128-multiple) under three query blocks, and streamed 4096 keys a block."""
    if s % 128:
        row = -(-s // 128) * 128
        return [(bq, row) for bq in (128, 256, 384)] + [(256, min(4096, row))]
    return sorted({(min(bq, s), min(bk, s)) for bq in BLOCKS_Q for bk in BLOCKS_K})

def _time_fn(fn, *args, iters=10):
    """Mean time per call, closed by a host readback (attention maps q-shaped
    to q-shaped, so the output chains back as the first argument; see
    utils/metrics.chained_time)."""
    from comfyui_parallelanything_tpu.utils.metrics import chained_time

    sec, _ = chained_time(lambda a: fn(a, *args[1:]), args[0], iters)
    return sec


def _run_shapes(shapes, dev, blocks=None, chunk_k=None):
    """Measure the given shapes inline, appending one JSON line each to
    KERNEL_BENCH.json. ``blocks`` stands in for every shape's sweep;
    ``chunk_k`` for the kernel's keys a softmax tile (its ``_CHUNK_K``, set
    before anything is traced: how a key block's tile split was measured)."""
    import jax
    import jax.numpy as jnp

    # (the package exports the function under the module's name)
    fa = importlib.import_module(
        "comfyui_parallelanything_tpu.ops.pallas.flash_attention")
    if chunk_k is None:
        chunk_k = fa._CHUNK_K
    fa._CHUNK_K = chunk_k

    from comfyui_parallelanything_tpu.ops.attention import (
        _chunk_threshold,
        _xla_attention,
        _xla_chunked_attention,
    )
    from comfyui_parallelanything_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    def xla_family(a, b_, c, scale):
        # The real competitor the auto backend would pick: chunked when the
        # S×S logits would blow HBM, plain otherwise, on attention_local's
        # own threshold.
        elems = a.shape[0] * a.shape[2] * a.shape[1] * b_.shape[1]
        if elems > _chunk_threshold():
            return _xla_chunked_attention(a, b_, c, scale)
        return _xla_attention(a, b_, c, scale)

    from bench import evidence_dir

    out_path = os.path.join(evidence_dir(), "KERNEL_BENCH.json")
    sweep = os.environ.get("KERNEL_SWEEP", "1") != "0"
    for label, b, s, h, d, *keys in shapes:
        sk = keys[0] if keys else s
        # (B, S, H·D), split into heads inside the timed program: what a
        # model's projections hand over, so no backend is charged (or spared)
        # a relayout of a 40-wide minor dimension that no model pays.
        q, k, v = (jax.random.normal(key, (b, n, h * d), jnp.bfloat16)
                   for key, n in zip(jax.random.split(jax.random.key(0), 3),
                                     (s, sk, sk)))

        def projected(fn, _h=h, _d=d):
            def run(a, b_, c):
                split = lambda x: x.reshape(*x.shape[:2], _h, _d)  # noqa: E731
                return fn(split(a), split(b_), split(c)).reshape(a.shape)
            return jax.jit(run)

        rec = {"shape": label, "b": b, "seq": s, "keys": sk, "heads": h,
               "head_dim": d, "platform": dev.platform,
               "device_kind": dev.device_kind, "chunk_k": chunk_k,
               "ts": time.time()}
        combos = blocks or (
            (COMBOS.get(label) or _combos(s)) if sweep else [(256, 256)])
        best = None  # (ms, bq, bk)
        for bq, bk in combos:
            try:
                ms = _time_fn(
                    projected(lambda a, b_, c, _bq=bq, _bk=bk: flash_attention(
                        a, b_, c, block_q=_bq, block_k=_bk, interpret=False
                    )),
                    q, k, v,
                ) * 1e3
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                rec[f"pallas_{bq}x{bk}_error"] = str(e)[:120]
                continue
            rec[f"pallas_{bq}x{bk}_ms"] = round(ms, 3)
            if best is None or ms < best[0]:
                best = (ms, bq, bk)
        if best is not None:
            rec["pallas_ms"] = round(best[0], 3)
            rec["block_q"], rec["block_k"] = best[1], best[2]
        try:
            rec["xla_ms"] = round(
                _time_fn(projected(lambda a, b_, c: xla_family(a, b_, c, d**-0.5)),
                         q, k, v) * 1e3, 3
            )
        except Exception as e:  # noqa: BLE001 — S×S logits OOM at video lengths
            rec["xla_error"] = str(e)[:200]
        if s % 128 and _chunk_threshold() < b * h * s * sk < 2 ** 31:
            # The third route of a ragged length: XLA with the whole logits
            # tensor in HBM, where it fits (8 GB of float32 logits at most:
            # past it nothing is tried).
            try:
                rec["xla_plain_ms"] = round(_time_fn(
                    projected(lambda a, b_, c: _xla_attention(a, b_, c, d**-0.5)),
                    q, k, v) * 1e3, 3)
            except Exception as e:  # noqa: BLE001 — the logits do not fit
                rec["xla_plain_error"] = str(e)[:200]
        if "pallas_ms" in rec and "xla_ms" in rec:
            rec["pallas_speedup"] = round(rec["xla_ms"] / rec["pallas_ms"], 2)
        print(json.dumps(rec))
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


# The q/k prologue's classes (ops/pallas/qk_prologue.py): (label, batch, rows,
# heads, head dim, rotary, column blocks of the source array — 3: a fused qkv
# projection, 7: FLUX's linear1, 1: q and k are arrays of their own).
PROLOGUE_SHAPES = [
    ("sd35m-b1-1024.x4096", 2, 4096, 24, 64, False, 3),
    ("flux-schnell-b1-1024.single4352", 1, 4352, 24, 128, True, 7),
    ("flux-schnell-b1-1024.img4096", 1, 4096, 24, 128, True, 3),
    ("zimage-b1-1024.joint4128", 1, 4128, 30, 128, True, 1),
    # Short rows, for the threshold: FLUX's 256 T5 tokens, 512² image
    # streams, SD3.5's 77 text tokens and Z-Image's 32 caption tokens.
    ("flux-schnell-b1-1024.txt256", 1, 256, 24, 128, True, 3),
    ("flux-schnell-b1-512.img1024", 1, 1024, 24, 128, True, 3),
    ("sd35m-b1-512.x1024", 2, 1024, 24, 64, False, 3),
    ("sd35m-b1-1024.ctx77", 2, 77, 24, 64, False, 3),
    ("zimage-b1-1024.cap32", 1, 32, 30, 128, True, 1),
]
# (rows, lanes) a grid step.
PROLOGUE_TILES = [(256, 4096), (256, 1024), (512, 1024), (512, 512),
                  (1024, 512)]
_HBM_BYTES_PER_S = 819e9  # v5e (benchmark/peaks.json's source)


def _device_time(fn, *args, runs: int = 10) -> float:
    """Mean seconds of DEVICE time a run of the jitted ``fn``, from the
    profiler's trace (the ``XLA Modules`` line of the chip's plane). The
    host's clock cannot time a program shorter than its dispatch (0.45 ms a
    call here), and looping it inside one program charges the loop's carried
    copies to it."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))  # compile, warm
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        for _ in range(runs):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        data = ProfileData.from_file(path)
    spans = [ev.duration_ns for plane in data.planes
             if plane.name == "/device:TPU:0"
             for line in plane.lines if line.name == "XLA Modules"
             for ev in line.events]
    if len(spans) < runs:
        raise RuntimeError(f"{len(spans)} module runs in the trace, {runs} made")
    return sum(spans[-runs:]) / runs / 1e9


def _run_prologue(shapes, dev, tiles=None):
    """The q/k prologue as the jnp functions lower it against the one-pass
    kernel, by rows and lanes a grid step: ms of device time a call on q AND
    k (:func:`_device_time`), the share of
    the HBM roofline (q and k read and written once), and how far the two
    forms' results lie apart. One JSON line a shape, appended to
    KERNEL_BENCH.json."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import evidence_dir
    from comfyui_parallelanything_tpu.ops.basic import rms_normalize
    from comfyui_parallelanything_tpu.ops.pallas.qk_prologue import (
        qk_prologue_call,
        rope_tables,
    )
    from comfyui_parallelanything_tpu.ops.rope import apply_rope, axis_rope_freqs

    out_path = os.path.join(evidence_dir(), "KERNEL_BENCH.json")
    for label, b, s, h, d, rotary, blocks in shapes:
        keys = jax.random.split(jax.random.key(0), 5)
        width = h * d
        make = lambda key, n: (  # noqa: E731
            3.0 * jax.random.normal(key, (b, s, n * width))).astype(jnp.bfloat16)
        srcs = ((make(keys[0], blocks),) if blocks > 1
                else (make(keys[0], 1), make(keys[1], 1)))
        scales = tuple(1.0 + 0.1 * jax.random.normal(k, (d,)) for k in keys[2:4])
        rope = None
        if rotary:
            ids = jax.random.randint(keys[4], (b, s, 3), 0, 64)
            rope = axis_rope_freqs(ids, (d // 4, 3 * d // 8, 3 * d // 8), 256.0)

        def xla_form(*src):
            if len(src) == 1:
                view = src[0].reshape(b, s, blocks, h, d)
                q, k = view[:, :, 0], view[:, :, 1]
            else:
                q, k = (x.reshape(b, s, h, d) for x in src)
            q, k = rms_normalize(q, scales[0]), rms_normalize(k, scales[1])
            if rope is not None:
                q, k = apply_rope(q, *rope), apply_rope(k, *rope)
            return q.reshape(b, s, width), k.reshape(b, s, width)

        def kernel_form(rows, lanes):
            def run(*src):
                return qk_prologue_call(
                    src[0], src[1] if len(src) > 1 else None, *scales,
                    None if rope is None else rope_tables(*rope), heads=h,
                    eps=1e-6, block_rows=rows, tile_lanes=lanes,
                    interpret=False)
            return run

        def timed(form):
            # q and k are the program's outputs: nothing of them can be
            # optimised away, and nothing is copied that a model would not.
            return _device_time(jax.jit(form), *srcs)

        def exact(*src):
            # float32 throughout, rounded once: what both forms approximate.
            f32 = tuple(x.astype(jnp.float32) for x in src)
            return tuple(x.astype(jnp.bfloat16) for x in xla_form(*f32))

        floor_ms = 4 * b * s * width * 2 / _HBM_BYTES_PER_S * 1e3
        rec = {"shape": label, "kind": "qk_prologue", "b": b, "rows": s,
               "heads": h, "head_dim": d, "rope": rotary,
               "source_blocks": blocks, "platform": dev.platform,
               "device_kind": dev.device_kind, "floor_ms": round(floor_ms, 4),
               "ts": time.time()}
        def host(pair):
            return [np.asarray(x.astype(jnp.float32)) for x in pair]

        def differ(a, b_):
            return float(np.mean([np.mean(x != y) for x, y in zip(a, b_)]))

        want, once = host(jax.jit(xla_form)(*srcs)), host(jax.jit(exact)(*srcs))
        rec["xla_ms"] = round(timed(xla_form) * 1e3, 4)
        rec["xla_differ_from_exact_share"] = differ(want, once)
        best = None
        for rows, lanes in tiles or PROLOGUE_TILES:
            tag = f"{rows}x{lanes}"
            try:
                form = kernel_form(rows, lanes)
                got = host(jax.jit(form)(*srcs))
                ms = timed(form) * 1e3
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                rec[f"fused_{tag}_error"] = str(e)[:160]
                continue
            rec[f"fused_{tag}_ms"] = round(ms, 4)
            rec[f"fused_{tag}_max_abs_diff"] = float(
                max(np.abs(w - g).max() for w, g in zip(want, got)))
            rec[f"fused_{tag}_differ_share"] = differ(want, got)
            rec[f"fused_{tag}_differ_from_exact_share"] = differ(once, got)
            if best is None or ms < best[0]:
                best = (ms, rows, lanes)
        if best is not None:
            rec["fused_ms"], rec["block_rows"], rec["tile_lanes"] = (
                round(best[0], 4), *best[1:])
            rec["fused_roofline_share"] = round(floor_ms / best[0], 3)
            rec["fused_speedup"] = round(rec["xla_ms"] / best[0], 2)
        print(json.dumps(rec))
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


# Nearest x2 upsample + 3x3 convolution (ops/basic.upsample2x_conv3x3): the
# low-resolution input (label, batch, height, width, channels) of the
# autoencoder decoder's three stages at 1 x 1024² and at sd15's 8 x 512², and
# of the UNets' upsamplers at the cells' shapes (CFG doubles the batch).
UPSAMPLE_SHAPES = [
    ("vae-b1-1024.up128", 1, 128, 128, 512),
    ("vae-b1-1024.up256", 1, 256, 256, 512),
    ("vae-b1-1024.up512", 1, 512, 512, 256),
    ("vae-b8-512.up64", 8, 64, 64, 512),
    ("vae-b8-512.up128", 8, 128, 128, 512),
    ("vae-b8-512.up256", 8, 256, 256, 256),
    ("sd15-b8-512.up8", 16, 8, 8, 1280),
    ("sd15-b8-512.up16", 16, 16, 16, 1280),
    ("sd15-b8-512.up32", 16, 32, 32, 640),
    ("sdxl-b1-1024.up32", 2, 32, 32, 1280),
    ("sdxl-b1-1024.up64", 2, 64, 64, 640),
]
# Whole decode programs (label, latent shape, configuration's name): what a
# stage's gain is worth where XLA fuses its neighbours into it.
UPSAMPLE_DECODERS = [
    ("vae-b1-1024.decoder", (1, 128, 128, 16), "sd3_vae_config"),
    ("vae-b8-512.decoder", (8, 64, 64, 4), "sd_vae_config"),
]


def _upsample_forms():
    """The forms ISSUE 38 asked to be measured, all the same mathematics as
    ``shipped`` (ops/basic.upsample2x_conv3x3), each ``f(x, kernel, bias,
    dtype)``: ``plain`` the pair as it was (repeat, then the 3x3 at the high
    resolution), ``resize`` the same behind ``jax.image.resize``'s gather, as
    the decoder had it; ``phases`` four 2x2 convolutions, one an output phase,
    stacked and reshaped into place; ``row-phases`` one 2x3 convolution a row
    phase with both column phases as 2·O output channels (zero taps: 24
    tap-products a source pixel) and a reshape; ``one-conv`` a single 2x2
    convolution over the once-padded input with 4·O output channels and four
    offset slices."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from comfyui_parallelanything_tpu.ops.basic import upsample2x_conv3x3

    def conv(x, k, padding):
        return lax.conv_general_dilated(
            x, k, (1, 1), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)

    def fold(k):
        """(3, 3, I, O) -> [a][b] the (2, 2, I, O) window of phase (a, b)."""
        k = k.astype(jnp.float32)
        rows = [jnp.stack([k[0], k[1] + k[2]]), jnp.stack([k[0] + k[1], k[2]])]
        return [[jnp.stack([r[:, 0], r[:, 1] + r[:, 2]], 1),
                 jnp.stack([r[:, 0] + r[:, 1], r[:, 2]], 1)] for r in rows]

    def biased(y, bias, dtype, repeat=1):
        if bias is not None:
            y = y + jnp.tile(bias.astype(jnp.float32), repeat)
        return y.astype(dtype)

    def interleave(phases):
        rows = [jnp.stack(r, axis=3) for r in phases]  # (B, H, W, 2, O)
        y = jnp.stack(rows, axis=2)  # (B, H, 2, W, 2, O)
        b, h, _, w, _, o = y.shape
        return y.reshape(b, 2 * h, 2 * w, o)

    def plain(x, kernel, bias, dtype):
        x = jnp.repeat(jnp.repeat(x.astype(dtype), 2, axis=1), 2, axis=2)
        return biased(conv(x, kernel.astype(dtype), "SAME"), bias, dtype)

    def resize(x, kernel, bias, dtype):
        b, h, w, c = x.shape
        x = jax.image.resize(x.astype(dtype), (b, 2 * h, 2 * w, c), "nearest")
        return biased(conv(x, kernel.astype(dtype), "SAME"), bias, dtype)

    def phases(x, kernel, bias, dtype):
        k, x = fold(kernel), x.astype(dtype)
        return interleave([[
            biased(conv(x, k[a][b].astype(dtype), ((1 - a, a), (1 - b, b))),
                   bias, dtype)
            for b in (0, 1)] for a in (0, 1)])

    def row_phases(x, kernel, bias, dtype):
        b, h, w, _ = x.shape
        k = kernel.astype(jnp.float32)
        zero, out = jnp.zeros_like(k[0, 0]), []
        for a, rows in enumerate(([k[0], k[1] + k[2]], [k[0] + k[1], k[2]])):
            kk = jnp.concatenate([
                jnp.stack([jnp.stack([r[0], r[1] + r[2], zero]) for r in rows]),
                jnp.stack([jnp.stack([zero, r[0] + r[1], r[2]]) for r in rows]),
            ], axis=-1).astype(dtype)  # (2, 3, I, 2·O)
            y = conv(x.astype(dtype), kk, ((1 - a, a), (1, 1)))
            out.append(biased(y, bias, dtype, 2).reshape(b, h, 2 * w, -1))
        return jnp.stack(out, axis=2).reshape(b, 2 * h, 2 * w, -1)

    def one_conv(x, kernel, bias, dtype):
        _, h, w, _ = x.shape
        k = fold(kernel)
        o = kernel.shape[-1]
        kk = jnp.concatenate([k[a][b] for a in (0, 1) for b in (0, 1)], -1)
        y = biased(conv(x.astype(dtype), kk.astype(dtype), ((1, 1), (1, 1))),
                   bias, dtype, 4)  # (B, H + 1, W + 1, 4·O)
        return interleave([[
            y[:, a:a + h, b:b + w, (2 * a + b) * o:(2 * a + b + 1) * o]
            for b in (0, 1)] for a in (0, 1)])

    return {"plain": plain, "resize": resize, "shipped": upsample2x_conv3x3,
            "phases": phases, "row-phases": row_phases, "one-conv": one_conv}


def _run_upsample(shapes, decoders, dev):
    """Every form of :func:`_upsample_forms` at the stages' shapes — ms of
    device time a call (:func:`_device_time`), the compiled program's
    temporaries, the largest gap to ``plain`` — and the whole decode programs
    with each form standing in for the shipped one. One JSON line a shape,
    appended to KERNEL_BENCH.json."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import evidence_dir
    from comfyui_parallelanything_tpu.models import vae
    from comfyui_parallelanything_tpu.ops import basic

    forms = _upsample_forms()
    out_path = os.path.join(evidence_dir(), "KERNEL_BENCH.json")

    def sweep(rec, program):
        """``program(form)`` -> (jitted function, its arguments), measured for
        every form: temporaries, device time, the largest gap to the first."""
        want = None
        for tag, form in forms.items():
            try:
                fn, args = program(form)
                compiled = fn.lower(*args).compile()
                rec[f"{tag}_temp_mb"] = round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e6, 1)
                rec[f"{tag}_ms"] = round(_device_time(compiled, *args) * 1e3, 4)
                got = np.asarray(compiled(*args).astype(jnp.float32))
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                rec[f"{tag}_error"] = str(e)[:160]
                continue
            want = got if want is None else want
            rec[f"{tag}_max_abs_diff"] = float(np.abs(got - want).max())
        print(json.dumps(rec), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    for label, b, h, w, c in shapes:
        keys = jax.random.split(jax.random.key(0), 3)
        x = jax.random.normal(keys[0], (b, h, w, c), jnp.bfloat16)
        kernel = jax.random.normal(keys[1], (3, 3, c, c)) * (9 * c) ** -0.5
        bias = 0.1 * jax.random.normal(keys[2], (c,))
        tap_ms = 2 * b * h * w * c * c / 197e12 * 1e3  # a tap-product a pixel
        sweep({"shape": label, "kind": "upsample_conv", "b": b, "h": h, "w": w,
               "channels": c, "platform": dev.platform,
               "device_kind": dev.device_kind, "ts": time.time(),
               "plain_floor_ms": round(36 * tap_ms, 4),
               "phase_floor_ms": round(16 * tap_ms, 4)},
              lambda form: (
                  jax.jit(lambda x, k, b_: form(x, k, b_, jnp.bfloat16)),
                  (x, kernel, bias)))

    shipped = basic.upsample2x_conv3x3
    for label, latent, config in decoders:
        module = vae.Decoder(getattr(vae, config)())
        z = jax.random.normal(jax.random.key(1), latent, jnp.float32)
        params = jax.jit(module.init)(jax.random.key(0), z)

        def decoder(form):
            # UpsampleConv looks the function up in its module when traced.
            basic.upsample2x_conv3x3 = form
            return jax.jit(module.apply), (params, z)

        try:
            sweep({"shape": label, "kind": "upsample_conv_decoder",
                   "latent": list(latent), "config": config,
                   "platform": dev.platform, "device_kind": dev.device_kind,
                   "ts": time.time()}, decoder)
        finally:
            basic.upsample2x_conv3x3 = shipped


def main() -> None:
    import jax

    from comfyui_parallelanything_tpu.utils import enable_compilation_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench_kernels: needs a TPU, found {dev}; nothing measured"
        )
    enable_compilation_cache()

    def option(name):
        return sys.argv[sys.argv.index(name) + 1] if name in sys.argv else None

    shapes = SHAPES
    if option("--shape") and not {"--prologue", "--upsample"} & set(sys.argv):
        labels = option("--shape").split(",")
        shapes = [sh for sh in SHAPES if sh[0] in labels]
        if len(shapes) != len(labels):
            raise SystemExit(f"unknown shape among {labels!r}")
    blocks = option("--blocks") and [
        tuple(int(n) for n in pair.split("x"))
        for pair in option("--blocks").split(",")]
    chunk_k = option("--chunk-k") and int(option("--chunk-k"))
    if "--prologue" in sys.argv:
        # The q/k prologue's rows; --shape and --blocks (rows x lanes a grid
        # step) narrow them as they narrow attention's.
        picked = [sh for sh in PROLOGUE_SHAPES
                  if not option("--shape") or sh[0] in option("--shape").split(",")]
        _run_prologue(picked, dev, blocks)
        return
    if "--upsample" in sys.argv:
        # The upsample + 3x3 forms; --shape narrows stages and decoders alike.
        def picked(rows):
            return [r for r in rows
                    if not option("--shape") or r[0] in option("--shape").split(",")]

        _run_upsample(picked(UPSAMPLE_SHAPES), picked(UPSAMPLE_DECODERS), dev)
        return
    _run_shapes(shapes, dev, blocks, chunk_k)


if __name__ == "__main__":
    main()
