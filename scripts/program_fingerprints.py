"""Fingerprints of the programs the cells share, so that a PR which touches a
shared file can show it left them what they were (ISSUE 42, item 7):

    python3 scripts/program_fingerprints.py      # from a checkout's root

prints one line a program — FLUX.1-schnell's, Z-Image's and WAN's step
programs at their cells' cuts and shapes, Z-Image's Qwen3 tower at its
32-token bucket, the video decode program as ``VideoVAE`` compiles it — with
a hash of its StableHLO text, LOWERED (not compiled: seconds, no chip) for a
described v5e with the routes a TPU takes. Equal text into one compiler is
one compiled program. A Mosaic kernel's payload carries the kernel source's
file paths, so the checkout's own path is named alike before hashing; run it
on the parent (``git archive`` copy) and on the change and compare."""

import base64
import hashlib
import importlib
import os
import re
import sys


def main() -> None:
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("TPU_LOG_DIR", "disabled"); os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")
    att._pallas_available = lambda: True
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    def shaped(tree): return jax.tree.map(lambda l: S(l.shape, l.dtype), tree)
    def norm(text):
        """Mosaic payloads carry the kernel source's file paths: decode, and name the checkout alike."""
        def body(m):
            raw = base64.b64decode(m.group(1))
            return "BODY<" + hashlib.sha256(raw.replace(os.getcwd().encode(), b"/root/scratch/XXXXXX")).hexdigest() + ">"
        return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    def fp(name, fn, params, *args):
        text = norm(jax.jit(fn).lower(params, *args).as_text())
        print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text), flush=True)
    from comfyui_parallelanything_tpu.models import flux, text_encoders, video_vae, zimage, wan
    # flux-schnell's step program at the cell's cut and shapes
    cfg = flux.flux_schnell_config(depth=3, depth_single_blocks=6)
    m = flux.FluxModel(cfg)
    args = (S((1, 128, 128, 16)), S((1,)), S((1, 256, 4096)))
    p = shaped(jax.eval_shape(lambda *a: m.init(jax.random.key(0), *a, y=jnp.zeros((1, 768)))["params"], *args))
    fp("flux-step", lambda p, x, t, c, y: m.apply({"params": p}, x, t, c, y=y), p, *args, S((1, 768)))
    # zimage's tower at its 32-token bucket
    tc = text_encoders.qwen3_4b_config()
    tm = text_encoders.Qwen3Model(tc)
    ids = S((1, 32), jnp.int32)
    p = shaped(jax.eval_shape(lambda i: tm.init(jax.random.key(0), i)["params"], ids))
    fp("zimage-tower", lambda p, i: tm.apply({"params": p}, i), p, ids)
    # zimage's step
    zc = zimage.zimage_turbo_config(n_layers=8)
    zm = zimage.ZImageModel(zc)
    args = (S((1, 128, 128, 16)), S((1,)), S((1, 32, 2560)))
    p = shaped(jax.eval_shape(lambda *a: zm.init(jax.random.key(0), *a)["params"], *args))
    fp("zimage-step", lambda p, x, t, c, y: zm.apply({"params": p}, x, t, c, y=y), p, *args, S((1, 1)))
    # wan's step at 5 blocks
    wc = wan.wan_14b_config(depth=5)
    wm = wan.WanModel(wc)
    args = (S((1, 13, 60, 104, 16)), S((1,)), S((1, 512, 4096)))
    p = shaped(jax.eval_shape(lambda *a: wm.init(jax.random.key(0), *a)["params"], *args))
    fp("wan-step", lambda p, x, t, c: wm.apply({"params": p}, x, t, c), p, *args)
    # the video decode program as VideoVAE compiles it
    class Captured(Exception): pass
    from jax._src import stages
    orig = stages.Lowered.compile
    def grab(self, *a, **k): raise Captured(self.as_text())
    stages.Lowered.compile = grab
    vc = video_vae.wan_vae_config()
    vm = video_vae.VideoAutoencoderKL(vc)
    p = shaped(jax.eval_shape(lambda: vm.init(jax.random.key(0), jnp.zeros((1, 5, 16, 16, 3)))["params"]))
    try:
        video_vae.VideoVAE(cfg=vc, params=p)._decode_program(p, S((1, 13, 60, 104, 16)))
    except Captured as e:
        text = norm(str(e)); print("video-decode", hashlib.sha256(text.encode()).hexdigest()[:16], len(text))


if __name__ == "__main__":
    main()
