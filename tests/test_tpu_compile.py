"""The flash kernels of the main path, compiled at real widths by the TPU's
own compiler for a DESCRIBED ``v5e:2x2`` chip (nothing is attached, nothing
runs): what interpret mode cannot show — a slice not aligned to the tiling,
more VMEM than a kernel may use — fails here, at no chip time.

The topology is described inside the module-scoped fixture ``topo`` and
nowhere else — never while a module is imported. The whole blocks and the
decoder compiled the same way are in ``test_compile_tpu_blocks.py`` and
``test_compile_tpu_decoder.py`` (files of their own, named to start early: one
of them is the longest test there is); they take ``topo`` from here. Several
files mean several xdist workers loading the TPU's library at once, which it
refuses unless told otherwise: the fixture tells it.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from comfyui_parallelanything_tpu.ops.pallas.flash_attention import (
    flash_attention,
)

# (label, B, S, H, D): FLUX-dev 1024² joint attention, a WAN 480p 81-frame
# clip, and an SD1.5 shape whose 40-wide heads are lane-padded to 128.
SHAPES = [
    ("flux-dev-1024", 1, 4608, 24, 128),
    ("wan-32k", 1, 32768, 12, 128),
    ("sd15-d40", 2, 4096, 8, 40),
]


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # one lock file a machine otherwise: the second worker's fixture would skip
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no compiler here is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # conftest pins "highest" matmul precision for CPU equivalence tests; the
    # chip path runs at the default, and that is the program compiled here
    # (at "highest" the upstream kernel's bf16 dot is refused by Mosaic).
    prev_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield desc
    jax.config.update("jax_default_matmul_precision", prev_precision)
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(one_chip, b, s, h, d, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((b, s, h, d), dtype, sharding=one_chip),) * 3


@pytest.mark.parametrize("block", [128, 256, 512])
@pytest.mark.parametrize("label,b,s,h,d", SHAPES, ids=[s[0] for s in SHAPES])
def test_flash_attention_compiles_for_v5e(one_chip, label, b, s, h, d, block):
    compiled = flash_attention.lower(
        *_qkv(one_chip, b, s, h, d), block_q=block, block_k=block,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


# The benchmark cells' UNet self-attention classes (CFG doubles the batch) and
# SD1.5 at 1024², as ops/pallas/tuning.py's shape rule routes them.
# (label, B, S, H, D)
UNET_CLASSES = [
    ("sd15-b8-512.self4096", 16, 4096, 8, 40),
    ("sd15-b8-512.self1024", 16, 1024, 8, 80),
    ("sdxl-b1-1024.self4096", 2, 4096, 10, 64),
    ("sdxl-b1-1024.self1024", 2, 1024, 20, 64),
    ("sd15-b2-1024.self16384", 4, 16384, 8, 40),
]


@pytest.mark.parametrize("label,b,s,h,d", UNET_CLASSES,
                         ids=[c[0] for c in UNET_CLASSES])
def test_unet_classes_compile_with_the_shipped_blocks(one_chip, label, b, s, h, d):
    """A head group's whole K and V in VMEM, 256 queries a block, 2048-key
    softmax tiles over static lane slices of a (B, S, H·D) block: what
    interpret mode cannot show is whether Mosaic takes the unaligned slices
    and whether the tiles fit the raised VMEM limit."""
    from comfyui_parallelanything_tpu.ops.pallas.tuning import PADDED_DIM_BLOCKS

    block_q, block_k = PADDED_DIM_BLOCKS
    compiled = flash_attention.lower(
        *_qkv(one_chip, b, s, h, d), block_q=block_q, block_k=block_k,
        interpret=False,
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # The kernel reads the projections' own layout: no transpose, no pad.
    assert " transpose(" not in hlo and " pad(" not in hlo
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


# SD3.5-medium's two attention classes at 1 x 1024² (CFG doubles the batch):
# the joint one is 77 text + 4096 image tokens, a ragged length.
MMDIT_CLASSES = [
    ("sd35m-b1-1024.joint4173", 2, 4173, 24, 64),
    ("sd35m-b1-1024.self4096", 2, 4096, 24, 64),
]


@pytest.mark.parametrize("label,b,s,h,d", MMDIT_CLASSES,
                         ids=[c[0] for c in MMDIT_CLASSES])
def test_mmdit_classes_compile_with_the_routed_blocks(one_chip, label, b, s, h, d):
    """The joint class goes to the kernel padded and masked: one key block of
    4224 keys walked in three 1408-key softmax tiles, 11 query blocks of 384
    — slices at offsets that are multiples of 128 but not of 2048."""
    from comfyui_parallelanything_tpu.ops.pallas.tuning import route

    _, block_q, block_k, _ = route(s, s, d, b * h, on_tpu=True,
                                   chunk_threshold=2**27)
    assert block_k == -(-s // 128) * 128 <= 4352
    compiled = flash_attention.lower(
        *_qkv(one_chip, b, s, h, d), block_q=block_q, block_k=block_k,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


# The classes whose head dim is a multiple of 128: FLUX-dev and the cell
# flux-schnell-b1-1024.closed-unique's joint attention, a WAN 480p clip, and
# the VAE decoder's one 512-wide head for 8 images of 512² and one of 1024².
LANE_ALIGNED_CLASSES = [
    ("flux-dev-1024", 1, 4608, 24, 128),
    ("flux-schnell-b1-1024.joint4352", 1, 4352, 24, 128),
    ("wan-32k", 1, 32768, 12, 128),
    ("vae-b8-512.mid4096", 8, 4096, 1, 512),
    ("vae-b1-1024.mid16384", 1, 16384, 1, 512),
]


@pytest.mark.parametrize("label,b,s,h,d", LANE_ALIGNED_CLASSES,
                         ids=[c[0] for c in LANE_ALIGNED_CLASSES])
def test_lane_aligned_classes_compile_with_the_routed_blocks(one_chip, label,
                                                             b, s, h, d):
    """A head's whole K and V as one key block where the rule says they fit
    (4352 keys in two 2176-key softmax tiles), a streamed block where it says
    they do not: the VMEM the rule budgets is VMEM the chip's compiler
    grants, and no row is padded on its way to the kernel."""
    from comfyui_parallelanything_tpu.ops.pallas.tuning import route

    backend, block_q, block_k, rule = route(s, s, d, b * h, on_tpu=True,
                                            chunk_threshold=2**27)
    assert (backend, rule) == ("pallas", "lane-aligned")
    compiled = flash_attention.lower(
        *_qkv(one_chip, b, s, h, d), block_q=block_q, block_k=block_k,
        interpret=False,
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert " transpose(" not in hlo and " pad(" not in hlo
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_cross_attention_keys_of_length_77_compile(one_chip):
    q = jax.ShapeDtypeStruct((2, 4096, 8, 40), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 77, 8, 40), jnp.bfloat16, sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("which", ["in-repo", "in-repo-unet"])
def test_batch_sharded_kernels_compile_for_four_chips(topo, which):
    """The data-parallel step and the VAE decode of a chain's latent hand the
    kernel operands sharded over four chips. The partitioner refuses a bare
    Mosaic call there (the first 4-chip run of chip_smoke.py failed on it);
    under the caller's context mesh the kernel is shard_mapped and compiles,
    with nothing gathered."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from comfyui_parallelanything_tpu.parallel.mesh import mesh_context

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data"))
    # The kl-f8 VAE's mid-block attention for 8 images of 512²: one 512-wide head.
    q = jax.ShapeDtypeStruct((8, 4096, 1, 512), jnp.bfloat16, sharding=rows)
    if which == "in-repo-unet":
        # The chain's UNet step: SD1.5's 4096-token self-attention at CFG
        # batch 16, four rows a chip, with the shape rule's blocks.
        q = jax.ShapeDtypeStruct((16, 4096, 8, 40), jnp.bfloat16, sharding=rows)
    def fn(q, k, v):
        # Both rows' blocks at 4096 keys (PR 33): the decoder's 4 MB row and
        # the UNet's as one key block.
        return flash_attention(q, k, v, block_q=256, block_k=4096,
                               interpret=False)

    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(fn).lower(q, q, q).compile()
    with mesh_context(mesh):
        compiled = jax.jit(fn).lower(q, q, q).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" not in hlo
    assert compiled.output_shardings.spec == P("data")


# -- Qwen-Image at its cell's shapes (qwen-image-b1-1328.closed) -------------------
# One block and one tower layer, each compiled for the described chip with the
# shipped routes and nothing run: what the cell's first chip call would
# otherwise find out at a minute a program. (The one-frame decode at 166 x 166
# is not here: its compile for the described chip takes this sandbox over 16
# minutes — the 1024² image decoder's takes 350 s in
# ``test_compile_tpu_decoder.py`` — so its temporaries are read on the chip,
# where the program compiles in the warm-up: PERF.md section 5.)


def test_a_qwen_image_block_compiles_at_6889_and_10_tokens(monkeypatch, one_chip):
    """``models/flux.DoubleBlock`` at Qwen-Image's widths on 83 x 83 image
    tokens and the fixed text's 10: the flash kernel on the ``ragged`` row
    (6,899 keys, streamed 4096 a block) and the image stream's q/k prologue
    are in the program; the text stream's 10 rows stay with XLA. The row is
    padded where the route pads it — inside the kernels' own calls — and
    nowhere else: no ``pad`` of 6,899 rows or more stands in the program
    outside them."""
    import math
    import re

    from comfyui_parallelanything_tpu.models import flux, qwen_image
    from tests.test_compile_tpu_blocks import _compile_block, _entry_graph

    S, bf16, f32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.float32
    cfg = qwen_image.qwen_image_config()
    compiled = _compile_block(
        monkeypatch, one_chip, flux.DoubleBlock(cfg),
        S((1, 6889, 3072), bf16), S((1, 10, 3072), bf16), S((1, 3072), bf16),
        (S((1, 6899, 64), f32), S((1, 6899, 64), f32)))
    text = compiled.as_text()
    graph = _entry_graph(text)
    calls = [op_name for op, _, _, op_name in graph.values() if op == "custom-call"]
    assert sum("flash_attention" in n for n in calls) >= 1
    assert sum("qk_prologue" in n for n in calls) == 1  # the image stream's
    for line in text.splitlines():
        if re.search(r"= \S+ pad\(", line) and not re.search(
                r"flash_attention|qk_prologue", line):
            for dims in re.findall(r"\w+\[([\d,]+)\]", line.split(" pad(")[0]):
                assert math.prod(int(d) for d in dims.split(",")) < 6899 * 3072, line


def test_a_qwen25vl_tower_layer_compiles_at_the_cells_bucket(monkeypatch, one_chip):
    """One layer of the causal tower in its Qwen2.5-VL configuration (biases
    on q / k / v, no q/k norms, 28 query on 4 key/value heads) at the cell's
    64-token bucket: 233,057,792 parameters a layer, as the issue reckons."""
    from comfyui_parallelanything_tpu.models import text_encoders
    from tests.test_compile_tpu_blocks import _compile_block

    S = jax.ShapeDtypeStruct
    cfg = text_encoders.qwen25_vl_7b_config()
    layer = text_encoders._Qwen3Layer(cfg)
    args = (S((1, 64, 3584), jnp.bfloat16),
            (S((1, 64, 64), jnp.float32), S((1, 64, 64), jnp.float32)))
    shapes = jax.eval_shape(lambda *a: layer.init(jax.random.key(0), *a), *args)
    assert sum(l.size for l in jax.tree.leaves(shapes)) == 233_057_792
    assert "q_norm" not in shapes["params"] and "bias" in shapes["params"]["k_proj"]
    assert _compile_block(monkeypatch, one_chip, layer, *args).as_text()
