"""The flash kernels of the main path, compiled at real widths by the TPU's
own compiler for a DESCRIBED ``v5e:2x2`` chip (nothing is attached, nothing
runs): what interpret mode cannot show — a slice not aligned to the tiling,
more VMEM than a kernel may use — fails here, at no chip time.

The topology is described inside a module-scoped fixture and nowhere else:
only one process at a time may load the TPU's library, so this must never
happen while a module is imported, and every such test lives in THIS file
(a second file could land on another xdist worker, whose fixture would skip).
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


# -- The q/k prologue (ops/pallas/qk_prologue.py) --------------------------------
# One block of each transformer family at its cell's shapes, with the backend
# reading as a TPU so that ops/attention.qk_prologue and attention() take the
# routes they take on the chip. What ISSUE 35 found between the qkv projection
# and the flash kernel — float32 copies of q and k, relaid twice — is what
# this guards against: nothing may stand there but the prologue's one call and
# the bf16 concatenation of the streams.


def _entry_graph(hlo: str) -> dict:
    """``{name: (opcode, shape, operands, op_name)}`` of the optimized HLO's
    ENTRY computation."""
    import re

    graph = {}
    for line in hlo[hlo.index("ENTRY"):].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)", line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        op_name = re.search(r'op_name="([^"]*)"', line)
        graph[name] = (opcode, shape, operands, op_name.group(1) if op_name else "")
    return graph


def _reach(graph: dict, starts: set, forward: bool) -> set:
    users = {}
    for name, (_, _, operands, _) in graph.items():
        for op in operands:
            users.setdefault(op, []).append(name)
    seen, todo = set(), list(starts)
    while todo:
        node = todo.pop()
        for nxt in (users.get(node, []) if forward else graph[node][2]):
            if nxt in graph and nxt not in seen:
                seen.add(nxt)
                todo.append(nxt)
    return seen


def _compile_block(monkeypatch, one_chip, module, *args):
    import importlib

    att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")
    monkeypatch.setattr(att, "_pallas_available", lambda: True)

    def shaped(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
            tree)

    args = shaped(args)
    params = shaped(jax.eval_shape(
        lambda *a: module.init(jax.random.key(0), *a), *args))
    fn = jax.jit(lambda p, *a: module.apply(p, *a))
    return fn.lower(params, *args).compile()


def _prologue_blocks():
    from comfyui_parallelanything_tpu.models import flux, mmdit, zimage

    bf16, f32 = jnp.bfloat16, jnp.float32
    S = jax.ShapeDtypeStruct

    def rope(rows):
        return (S((1, rows, 64), f32), S((1, rows, 64), f32))

    return {
        # (module, args, projections' op_name, prologue calls, rows x width)
        "sd35m-joint-dual": (
            mmdit.JointBlock(mmdit.sd35_medium_config(), dual_attn=True),
            (S((2, 4096, 1536), bf16), S((2, 77, 1536), bf16), S((2, 1536), bf16)),
            r"x_attn_in2?/qkv/", 2, 2 * 4096 * 1536),
        "flux-double": (
            flux.DoubleBlock(flux.FluxConfig()),
            (S((1, 4096, 3072), bf16), S((1, 256, 3072), bf16),
             S((1, 3072), bf16), rope(4352)),
            r"(img|txt)_attn_qkv/", 2, 4096 * 3072),
        "flux-single": (
            flux.SingleBlock(flux.FluxConfig()),
            (S((1, 4352, 3072), bf16), S((1, 3072), bf16), rope(4352)),
            r"linear1/", 1, 4352 * 3072),
        "zimage-main": (
            zimage.ZImageBlock(zimage.zimage_turbo_config()),
            (S((1, 4128, 3840), bf16), rope(4128), S((1, 256), f32)),
            r"to_[qk]/", 1, 4128 * 3840),
    }


@pytest.mark.parametrize(
    "label", ["sd35m-joint-dual", "flux-double", "flux-single", "zimage-main"])
def test_nothing_but_the_prologue_between_projection_and_flash(
        monkeypatch, one_chip, label):
    """The prologue's custom call is in the block's program, reads what the
    projection wrote and writes what the flash kernel (or the streams' bf16
    concatenation before it) reads: on every path from a q/k projection
    through the prologue to the flash kernel's call there is NO ``copy`` or
    ``transpose`` and no float32 array of rows x H·D elements or more."""
    import math
    import re

    module, args, projection, n_calls, elements = _prologue_blocks()[label]
    graph = _entry_graph(
        _compile_block(monkeypatch, one_chip, module, *args).as_text())

    def named(pattern, opcode=None):
        return {n for n, (op, _, _, op_name) in graph.items()
                if re.search(pattern, op_name) and (opcode is None or op == opcode)}

    prologues = named(r"qk_prologue", "custom-call")
    flashes = named(r"flash_attention", "custom-call")
    projections = named(projection + r".*dot_general")
    assert len(prologues) == n_calls and flashes and projections
    # Every prologue reads a projection's output as it was written ...
    before = _reach(graph, projections, True) & _reach(graph, prologues, False)
    # ... and the flash kernel reads the prologue's.
    after = _reach(graph, prologues, True) & _reach(graph, flashes, False)
    for call in prologues:
        assert set(graph[call][2]) & (projections | before), call
    assert after or all(set(graph[f][2]) & prologues for f in flashes)
    for name in before | after:
        opcode, shape, _, op_name = graph[name]
        assert opcode not in ("copy", "transpose"), (name, shape, op_name)
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", shape):
            size = math.prod(int(d) for d in dims.split(","))
            assert not (dtype == "f32" and size >= elements), (name, shape)


# The autoencoder's decode program at 1 x 1024² (every 1024² cell's, once a
# request), by XLA's own analyses of the compiled program. Before PR 38 its
# three upsamplers were ``jax.image.resize`` (two gather fusions a stage, a
# copy, a pad, a copy and a slice) and a 3x3 convolution at the HIGH
# resolution: 10.16 TFLOP, 1,361,243,136 B of temporaries.
DECODER_PARENT_TEMP_BYTES = 1_361_243_136


def test_decoder_upsamplers_run_at_the_low_resolution(monkeypatch, one_chip):
    """``ops/basic.upsample2x_conv3x3`` in the decoder's program: no gather is
    left, the three pairs cost 16 tap-products a source pixel where they cost
    36 (-1.55 TFLOP), and the program's temporaries did not grow —
    ``flux-schnell`` runs at 15.1-15.3 GB of the chip's 16."""
    from comfyui_parallelanything_tpu.models import vae

    compiled = _compile_block(
        monkeypatch, one_chip, vae.Decoder(vae.sd3_vae_config()),
        jax.ShapeDtypeStruct((1, 128, 128, 16), jnp.float32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the mid-block attention's flash kernel
    assert "gather" not in text
    assert compiled.cost_analysis()["flops"] <= 8.8e12  # parent 10.16e12
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= DECODER_PARENT_TEMP_BYTES
