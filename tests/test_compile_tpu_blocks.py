"""One block of each transformer family at its cell's shapes, compiled by the
TPU's own compiler for the described ``v5e:2x2`` chip of
``test_tpu_compile.py`` (nothing is attached, nothing runs)."""

import jax
import jax.numpy as jnp
import pytest

from tests.test_tpu_compile import one_chip, topo  # noqa: F401 — fixtures

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
