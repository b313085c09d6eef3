"""host-sync: no device sync/transfer inside timed loops or hot step paths.

The PR 3 discipline, now enforced: a ``block_until_ready`` / device→host
transfer inside a TIMED region books transfer time as compute (the exact
lie ``stream-prefetch-wait`` exists to prevent — "exposed transfer is
booked as wait, never compute"), and one inside a per-step hot path adds a
host round-trip to every sampler step. Two scopes:

1. **timed loops** (detected): a function that stamps
   ``t = time.perf_counter()`` and later computes ``time.perf_counter() -
   t`` brackets a timed window; any banned sync inside a ``for``/``while``
   loop within that window is flagged. (Syncs between the stamps but
   outside a loop are the closing boundary — ``StepTimer``'s honest-timing
   block — and are the loop-free pattern the repo's timers use.)

2. **hot step paths** (declared, :data:`HOT_PATHS`): the per-step compiled
   dispatch paths. EVERY banned sync there is flagged — the legitimate
   boundary syncs (the serving dispatch's completion block, streaming's
   backpressure and trace-mode prefetch-wait blocks) carry
   ``# palint: allow[host-sync]`` pragmas whose justifications ARE the
   discipline, reviewed in place; a new sync shows up as a finding.

Banned: ``block_until_ready``, ``jax.device_get``, ``np.asarray``,
``force_ready``, ``.item()``, and ``float(x[...])``/``float(f(...))``
(a float() on a subscript/call result is how device scalars leak to host
mid-loop; ``float(name)`` on a host scalar is not flagged).
"""

from __future__ import annotations

import ast

NAME = "host-sync"
DOC = "no host sync/transfer in timed loops or compiled-step hot paths"

# (path suffix, flattened qualname suffix) — the per-step hot paths. The
# bench timed loop itself is covered by scope 1 (chained_time) plus the
# `step` closure here.
HOT_PATHS = (
    ("comfyui_parallelanything_tpu/serving/bucket.py", "StepBucket.dispatch"),
    ("comfyui_parallelanything_tpu/serving/decode.py",
     "DecodeQueue._dispatch"),
    ("comfyui_parallelanything_tpu/parallel/streaming.py",
     "StreamingRunner.__call__"),
    ("bench.py", "step"),
    # The eager k-sampler loops: schedule scalars live on the host, and a
    # read between steps stalls the host behind the previous step's latent.
    ("comfyui_parallelanything_tpu/sampling/k_samplers.py", "sample_planned"),
    ("comfyui_parallelanything_tpu/sampling/k_samplers.py", "sample_lms"),
    ("comfyui_parallelanything_tpu/sampling/k_samplers.py", "_sample_unipc"),
    ("comfyui_parallelanything_tpu/sampling/k_samplers.py",
     "EpsDenoiser.fused_eval"),
)

_SYNC_ATTRS = {"block_until_ready", "device_get", "item"}
_SYNC_NAMES = {"force_ready"}


def _banned_call(node: ast.Call) -> str | None:
    """The banned-construct label for this call, or None."""
    fn = node.func
    if isinstance(fn, ast.Attribute):
        if fn.attr in _SYNC_ATTRS:
            return f".{fn.attr}()"
        # numpy's asarray is a device→host transfer; jnp.asarray is the
        # opposite direction (host→device staging) and stays legal.
        if fn.attr == "asarray" and isinstance(fn.value, ast.Name) and \
                fn.value.id in ("np", "numpy", "_np", "onp"):
            return f"{fn.value.id}.asarray()"
    elif isinstance(fn, ast.Name):
        if fn.id in _SYNC_NAMES:
            return f"{fn.id}()"
        if fn.id == "float" and node.args and isinstance(
                node.args[0], (ast.Subscript, ast.Call)):
            return "float(<device value>)"
    return None


def _functions(tree):
    """Yield (flattened qualname, node) for every function, including
    closures (qualname drops the `<locals>` hops: `Outer.inner`)."""
    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                yield q, child
                yield from walk(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
            else:
                yield from walk(child, prefix)
    yield from walk(tree, "")


def _timed_window(fn_node):
    """(start_line, end_line) of the perf_counter()-bracketed region in
    this function's own body (nested defs excluded), or None."""
    def is_pc_call(node):
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "perf_counter")

    starts: dict[str, int] = {}
    end_by_name: dict[str, int] = {}
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign) and is_pc_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    starts.setdefault(t.id, node.lineno)
        elif (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
              and is_pc_call(node.left)
              and isinstance(node.right, ast.Name)):
            end_by_name[node.right.id] = max(
                end_by_name.get(node.right.id, 0), node.lineno)
    windows = [(starts[n], end_by_name[n]) for n in starts
               if n in end_by_name and end_by_name[n] > starts[n]]
    if not windows:
        return None
    return min(w[0] for w in windows), max(w[1] for w in windows)


def _loop_lines(fn_node, lo: int, hi: int) -> set[int]:
    """Lines inside for/while loops that start within [lo, hi] in this
    function (nested functions included — a closure dispatched per
    iteration is still the loop body)."""
    lines: set[int] = set()
    for node in ast.walk(fn_node):
        if isinstance(node, (ast.For, ast.While)) and lo <= node.lineno <= hi:
            for sub in ast.walk(node):
                ln = getattr(sub, "lineno", None)
                if ln is not None:
                    lines.add(ln)
    return lines


def run(ctx) -> list[dict]:
    findings: list[dict] = []
    seen: set[tuple] = set()

    def add(f, node, label, why):
        key = (f.rel, node.lineno, label)
        if key in seen:
            return
        seen.add(key)
        findings.append({
            "path": f.rel, "line": node.lineno, "code": "sync-in-hot-path",
            "message": f"{label} {why} — the PR 3 discipline: exposed "
                       f"transfer is booked as wait, never compute",
        })

    for f in ctx.files:
        if f.tree is None or f.rel.startswith("scripts/"):
            continue
        hot_names = tuple(q for suffix, q in HOT_PATHS
                          if f.rel.endswith(suffix))
        for qual, fn_node in _functions(f.tree):
            is_hot = any(qual == q or qual.endswith("." + q)
                         for q in hot_names)
            window = _timed_window(fn_node)
            if not is_hot and window is None:
                continue
            loop_lines = (_loop_lines(fn_node, *window)
                          if window is not None else set())
            for node in ast.walk(fn_node):
                if not isinstance(node, ast.Call):
                    continue
                label = _banned_call(node)
                if label is None:
                    continue
                if is_hot:
                    add(f, node, label,
                        f"in hot step path `{qual}`")
                elif node.lineno in loop_lines:
                    add(f, node, label,
                        f"inside a loop in `{qual}`'s timed "
                        f"perf_counter window")
    return findings
