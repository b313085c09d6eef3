"""Per-op-class MFU budget for a bench rung.

The round-3 hardware table shows sd15_16 at 8.6% MFU while sdxl_8 hits 40% on
the same chip — a 4.7× gap that needs a *budget* (where do the 91% of cycles
go?) before a live window can fix it. This script produces that budget WITHOUT
hardware: it traces the rung's denoise-step jaxpr, walks every equation
(recursing into pjit/closed-call subjaxprs), and buckets exact FLOPs and
memory traffic by op class:

- ``conv``       — conv_general_dilated (the UNet trunk)
- ``matmul``     — dot_general (attention projections, transformer MLPs,
                   attention score/value products)
- ``attention``  — the dot_generals of attention score/value products
                   (contraction or output dim is a sequence length from this
                   trace) — split out because lane-padding waste lives here
- ``elementwise`` — everything else, costed by bytes touched (norms,
                   activations, softmax, residual adds)

Roofline projection per class (v5e-1: 197 bf16 TFLOP/s, 819 GB/s HBM):
``t_class = max(flops / peak_flops, bytes / hbm_bw)``. The MXU-waste model
additionally reports matmul time at the PADDED contraction width (lane
granularity 128): a 40-wide head dim costs the MXU the same as 128 — the
padded/unpadded ratio is the ceiling a lane-respecting kernel can claw back.

Output: a table on stdout + ``MFU_BUDGET.json`` next to the other evidence
artifacts. Run for any rung: ``BENCH_CONFIG=sd15_16 python scripts/mfu_budget.py``.
CPU-safe (pure tracing; nothing executes).
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# The jaxpr walk lives in utils/roofline.py now (ONE FLOPs counter shared by
# this budget, bench.py's step-cost accessor, and the roofline layer — the
# two sources can no longer silently disagree); this script keeps the
# per-op-class presentation over it. Re-exported names (walk/analytic_flops)
# keep the historical entry points working. Loaded STANDALONE by file path
# (the scripts/roofline_report.py pattern): importing through the package
# `__init__` chain pulls jax at module level — the standalone-contract
# drift palint's pass now fails CI on.


def _load_roofline():
    import importlib.util

    path = os.path.join(_REPO, "comfyui_parallelanything_tpu", "utils",
                        "roofline.py")
    spec = importlib.util.spec_from_file_location("pa_roofline_mfu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_roofline = _load_roofline()
analytic_flops = _roofline.analytic_flops  # re-export (bench's fallback)
empty_acc = _roofline.empty_acc
walk = _roofline.walk_jaxpr

PEAK_FLOPS = 197e12  # v5e bf16
HBM_BW = 819e9       # v5e HBM bytes/s
# (the MXU 128-lane padding model lives with the walk in utils/roofline.py)


def main():
    global jax
    import jax
    import jax.numpy as jnp

    import bench

    rung = os.environ.get("BENCH_CONFIG", "sd15_16")
    model, batch, lat_shape, ctx_len, ctx_dim, kwargs, workload, *mb = (
        bench._RUNGS[rung](jnp, jax.random.key(0))
    )
    x = jnp.zeros(lat_shape, jnp.bfloat16)
    t = jnp.zeros((batch,), jnp.float32)
    ctx = jnp.zeros((batch, ctx_len, ctx_dim), jnp.bfloat16)

    jaxpr = jax.make_jaxpr(
        lambda p, x, t, c: model.apply(p, x, t, c, **kwargs)
    )(model.params, x, t, ctx)

    # Sequence lengths that can appear as attention S×S outputs: every
    # spatial-token count at the UNet/DiT resolutions in this trace.
    side = lat_shape[1]
    seq_lens = {ctx_len}
    for s in range(8):
        if side >> s:
            seq_lens.add((side >> s) * (lat_shape[2] >> s))

    acc = empty_acc()
    walk(jaxpr.jaxpr, acc, seq_lens)
    by_prim = acc.pop("_by_prim", {})

    total_flops = sum(c["flops"] for c in acc.values())
    rows, total_ms = [], 0.0
    for cls, c in acc.items():
        t_flops = c["flops"] / PEAK_FLOPS
        t_pad = c["flops_padded"] / PEAK_FLOPS
        t_mem = c["bytes"] / HBM_BW
        t_cls = max(t_pad, t_mem)
        total_ms += t_cls * 1e3
        rows.append({
            "class": cls, "count": c["count"], "gflops": c["flops"] / 1e9,
            "gflops_padded": c["flops_padded"] / 1e9,
            "gbytes": c["bytes"] / 1e9,
            "ms_compute": t_flops * 1e3, "ms_padded": t_pad * 1e3,
            "ms_memory": t_mem * 1e3, "ms_roofline": t_cls * 1e3,
            "bound": "memory" if t_mem > t_pad else "compute",
        })
    out = {
        "rung": rung, "workload": workload, "batch": batch,
        "total_model_gflops": total_flops / 1e9,
        "ideal_s_it": total_flops / PEAK_FLOPS,
        "roofline_s_it": total_ms / 1e3,
        "roofline_mfu": (total_flops / PEAK_FLOPS) / (total_ms / 1e3)
        if total_ms else None,
        "classes": rows,
        "peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
    }
    path = os.path.join(bench.evidence_dir(), "MFU_BUDGET.json")
    existing = []
    if os.path.exists(path):
        existing = json.load(open(path))
        if not isinstance(existing, list):
            existing = [existing]
    existing = [e for e in existing if e.get("rung") != rung] + [out]
    json.dump(existing, open(path, "w"), indent=1)

    hdr = (f"{'class':18} {'n':>5} {'GFLOP':>10} {'GFLOP(pad)':>11} "
           f"{'GB':>8} {'ms@peak':>8} {'ms(pad)':>8} {'ms(mem)':>8} "
           f"{'roofline':>9} bound")
    print(hdr)
    for r in rows:
        print(f"{r['class']:18} {r['count']:>5} {r['gflops']:>10.1f} "
              f"{r['gflops_padded']:>11.1f} {r['gbytes']:>8.2f} "
              f"{r['ms_compute']:>8.2f} {r['ms_padded']:>8.2f} "
              f"{r['ms_memory']:>8.2f} {r['ms_roofline']:>9.2f} {r['bound']}")
    top = sorted(by_prim.items(), key=lambda kv: -kv[1][1])[:8]
    out["elementwise_top"] = [
        {"prim": k, "count": v[0], "gbytes": v[1] / 1e9} for k, v in top
    ]
    print("\nelementwise top contributors (UNFUSED bytes — XLA fuses most;"
          " ranking, not prediction):")
    for k, v in top:
        print(f"  {k:28} n={v[0]:>5}  {v[1]/1e9:>8.2f} GB")
    print(f"\nrung={rung}  model={total_flops/1e12:.2f} TFLOP/step  "
          f"ideal={out['ideal_s_it']*1e3:.1f} ms/it  "
          f"unfused-roofline={total_ms:.1f} ms/it  "
          f"unfused-roofline-MFU={out['roofline_mfu']:.1%}")
    print(f"budget written to {path}")


if __name__ == "__main__":
    main()
