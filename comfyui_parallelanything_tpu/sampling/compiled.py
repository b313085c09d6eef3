"""Whole-loop compiled sampling: the entire denoise loop as ONE jitted program.

The reference's hot path re-enters the (monkey-patched) ``forward`` from Python
every denoise step (any_device_parallel.py:1287) — cheap on CUDA, but on TPU
each re-entry pays dispatch latency and re-allocates the latent in HBM. This
module compiles the *whole sampler loop* — schedule walk, CFG doubling, model
forward, latent update, optional inpaint-mask blend — into a single XLA program
via ``lax.scan``, with the input latent **donated** so every intermediate x_t
lives in the scan carry and the per-step host round-trip disappears.

Opt-in via ``run_sampler(..., compile_loop=True)``. The compiled path covers
the single-program cases (bare models; single-platform-group ParallelModel
chains, replicated or FSDP). It intentionally does NOT cover:

- heterogeneous chains (host-side scatter between per-platform programs cannot
  live inside one XLA program) — falls back to the eager loops;
- user callbacks (arbitrary Python per step) — falls back; the latent-mask
  inpainting hook IS supported, traced into the loop;
- step-level OOM demotion (parity 1435-1448): one program means one
  allocation decision at compile time. Elasticity stays with the eager path.

Each scan sampler is its eager twin (``ddim.py``, ``flow.py``, and for the
k-family the plans of ``lane_specs.py`` that ``k_samplers.sample_planned``
walks) with the Python schedule branches as ``jnp.where`` on the step index;
``tests/test_compiled.py`` pins eager/compiled equivalence for the full
sampler menu.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.split import (
    is_arraylike as _is_arraylike,
    pad_leaf as _pad_leaf,
    slice_padded as _slice_padded,
)
from ..utils import numerics
from .cfg import double_kwargs, rescale_guidance
from .k_samplers import (
    RNG_SAMPLERS,
    EpsDenoiser,
    ancestral_steps as _ancestral,
    lms_coefficient_matrix,
    unipc_coeff_table,
)

__all__ = [
    "TraceSpec",
    "trace_spec_of",
    "compiled_k_sample",
    "compiled_ddim_sample",
    "compiled_flow_sample",
    "lane_step_program",
]


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """A model reduced to what one XLA program needs: a pure apply + params
    (already placed/sharded), and the mesh to pin the batch axis to (None for
    single-device models)."""

    apply: Callable[..., Any]  # (params, x, t, context, **kwargs)
    params: Any
    mesh: Any = None
    data_axis: str | None = None


_plain_callable_specs: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def trace_spec_of(model) -> TraceSpec | None:
    """A TraceSpec for ``model``, or None when it cannot run as one program.

    ParallelModel exposes ``.traceable()`` (None for hybrid chains, active
    sequence-parallel contexts, and weight-streaming mode — a streamed
    model's full pytree must never be closed over by one program);
    DiffusionModel / ``(apply, params)`` are pure by construction; a bare
    callable is *assumed* pure — the documented contract of
    ``compile_loop=True``."""
    if getattr(model, "is_streaming", False):
        # Belt-and-braces for streaming wrappers that also quack
        # .apply/.params: the duck-typed branches below would trace the FULL
        # host pytree into the loop program and materialize it on-device.
        return None
    traceable = getattr(model, "traceable", None)
    if callable(traceable):
        return traceable()
    apply = getattr(model, "apply", None)
    params = getattr(model, "params", None)
    if callable(apply) and params is not None:
        return TraceSpec(apply=apply, params=params)
    if isinstance(model, tuple) and len(model) == 2 and callable(model[0]):
        return TraceSpec(apply=model[0], params=model[1])
    if callable(model):
        spec = _plain_callable_specs.get(model)
        if spec is None:

            def apply_plain(params, x, t, context=None, *, _m=model, **kwargs):
                return _m(x, t, context, **kwargs)

            spec = TraceSpec(apply=apply_plain, params=())
            _plain_callable_specs[model] = spec
        return spec
    return None


# ---------------------------------------------------------------------------
# placement: pad the batch to the data-axis width and shard (the compiled-path
# analogue of _dp_on_group's place(); orchestrator.py applies it per step, here
# it happens once at loop entry)
# ---------------------------------------------------------------------------


def _place_batch(tree, batch: int, padded: int, mesh, data_axis):
    """Pad+shard batch-dim leaves, replicate other array leaves (mesh case);
    pad only on single-device (mesh None)."""
    if mesh is None:
        if padded == batch:
            return tree
        return jax.tree.map(
            lambda l: _pad_leaf(l, padded - batch)
            if _is_arraylike(l) and l.ndim > 0 and l.shape[0] == batch
            else l,
            tree,
        )
    sharded = NamedSharding(mesh, P(data_axis))
    repl = NamedSharding(mesh, P())

    def leaf(l):
        if not _is_arraylike(l):
            return l
        if l.ndim > 0 and l.shape[0] == batch:
            return jax.device_put(_pad_leaf(l, padded - batch), sharded)
        return jax.device_put(l, repl)

    return jax.tree.map(leaf, tree)


def _constrain(x, mesh, data_axis):
    """Re-pin the carry's batch sharding each step so XLA's propagation can't
    drift it onto a replicated layout mid-loop."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(data_axis)))


def step_keys(rng, n: int) -> jnp.ndarray:
    """Per-step keys via the occupancy-independent ``fold_in(rng, i)``
    discipline (round 10): the key for step i depends only on (rng, i) — not
    on how many steps ran before or which other work shares a dispatch — so
    compiled noise == eager noise == serving-lane noise at any occupancy."""
    return jnp.stack([jax.random.fold_in(rng, i) for i in range(n)])


def _mask_blend(x, mask, keep):
    return x * mask + keep * (1.0 - mask)


# ---------------------------------------------------------------------------
# k-family scan loops (sigma-space). Each mirrors its eager twin; `denoise`
# is an EpsDenoiser built inside the jitted program.
# ---------------------------------------------------------------------------


def _scan_euler(denoise, x, sigmas, keys, post, constrain):
    def body(x, per):
        i, s, s_next = per
        x0 = denoise(x, s)
        d = (x - x0) / s
        x = x + d * (s_next - s)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:]))
    return x


def _scan_euler_ancestral(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        sigma_down, sigma_up = _ancestral(s, s_next, eta)
        d = (x - x0) / s
        x = x + d * (sigma_down - s)
        noise = jax.random.normal(key, x.shape, x.dtype)
        x = x + jnp.where(s_next > 0, sigma_up, 0.0) * noise
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_dpm_2(denoise, x, sigmas, keys, post, constrain):
    # Interior steps have s_next > 0; the final step (s_next == 0) is plain
    # Euler — epilogue, same shape discipline as _scan_heun.
    def body(x, per):
        i, s, s_next = per
        x0 = denoise(x, s)
        d = (x - x0) / s
        sigma_mid = jnp.exp(0.5 * (jnp.log(s) + jnp.log(s_next)))
        x_2 = x + d * (sigma_mid - s)
        x0_2 = denoise(x_2, sigma_mid)
        d_2 = (x_2 - x0_2) / sigma_mid
        x = x + d_2 * (s_next - s)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n - 1), sigmas[:-2], sigmas[1:-1]))
    x0 = denoise(x, sigmas[n - 1])
    d = (x - x0) / sigmas[n - 1]
    x = x + d * (sigmas[n] - sigmas[n - 1])
    return constrain(post(n - 1, x))


def _scan_dpm_2_ancestral(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    # The second-order branch sits under lax.cond, not jnp.where: the final
    # step (sigma_down == 0, Euler) must not execute — or pay for — the
    # midpoint model call its eager twin skips.
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        sd, su = _ancestral(s, s_next, eta)
        d = (x - x0) / s

        def euler_branch(x):
            return x + d * (sd - s)

        def midpoint_branch(x):
            sigma_mid = jnp.exp(0.5 * (jnp.log(s) + jnp.log(sd)))
            x_2 = x + d * (sigma_mid - s)
            x0_2 = denoise(x_2, sigma_mid)
            d_2 = (x_2 - x0_2) / sigma_mid
            return x + d_2 * (sd - s)

        x = jax.lax.cond(sd > 0, midpoint_branch, euler_branch, x)
        noise = jax.random.normal(key, x.shape, x.dtype)
        x = x + jnp.where(s_next > 0, su, 0.0) * noise
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_dpmpp_2s_ancestral(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        sd, su = _ancestral(s, s_next, eta)

        def euler_branch(x):
            d = (x - x0) / s
            return x + d * (sd - s)

        def second_branch(x):
            t, t_next = -jnp.log(s), -jnp.log(sd)
            h = t_next - t
            sigma_mid = jnp.exp(-(t + 0.5 * h))
            x_2 = (sigma_mid / s) * x - jnp.expm1(-0.5 * h) * x0
            x0_2 = denoise(x_2, sigma_mid)
            return (sd / s) * x - jnp.expm1(-h) * x0_2

        x = jax.lax.cond(sd > 0, second_branch, euler_branch, x)
        noise = jax.random.normal(key, x.shape, x.dtype)
        x = x + jnp.where(s_next > 0, su, 0.0) * noise
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_dpmpp_sde(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    r = 0.5

    def body(x, per):
        i, s, s_next, key = per
        k_mid, k_end = jax.random.split(key)
        x0 = denoise(x, s)

        def euler_branch(x):
            d = (x - x0) / s
            return x + d * (s_next - s)

        def full_branch(x):
            t, t_next = -jnp.log(s), -jnp.log(s_next)
            h = t_next - t
            sigma_mid = jnp.exp(-(t + r * h))
            fac = 1.0 / (2.0 * r)
            sd1, su1 = _ancestral(s, sigma_mid, eta)
            t_down1 = -jnp.log(jnp.maximum(sd1, 1e-10))
            x_2 = (sd1 / s) * x - jnp.expm1(t - t_down1) * x0
            x_2 = x_2 + su1 * jax.random.normal(k_mid, x.shape, x.dtype)
            x0_2 = denoise(x_2, sigma_mid)
            sd2, su2 = _ancestral(s, s_next, eta)
            t_down2 = -jnp.log(jnp.maximum(sd2, 1e-10))
            x0_blend = (1.0 - fac) * x0 + fac * x0_2
            out = (sd2 / s) * x - jnp.expm1(t - t_down2) * x0_blend
            return out + su2 * jax.random.normal(k_end, x.shape, x.dtype)

        x = jax.lax.cond(s_next > 0, full_branch, euler_branch, x)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_euler_ancestral_rf(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    # Mirrors sample_euler_ancestral_rf (rectified-flow renoise form).
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)

        def final(x):
            return x0

        def step(x):
            downstep = 1.0 + (s_next / s - 1.0) * eta
            sd = s_next * downstep
            alpha_ip1 = 1.0 - s_next
            alpha_down = 1.0 - sd
            renoise = jnp.sqrt(jnp.maximum(
                s_next**2 - sd**2 * alpha_ip1**2 / alpha_down**2, 0.0
            ))
            xx = (sd / s) * x + (1.0 - sd / s) * x0
            return (alpha_ip1 / alpha_down) * xx + renoise * jax.random.normal(
                key, x.shape, x.dtype
            )

        x = jax.lax.cond(s_next > 0, step, final, x)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_dpmpp_2s_ancestral_rf(denoise, x, sigmas, keys, post, constrain,
                                eta=1.0):
    # Mirrors sample_dpmpp_2s_ancestral_rf (flow log-SNR midpoint + RF renoise).
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        downstep = 1.0 + (s_next / s - 1.0) * eta
        sd = s_next * downstep
        a1 = 1.0 - s_next
        ad = 1.0 - sd
        renoise = jnp.sqrt(jnp.maximum(s_next**2 - sd**2 * a1**2 / ad**2, 0.0))

        def euler_branch(x):
            d = (x - x0) / s
            return x + d * (sd - s)

        def second_branch(x):
            # λ diverges at σ=1: clamp the formula's input and pin the result
            # to the host's fixed 0.9999 midpoint there (the clamped value
            # only feeds the discarded where-branch).
            s_c = jnp.minimum(s, 0.999999)
            t_i = jnp.log((1.0 - s_c) / s_c)
            t_down = jnp.log((1.0 - sd) / sd)
            sigma_mid = jnp.where(
                s >= 1.0,
                jnp.float32(0.9999),
                1.0 / (jnp.exp(t_i + 0.5 * (t_down - t_i)) + 1.0),
            )
            u = (sigma_mid / s) * x + (1.0 - sigma_mid / s) * x0
            x0_2 = denoise(u, sigma_mid)
            return (sd / s) * x + (1.0 - sd / s) * x0_2

        x = jax.lax.cond(s_next > 0, second_branch, euler_branch, x)
        noise = jax.random.normal(key, x.shape, x.dtype)
        x = jnp.where(s_next > 0, (a1 / ad) * x + renoise * noise, x)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_lcm_rf(denoise, x, sigmas, keys, post, constrain):
    # Mirrors sample_lcm_rf: flow-interpolant renoise t·n + (1−t)·x0.
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        noise = jax.random.normal(key, x.shape, x.dtype)
        renoised = s_next * noise + (1.0 - s_next) * x0
        x = jnp.where(s_next > 0, renoised, x0)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


# prediction="flow" scan-twin swaps (host CONST-dispatch parity; mirrors
# k_samplers.FLOW_VARIANTS — runner rejects FLOW_REJECT before reaching here).
SCAN_FLOW_VARIANTS = {
    "euler_ancestral": _scan_euler_ancestral_rf,
    "dpmpp_2s_ancestral": _scan_dpmpp_2s_ancestral_rf,
    "lcm": _scan_lcm_rf,
}


def _scan_heun(denoise, x, sigmas, keys, post, constrain):
    # Interior steps have s_next > 0; the final step (s_next == 0) is Euler,
    # which collapses to x = denoise(x, s) — run it as an epilogue so the scan
    # body keeps the uniform two-call shape without dividing by zero.
    def body(x, per):
        i, s, s_next = per
        x0 = denoise(x, s)
        d = (x - x0) / s
        x_pred = x + d * (s_next - s)
        x0_2 = denoise(x_pred, s_next)
        d2 = (x_pred - x0_2) / s_next
        x = x + 0.5 * (d + d2) * (s_next - s)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n - 1), sigmas[:-2], sigmas[1:-1]))
    x = denoise(x, sigmas[n - 1])
    return constrain(post(n - 1, x))


def _scan_dpmpp_2m(denoise, x, sigmas, keys, post, constrain):
    s_prev = jnp.concatenate([sigmas[:1], sigmas[:-2]])  # dummy at i==0

    def body(carry, per):
        x, old_x0 = carry
        i, s, s_next, sp = per
        x0 = denoise(x, s)
        t, t_next = -jnp.log(s), -jnp.log(jnp.maximum(s_next, 1e-10))
        h = t_next - t
        simple = (s_next / s) * x - jnp.expm1(-h) * x0
        h_last = t - (-jnp.log(sp))
        r = jnp.where(i == 0, 1.0, h_last / h)
        x0_prime = (1 + 1 / (2 * r)) * x0 - (1 / (2 * r)) * old_x0
        multi = (s_next / s) * x - jnp.expm1(-h) * x0_prime
        x = jnp.where((i == 0) | (s_next == 0.0), simple, multi)
        x = constrain(post(i, x))
        return (x, x0), None

    n = len(sigmas) - 1
    (x, _), _ = jax.lax.scan(
        body, (x, jnp.zeros_like(x)), (jnp.arange(n), sigmas[:-1], sigmas[1:], s_prev)
    )
    return x


def _scan_dpmpp_2m_sde(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    def body(carry, per):
        x, old_x0, h_last, have = carry
        i, s, s_next, key = per
        x0 = denoise(x, s)
        last = s_next == 0.0
        t, t_next = -jnp.log(s), -jnp.log(jnp.maximum(s_next, 1e-10))
        h = t_next - t
        eta_h = eta * h
        x_new = (s_next / s) * jnp.exp(-eta_h) * x + (-jnp.expm1(-h - eta_h)) * x0
        r_safe = jnp.where(have > 0, h_last / h, 1.0)
        x_new = x_new + have * (
            0.5 * (-jnp.expm1(-h - eta_h)) * (1 / r_safe) * (x0 - old_x0)
        )
        if eta > 0:
            x_new = x_new + s_next * jnp.sqrt(
                jnp.maximum(-jnp.expm1(-2 * eta_h), 0.0)
            ) * jax.random.normal(key, x.shape, x.dtype)
        x = jnp.where(last, x0, x_new)
        x = constrain(post(i, x))
        # History updates only on non-final steps (k-diffusion keeps h_last
        # untouched when s_next == 0); old_x0 updates unconditionally, matching
        # the eager loop's assignment outside the else-branch.
        return (x, x0, jnp.where(last, h_last, h), jnp.where(last, have, 1.0)), None

    n = len(sigmas) - 1
    (x, _, _, _), _ = jax.lax.scan(
        body,
        (x, jnp.zeros_like(x), jnp.float32(1.0), jnp.float32(0.0)),
        (jnp.arange(n), sigmas[:-1], sigmas[1:], keys),
    )
    return x


def _scan_dpmpp_3m_sde(denoise, x, sigmas, keys, post, constrain, eta=1.0):
    def body(carry, per):
        x, x0_1, x0_2, h_1, h_2, count = carry
        i, s, s_next, key = per
        x0 = denoise(x, s)
        last = s_next == 0.0
        t, t_next = -jnp.log(s), -jnp.log(jnp.maximum(s_next, 1e-10))
        h = t_next - t
        h_eta = h * (eta + 1.0)
        base = jnp.exp(-h_eta) * x + (-jnp.expm1(-h_eta)) * x0
        phi_2 = jnp.expm1(-h_eta) / h_eta + 1.0
        # 2nd-order correction (one history entry)
        r_2 = h_1 / h
        d_2 = (x0 - x0_1) / r_2
        second = base + phi_2 * d_2
        # 3rd-order correction (two history entries)
        r0, r1 = h_1 / h, h_2 / h
        d1_0 = (x0 - x0_1) / r0
        d1_1 = (x0_1 - x0_2) / r1
        d1 = d1_0 + (d1_0 - d1_1) * r0 / (r0 + r1)
        d2 = (d1_0 - d1_1) / (r0 + r1)
        phi_3 = phi_2 / h_eta - 0.5
        third = base + phi_2 * d1 - phi_3 * d2
        x_new = jnp.where(count >= 2, third, jnp.where(count == 1, second, base))
        if eta > 0:
            x_new = x_new + s_next * jnp.sqrt(
                jnp.maximum(-jnp.expm1(-2.0 * eta * h), 0.0)
            ) * jax.random.normal(key, x.shape, x.dtype)
        x = jnp.where(last, x0, x_new)
        x = constrain(post(i, x))
        # No history update on a zero step (eager `continue`).
        carry = (
            x,
            jnp.where(last, x0_1, x0),
            jnp.where(last, x0_2, x0_1),
            jnp.where(last, h_1, h),
            jnp.where(last, h_2, h_1),
            jnp.where(last, count, count + 1),
        )
        return carry, None

    n = len(sigmas) - 1
    z = jnp.zeros_like(x)
    (x, *_), _ = jax.lax.scan(
        body,
        (x, z, z, jnp.float32(1.0), jnp.float32(1.0), jnp.int32(0)),
        (jnp.arange(n), sigmas[:-1], sigmas[1:], keys),
    )
    return x


def _scan_lms(denoise, x, sigmas, keys, post, constrain, coeffs=None):
    # Coefficients depend only on the (concrete) schedule — precomputed on the
    # host by the entry point (sigmas is a tracer here), zero-padded per row to
    # the running order, so the scan body is a fixed-shape history contraction.
    order = coeffs.shape[1]

    def body(carry, per):
        x, hist = carry
        i, s = per
        x0 = denoise(x, s)
        d = (x - x0) / s
        hist = jnp.roll(hist, 1, axis=0).at[0].set(d)  # hist[j] = d_{i-j}
        x = x + jnp.tensordot(coeffs[i], hist, axes=([0], [0]))
        x = constrain(post(i, x))
        return (x, hist), None

    n = len(sigmas) - 1
    hist0 = jnp.zeros((order,) + x.shape, x.dtype)
    (x, _), _ = jax.lax.scan(body, (x, hist0), (jnp.arange(n), sigmas[:-1]))
    return x


def _scan_unipc(denoise, x, sigmas, keys, post, constrain, coeffs=None):
    # Variant-agnostic: the host-precomputed table (unipc_coeff_table) bakes
    # B_h/rho differences between bh1 and bh2 into the per-step rows. History
    # carry holds the last three model evaluations (zeros early — the
    # zero-padded rki/rho columns cancel them, mirroring the eager ramp-up).
    def body(carry, per):
        x, h1, h2, h3 = carry
        i, s, s_next, c = per
        hphi1, Bh, rp0, rp1, rc0, rc1, rct, rki0, rki1 = (c[k] for k in range(9))
        m0 = h1
        D1_1 = (h2 - m0) * rki0
        D1_2 = (h3 - m0) * rki1
        base = (s_next / s) * x - hphi1 * m0

        def step_branch(x):
            x_pred = base - Bh * (rp0 * D1_1 + rp1 * D1_2)
            m_t = denoise(x_pred, s_next)
            return (
                base - Bh * (rc0 * D1_1 + rc1 * D1_2 + rct * (m_t - m0)),
                m_t,
            )

        def terminal_branch(x):
            return m0, m0  # history entry is never consumed after a terminal step

        x, m_t = jax.lax.cond(s_next > 0, step_branch, terminal_branch, x)
        x = constrain(post(i, x))
        return (x, m_t, h1, h2), None

    n = len(sigmas) - 1
    m_init = denoise(x, sigmas[0])
    z = jnp.zeros_like(x)
    (x, *_), _ = jax.lax.scan(
        body, (x, m_init, z, z), (jnp.arange(n), sigmas[:-1], sigmas[1:], coeffs)
    )
    return x


def _scan_lcm(denoise, x, sigmas, keys, post, constrain):
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        noise = jax.random.normal(key, x.shape, x.dtype)
        x = x0 + jnp.where(s_next > 0, s_next, 0.0) * noise
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


def _scan_ddpm(denoise, x, sigmas, keys, post, constrain):
    def body(x, per):
        i, s, s_next, key = per
        x0 = denoise(x, s)
        eps = (x - x0) / s
        acp = 1.0 / (s**2 + 1.0)
        acp_prev = 1.0 / (s_next**2 + 1.0)
        alpha = acp / acp_prev
        x_a = x / jnp.sqrt(1.0 + s**2)
        mu = jnp.sqrt(1.0 / alpha) * (
            x_a - (1.0 - alpha) * eps / jnp.sqrt(1.0 - acp)
        )
        var = (1.0 - alpha) * (1.0 - acp_prev) / jnp.maximum(1.0 - acp, 1e-12)
        noisy = (
            mu + jnp.sqrt(jnp.maximum(var, 0.0))
            * jax.random.normal(key, x.shape, x.dtype)
        ) * jnp.sqrt(1.0 + s_next**2)
        x = jnp.where(s_next > 0, noisy, mu)
        return constrain(post(i, x)), None

    n = len(sigmas) - 1
    x, _ = jax.lax.scan(body, x, (jnp.arange(n), sigmas[:-1], sigmas[1:], keys))
    return x


SCAN_SAMPLERS = {
    "euler": _scan_euler,
    "euler_ancestral": _scan_euler_ancestral,
    "heun": _scan_heun,
    "dpm_2": _scan_dpm_2,
    "dpm_2_ancestral": _scan_dpm_2_ancestral,
    "lms": _scan_lms,
    "dpmpp_2s_ancestral": _scan_dpmpp_2s_ancestral,
    "dpmpp_sde": _scan_dpmpp_sde,
    "dpmpp_2m": _scan_dpmpp_2m,
    "dpmpp_2m_sde": _scan_dpmpp_2m_sde,
    "dpmpp_3m_sde": _scan_dpmpp_3m_sde,
    "lcm": _scan_lcm,
    "ddpm": _scan_ddpm,
    "uni_pc": _scan_unipc,
    "uni_pc_bh2": _scan_unipc,
}

# Samplers whose scan body consumes a host-precomputed schedule-derived table
# (built in compiled_k_sample; sigmas is a tracer inside the loop program).
_AUX_SAMPLERS = ("lms", "uni_pc", "uni_pc_bh2")


# ---------------------------------------------------------------------------
# the jitted loop programs. Unhashable static kwargs follow the orchestrator's
# pattern (orchestrator.py _jit_for): bake them into a closure and cache the
# jitted closure by static_kwargs_key, so repeated run_sampler calls with the
# same shapes/config hit the compile cache instead of re-tracing.
# ---------------------------------------------------------------------------

_loop_jits: dict[tuple, Callable] = {}
# Bounded FIFO: entries hold the spec's apply fn (strongly) and a compiled
# executable — a long-lived host cycling through many models must not grow
# without limit. aggressive_cleanup(clear_compile_cache=True) (the teardown /
# purge_cache path) empties it entirely via clear_compiled_loops().
_LOOP_CACHE_MAX = 32


def clear_compiled_loops() -> None:
    """Drop every cached loop program (called from aggressive_cleanup on the
    purge/teardown path, so ParallelModel.cleanup() reaches this cache too)."""
    _loop_jits.clear()


def _donate_for(spec: TraceSpec) -> bool:
    """Donate the input latent only off-CPU — the CPU backend doesn't implement
    donation and would warn on every call."""
    if spec.mesh is not None:
        return spec.mesh.devices.flat[0].platform != "cpu"
    leaves = jax.tree.leaves(spec.params)
    if leaves and hasattr(leaves[0], "devices"):
        return next(iter(leaves[0].devices())).platform != "cpu"
    return jax.default_backend() != "cpu"


def _get_loop_jit(kind: str, spec: TraceSpec, static: dict, meta: tuple, build,
                  donate: tuple = (1,)):
    """Cache key mirrors the repo's jit-cache discipline: the ambient
    sequence_parallel context is read at trace time inside ops.attention, so it
    must key the cache (ops/attention.py contract; orchestrator._jit_for does
    the same). ``build`` must close over (apply, mesh, data_axis) only — NOT
    the params pytree — so params always arrive as the first call argument
    (a bare callable's apply may still close over its own weights, which is why
    the cache is bounded and clearable above)."""
    from ..ops.attention import sequence_ctx_key
    from ..parallel.split import static_kwargs_key
    from ..utils.telemetry import instrument_jit

    key = (kind, spec.apply, static_kwargs_key(static), meta, spec.mesh,
           spec.data_axis, sequence_ctx_key())
    fn = _loop_jits.get(key)
    if fn is None:
        while len(_loop_jits) >= _LOOP_CACHE_MAX:
            _loop_jits.pop(next(iter(_loop_jits)))
        impl = build(dict(static))
        donate = donate if _donate_for(spec) else ()
        # Compile accounting (utils/telemetry.py): the k-family bakes the
        # sampler name into the program label; the other kinds are
        # one-program-per-kind.
        prog = f"loop:{kind}:{meta[0]}" if kind == "k" else f"loop:{kind}"
        jitted = instrument_jit(impl, prog, donate_argnums=donate)
        if spec.mesh is None:
            fn = jitted
        else:
            from ..parallel.mesh import mesh_context

            @functools.wraps(jitted)
            def fn(*args, **kwargs):
                # Partitioned over spec.mesh: must be called under it
                # (parallel/mesh.mesh_context).
                with mesh_context(spec.mesh):
                    return jitted(*args, **kwargs)

        _loop_jits[key] = fn
    return fn


def _donation_safe(x, *others):
    """A donated buffer must not alias another argument: ddim/flow at
    denoise=1.0 pass the same array as both the latent and the mask-noise
    reference. Copy the latent when aliased."""
    if any(o is x for o in others):
        return jnp.copy(x)
    return x


def _model_fn(apply, params, static_kwargs):
    def fn(x, t, context=None, **kwargs):
        return apply(params, x, t, context, **kwargs, **static_kwargs)

    return fn


def _post_from(mask, keep_at):
    if mask is None:
        return lambda i, x: x
    return lambda i, x: _mask_blend(x, mask, keep_at(i))


def _emit_numerics(out, emit: bool):
    """Attach the sentinel's aux outputs (utils/numerics.py) to a loop
    program's result inside the jitted body: final-latent stats vector +
    bf16 digest — computed on-device, read by the caller at a boundary that
    syncs anyway (the loop's own completion)."""
    if not emit:
        return out
    return out, numerics.array_stats(out), numerics.digest(out)


def _collect_numerics(out, emit: bool, program: str):
    """Unpack a loop program's numerics aux outputs and feed the sentinel:
    a non-finite final latent records an event (counter + last-event + trace
    span), and the digest lands in the bounded fingerprint ring. No-op (and
    no host pull) when the sentinel was off at trace time."""
    if not emit:
        return out
    out, stats, dig = out
    s = np.asarray(stats)
    if s[0] > 0:
        numerics.sentinel.record_event(
            "compiled-loop", program=program, **numerics.stats_to_dict(s)
        )
    numerics.sentinel.record_fingerprints(
        where=program, digests=[int(np.asarray(dig))]
    )
    return out


# ---------------------------------------------------------------------------
# entry points (called by sampling.runner when compile_loop=True)
# ---------------------------------------------------------------------------


def _prep(spec: TraceSpec, batch: int, trees: list):
    """Pad the batch to the data-axis width and place every input tree; returns
    (placed_trees, padded)."""
    if spec.mesh is not None:
        n = spec.mesh.shape[spec.data_axis]
    else:
        n = 1
    padded = batch + ((-batch) % n)
    return [
        _place_batch(t, batch, padded, spec.mesh, spec.data_axis) for t in trees
    ], padded


def compiled_k_sample(
    spec: TraceSpec, sampler: str, x, sigmas, context, *,
    cfg_scale, uncond_context, uncond_kwargs, acp, prediction, cfg_rescale,
    rng=None, mask=None, mask_init=None, mask_noise=None, model_kwargs=None,
):
    from ..parallel.split import partition_kwargs

    batch = x.shape[0]
    traced, static = partition_kwargs(model_kwargs or {})
    # Static (non-array) uncond kwargs are ignored: double_kwargs only swaps
    # batch-dim arrays into the uncond half, same as the eager denoiser.
    u_traced, _ = partition_kwargs(uncond_kwargs or {})
    keys = (
        step_keys(jax.random.fold_in(rng, 1), len(sigmas) - 1)
        if sampler in RNG_SAMPLERS
        else None
    )
    # Schedule-derived coefficient tables are integrated here from the
    # concrete sigmas (they are tracers inside the loop program).
    if sampler == "lms":
        aux = jnp.asarray(lms_coefficient_matrix(np.asarray(sigmas)), x.dtype)
    elif sampler in ("uni_pc", "uni_pc_bh2"):
        aux = jnp.asarray(
            unipc_coeff_table(
                np.asarray(sigmas),
                variant="bh2" if sampler.endswith("bh2") else "bh1",
            ),
            x.dtype,
        )
    else:
        aux = None
    x = _donation_safe(x, mask_noise, mask_init)
    placed, padded = _prep(
        spec, batch,
        [x, context, uncond_context, traced, u_traced, mask, mask_init, mask_noise],
    )
    x, context, uncond_context, traced, u_traced, mask, mask_init, mask_noise = placed
    # The sentinel flag is part of the program signature (stats/digest aux
    # outputs), so it keys the jit cache via meta — toggling it re-traces
    # instead of silently returning the wrong tuple shape.
    emit = numerics.on()
    meta = (sampler, float(cfg_scale), float(cfg_rescale), prediction, emit)
    apply_fn, mesh, axis = spec.apply, spec.mesh, spec.data_axis

    def build(bound_static):
        def impl(params, x, sigmas, keys, aux, context, uncond_context, kwargs,
                 u_kwargs, acp, mask, mask_init, mask_noise):
            denoise = EpsDenoiser(
                _model_fn(apply_fn, params, bound_static), context,
                cfg_scale=meta[1], uncond_context=uncond_context,
                uncond_kwargs=u_kwargs, alphas_cumprod=acp,
                prediction=meta[3], cfg_rescale=meta[2], **kwargs,
            )
            if meta[3] == "flow":
                # Flow forward process: keep-region re-pinned to
                # (1−t)·init + t·noise at each step's flow time.
                post = _post_from(
                    mask,
                    lambda i: (1.0 - sigmas[i + 1]) * mask_init
                    + sigmas[i + 1] * mask_noise,
                )
            else:
                post = _post_from(
                    mask, lambda i: mask_init + mask_noise * sigmas[i + 1]
                )
            constrain = lambda v: _constrain(v, mesh, axis)  # noqa: E731
            sampler_fn = SCAN_SAMPLERS[meta[0]]
            if meta[3] == "flow":
                sampler_fn = SCAN_FLOW_VARIANTS.get(meta[0], sampler_fn)
            if meta[0] in _AUX_SAMPLERS:
                out = sampler_fn(denoise, x, sigmas, keys, post, constrain,
                                 coeffs=aux)
            else:
                out = sampler_fn(denoise, x, sigmas, keys, post, constrain)
            return _emit_numerics(out, emit)

        return impl

    fn = _get_loop_jit("k", spec, static, meta, build)
    out = fn(
        spec.params, x, sigmas, keys, aux, context, uncond_context, traced,
        u_traced or None, acp, mask, mask_init, mask_noise,
    )
    out = _collect_numerics(out, emit, f"loop:k:{sampler}")
    return _slice_padded(out, batch, padded)


def compiled_ddim_sample(
    spec: TraceSpec, x, ts, acp, context, *,
    cfg_scale, uncond_context, uncond_kwargs, prediction, cfg_rescale,
    mask=None, mask_init=None, mask_noise=None, model_kwargs=None,
):
    from ..parallel.split import partition_kwargs

    batch_orig = x.shape[0]
    traced, static = partition_kwargs(model_kwargs or {})
    u_traced, _ = partition_kwargs(uncond_kwargs or {})
    a_t = acp[ts]
    a_prev = jnp.concatenate([acp[ts[1:]], jnp.ones((1,), acp.dtype)])
    x = _donation_safe(x, mask_noise, mask_init)
    placed, padded = _prep(
        spec, batch_orig,
        [x, context, uncond_context, traced, u_traced, mask, mask_init, mask_noise],
    )
    x, context, uncond_context, traced, u_traced, mask, mask_init, mask_noise = placed
    emit = numerics.on()
    meta = (float(cfg_scale), float(cfg_rescale), prediction, emit)
    apply_fn, mesh, axis = spec.apply, spec.mesh, spec.data_axis

    def build(bound_static):
        def impl(params, x, ts, a_t, a_prev, context, uncond_context, kwargs,
                 u_kwargs, mask, mask_init, mask_noise):
            model = _model_fn(apply_fn, params, bound_static)
            cfg_scale_, cfg_rescale_, prediction_ = meta[:3]
            batch = x.shape[0]
            use_cfg = cfg_scale_ != 1.0 and uncond_context is not None
            post = _post_from(
                mask,
                lambda i: jnp.sqrt(a_prev[i]) * mask_init
                + jnp.sqrt(1.0 - a_prev[i]) * mask_noise,
            )

            def body(x, per):
                i, t, at, aprev = per
                t_vec = jnp.full((batch,), t, jnp.float32)
                if use_cfg:
                    kw = double_kwargs(kwargs, u_kwargs, batch)
                    out_both = model(
                        jnp.concatenate([x, x], axis=0),
                        jnp.concatenate([t_vec, t_vec], axis=0),
                        jnp.concatenate([context, uncond_context], axis=0),
                        **kw,
                    )
                    out_c, out_u = jnp.split(out_both, 2, axis=0)
                    out = out_u + cfg_scale_ * (out_c - out_u)
                    out = rescale_guidance(out, out_c, cfg_rescale_)
                else:
                    out = model(x, t_vec, context, **kwargs)
                if prediction_ == "v":
                    x0 = jnp.sqrt(at) * x - jnp.sqrt(1.0 - at) * out
                    eps = (x - jnp.sqrt(at) * x0) / jnp.sqrt(1.0 - at)
                else:
                    eps = out
                    x0 = (x - jnp.sqrt(1.0 - at) * eps) / jnp.sqrt(at)
                x = jnp.sqrt(aprev) * x0 + jnp.sqrt(1.0 - aprev) * eps
                return _constrain(post(i, x), mesh, axis), None

            n = len(ts)
            x, _ = jax.lax.scan(body, x, (jnp.arange(n), ts, a_t, a_prev))
            return _emit_numerics(x, emit)

        return impl

    fn = _get_loop_jit("ddim", spec, static, meta, build)
    out = fn(
        spec.params, x, ts, a_t, a_prev, context, uncond_context, traced,
        u_traced or None, mask, mask_init, mask_noise,
    )
    out = _collect_numerics(out, emit, "loop:ddim")
    return _slice_padded(out, batch_orig, padded)


def compiled_flow_sample(
    spec: TraceSpec, x, ts, context, *,
    cfg_scale, uncond_context, uncond_kwargs, guidance, cfg_rescale,
    mask=None, mask_init=None, mask_noise=None, model_kwargs=None,
):
    from ..parallel.split import partition_kwargs

    batch_orig = x.shape[0]
    traced, static = partition_kwargs(model_kwargs or {})
    u_traced, _ = partition_kwargs(uncond_kwargs or {})
    x = _donation_safe(x, mask_noise, mask_init)
    placed, padded = _prep(
        spec, batch_orig,
        [x, context, uncond_context, traced, u_traced, mask, mask_init, mask_noise],
    )
    x, context, uncond_context, traced, u_traced, mask, mask_init, mask_noise = placed
    emit = numerics.on()
    meta = (
        float(cfg_scale), float(cfg_rescale),
        None if guidance is None else float(guidance), emit,
    )
    apply_fn, mesh, axis = spec.apply, spec.mesh, spec.data_axis

    def build(bound_static):
        def impl(params, x, ts, context, uncond_context, kwargs, u_kwargs,
                 mask, mask_init, mask_noise):
            model = _model_fn(apply_fn, params, bound_static)
            cfg_scale_, cfg_rescale_, guidance_ = meta[:3]
            batch = x.shape[0]
            use_cfg = cfg_scale_ != 1.0 and uncond_context is not None
            kw = dict(kwargs)
            if guidance_ is not None:
                kw["guidance"] = jnp.full((batch,), guidance_, jnp.float32)
            post = _post_from(
                mask,
                lambda i: (1.0 - ts[i + 1]) * mask_init + ts[i + 1] * mask_noise,
            )

            def body(x, per):
                i, t, t_next = per
                t_vec = jnp.full((batch,), t, jnp.float32)
                if use_cfg:
                    kw2 = double_kwargs(kw, u_kwargs, batch)
                    v_both = model(
                        jnp.concatenate([x, x], axis=0),
                        jnp.concatenate([t_vec, t_vec], axis=0),
                        jnp.concatenate([context, uncond_context], axis=0),
                        **kw2,
                    )
                    v_c, v_u = jnp.split(v_both, 2, axis=0)
                    v = v_u + cfg_scale_ * (v_c - v_u)
                    v = rescale_guidance(v, v_c, cfg_rescale_)
                else:
                    v = model(x, t_vec, context, **kw)
                x = x + (t_next - t) * v
                return _constrain(post(i, x), mesh, axis), None

            n = len(ts) - 1
            x, _ = jax.lax.scan(body, x, (jnp.arange(n), ts[:-1], ts[1:]))
            return _emit_numerics(x, emit)

        return impl

    fn = _get_loop_jit("flow", spec, static, meta, build)
    out = fn(
        spec.params, x, ts, context, uncond_context, traced, u_traced or None,
        mask, mask_init, mask_noise,
    )
    out = _collect_numerics(out, emit, "loop:flow")
    return _slice_padded(out, batch_orig, padded)


# ---------------------------------------------------------------------------
# per-lane batched step (round 7, generalized round 10, serving/): ONE
# compiled dispatch advances a fixed-width batch of lanes, each carrying its
# OWN (sigma, state, sampler) — the step-boundary seam continuous batching
# joins and leaves at. The model eval (the only FLOPs that matter) is shared;
# each lane's sampler update is the host-precomputed linear combination its
# LaneStepSpec emitted (sampling/lane_specs.py), so lanes running DIFFERENT
# samplers — including two-eval and stochastic families — ride one dispatch.
# Padded/retired lanes are masked with jnp.where (a select, so a junk
# pad-lane value can never leak into a live lane — per-sample independence of
# the model does the rest).
# ---------------------------------------------------------------------------


def lane_step_program(
    spec: TraceSpec, *, prediction: str, use_cfg: bool, cfg_rescale: float,
    static_kwargs: dict, emit_stats: bool = False, broadcast_cond: bool = False,
    broadcast_kwargs: bool = False, n_extra: int | None = None,
    mc_has_y: bool = False, control_apply=None, lora_sig: tuple = (),
):
    """The jitted per-step program for one serving bucket (W = lane width,
    b = per-request batch):

    ``fn(params, x[W,b,...], xe[W,b,...], h1[W,b,...], h2[W,b,...],
    sigma_eval[W], active[W] f32, cfg_scale[W], coef[W,4,6] f32,
    noise_keys[W,2] u32, context[W,b,L,D]|None, uncond_context|None, kwargs,
    u_kwargs, log_sigmas|None, mask[W,b,...], mask_init[W,b,...],
    mask_noise[W,b,...], mask_mix[W,3], [capability overlays...])
    -> (x', xe', h1', h2')``

    One batched model eval at per-lane ``(xe, sigma_eval)`` — the σ→timestep
    log-interp, 1/√(σ²+1) input scaling, and CFG mix (per-lane cfg_scale) all
    broadcast over the lane axis — produces the denoised estimate ``x0``;
    then every state slot updates as the ``coef``-weighted combination of
    ``(x, xe, x0, h1, h2, noise)``. ``noise`` is one per-lane draw from the
    lane's own key (threefry key data, occupancy-independent by the fold_in
    discipline), so stochastic lanes are bit-identical alone or co-batched.
    The sampler never appears in the program: traffic-mix changes can't
    recompile. Inactive lanes get sigma pinned to 1.0 (no divide-by-zero),
    identity coefficients, and a where-select pass-through. Cached via the
    loop-jit cache (bounded, clearable); all four state stacks are donated.

    ``emit_stats`` (the numerics sentinel, utils/numerics.py) appends two aux
    outputs — per-lane ``[W, 4]`` stats (non-finite count over x'∪xe', then
    max|x'|/mean/rms) and per-lane bf16 digests ``[W]`` — computed on-device
    inside the same dispatch, and keeps ``xe`` UNdonated so the quarantine
    path can re-run the failing eval input through the model's PipelineSpec
    stages after the fact.

    ``broadcast_cond`` (round 17, sibling-seed cond sharing): ``context`` /
    ``uncond_context`` arrive as ONE per-request tensor ``[b, L, D]``
    referenced by every lane — broadcast over the lane axis inside the
    program instead of stacked per-lane on the host. An N-seed fanout of one
    prompt then costs one cond tensor in HBM (not W copies) and zero
    per-lane cond transfers at seat time. Bit-discipline: the broadcast
    materializes the IDENTICAL ``[n, L, D]`` values the stacked path
    reshapes to, so everything downstream of the flatten is the same
    program graph on the same values (tests pin broadcast-vs-stacked
    equality bitwise on CPU).

    ``broadcast_kwargs`` (PR 12 remainder): the TRACED kwargs trees —
    ``kwargs`` / ``u_kwargs`` (pooled ``y`` vectors, per-request
    ``guidance``, the negative-prompt/uncond extras) — arrive as ONE
    per-request tree referenced by every lane and broadcast over the lane
    axis inside the program, exactly like ``broadcast_cond`` above. A
    sibling-seed fanout then stops stacking identical uncond rows too:
    same values, same downstream graph as the stacked variant (the flatten
    sees the identical ``[n, ...]`` tree either way).

    Capability axes (round 16, universal lane batching). Every feature that
    used to force inline fallback is per-lane STATE here, so a mixed queue
    shares the one dispatch:

    - **denoise mask** (img2img/inpaint) — always-on inputs ``mask`` /
      ``mask_init`` / ``mask_noise`` ``[W, b, ...]`` plus a per-dispatch
      ``mask_mix[W, 3]`` of ``(gate, keep_a, keep_b)`` host scalars. On
      σ-interval completion the lane's x'/xe' re-pin the keep region to
      ``keep_a·init + keep_b·noise`` (the eager masked_callback formula per
      prediction family); zero-gate lanes are a where-select pass-through, so
      plain txt2img lanes ride the SAME program — no variant, no recompile,
      bitwise across any traffic mix.
    - **multi-cond CFG** (``n_extra`` = the bucket's max extra-cond count K) —
      K extra eval row-blocks share the model call; per-lane weight maps
      ``mc_w0``/``mc_w`` (area/mask/strength composed host-side at seat,
      zero for non-users) and traced per-extra progress windows ``mc_win``
      reproduce EpsDenoiser._combine_conds op-for-op, with zero-weight lanes
      falling through to their own eps bitwise (den == 0 → primary).
    - **ControlNet** (``control_apply``) — the control trunk joins the shared
      eval over ALL rows with a per-lane hint stack and traced per-lane
      ``(strength, window)``; residuals scale by the apply_control gate and
      feed the base model's ``control`` kwarg. Zero-strength lanes get exact
      zero residual trees (additive no-op on values).
    - **per-lane LoRA** (``lora_sig`` = ordered ``(path, m, k)`` targets) —
      A/B factors arrive stacked on the lane axis (rank-padded to the
      bucket's max; zero factors → bitwise-identity delta) and the eval
      re-groups rows lane-major and vmaps the model with per-lane merged
      target leaves ``W + b @ a`` — the Punica/S-LoRA batched-adapter
      formulation, so any LoRA mix shares one compiled program.

    Each overlay is a cached program VARIANT (same bounded loop-jit cache the
    PR 12 shared→stacked demotion uses): materializing a capability the
    bucket epoch hasn't seen compiles once; traffic mix within a capability
    set never recompiles. Cross-variant legs are allclose-at-bf16, same-
    program legs stay bitwise (the serving equivalence matrix pins both)."""
    lora_sig = tuple(tuple(t) for t in lora_sig)
    meta = ("serve", prediction, bool(use_cfg), float(cfg_rescale),
            bool(emit_stats), bool(broadcast_cond), bool(broadcast_kwargs),
            None if n_extra is None else int(n_extra), bool(mc_has_y),
            control_apply, lora_sig)
    use_mc = n_extra is not None
    K = int(n_extra or 0)
    use_control = control_apply is not None
    apply_fn, mesh, axis = spec.apply, spec.mesh, spec.data_axis

    def build(bound_static):
        def impl(params, x, xe, h1, h2, sigma_eval, active, cfg_scale, coef,
                 noise_keys, context, uncond_context, kwargs, u_kwargs,
                 log_sigmas, mask, mask_init, mask_noise, mask_mix,
                 mc_w0=None, mc_ctx=None, mc_w=None, mc_win=None, mc_y=None,
                 ctrl_params=None, ctrl_hint=None, ctrl_strength=None,
                 ctrl_win=None, lora_ab=()):
            model = _model_fn(apply_fn, params, bound_static)
            W, b = x.shape[0], x.shape[1]
            n = W * b

            def flatten(tree):
                return jax.tree.map(
                    lambda l: l.reshape((n,) + l.shape[2:]), tree
                )

            def bcast(v, ndim):
                return v.reshape(v.shape + (1,) * (ndim - 1))

            lane = lambda v: jnp.repeat(v, b, total_repeat_length=n)  # noqa: E731
            if broadcast_cond:
                # Shared-cond lanes: one [b, ...] tensor broadcast to the
                # [W, b, ...] stack the flatten below expects — same values,
                # same downstream graph as the stacked variant.
                if context is not None:
                    context = jnp.broadcast_to(
                        context[None], (W,) + context.shape
                    )
                if uncond_context is not None:
                    uncond_context = jnp.broadcast_to(
                        uncond_context[None], (W,) + uncond_context.shape
                    )
            if broadcast_kwargs:
                # Shared traced kwargs (the PR 12 remainder): one [b, ...]
                # tree per request, broadcast over the lane axis — the
                # uncond/negative-prompt extras stop stacking too.
                bc = lambda l: jnp.broadcast_to(l[None], (W,) + l.shape)  # noqa: E731
                if kwargs:
                    kwargs = jax.tree.map(bc, kwargs)
                if u_kwargs:
                    u_kwargs = jax.tree.map(bc, u_kwargs)
            flat = xe.reshape((n,) + xe.shape[2:])
            s = jnp.where(active > 0, sigma_eval, jnp.float32(1.0))
            s_flat = lane(s)
            if prediction == "flow":
                # Flow time IS the sigma (EpsDenoiser flow branch).
                t_vec = s_flat
                x_in = flat
                scale_flat = None
            else:
                scale_flat = 1.0 / jnp.sqrt(s_flat**2 + 1.0)
                t_vec = jnp.interp(
                    jnp.log(s_flat), log_sigmas,
                    jnp.arange(log_sigmas.shape[0], dtype=jnp.float32),
                )
                x_in = flat * bcast(scale_flat, flat.ndim)
            ctx = None if context is None else flatten(context)
            kw = flatten(kwargs) if kwargs else {}

            # --- role blocks: [cond | uncond? | extra_0 .. extra_{K-1}],
            # each n rows of the ONE shared eval. Inline calls the model once
            # per extra (token lengths may differ there); bucket eligibility
            # pins extras to the primary's (L, D), so here they batch.
            roles_ctx = [ctx]
            roles_kw = [kw]
            if use_cfg:
                u_kw = flatten(u_kwargs) if u_kwargs else {}
                extra_keys = set(u_kw) - set(kw)
                if extra_keys:
                    raise ValueError(
                        f"uncond kwargs carry keys absent from cond kwargs: "
                        f"{sorted(extra_keys)}"
                    )
                roles_ctx.append(flatten(uncond_context))
                roles_kw.append({**kw, **u_kw})
            for k_i in range(K):
                roles_ctx.append(
                    mc_ctx[:, k_i].reshape((n,) + mc_ctx.shape[3:])
                )
                kw_e = dict(kw)
                if mc_has_y:
                    kw_e["y"] = mc_y[:, k_i].reshape((n,) + mc_y.shape[3:])
                roles_kw.append(kw_e)
            R = len(roles_kw)

            if use_control:
                hint_flat = ctrl_hint.reshape((n,) + ctrl_hint.shape[2:])
                # apply_control's gate, per lane: strength × progress window
                # (ops.basic.progress_window_gate with traced bounds; the
                # default (0, 1) window is exactly 1.0, matching the inline
                # no-window fast path bitwise). apply_control keeps the
                # eps/v linear-in-t approximation for every family.
                prog_c = 1.0 - t_vec / 999.0
                on = (prog_c >= lane(ctrl_win[:, 0])) & (
                    prog_c <= lane(ctrl_win[:, 1])
                )
                gain_flat = lane(ctrl_strength) * on.astype(jnp.float32)

            if lora_sig:
                # Lane-major layout: rows grouped per lane [W, R·b, ...] and
                # the model vmapped over lanes with per-lane merged LoRA
                # target leaves (W_eff = W + b @ a; zero-padded factors give
                # a bitwise-zero delta for LoRA-free lanes / rank slots).
                from ..models.lora import get_path as _getp, set_path as _setp

                group = lambda r_: r_.reshape((W, b) + r_.shape[1:])  # noqa: E731
                cat1 = lambda rs: jnp.concatenate(rs, axis=1)  # noqa: E731
                x_l = cat1([group(x_in)] * R)
                t_l = cat1([group(t_vec)] * R)
                ctx_l = (
                    None if ctx is None
                    else cat1([group(r_) for r_ in roles_ctx])
                )
                kw_l = {
                    k_: cat1([group(r_[k_]) for r_ in roles_kw])
                    for k_ in kw
                }
                hint_l = (
                    cat1([group(hint_flat)] * R) if use_control else None
                )
                gain_l = (
                    cat1([group(gain_flat)] * R) if use_control else None
                )

                def one_lane(ab, xr, tr, cr, kwr, hr, gr):
                    p = params
                    for (path, _m, _k), (a_, b_) in zip(lora_sig, ab):
                        w_ = _getp(p, path)
                        # nd targets: the factors address the
                        # (shape[0], prod(rest)) flattening (models/lora.py).
                        p = _setp(p, path, w_ + (b_ @ a_)
                                  .reshape(w_.shape).astype(w_.dtype))
                    call_kw = dict(kwr)
                    if use_control:
                        ctrl = control_apply(
                            ctrl_params, xr, tr, cr, hint=hr,
                            y=kwr.get("y"),
                        )
                        ctrl = jax.tree.map(
                            lambda r_: r_ * bcast(gr, r_.ndim), ctrl
                        )
                        call_kw["control"] = ctrl
                    return apply_fn(p, xr, tr, cr, **call_kw, **bound_static)

                out_l = jax.vmap(
                    one_lane,
                    in_axes=(0, 0, 0, None if ctx_l is None else 0, 0,
                             None if hint_l is None else 0,
                             None if gain_l is None else 0),
                )(lora_ab, x_l, t_l, ctx_l, kw_l, hint_l, gain_l)
                outs = [
                    r_.reshape((n,) + r_.shape[2:])
                    for r_ in jnp.split(out_l, R, axis=1)
                ]
            else:
                x_all = jnp.concatenate([x_in] * R, axis=0)
                t_all = jnp.concatenate([t_vec] * R, axis=0)
                ctx_all = (
                    None if ctx is None
                    else jnp.concatenate(roles_ctx, axis=0)
                )
                kw_all = {
                    k_: jnp.concatenate([r_[k_] for r_ in roles_kw], axis=0)
                    for k_ in kw
                }
                if use_control:
                    hint_all = jnp.concatenate([hint_flat] * R, axis=0)
                    gain_all = jnp.concatenate([gain_flat] * R, axis=0)
                    ctrl = control_apply(
                        ctrl_params, x_all, t_all, ctx_all, hint=hint_all,
                        y=kw_all.get("y"),
                    )
                    kw_all["control"] = jax.tree.map(
                        lambda r_: r_ * bcast(gain_all, r_.ndim), ctrl
                    )
                out = model(x_all, t_all, ctx_all, **kw_all)
                outs = (
                    jnp.split(out, R, axis=0) if R > 1 else [out]
                )

            eps_c = outs[0]
            if use_mc:
                # EpsDenoiser._combine_conds, lane-batched: per-lane weight
                # maps (strength/area/mask composed at seat, full [W, b, ...]
                # per-sample stacks) flatten like the state; zero-map lanes
                # give den == 0 → the primary eps passes through bitwise.
                m0_rows = mc_w0.reshape((n,) + mc_w0.shape[2:])
                num = m0_rows * eps_c
                den = m0_rows * jnp.ones_like(eps_c[..., :1])
                flow_t = prediction == "flow"
                prog_m = 1.0 - (t_vec if flow_t else t_vec / 999.0)
                for k_i in range(K):
                    eps_e = outs[1 + (1 if use_cfg else 0) + k_i]
                    g = (
                        (prog_m >= lane(mc_win[:, k_i, 0]))
                        & (prog_m <= lane(mc_win[:, k_i, 1]))
                    ).astype(jnp.float32)
                    m_k = mc_w[:, k_i].reshape(
                        (n,) + mc_w.shape[3:]
                    ) * g.reshape((-1,) + (1,) * (eps_e.ndim - 1))
                    num = num + m_k * eps_e
                    den = den + m_k * jnp.ones_like(eps_e[..., :1])
                eps_c = jnp.where(den > 0, num / jnp.maximum(den, 1e-8), eps_c)
            if use_cfg:
                eps_u = outs[1]
                cfg_flat = bcast(lane(cfg_scale), eps_c.ndim)
                eps = eps_u + cfg_flat * (eps_c - eps_u)
                eps = rescale_guidance(eps, eps_c, float(cfg_rescale))
            else:
                eps = eps_c
            if prediction == "v":
                x0_flat = (
                    flat / bcast(s_flat**2 + 1.0, flat.ndim)
                    - eps * bcast(s_flat * scale_flat, flat.ndim)
                )
            else:
                # eps: x0 = x − σ·eps. flow: x0 = x − σ·v — the same expression.
                x0_flat = flat - bcast(s_flat, flat.ndim) * eps
            x0 = x0_flat.reshape(x.shape)
            # Per-lane noise from per-lane key data: vmapped normal over lane
            # keys == each lane's solo normal(key, (b, ...)) draw, bitwise.
            noise = jax.vmap(
                lambda k: jax.random.normal(
                    jax.random.wrap_key_data(k), x.shape[1:], x.dtype
                )
            )(noise_keys)
            basis = (x, xe, x0, h1, h2, noise)

            def mix(j):
                acc = None
                for k, base in enumerate(basis):
                    term = bcast(coef[:, j, k], x.ndim) * base
                    acc = term if acc is None else acc + term
                return acc.astype(x.dtype)

            live = bcast(active > 0, x.ndim)
            new = tuple(
                _constrain(jnp.where(live, mix(j), old), mesh, axis)
                for j, old in enumerate((x, xe, h1, h2))
            )
            # Denoise-mask re-pin (always-on capability axis): on σ-interval
            # completion a masked lane's x'/xe' keep region re-pins to
            # keep_a·init + keep_b·noise — the eager masked_callback blend,
            # gated per lane by the host-computed mask_mix so maskless lanes
            # are a structural where-pass-through (histories untouched,
            # matching the inline path where the blend is a post-step
            # callback that never sees sampler history).
            m_gate = bcast(mask_mix[:, 0] > 0, x.ndim)
            keep = (
                bcast(mask_mix[:, 1], x.ndim) * mask_init
                + bcast(mask_mix[:, 2], x.ndim) * mask_noise
            )
            blend = lambda v: (  # noqa: E731
                _mask_blend(v, mask, keep)
            ).astype(x.dtype)
            new = (
                _constrain(jnp.where(m_gate, blend(new[0]), new[0]), mesh, axis),
                _constrain(jnp.where(m_gate, blend(new[1]), new[1]), mesh, axis),
                new[2], new[3],
            )
            if not emit_stats:
                return new
            # Per-lane stats (xe' folded into the non-finite count: a NaN a
            # two-eval sampler parks mid-step is caught at THIS dispatch) and
            # lane-local digests — tiny reductions riding the same program.
            return new + (
                numerics.lane_stats(new[0], extra=new[1]),
                numerics.lane_digest(new[0]),
            )

        return impl

    return _get_loop_jit("serve", spec, static_kwargs, meta, build,
                         donate=(1, 3, 4) if emit_stats else (1, 2, 3, 4))
