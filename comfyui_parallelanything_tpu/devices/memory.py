"""Device memory introspection — the HBM analogue of get_free_vram.

Reference (any_device_parallel.py:724-735): free MB on a CUDA device via
``total_memory - memory_allocated``, and 0 for any non-CUDA device. Here the probe reads
``jax.Device.memory_stats()`` (``bytes_limit`` / ``bytes_in_use``), returning 0 for
devices that expose no stats (host CPU), so CPU-only chains fall back to pure
user weights exactly like the reference (any_device_parallel.py:738-739).

Beyond the reference: ``ResidencyTracker`` — live-buffer accounting for the
weight-streaming executor (parallel/streaming.py). The streamed path's whole
contract is a bound on device-resident weight bytes (≈ 2 stages + activations);
the tracker records every stage placement/retirement so tests can assert that
bound off-hardware, where ``memory_stats()`` reports nothing.

Telemetry surface (round 9): ``device_memory_stats`` / ``memory_snapshot`` /
``publish_memory_gauges`` feed the ``pa_hbm_*`` gauges, ``GET /health``, and
the perf ledger's ``peak_hbm_bytes`` watermark. Where the backend exposes no
``memory_stats()`` (host CPU), the snapshot reports a
DETERMINISTIC pseudo-limit (``PA_CPU_FAKE_HBM_BYTES``, default 8 GiB) with
``bytes_in_use`` summed from the process's live jax arrays on that device —
so off-hardware tests can assert the utilization math instead of skipping it.
The parity probes above (``total_memory_bytes``/``free_memory_bytes``) keep
returning 0 off-hardware on purpose: the hybrid chain's weighting fallback
(any_device_parallel.py:738-739) is routing behavior, not telemetry, and must
not start believing a fake limit.
"""

from __future__ import annotations

import dataclasses
import os

import jax

# Deterministic pseudo-capacity reported for devices without memory_stats().
CPU_FALLBACK_LIMIT_BYTES = 8 * 2**30


def _stats(device: jax.Device) -> dict | None:
    try:
        return device.memory_stats()
    except Exception:
        return None


def total_memory_bytes(device: jax.Device) -> int:
    """Device memory capacity in bytes; 0 when the backend exposes no stats."""
    stats = _stats(device)
    if not stats:
        return 0
    return int(stats.get("bytes_limit", 0))


def free_memory_bytes(device: jax.Device) -> int:
    """Free HBM in bytes (limit - in_use); 0 when unavailable.

    Parity: get_free_vram (any_device_parallel.py:724-735) returns
    ``total_memory - memory_allocated`` in MB for CUDA and 0 otherwise.
    """
    stats = _stats(device)
    if not stats:
        return 0
    limit = int(stats.get("bytes_limit", 0))
    in_use = int(stats.get("bytes_in_use", 0))
    return max(0, limit - in_use)


def usable_hbm_bytes(device: jax.Device) -> int:
    """The HBM budget the weights-don't-fit routing compares against: the
    ``PA_HBM_BUDGET_BYTES`` override when set (a chip's *usable* HBM can sit
    below the reported ``bytes_limit``; a measured ceiling belongs in the env,
    not hardcoded), otherwise 90% of the device's reported capacity (runtime/framework
    reservations come off the top before any weight lands). 0 when the backend
    exposes no stats (host CPU) — the caller must then budget explicitly."""
    override = os.environ.get("PA_HBM_BUDGET_BYTES")
    if override:
        return int(override)
    total = total_memory_bytes(device)
    return int(total * 0.9)


def _device_label(device: jax.Device) -> str:
    return f"{device.platform}:{device.id}"


def _fallback_in_use(devices) -> dict:
    """ONE pass over the process's live jax arrays, bucketing per-shard bytes
    by device — the deterministic ``bytes_in_use`` stand-in where the backend
    reports nothing. A sharded array contributes its per-shard slice
    (nbytes / device count) to each of its devices."""
    wanted = {d: 0 for d in devices}
    for arr in jax.live_arrays():
        try:
            devs = arr.sharding.device_set
        except Exception:
            continue
        per_shard = arr.nbytes // max(1, len(devs))
        for d in devs:
            if d in wanted:
                wanted[d] += per_shard
    return wanted


def _device_backed_stats(device: jax.Device) -> dict | None:
    stats = _stats(device)
    if not stats or int(stats.get("bytes_limit", 0)) <= 0:
        return None
    return {
        "device": _device_label(device),
        "bytes_limit": int(stats.get("bytes_limit", 0)),
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)) or None,
        "source": "device",
    }


def _fallback_stats(device: jax.Device, in_use: int) -> dict:
    limit = int(os.environ.get("PA_CPU_FAKE_HBM_BYTES",
                               str(CPU_FALLBACK_LIMIT_BYTES)))
    return {
        "device": _device_label(device),
        "bytes_limit": limit,
        "bytes_in_use": in_use,
        "peak_bytes_in_use": None,
        "source": "fallback",
    }


def device_memory_stats(device: jax.Device) -> dict:
    """Telemetry stats for one device: real ``memory_stats()`` where exposed
    (``source: "device"``), else the deterministic fallback
    (``source: "fallback"`` — pseudo-limit ``$PA_CPU_FAKE_HBM_BYTES`` or
    8 GiB, in-use from live arrays)."""
    s = _device_backed_stats(device)
    if s is not None:
        return s
    return _fallback_stats(device, _fallback_in_use([device])[device])


def memory_snapshot(devices=None) -> list[dict]:
    """Per-device stats + utilization for every (or the given) device — the
    body of ``GET /health``'s ``hbm`` section and the postmortem bundle's
    ``memory.json``. Fallback accounting is a single live-array pass shared
    by all devices, not one walk per device — the snapshot runs per bench
    warmup step and per traced streaming stage."""
    devices = list(devices) if devices is not None else list(jax.devices())
    stats = [(d, _device_backed_stats(d)) for d in devices]
    fallback_in_use = None
    out = []
    for d, s in stats:
        if s is None:
            if fallback_in_use is None:
                fallback_in_use = _fallback_in_use(
                    [dd for dd, ss in stats if ss is None]
                )
            s = _fallback_stats(d, fallback_in_use[d])
        limit = s["bytes_limit"]
        s["utilization"] = (
            round(s["bytes_in_use"] / limit, 6) if limit > 0 else None
        )
        out.append(s)
    return out


def publish_memory_gauges(devices=None) -> list[dict]:
    """Export per-device ``pa_hbm_bytes_limit`` / ``pa_hbm_bytes_in_use`` /
    ``pa_hbm_utilization`` gauges (the Prometheus view of the snapshot);
    returns the snapshot so callers need only one pass."""
    from ..utils.metrics import registry

    snap = memory_snapshot(devices)
    for s in snap:
        lbl = {"device": s["device"]}
        registry.gauge("pa_hbm_bytes_limit", s["bytes_limit"], labels=lbl,
                       help="device memory capacity (deterministic pseudo-"
                            "limit where the backend exposes no stats)")
        registry.gauge("pa_hbm_bytes_in_use", s["bytes_in_use"], labels=lbl,
                       help="device memory in use (live-array fallback "
                            "off-hardware)")
        if s["utilization"] is not None:
            registry.gauge("pa_hbm_utilization", s["utilization"], labels=lbl,
                           help="bytes_in_use / bytes_limit")
    return snap


@dataclasses.dataclass
class ResidencyTracker:
    """Accounting of live *streamed-weight* bytes on a device.

    The streaming scheduler (parallel/streaming.py) calls ``place(tag, n)``
    when it dispatches a stage's host→HBM transfer and ``retire(tag)`` once
    that stage's compute has completed AND its buffers have been released —
    so ``live_bytes`` tracks the scheduler's weight footprint and
    ``peak_bytes`` is the number the 2-stage bound is asserted on.
    ``resident_bytes`` counts the permanently-placed remainder (prepare/
    finalize params), reported separately because it is not part of the
    double-buffer ring."""

    live_bytes: int = 0
    peak_bytes: int = 0
    resident_bytes: int = 0
    _tags: dict = dataclasses.field(default_factory=dict)

    def place(self, tag, nbytes: int) -> None:
        if tag in self._tags:
            raise ValueError(f"stage {tag!r} placed twice without retirement")
        self._tags[tag] = int(nbytes)
        self.live_bytes += int(nbytes)
        self.peak_bytes = max(self.peak_bytes, self.live_bytes)

    def retire(self, tag) -> None:
        self.live_bytes -= self._tags.pop(tag)

    def add_resident(self, nbytes: int) -> None:
        self.resident_bytes += int(nbytes)

    @property
    def live_tags(self) -> tuple:
        return tuple(self._tags)

    def publish_gauges(self, device: str, bound_bytes: int | None = None
                       ) -> None:
        """Export the tracker's accounting as ``pa_hbm_stream_*`` gauges —
        the streamed-weight residency view of HBM, next to the raw
        ``pa_hbm_bytes_*`` device gauges. ``bound_bytes`` is the budget the
        scheduler promises to stay under (2 × max stage)."""
        from ..utils.metrics import registry

        lbl = {"device": device}
        registry.gauge("pa_hbm_stream_live_bytes", self.live_bytes,
                       labels=lbl,
                       help="streamed-weight bytes currently resident")
        registry.gauge("pa_hbm_stream_peak_bytes", self.peak_bytes,
                       labels=lbl,
                       help="peak streamed-weight residency this process")
        registry.gauge("pa_hbm_stream_resident_bytes", self.resident_bytes,
                       labels=lbl,
                       help="permanently-placed prepare/finalize bytes")
        if bound_bytes:
            registry.gauge("pa_hbm_stream_bound_bytes", bound_bytes,
                           labels=lbl,
                           help="the 2-stage residency bound the scheduler "
                                "is held to")
