"""Device discovery for the device-chain API.

Reference behavior (any_device_parallel.py:770-786, ParallelDevice.get_available_devices):
the dropdown enumerates ``cpu`` always, then ``cuda:i`` / ``mps`` / ``xpu:i`` /
DirectML ``privateuseone:i`` as available. The TPU-native equivalent enumerates ``cpu``
always, then ``tpu:i`` from ``jax.devices('tpu')``. Device identifiers are strings of the
form ``"<platform>"`` or ``"<platform>:<index>"`` (e.g. ``"tpu:3"``, ``"cpu"``), matching
the reference's string-keyed chain entries (any_device_parallel.py:823-832).
"""

from __future__ import annotations

import functools

import jax

def is_tpu_device(d: jax.Device) -> bool:
    return d.platform == "tpu"


def _platform_devices(platform: str) -> list[jax.Device]:
    """All jax devices for a platform, or [] when that backend is absent."""
    try:
        return list(jax.devices(platform))
    except RuntimeError:
        return []


@functools.cache
def available_devices() -> list[str]:
    """Enumerate selectable device strings, accelerators first, ``cpu`` always present.

    Mirrors ParallelDevice.get_available_devices (any_device_parallel.py:770-786), with
    ``tpu:i`` taking the role of ``cuda:i``. Any other accelerator platform JAX exposes
    (e.g. ``gpu``) is listed too, so the chain API is backend-agnostic.
    """
    out: list[str] = []
    seen_platforms: set[str] = set()
    for dev in jax.devices():
        plat = dev.platform
        if plat == "cpu":
            continue
        seen_platforms.add(plat)
        out.append(f"{plat}:{dev.id}")
    # Non-default accelerator backends (e.g. tpu present but cpu is default platform).
    if "tpu" not in seen_platforms:
        for dev in _platform_devices("tpu"):
            out.append(f"tpu:{dev.id}")
    if "gpu" not in seen_platforms:
        for dev in _platform_devices("gpu"):
            out.append(f"gpu:{dev.id}")
    out.append("cpu")
    return out


def device_platform(device_str: str) -> str:
    """``"tpu:3"`` -> ``"tpu"``; ``"cpu"`` -> ``"cpu"``."""
    return device_str.split(":", 1)[0].lower()


def get_device(device_str: str) -> jax.Device:
    """Resolve a device string to a live ``jax.Device``.

    Raises ``ValueError`` for unknown platforms or out-of-range indices — the analogue
    of the reference's per-device validation in the replica loop
    (any_device_parallel.py:1037-1042), which skips invalid chain entries.
    """
    plat = device_platform(device_str)
    idx = 0
    if ":" in device_str:
        try:
            idx = int(device_str.split(":", 1)[1])
        except ValueError as e:
            raise ValueError(f"Malformed device string {device_str!r}") from e
    devs = _platform_devices(plat)
    if not devs:
        raise ValueError(f"No devices available for platform {plat!r} (from {device_str!r})")
    for d in devs:
        if d.id == idx:
            return d
    raise ValueError(
        f"Device index {idx} out of range for platform {plat!r} "
        f"({len(devs)} device(s) available)"
    )


def default_device() -> jax.Device:
    """The canonical compute device — analogue of
    comfy.model_management.get_torch_device() (consumed at any_device_parallel.py:952)."""
    devs = _platform_devices("tpu")
    if devs:
        return devs[0]
    devs = _platform_devices("gpu")
    if devs:
        return devs[0]
    return jax.devices("cpu")[0]
