"""Hardware provenance: the fingerprint every measurement is stamped with.

A number measured on jax-on-CPU says nothing about the chip, so every
measurement names the device it ran on. One dict — platform, device
kind/count, host cores, jax/jaxlib versions, git sha, clock source —
computed once per process and stamped into

- the management REST hotpath summary (`profile.provenance`),
- span resource attributes (observe/spans.py OTLP envelope),

with ``proxy: true`` whenever the detected platform is not a TPU.

jax is imported lazily inside `fingerprint()`: importing this module
opens no backend, and only a process that owns the device calls it.
"""

from __future__ import annotations

import os
import platform as _platform
import subprocess
import time
from typing import Any, Dict, Optional

# the fields two runs must share to be COMPARABLE (`fingerprint_key`).
# git sha is deliberately excluded — comparing across commits on the
# same hardware is the whole point — and so is the clock source
# (informational, not a perf axis).
KEY_FIELDS = (
    "platform",
    "device_kind",
    "device_count",
    "host_cores",
    "jax",
    "jaxlib",
)

# platforms that count as the accelerator of record
_RECORD_PLATFORMS = ("tpu",)

_CACHE: Optional[Dict[str, Any]] = None


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            capture_output=True,
            text=True,
            timeout=5,
        )
        if out.returncode == 0:
            return out.stdout.strip()
    except Exception:  # noqa: BLE001 — provenance must never raise
        pass
    return ""


def _clock_source() -> str:
    """Which clock perf_counter timings actually stand on: the kernel's
    clocksource when readable (tsc vs hpet/acpi_pm changes what a
    microsecond histogram means), else python's perf_counter impl."""
    try:
        p = "/sys/devices/system/clocksource/clocksource0/current_clocksource"
        with open(p) as f:
            return f.read().strip()
    except OSError:
        pass
    try:
        return time.get_clock_info("perf_counter").implementation
    except Exception:  # noqa: BLE001 — informational field only
        return "unknown"


def fingerprint(refresh: bool = False) -> Dict[str, Any]:
    """The process-wide hardware fingerprint (computed once, cached).

    Returns a fresh dict each call (callers stamp it into JSON docs they
    then mutate). ``proxy`` is True on any non-TPU backend, so that a
    dashboard can refuse to headline a CPU number.
    """
    global _CACHE
    if _CACHE is None or refresh:
        info: Dict[str, Any] = {
            "host_cores": os.cpu_count() or 0,
            "machine": _platform.machine(),
            "python": _platform.python_version(),
            "git_sha": _git_sha(),
            "clock_source": _clock_source(),
        }
        import jax
        import jaxlib

        info["jax"] = jax.__version__
        info["jaxlib"] = jaxlib.__version__
        # a backend that fails to initialise raises here: the caller
        # asked which device it runs on, and "unknown" is not an answer
        dev = jax.devices()[0]
        info["platform"] = dev.platform
        info["device_kind"] = dev.device_kind
        info["device_count"] = len(jax.devices())
        info["proxy"] = info["platform"] not in _RECORD_PLATFORMS
        _CACHE = info
    return dict(_CACHE)


def is_proxy() -> bool:
    """True when the detected backend is NOT a TPU (the number is a
    CPU/GPU proxy, never a number of record)."""
    return bool(fingerprint().get("proxy", True))


def fingerprint_key(fp: Optional[Dict[str, Any]] = None) -> str:
    """Stable comparability key over KEY_FIELDS. Two runs with different
    keys must never be compared."""
    if fp is None:
        fp = fingerprint()
    return "|".join(str(fp.get(k, "")) for k in KEY_FIELDS)


def resource_attrs() -> Dict[str, Any]:
    """Span resource attributes (OTLP envelope): the fingerprint fields
    flattened under the `hw.` prefix, the idiomatic resource keys."""
    fp = fingerprint()
    return {
        "hw.platform": fp["platform"],
        "hw.device_kind": fp["device_kind"],
        "hw.device_count": fp["device_count"],
        "hw.host_cores": fp["host_cores"],
        "hw.jax": fp["jax"],
        "hw.jaxlib": fp["jaxlib"],
        "hw.git_sha": fp["git_sha"],
        "hw.clock_source": fp["clock_source"],
        "hw.proxy": bool(fp["proxy"]),
    }
