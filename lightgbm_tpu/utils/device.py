"""Device/platform selection.

The framework runs on whatever backend jax selects (``JAX_PLATFORMS``
is honoured by this installation: unset on a TPU host it is the TPU,
``cpu`` in the test suite). The one override is the ``device_type``
parameter (config.py): ``cpu`` routes the kernel-route decisions to the
host XLA paths, ``tpu``/``gpu``/``cuda`` clear that routing and REQUIRE
an accelerator (``require_accelerator``). The routing is process-wide
module state — the last ``Config.set`` carrying ``device_type`` wins.
"""
from __future__ import annotations

from typing import List, Optional

import jax

# platform requested by config (device_type=cpu); None = jax's default
_config_platform: Optional[str] = None


def set_config_platform(platform: Optional[str]) -> None:
    """Install (or clear, with None) the config-level device routing
    (device_type)."""
    global _config_platform
    _config_platform = platform


def get_devices() -> List:
    if _config_platform:
        return jax.local_devices(backend=_config_platform)
    return jax.devices()


def get_global_devices() -> List:
    """EVERY process's devices of the selected backend — the device
    set a multi-process training mesh must span (a collective over a
    subset would leave peers waiting forever). Single-process this is
    exactly get_devices(); under jax.distributed the config routing
    goes through jax.devices(backend), which is global."""
    if jax.process_count() == 1:
        return get_devices()
    return (jax.devices(_config_platform) if _config_platform
            else jax.devices())


def require_accelerator(requested: str) -> None:
    """``device_type=tpu|gpu|cuda`` was asked for explicitly: a process
    whose jax backend is the CPU must not quietly train there under an
    accelerator request."""
    from . import log
    d = jax.devices()[0]
    if d.platform not in ("tpu", "gpu"):
        log.fatal(f"device_type={requested} requested but jax found no "
                  f"accelerator (platform {d.platform!r}, JAX_PLATFORMS="
                  f"{jax.config.jax_platforms!r}); use device_type=cpu "
                  f"to train on the host")


def on_tpu() -> bool:
    """True when framework computation actually runs on a TPU device —
    gates Pallas kernel dispatch (Pallas TPU kernels can't lower for the
    CPU backend). Honors the device_type routing like get_devices()."""
    return get_devices()[0].platform == "tpu"
