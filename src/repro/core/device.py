"""Which accelerator this host has, and who may use it.

A TPU chip belongs to one process at a time. A second process that asks
for it fails to initialise the TPU backend, and with ``JAX_PLATFORMS``
unset JAX then quietly carries on with the CPU backend. For the mapper
that would mean a probSAT walk on the host CPU while the served path
looks healthy. This module lets the code observe the hardware without
initialising JAX (so it stays importable from the fork-safe worker chain):

* :func:`attached_platform` reads the PCI bus the same way JAX's own TPU
  start-up probe does;
* :func:`require_attached_backend` fails a process whose JAX backend is
  not the accelerator the host has;
* :func:`enable_compile_cache` is what the entry points call to keep
  JAX's persistent compilation cache (never called at library import).
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import os
from pathlib import Path
from typing import Optional

_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips (v3, v4, v5p, v5e, v6e, 7x)
_TPU_PCI_DEVICES = {"0x0027", "0x005e", "0x0062", "0x0063", "0x006f",
                    "0x0076"}

# the checkout root: src/repro/core/device.py -> parents[3]
_REPO_ROOT = Path(__file__).resolve().parents[3]


@functools.lru_cache(maxsize=1)
def _probe_hardware() -> Optional[str]:
    """``"tpu"`` when a TPU chip sits on the PCI bus and the TPU runtime
    is installed (JAX would then start its TPU backend), else None."""
    if importlib.util.find_spec("libtpu") is None \
            and not os.environ.get("TPU_LIBRARY_PATH"):
        return None
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            if Path(vendor).read_text().strip() != _GOOGLE_PCI_VENDOR:
                continue
            dev = Path(vendor).with_name("device").read_text().strip()
        except OSError:
            continue
        if dev in _TPU_PCI_DEVICES:
            return "tpu"
    return None


def attached_platform() -> Optional[str]:
    """``"tpu"`` when this host has a TPU that ``JAX_PLATFORMS`` does not
    rule out, else None. Never imports jax."""
    plat = _probe_hardware()
    allowed = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if plat is None or (allowed and plat not in allowed.split(",")):
        return None
    return plat


def require_attached_backend() -> None:
    """Raise unless JAX's default backend is the host's accelerator.

    A process that needs the chip and got another backend (the chip is
    held by another process, or the runtime failed to start) must fail,
    not walk on the CPU in the chip's place."""
    plat = attached_platform()
    if plat is None:
        return
    import jax
    got = jax.default_backend()
    if got != plat:
        raise RuntimeError(
            f"this host has a {plat.upper()} but JAX initialised the "
            f"{got!r} backend in process {os.getpid()}; the chip is "
            f"probably held by another process. Refusing to run the "
            f"device walk on {got!r} instead")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (JAX reads
    it itself). Otherwise the cache lives at the fixed in-checkout path
    ``<repo>/.jax_cache`` (gitignored): the directory is part of the cache
    key, so it must not move between runs. Returns the directory used."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: the walk's kernels compile in well under the
    # default one-second threshold, and each cold chip run pays for them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
