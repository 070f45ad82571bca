"""Launcher environment hygiene for multi-device and cluster runs.

The production launch scripts this repo reproduces preload tcmalloc
(glibc malloc fragments badly under XLA's large transient allocations),
raise the tcmalloc large-alloc report threshold so multi-GB parameter
stacks don't spam stderr, silence TF's C++ logging, and size the fake
host platform with ``--xla_force_host_platform_device_count=N`` so a
single CPU process presents N devices to jax.

All of these are READ AT PROCESS START (LD_PRELOAD by the dynamic
linker, XLA_FLAGS at backend initialization), which is why this module
deliberately never imports jax at module level: it must be importable
— and ``apply()``-able — before the first jax import.  Entry points:

``host_env``    — build the env-var overlay (pure; no side effects).
``apply``       — install the overlay into ``os.environ`` for THIS
                  process; call before importing jax.
``child_env``   — a minimal sanitized environment for a subprocess
                  (the tests' 8-fake-device pattern) or a rendered
                  cluster Job container.
``enable_compile_cache`` — turn on JAX's persistent compilation cache
                  (``$JAX_COMPILATION_CACHE_DIR``, else one fixed
                  directory in the checkout); the CLIs call it under
                  their ``__main__`` guard, tests never do.

CI's mesh-smoke job and tests/test_mesh_engine.py drive the sharded
round engine through ``child_env(devices=8)`` + an in-child ``apply()``.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys
import warnings
from typing import Dict, Optional, Union

# Debian/Ubuntu path first (the CI and container image), then the
# common fallbacks.  find_tcmalloc() probes in order.
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib64/libtcmalloc.so.4",
)

# 60 GB: parameter stacks of a few GB must not trip tcmalloc's
# large-alloc stderr report on every round
TCMALLOC_REPORT_THRESHOLD = "60000000000"

# the persistent compile cache's home when $JAX_COMPILATION_CACHE_DIR is
# unset: fixed (a moving directory never hits) and gitignored
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_DEVCOUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=\d+")


def find_tcmalloc() -> Optional[str]:
    """First existing tcmalloc shared object, or None."""
    for path in TCMALLOC_CANDIDATES:
        if os.path.exists(path):
            return path
    return None


def xla_host_devices_flag(n: int) -> str:
    return f"--xla_force_host_platform_device_count={int(n)}"


def merge_xla_flags(new: str, existing: str = "") -> str:
    """Append ``new`` to an XLA_FLAGS string, dropping any prior
    device-count flag it supersedes."""
    kept = _DEVCOUNT_FLAG.sub("", existing or "").split()
    return " ".join(kept + [new]) if new else " ".join(kept)


def host_env(devices: Optional[int] = None, *,
             tcmalloc: Union[bool, str] = "auto",
             platform: Optional[str] = None,
             base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Env-var overlay for a launched process (pure — no side effects).

    devices:  present this many fake host devices (XLA_FLAGS
              ``--xla_force_host_platform_device_count``); None leaves
              the device count alone.
    tcmalloc: "auto" probes the local filesystem and preloads tcmalloc
              when found; True forces the Debian path (for rendering a
              container env on a host that doesn't have the lib);
              False omits LD_PRELOAD.
    platform: set JAX_PLATFORMS (e.g. "cpu" — load-bearing on non-TPU
              boxes where libtpu's GCP-metadata probes would hang).
    base:     start from these vars instead of an empty dict.
    """
    env = dict(base or {})
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                   TCMALLOC_REPORT_THRESHOLD)
    lib = (find_tcmalloc() if tcmalloc == "auto"
           else TCMALLOC_CANDIDATES[0] if tcmalloc is True else None)
    if lib:
        env.setdefault("LD_PRELOAD", lib)
    if platform:
        env["JAX_PLATFORMS"] = platform
    if devices is not None:
        env["XLA_FLAGS"] = merge_xla_flags(xla_host_devices_flag(devices),
                                           env.get("XLA_FLAGS", ""))
    return env


def tpu_expected() -> bool:
    """Would a fresh JAX process started from this environment take the
    TPU?  Decided without initializing a backend — doing that would
    claim the chip: ``$JAX_PLATFORMS`` names ``tpu``, or is unset and
    libtpu is installed."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return "tpu" in platforms.split(",")
    return importlib.util.find_spec("libtpu") is not None


def _jax_backend_live() -> bool:
    """Has a jax backend already initialized (and thus consumed
    XLA_FLAGS)?  Merely having imported jax is fine — flags are read at
    the first device/compile call, not at import."""
    mod = sys.modules.get("jax")
    if mod is None:
        return False
    try:
        return bool(mod._src.xla_bridge._backends)
    except AttributeError:      # private layout moved: be conservative
        return True


def apply(devices: Optional[int] = None, *,
          platform: Optional[str] = None,
          tcmalloc: Union[bool, str] = False) -> Dict[str, str]:
    """Install the launcher overlay into THIS process's environment.

    Must run before the first jax import: XLA reads XLA_FLAGS at
    backend initialization and never again.  LD_PRELOAD cannot take
    effect in-process (the dynamic linker already ran), so tcmalloc
    defaults to False here — it only matters for ``host_env``/
    ``child_env`` consumers that exec a fresh process.

    Returns the applied overlay.
    """
    if _jax_backend_live():
        warnings.warn("repro.launch.env.apply() called after the jax "
                      "backend initialized: XLA_FLAGS were already read "
                      "and will be ignored", RuntimeWarning)
    env = host_env(devices, tcmalloc=tcmalloc, platform=platform,
                   base={"XLA_FLAGS": os.environ["XLA_FLAGS"]}
                   if "XLA_FLAGS" in os.environ else None)
    os.environ.update(env)
    return env


def child_env(devices: Optional[int] = None, *,
              platform: str = "cpu", pythonpath: str = "src",
              tcmalloc: Union[bool, str] = False) -> Dict[str, str]:
    """Minimal sanitized environment for a subprocess that must see
    ``devices`` fake host devices — the subprocess-test pattern: a bare
    PATH/HOME/PYTHONPATH plus the launcher overlay, nothing inherited
    that could re-route the jax backend."""
    base = {
        "PYTHONPATH": pythonpath,
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
    }
    return host_env(devices, tcmalloc=tcmalloc, platform=platform,
                    base=base)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set.
    Otherwise the cache goes to :data:`DEFAULT_COMPILE_CACHE`, exported
    through the same variable so spawned workers share it.
    """
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 DEFAULT_COMPILE_CACHE)
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
