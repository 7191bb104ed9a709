"""Persistent XLA compilation cache.

The framework's bucketed shapes produce a bounded but non-trivial set of
programs; caching the compiled executables on disk removes that cost from
every run after the first (and from every window after the first in a
run).

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  sets no other directory.
* unset: a fixed ``.xla_cache/`` inside the checkout, whether or not the
  checkout is a git repository. The path is part of what makes a later
  run hit, so it never depends on ``HOME`` or the temp dir.

The entry points (``cli.main``, ``chip_smoke.py``, ``bench.py``) call
:func:`enable_compilation_cache` before their first compile.
"""

from __future__ import annotations

import logging
import os

LOG = logging.getLogger("tpu_cooccurrence")

#: The default cache root: ``.xla_cache/`` in the checkout.
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".xla_cache")

_enabled = False


def _host_fingerprint() -> str:
    """Short stable id of this host's CPU feature set (+ platform)."""
    import hashlib
    import platform

    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 lists features under "flags", aarch64 under "Features".
                if line.startswith(("flags", "Features")):
                    feats = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    digest = hashlib.sha1(
        f"{platform.machine()}|{feats}".encode()).hexdigest()[:12]
    return digest


def cache_dir() -> str:
    """The directory JAX's persistent cache uses ("" when it is off)."""
    import jax

    return jax.config.jax_compilation_cache_dir or ""


def enable_compilation_cache() -> str:
    """Idempotently point JAX's persistent compilation cache at disk.

    Returns the directory in use."""
    global _enabled
    import jax

    if not _enabled:
        _enabled = True
        try:
            if not jax.config.jax_compilation_cache_dir:
                # The checkout can be copied between hosts with different
                # CPU feature sets; XLA:CPU AOT results are feature-
                # specific and loading a foreign one risks SIGILL, so each
                # host gets its own bucket under the fixed root.
                path = os.path.join(DEFAULT_DIR, _host_fingerprint())
                os.makedirs(path, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", path)
            # Cache every program: the per-window programs are small and
            # would otherwise all fall under the default cutoffs.
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        except Exception as exc:  # pragma: no cover - unwritable checkout
            LOG.info("persistent compilation cache unavailable: %s", exc)
    return cache_dir()
