"""Process-level platform configuration — the bits that must land in the
environment BEFORE the first `import jax`.

JAX's CPU backend exposes exactly one device unless
`--xla_force_host_platform_device_count=N` is in `XLA_FLAGS` when the
backend initializes, and backend initialization happens at first import.
That makes host-device-count a *launcher* concern, not a library one: any
entry point that wants a multi-device CPU mesh (the tensor-parallel bench
sweep, the serving example, the CI mesh-smoke job) has to set the flag
before anything in its import graph touches jax.

This module therefore imports NOTHING from jax at module scope and has no
side effects on import.  Entry points use it like:

    from repro import platform
    platform.configure_from_argv()     # peeks --devices N from sys.argv
    import jax                         # backend now sees N host devices

or explicitly: `platform.set_host_device_count(4)`.

Setting the flag after jax has initialized cannot work, so that case warns
and leaves the environment alone rather than silently lying about the
device count the process will actually see.

`configure_compile_cache()` gives JAX's persistent compilation cache its one
directory: `JAX_COMPILATION_CACHE_DIR` where it is set (JAX reads it itself),
and otherwise `.jax_cache/` at the root of the checkout.  The path is part
of the cache's key, so it is fixed: never a temporary name, a process id or
the time.
"""

from __future__ import annotations

import os
import re
import sys
import warnings
from pathlib import Path
from typing import List, Optional

_FLAG = "--xla_force_host_platform_device_count"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def _jax_initialized() -> bool:
    """True once jax is imported (its backends latch XLA_FLAGS then)."""
    return "jax" in sys.modules


def host_device_count() -> Optional[int]:
    """The host device count currently requested in XLA_FLAGS, or None."""
    m = re.search(rf"{_FLAG}=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else None


def set_host_device_count(n: int) -> bool:
    """Request `n` virtual host devices from the CPU backend.

    Merges into any existing XLA_FLAGS (replacing a previous
    host-device-count flag, preserving everything else).  Returns True if
    the environment was updated; False — with a warning — when jax is
    already imported and the flag can no longer take effect.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"host device count must be >= 1, got {n}")
    if _jax_initialized():
        seen = host_device_count() or 1
        if seen != n:
            warnings.warn(
                f"jax is already imported; cannot change host device count "
                f"to {n} (the backend latched XLA_FLAGS at import, "
                f"currently {seen}). Call repro.platform before importing "
                f"jax.", RuntimeWarning, stacklevel=2)
            return False
        return True
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(f"{_FLAG}=")]
    flags.append(f"{_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    return True


def configure_from_argv(argv: Optional[List[str]] = None) -> Optional[int]:
    """Peek `--devices N` out of `argv` (default `sys.argv`) and apply it
    before the caller's jax import.

    This deliberately bypasses argparse: parsers live *below* the entry
    point's jax imports, far too late to influence backend init.  The flag
    stays in argv for the real parser to consume (and document).  Returns
    the device count applied, or None when the flag is absent.
    """
    args = list(sys.argv if argv is None else argv)
    n: Optional[int] = None
    for i, a in enumerate(args):
        if a == "--devices" and i + 1 < len(args):
            n = int(args[i + 1])
        elif a.startswith("--devices="):
            n = int(a.split("=", 1)[1])
    if n is not None:
        set_host_device_count(n)
    return n


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    With `JAX_COMPILATION_CACHE_DIR` set the directory is left alone: JAX
    already keeps its cache there.  Otherwise the cache goes to
    `CHECKOUT_CACHE`.
    Either way an entry's key covers the program's metadata: the serving
    runtime reads each step program's scope table from the executable
    (`repro.serve.trace.launch`), so a program must never load the
    executable of one that differs from it only in metadata."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
