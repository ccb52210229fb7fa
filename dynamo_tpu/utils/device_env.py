"""Process-level device settings: where compiled programs are cached and
which chip a launched process may open.

Both are decided by the launcher, from outside the program:

- **Compile cache.** ``JAX_COMPILATION_CACHE_DIR`` set → JAX reads it
  itself and this module sets nothing in code.  Unset → the cache lives at
  ``<checkout>/.jax_cache`` (git-ignored), resolved from this package's own
  location — a fixed path, because the path is part of the cache key and a
  directory that moves never hits.
- **One process per chip.** A TPU chip belongs to one process at a time and
  a process opens every chip it can see.  N one-chip workers on one host
  therefore each get :func:`one_chip_env` merged into their environment by
  whoever starts them, *before* JAX is imported in the child.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0}
_listening = False


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — two levels above this package."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT or event == _MISS:
        with _lock:
            _counts["hits" if event == _HIT else "misses"] += 1


def configure_compile_cache() -> str:
    """Call first thing in every entry point that compiles.  Returns the
    directory in use.  Also starts counting persistent-cache hits/misses
    (see :func:`compile_cache_stats`)."""
    global _listening
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = default_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    with _lock:
        if not _listening:
            _listening = True
            jax.monitoring.register_event_listener(_on_event)
    return path


def compile_cache_stats() -> Dict[str, object]:
    """Persistent-cache traffic of this process since
    :func:`configure_compile_cache`: a hit is a program read back instead
    of compiled, a miss one that was compiled and written."""
    with _lock:
        out: Dict[str, object] = dict(_counts)
    out["dir"] = os.environ.get(CACHE_ENV) or default_cache_dir()
    out["from_env"] = bool(os.environ.get(CACHE_ENV))
    return out


def one_chip_env(index: int) -> Dict[str, str]:
    """Environment that makes a child process see exactly chip ``index`` of
    this host (established on a v5e 2x2 host, libtpu 0.0.34: the three
    together let processes hold distinct chips concurrently;
    ``TPU_VISIBLE_CHIPS`` alone trips libtpu's multi-process lock)."""
    return {
        "TPU_VISIBLE_CHIPS": str(int(index)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


if __name__ == "__main__":
    # shell launchers:  env $(python -m dynamo_tpu.utils.device_env 2) python -m …
    import sys

    print(" ".join(f"{k}={v}" for k, v in one_chip_env(int(sys.argv[1])).items()))
