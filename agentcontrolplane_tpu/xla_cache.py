"""Persistent XLA compilation cache.

TPU compiles are the dominant cold-start cost, and the serving engine has
a bounded-but-real matrix of programs (prefill buckets x batch sizes,
decode widths, constrained variants). The persistent cache makes every
compile a once-per-machine cost instead of once-per-process: the second
`acp-tpu run`, `chip_smoke.py`, `acpbench.run` and every test process reuse
the same compiled artifacts.

Where it lives is decided from outside: when ``JAX_COMPILATION_CACHE_DIR``
is set, jax reads it itself and this module sets no directory; otherwise
the cache sits at one fixed path inside the checkout (``.jax_cache``
beside the package — the path is part of a cache entry's key, so it must
never move between runs). Opt out with ``ACP_XLA_CACHE=0``.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger("acp_tpu.xla_cache")

_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

_enabled = False


def enable_persistent_compilation_cache() -> bool:
    """Idempotent; safe to call before or after backend init (jax only
    consults the config at compile time). Returns True when active."""
    global _enabled
    if _enabled:
        return True
    if os.environ.get("ACP_XLA_CACHE", "1") in ("0", "false", "no"):
        return False
    try:
        import jax

        if jax.process_count() > 1:
            # Multi-host lockstep requires every rank to COMPILE the same
            # program the same way. A cache hit on one rank + fresh compile
            # on another can decompose collectives differently (observed as
            # gloo size-mismatch aborts on CPU meshes); per-process caches
            # also race on shared filesystems. Cold compiles are once per
            # process here — correctness wins.
            log.info("multi-host run: persistent compilation cache disabled")
            return False
    except Exception:
        pass  # backend not initialized yet; single-process paths continue
    try:
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
        # cache everything: the engine's programs are individually small but
        # numerous, and the default min-compile-time filter would skip the
        # narrow decode widths
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _enabled = True
        log.info("persistent XLA compilation cache at %s", cache_dir())
    except Exception as e:  # never let cache plumbing break serving
        log.warning("persistent compilation cache unavailable: %s", e)
        return False
    return True


def cache_dir() -> str | None:
    """Where compiled programs persist, as jax will resolve it (None = no
    persistent cache)."""
    import jax

    return jax.config.jax_compilation_cache_dir
