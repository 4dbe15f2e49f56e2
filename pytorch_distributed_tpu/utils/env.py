"""Versioned runtime-environment manifest.

TPU-native replacement for the reference's cluster environment pinning
(``import hf_env; hf_env.set_env('202111')`` — the first two lines of every
reference script). Instead of swapping a container image, we verify the
installed JAX/flax/optax stack against a named manifest. The one rule
for where the persistent compilation cache lives is here too
(``compile_cache_dir``), shared by every entry point.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass, field

logger = logging.getLogger("pytorch_distributed_tpu")


@dataclass(frozen=True)
class EnvManifest:
    """Minimum-version pins for a named environment."""

    name: str
    min_versions: dict = field(default_factory=dict)


# Manifests are named by YYYYMM like the reference's '202111'.
MANIFESTS = {
    "202607": EnvManifest(
        name="202607",
        # the one installation this tree is written for (pyproject.toml
        # carries the same pins)
        min_versions={"jax": (0, 9), "flax": (0, 12), "optax": (0, 2)},
    ),
}

_active_env: str | None = None


def _version_tuple(version: str) -> tuple:
    parts = []
    for piece in version.split(".")[:3]:
        digits = "".join(ch for ch in piece if ch.isdigit())
        parts.append(int(digits) if digits else 0)
    return tuple(parts)


def set_env(name: str = "202607", strict: bool = False) -> EnvManifest:
    """Pin and verify the runtime environment.

    Mirrors ``hf_env.set_env(version)`` (every reference script, lines 1-2):
    call once at program start, before heavy imports do real work.

    Args:
      name: manifest name (default the current one).
      strict: raise on a version pin violation instead of warning.
    """
    global _active_env
    manifest = MANIFESTS.get(name)
    if manifest is None:
        raise ValueError(
            f"unknown environment manifest {name!r}; known: {sorted(MANIFESTS)}"
        )

    import importlib

    for mod_name, min_version in manifest.min_versions.items():
        try:
            mod = importlib.import_module(mod_name)
        except ImportError:
            msg = f"environment {name!r} requires {mod_name} but it is not installed"
            if strict:
                raise RuntimeError(msg)
            logger.warning(msg)
            continue
        have = _version_tuple(getattr(mod, "__version__", "0"))
        if have < tuple(min_version):
            msg = (
                f"environment {name!r} pins {mod_name}>="
                f"{'.'.join(map(str, min_version))}, found {mod.__version__}"
            )
            if strict:
                raise RuntimeError(msg)
            logger.warning(msg)

    _active_env = name
    return manifest


def active_env() -> str | None:
    return _active_env


#: the checkout this package was imported from
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir(requested: str | None = None) -> str:
    """The directory this run keeps jax's persistent compilation cache
    in — the one rule every entry point shares (recipes, trainers,
    ``scripts/warmup.py``, ``chip_smoke.py``).

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory, and code
    sets no other: the machine that runs the program decides where its
    cache lives, and ``requested`` (``--compile-cache-dir`` /
    ``TrainerConfig.compile_cache_dir``) loses to it. Unset, ``requested``
    is used, and with neither the cache goes to ``<repo>/.jax_cache`` —
    a fixed path, because the path is part of the cache key and a
    directory that moves (a home, a tempdir, a pid) never hits.
    """
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR") or requested
            or os.path.join(REPO_ROOT, ".jax_cache"))


def enable_compile_cache(requested: str | None = None) -> str:
    """Turn jax's persistent compilation cache on at
    ``compile_cache_dir(requested)``; returns the directory."""
    from pytorch_distributed_tpu.compilecache import enable_persistent_cache

    return enable_persistent_cache(compile_cache_dir(requested))
