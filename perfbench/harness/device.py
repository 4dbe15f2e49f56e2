"""The device a run is on: found or refused, its peaks, its memory."""

from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoAccelerator(SystemExit):
    pass


def require_devices(chips: int, allow_cpu: bool = False) -> list:
    """The ``chips`` devices this cell runs on. A measuring run that finds
    no accelerator, or fewer chips than the cell asks for, fails here: it
    never labels a CPU's numbers with a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" and not allow_cpu:
        raise NoAccelerator(
            f"perfbench: needs a TPU, jax found {devices}; numbers from a "
            "CPU are never written under a device metric's name")
    if len(devices) < chips:
        raise NoAccelerator(
            f"perfbench: the cell needs {chips} chip(s), jax found "
            f"{len(devices)}: {devices}")
    return list(devices[:chips])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program's one rule
    puts it (``utils.env.compile_cache_dir``: ``JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``), with no size limit of its own.
    A limit below what one cell compiles (the paged server's programs are
    190 MB; this machine's environment sets 192 MiB for everything) makes
    every run compile everything again: least-recently-used eviction over
    a working set that does not fit evicts each entry before its next use."""
    import jax

    from pytorch_distributed_tpu.compilecache import process_compile_totals
    from pytorch_distributed_tpu.utils.env import enable_compile_cache

    jax.config.update("jax_compilation_cache_max_size", -1)
    process_compile_totals()  # installs the listener that counts hits
    return enable_compile_cache()


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, by ``device_kind``. An unknown kind is
    an error, not a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"perfbench/peaks.json has no entry for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def compiled_peak_bytes(compiled) -> int:
    """Bytes one compiled program needs live on its fullest device:
    arguments + outputs + temporaries - aliased (``memory_analysis()``).
    ``memory_stats()['peak_bytes_in_use']`` does not count a program's
    temporaries on this runtime (PERF.md, Open questions)."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def device_record(devices: list, memory_peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak_bytes)}
