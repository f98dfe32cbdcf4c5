"""Backend setup — the one place a process that runs JAX pins its
platform, turns on CPU cross-process collectives, and places the
persistent compilation cache (SURVEY.md §7 "Hard parts").

The platform is ``JAX_PLATFORMS``, nothing else. The supervisor injects
it from ``resources`` (runtime/env.py): ``tpu,cpu`` for a ``tpu_chips``
replica — the chip first, so a missing or busy chip raises in that
process instead of computing on the CPU, with the CPU backend beside it
for host-side weight init — and ``cpu`` for a ``cpu_devices`` replica.
JAX reads the variable at import; :func:`setup_backend` applies it again
through ``jax.config`` because a warm standby (controller/standby.py)
imported JAX before the job's environment existed.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional


def compile_cache_dir() -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it, else ``.xla_cache/`` at the root of this
    checkout. The path is part of the cache's key, so it is derived from
    the package's location — never from the current directory, a state
    dir or a temporary name, which move between runs and so never hit."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parents[2] / ".xla_cache"
    )


# What JAX's compilation cache told this process so far, by the names the
# status records carry (:func:`compile_counts`). A miss is recorded where a
# freshly compiled program is written to the cache, which with the
# threshold at 0 (:func:`setup_backend`) is every program compiled through
# it; one with a host callback in it is never written, and never counted.
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_misses": "programs_compiled",
    "/jax/compilation_cache/cache_hits": "programs_from_cache",
}
_compile_counts: Optional[dict] = None  # None until setup_backend places the cache


def _count_cache_event(event: str, **_kwargs) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _compile_counts[name] += 1


def compile_counts() -> dict:
    """``programs_compiled`` and ``programs_from_cache`` of this process up
    to now, for a status record: a warm start reads 0 compiled. Empty where
    :func:`setup_backend` placed no cache (never called, or the gloo world
    that runs without one). Imports no JAX."""
    return dict(_compile_counts or {})


def setup_backend(platform: Optional[str] = None) -> str:
    """Pin the JAX platform, enable gloo for multi-process CPU worlds,
    place the compilation cache (:func:`compile_cache_dir`) and count what
    it is asked for (:func:`compile_counts`).

    Must be called before any JAX computation or device query. Returns
    the pinned platform string (``""`` = JAX's own default)."""
    global _compile_counts
    import jax

    platform = platform or os.environ.get("JAX_PLATFORMS", "")
    if platform:
        jax.config.update("jax_platforms", platform)
    if (
        platform == "cpu"
        and int(os.environ.get("TPUJOB_NUM_PROCESSES", "1")) > 1
    ):
        # Gloo gives the CPU backend real inter-process collectives — the
        # stand-in for ICI/DCN when testing multi-host topologies locally
        # (SURVEY.md §4: multi-host without a pod). Only for multi-process
        # worlds: gloo needs the distributed client jax.distributed.
        # initialize creates, and building a single-process CPU backend
        # with gloo configured but no client hard-fails at first use
        # (observed on this jaxlib), taking every single-process jax
        # test/workload down with it.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # The persistent compilation cache is poison for this combination:
        # an XLA:CPU executable with gloo collective thunks deserializes
        # into something that heap-corrupts on execution (observed on this
        # jaxlib: every cache-HIT life of a restarted gang segfaults in
        # the jitted step within seconds, while every cold-compile life is
        # fine). The cache's win is the TPU cold-compile skip; CPU test
        # worlds compile in ~3s, so trade that for not crashing.
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
        # Every compiled program is kept, however fast it compiled. JAX's
        # default keeps only those that took a second or more, so a run's
        # many small programs (a weight leaf's quantisation, a cache's
        # zeros, a sampler) compiled again on every start, and one that
        # takes about a second was kept or not by what the machine was
        # doing on the checkout's first run (PERF.md section 6, PR 32).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if _compile_counts is None:
            _compile_counts = dict.fromkeys(_CACHE_EVENTS.values(), 0)
            jax.monitoring.register_event_listener(_count_cache_event)
        # The names on the device's work (``jax.named_scope``, Flax's module
        # names) reach a profile through the compiled program's metadata.
        # JAX leaves metadata out of the cache's key by default, so a hit
        # would hand back a program compiled before a scope was named — or
        # by another checkout without it — and the trace would read stale
        # names. With it in the key, a renamed scope compiles again.
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return platform


def device_report() -> dict:
    """What this process computes on, as JAX reports it: the platform,
    ``device_kind``, the world's and this process's device counts, this
    process's device ids, and each local device's ``bytes_in_use`` /
    ``peak_bytes_in_use`` (``None`` where the backend keeps no memory
    stats — the CPU). Workloads put it in their result and on the status
    channel so a run names its device instead of implying one."""
    import jax

    local = jax.local_devices()
    stats = [d.memory_stats() or {} for d in local]
    return {
        "platform": local[0].platform,
        "device_kind": local[0].device_kind,
        "device_count": jax.device_count(),
        "local_device_count": len(local),
        "local_device_ids": [d.id for d in local],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }
