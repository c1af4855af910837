"""Shared utilities (reference fedml_api/utils parity).

- ``raise_error``: contextmanager logging the traceback before re-raising
  (context.py:9-18 ``raise_MPI_error`` — but without the Abort: callers
  decide lifecycle; use HeartbeatMonitor / nan_guard for containment);
- ``get_lock``: contextmanager around a ``threading.Lock`` (context.py:30);
- ``logging_config``: per-rank logging format (utils/logger.py:7,
  main_fedavg.py:411-415);
- ``post_complete_message_to_sweep_process``: fifo signal used by sweep
  drivers (fedavg/utils.py:19-27);
- ``use_compile_cache``: where an entry point keeps JAX's persistent
  compile cache;
- ``device_placement``: which backend this process runs on, refusing
  JAX's quiet TPU-to-CPU fallback.
"""

from __future__ import annotations

import contextlib
import logging
import os
import re
import traceback


@contextlib.contextmanager
def raise_error(logger: logging.Logger | None = None):
    try:
        yield
    except Exception:
        (logger or logging.getLogger(__name__)).error(traceback.format_exc())
        raise


@contextlib.contextmanager
def get_lock(lock):
    lock.acquire()
    try:
        yield lock
    finally:
        lock.release()


def logging_config(process_id: int = 0, level=logging.INFO):
    """Per-rank prefixed logging (reference main_fedavg.py:411-415)."""
    logging.basicConfig(
        level=level,
        format=(
            f"[rank {process_id}] %(asctime)s %(levelname)s "
            "%(filename)s:%(lineno)d %(message)s"
        ),
        force=True,
    )


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: ``<checkout>/.jax_cache`` — the compile cache when nothing places it.
DEFAULT_COMPILE_CACHE = os.path.join(_CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry point and
    return its directory. Placed from outside: when
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set here. Otherwise the cache is ``<checkout>/.jax_cache``
    — one fixed path (never ``/tmp``, a pid or a timestamp), because a
    cache a later process cannot find again is no cache. Call it before
    the first compilation: JAX opens the cache once per process.

    The cache's key holds the program's metadata. JAX leaves it out by
    default, and a cache that outlives a source change then hands back an
    executable compiled from the OLD source, whose operation names show
    up in every profile as if they were this program's: on the chip the
    round's ops carried frames of a file deleted two PRs before and none
    of the ``jax.named_scope`` phases just added (PERF.md, PR 25). Source
    paths are keyed relative to the checkout, so a checkout that moves
    still hits."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(_CHECKOUT + os.sep))
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def device_placement() -> str:
    """``"<platform> x<count> (<device_kind>)"`` for this process's
    default JAX backend — initializes it, so on a TPU host this takes the
    chips. With ``JAX_PLATFORMS`` unset JAX survives a TPU it cannot
    initialize (held by another process, broken libtpu) by logging at
    INFO and running on the CPU; that is raised here instead, so a
    process started on a TPU host either gets the chip or fails. Setting
    ``JAX_PLATFORMS=cpu`` is how a caller asks for the CPU."""
    import jax
    # What JAX itself consults before its "a TPU may be present" warning.
    from jax._src import hardware_utils

    backend = jax.default_backend()
    chips = hardware_utils.num_available_tpu_chips_and_device_id()[0]
    if backend == "cpu" and chips and not os.environ.get("JAX_PLATFORMS"):
        raise RuntimeError(
            f"this host has {chips} TPU chip(s) but JAX fell back to the "
            "CPU: another process holds the chip or libtpu failed to "
            "start. One process per chip; set JAX_PLATFORMS=cpu to run "
            "on the CPU on purpose.")
    devices = jax.devices()
    return f"{backend} x{len(devices)} ({devices[0].device_kind})"


def rss_mb() -> float:
    """CURRENT host RSS in MB (/proc/self/statm — Linux; falls back to
    the getrusage peak elsewhere). Current, not ru_maxrss: the process
    peak is monotone, so point-in-time memory claims (the sharded
    store's flat-RSS story, a sim drill's host-memory axis) need live
    samples. Single-sourced here for ``sim.FleetResult.summary()``'s
    host-RSS axis."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e6
    except Exception:
        # Non-Linux fallback: ru_maxrss is the MONOTONE process peak
        # (point-in-time claims degenerate toward ratio 1.0 here —
        # Linux is the measured platform), and macOS reports bytes
        # where Linux uses KB.
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return peak / (1024.0 ** 2 if sys.platform == "darwin" else 1024.0)


def post_complete_message_to_sweep_process(args, pipe_path: str = "./tmp/fedml"):
    """Write a completion line to a fifo so a sweep driver can advance
    (reference fedavg/utils.py:19-27). No-op if the fifo cannot be created."""
    try:
        os.makedirs(os.path.dirname(pipe_path), exist_ok=True)
        if not os.path.exists(pipe_path):
            os.mkfifo(pipe_path)
        fd = os.open(pipe_path, os.O_WRONLY | os.O_NONBLOCK)
        try:
            os.write(fd, f"training is finished! \n{args}\n".encode())
        finally:
            os.close(fd)
    except OSError:
        logging.getLogger(__name__).debug("no sweep fifo reader; skipping")
