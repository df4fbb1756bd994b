"""Runtime/device helpers for the partitioning facade.

Deliberately free of ``jax``/``repro`` imports at module level: CLIs call
``force_host_devices`` *before* anything that could initialize a jax
backend, and importing this module must never be the thing that does it.
"""
from __future__ import annotations

import os
import sys
from typing import Any, List, Optional, Sequence

_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CACHE_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
# <checkout>/.jax_cache: a fixed path, since a cache whose directory
# moves is never found again (this file is <checkout>/src/repro/api/...)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def jax_backend_initialized() -> bool:
    """True iff a jax backend has been created in this process (at which
    point the device count is locked and XLA_FLAGS edits are ignored)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and bool(xb._backends)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    the directory is left alone. Otherwise the cache goes to
    ``<checkout>/.jax_cache``. Either way every compile is cached, not
    only those over JAX's default one second: the per-level programs of
    the multilevel scheme are small and many. Entry points call this
    before JAX initializes; child processes inherit the setting.
    """
    os.environ.setdefault(_CACHE_MIN_SECS_ENV, "0")
    os.environ.setdefault(_CACHE_ENV, DEFAULT_CACHE_DIR)
    if "jax" in sys.modules:     # imported already: its config is read
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          os.environ[_CACHE_ENV])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(os.environ[_CACHE_MIN_SECS_ENV]))
    return os.environ[_CACHE_ENV]


def local_tpu_chips() -> int:
    """TPU chips on this host's PCI bus. Opens no device and starts no
    backend, so a parent process can ask without holding a chip."""
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def device_count() -> int:
    """Devices visible to jax (initializes the backend on first call)."""
    import jax
    return len(jax.devices())


def device_slices(num_slices: int,
                  devices_per_slice: int) -> List[List[Any]]:
    """Carve the host's devices into ``num_slices`` disjoint contiguous
    slices of ``devices_per_slice`` devices each (the serving tier's
    worker meshes — saxml-style: one model server per device group).

    Initializes jax. Raises ``RuntimeError`` when the host doesn't have
    ``num_slices * devices_per_slice`` devices — oversubscribing a
    device into two meshes would serialize their collectives against
    each other, which is exactly what a multi-mesh tier exists to avoid.
    """
    if num_slices < 1 or devices_per_slice < 1:
        raise ValueError(
            "need num_slices >= 1 and devices_per_slice >= 1, got "
            f"{num_slices} x {devices_per_slice}")
    import jax
    devs = jax.devices()
    need = num_slices * devices_per_slice
    if len(devs) < need:
        # name the shortfall AND the largest feasible carve, both ways
        # round — the caller decides whether to shrink the slice count
        # or the slices themselves
        feas_slices = len(devs) // devices_per_slice
        feas_per = len(devs) // num_slices
        if feas_slices >= 1:
            hint = (f"largest feasible: {feas_slices} slice(s) of "
                    f"{devices_per_slice}")
            if feas_per >= 1 and feas_per != devices_per_slice:
                hint += (f", or {num_slices} slice(s) of {feas_per} "
                         "device(s)")
        elif feas_per >= 1:
            hint = (f"largest feasible: {num_slices} slice(s) of "
                    f"{feas_per} device(s)")
        else:
            hint = "no carve of this shape is feasible"
        if devs[0].platform == "cpu":
            hint += (". Force more host devices with force_host_devices() "
                     "before any jax computation")
        raise RuntimeError(
            f"cannot carve {num_slices} slice(s) of {devices_per_slice} "
            f"device(s) ({need} total): only {len(devs)} device(s) "
            f"available ({devs[0].platform}); {hint}")
    return [devs[i * devices_per_slice:(i + 1) * devices_per_slice]
            for i in range(num_slices)]


def distributed_init(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids: Optional[Sequence[int]] = None
                     ) -> dict:
    """Multi-process jax runtime for the serving fabric's workers.

    Wraps ``jax.distributed.initialize`` so each fabric worker process
    owns its own mesh over *its* slice of a real multi-host topology —
    the mode that lets the ``dist`` / ``dist-grid`` backends stop
    depending on ``force_host_devices``-faked devices. Arguments fall
    back to the ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` /
    ``REPRO_PROCESS_ID`` environment variables (and from there to jax's
    own cluster auto-detection inputs).

    ``num_processes`` of 1 (or unset with no coordinator) is the
    single-process mode: a deliberate no-op, so the same worker entry
    point runs unchanged on a laptop, in CI (with
    ``force_host_devices``) and on a cluster. Returns an info dict
    (``mode``, ``process_id``, ``num_processes``).

    Must run before any jax computation: like ``force_host_devices``,
    this raises ``RuntimeError`` once a backend exists rather than
    silently doing nothing.
    """
    coordinator_address = coordinator_address or \
        os.environ.get("REPRO_COORDINATOR") or None
    if num_processes is None:
        env_np = os.environ.get("REPRO_NUM_PROCESSES")
        num_processes = int(env_np) if env_np else None
    if process_id is None:
        env_pid = os.environ.get("REPRO_PROCESS_ID")
        process_id = int(env_pid) if env_pid else None
    if coordinator_address is None and (num_processes or 1) <= 1:
        return {"mode": "single-process", "process_id": 0,
                "num_processes": 1}
    if num_processes is not None and num_processes < 1:
        raise ValueError(
            f"num_processes must be >= 1, got {num_processes}")
    if process_id is not None and num_processes is not None and \
            not (0 <= process_id < num_processes):
        raise ValueError(
            f"process_id {process_id} out of range for "
            f"{num_processes} process(es)")
    if jax_backend_initialized():
        raise RuntimeError(
            "cannot initialize the multi-process runtime: jax already "
            "has a backend in this process. Call distributed_init() "
            "before any jax computation (first thing in main()).")
    import jax
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id,
        local_device_ids=local_device_ids)
    return {"mode": "multi-process",
            "process_id": jax.process_index(),
            "num_processes": jax.process_count()}


def force_host_devices(n: int) -> None:
    """Force ``n`` host (CPU) devices via XLA_FLAGS.

    Safe to call multiple times; replaces any earlier count in the flag.
    If jax is already *initialized* this cannot take effect any more:
    the call is a no-op when enough devices exist, and raises a clear
    ``RuntimeError`` otherwise (instead of the old silent reliance on
    import order).
    """
    if n <= 0:
        return
    if jax_backend_initialized():
        have = device_count()
        if have >= n:
            return
        raise RuntimeError(
            f"cannot force {n} host devices: jax is already initialized "
            f"with {have} device(s). Call force_host_devices() before any "
            "jax computation (e.g. first thing in main()), or run in a "
            "fresh subprocess.")
    kept = [t for t in os.environ.get("XLA_FLAGS", "").split()
            if not t.startswith(_FLAG)]
    kept.append(f"{_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)
