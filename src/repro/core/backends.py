"""Versioned device-backend registry — the CUDA/ROCm plugin split.

CRIUgpu registers its CUDA and AMD/KFD plugins against the CRIU plugin API
and CRIU picks whichever matches the hardware; the plugin carries a version
stamp so a CRIU built for a different plugin ABI refuses to load it
(paper §3.1.3).  We mirror that: a ``DeviceBackend`` is a named, versioned,
feature-stamped plugin that owns the device side of the dump/restore hook
sequence, and the registry here maps names to factories:

  "jax"   — the JAX-array backend (``DevicePlugin``): device lock, shard
            dedup, sharded/elastic restore.  The CUDA-analogue default.
  "host"  — host-numpy fallback: captures every leaf as host memory and
            restores without touching devices.  Used by the CLI's
            ``restore --dry-run`` and by environments where device
            placement is unavailable or unwanted.

Backends register with the ``api_version`` they were built against; a
mismatch is rejected at registration (and again by ``PluginRegistry.add``),
so a stale backend can never silently corrupt an image.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, FrozenSet, Iterable

from repro.core.plugins import (PLUGIN_API_VERSION, HookContext,
                                Plugin, PluginVersionError)

try:  # Protocol is typing-only sugar; keep the module importable anywhere
    from typing import Protocol, runtime_checkable
except ImportError:  # pragma: no cover
    Protocol = object

    def runtime_checkable(cls):
        return cls


class BackendError(RuntimeError):
    """Unknown backend name or invalid registration."""


#: Feature flags of the "jax" backend; DevicePlugin.features references
#: this so the registration below and the plugin stamp cannot drift.
JAX_BACKEND_FEATURES = frozenset({
    "device_arrays", "sharded_restore", "parallel_restore",
    "elastic_restore", "replica_dedup", "chunked_packs", "pipelined_io",
    "dirty_tracking"})


class DirtyTrackingMixin:
    """Concurrent-capture (soft-freeze) protocol shared by backends that
    advertise the "dirty_tracking" feature.

    Four pieces: a flat keyed view of the live roots (``flatten_keys``),
    single-leaf capture (``capture_entry``), wiring a
    :class:`repro.core.dirty.DirtyTracker` to stream retirements
    (``begin_tracking``/``end_tracking``), and the explicit CRAC-style
    capture boundary (``attach_streams``/``drain_streams`` — every
    capture pause drains the injectable fake streams and fails fast with
    :class:`repro.core.streams.UnsafeOpInFlight` if an op cannot be
    quiesced, instead of snapshotting torn state).
    """

    streams = None            # Optional[repro.core.streams.StreamSet]
    _tracker = None

    def attach_streams(self, streams) -> None:
        """Install the injectable fake-stream plane (tests, sims, the
        host backend's async-dispatch model)."""
        self.streams = streams

    def drain_streams(self) -> None:
        """Quiesce the capture boundary; raises UnsafeOpInFlight on a
        stuck op.  Called under the device lock at every pause."""
        if self.streams is None:
            return
        from repro.core.streams import UnsafeOpInFlight
        stuck = self.streams.drain()
        if stuck:
            raise UnsafeOpInFlight(stuck)

    def flatten_keys(self, roots: Dict[str, Any]) -> Dict[str, Any]:
        """roots -> {"state::path": leaf} in capture order."""
        from repro.core.device_plugin import flatten_with_paths
        out: Dict[str, Any] = {}
        for name, tree in roots.items():
            for key, leaf in flatten_with_paths(tree).items():
                out[f"{name}::{key}"] = leaf
        return out

    def capture_entry(self, leaf: Any) -> Dict[str, Any]:
        """Capture one leaf into a snapshot entry dict.  Overridden by
        the jax backend to capture device arrays shard-by-shard."""
        import numpy as np
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return {"kind": "np", "data": np.asarray(leaf)}
        return {"kind": "host", "value": leaf}

    def begin_tracking(self, tracker) -> None:
        """Route stream retirements into the dirty set for the duration
        of a concurrent capture."""
        self._tracker = tracker
        if self.streams is not None:
            self.streams.on_retire = (
                lambda op: tracker.note_many(op.targets))

    def end_tracking(self) -> None:
        self._tracker = None
        if self.streams is not None:
            self.streams.on_retire = None


@runtime_checkable
class DeviceBackend(Protocol):
    """The device side of the checkpoint contract.

    Structural protocol extracted from ``DevicePlugin``: any Plugin that
    implements the three device hooks (pause / checkpoint / resume-late)
    plus the identity stamps can serve as the engine's device backend.
    """

    name: str
    api_version: int
    features: FrozenSet[str]

    def pause_devices(self, ctx: HookContext) -> None: ...
    def checkpoint_devices(self, ctx: HookContext) -> None: ...
    def update_topology_map(self, ctx: HookContext) -> None: ...
    def resume_devices_late(self, ctx: HookContext) -> None: ...


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    factory: Callable[..., Plugin]
    api_version: int
    features: FrozenSet[str]
    description: str = ""


_REGISTRY: Dict[str, BackendSpec] = {}


def register_backend(name: str, factory: Callable[..., Plugin], *,
                     api_version: int,
                     features: Iterable[str] = (),
                     description: str = "",
                     override: bool = False) -> BackendSpec:
    """Register a device backend under `name`.

    Rejects (PluginVersionError) backends declaring an api_version other
    than the one this engine speaks — the CRIU "plugin built for another
    CRIU" refusal, at registration time rather than dump time.
    """
    if api_version != PLUGIN_API_VERSION:
        raise PluginVersionError(
            f"backend {name!r} declares api_version={api_version}; "
            f"this engine speaks api_version={PLUGIN_API_VERSION}")
    if name in _REGISTRY and not override:
        raise BackendError(f"backend {name!r} already registered")
    spec = BackendSpec(name=name, factory=factory, api_version=api_version,
                       features=frozenset(features),
                       description=description)
    _REGISTRY[name] = spec
    return spec


def create_backend(name: str, **kwargs) -> Plugin:
    """Instantiate a registered backend by name."""
    try:
        spec = _REGISTRY[name]
    except KeyError:
        raise BackendError(
            f"unknown device backend {name!r}; "
            f"registered: {sorted(_REGISTRY)}") from None
    plugin = spec.factory(**kwargs)
    if getattr(plugin, "api_version", None) != PLUGIN_API_VERSION:
        raise PluginVersionError(
            f"backend {name!r} produced a plugin with "
            f"api_version={getattr(plugin, 'api_version', None)!r}")
    plugin.backend_name = name       # registry name (plugin.name may differ)
    return plugin


def available_backends() -> Dict[str, Dict[str, Any]]:
    """name -> {api_version, features, description} for capability reports."""
    return {n: {"api_version": s.api_version,
                "features": sorted(s.features),
                "description": s.description}
            for n, s in sorted(_REGISTRY.items())}


# ---------------------------------------------------------------- host
class HostNumpyBackend(DirtyTrackingMixin, Plugin):
    """Device backend that never touches an accelerator.

    Capture converts every array leaf to host numpy (one logical shard);
    restore materialises numpy arrays and leaves device placement to the
    caller.  This is the "no driver" path: image surgery, CLI dry-run
    restores, and CI machines without working accelerator runtimes.
    """

    name = "host"
    api_version = PLUGIN_API_VERSION
    features = frozenset({"host_arrays", "dry_run_restore",
                          "chunked_packs", "pipelined_io",
                          "dirty_tracking"})

    def __init__(self, lock_timeout_s: float = 10.0,
                 restore_threads: int = 0):
        # same constructor surface as the jax backend so the engine can
        # build either from one options object
        from repro.core.lock import DeviceLock
        self.lock = DeviceLock(lock_timeout_s)
        self.restore_threads = restore_threads
        self.streams = None

    # --- dump ---
    def pause_devices(self, ctx: HookContext) -> None:
        ctx.stats["lock_s"] = self.lock.lock([])
        self.drain_streams()       # CRAC boundary: may raise UnsafeOp

    def checkpoint_devices(self, ctx: HookContext) -> None:
        import numpy as np
        t0 = time.perf_counter()
        host_bytes = 0
        for name, tree in getattr(ctx, "roots", {}).items():
            from repro.core.device_plugin import flatten_with_paths
            cap: Dict[str, Any] = {}
            for key, leaf in flatten_with_paths(tree).items():
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                    arr = np.asarray(leaf)
                    cap[key] = {"kind": "np", "data": arr}
                    host_bytes += arr.nbytes
                else:
                    cap[key] = {"kind": "host", "value": leaf}
            ctx.device_snapshot[name] = cap
        ctx.stats["device_to_host_s"] = time.perf_counter() - t0
        ctx.stats["capture_s"] = ctx.stats["device_to_host_s"]
        ctx.stats["device_bytes"] = float(host_bytes)

    # --- restore ---
    def update_topology_map(self, ctx: HookContext) -> None:
        ctx.topology_map["mode"] = "host"
        ctx.topology_map["target"] = None

    @staticmethod
    def _place_entry(reader, state: str, path: str):
        from repro.core.device_plugin import assemble_global, rebuild_entry
        return rebuild_entry(reader.load_entry(state, path), assemble_global)

    def resume_devices_late(self, ctx: HookContext) -> None:
        from repro.core.device_plugin import assemble_global, restore_eager
        t0 = time.perf_counter()
        reader = ctx.reader
        threads = getattr(ctx, "restore_threads", 0) or self.restore_threads
        if getattr(ctx, "lazy", False):
            from repro.core.lazy import resume_with_schedule
            resume_with_schedule(ctx, self._place_entry, threads)
            self.lock.unlock()                        # resume on criticals
            ctx.stats["host_to_device_s"] = time.perf_counter() - t0
            ctx.stats["place_s"] = ctx.stats.get("place_critical_s", 0.0)
            return
        place_s = restore_eager(ctx, reader, threads,
                                lambda s, p, e: assemble_global(e))
        self.lock.unlock()
        ctx.stats["host_to_device_s"] = time.perf_counter() - t0
        ctx.stats["place_s"] = place_s


def _make_jax_backend(**kwargs) -> Plugin:
    from repro.core.device_plugin import DevicePlugin
    return DevicePlugin(**kwargs)


register_backend(
    "jax", _make_jax_backend, api_version=PLUGIN_API_VERSION,
    features=JAX_BACKEND_FEATURES,
    description="JAX-array device backend (lock, shard dedup, elastic "
                "restore) — the CUDA-plugin analogue")

register_backend(
    "host", HostNumpyBackend, api_version=PLUGIN_API_VERSION,
    features=HostNumpyBackend.features,
    description="host-numpy fallback: capture/restore without touching "
                "devices (CLI dry-run, driverless environments)")
