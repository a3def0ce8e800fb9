"""Device plugin: transparent capture/restore of accelerator state.

This is the cuda-checkpoint/KFD analogue.  The JAX runtime owns every byte
of device state as ``jax.Array`` shards; the plugin:

  PAUSE_DEVICES        — quiesce: drain async dispatch (DeviceLock), count
                         unregistered live device arrays (the NVML-leftover
                         analogue of paper §4.4) and record them;
  CHECKPOINT_DEVICES   — device→host: copy every addressable shard
                         (replica 0 only — replicated shards are deduped the
                         way CRIU dedups COW pages) into host memory along
                         with avals + sharding descriptors;
  RESUME_DEVICES_LATE  — host→device: rebuild arrays, fast-path 1:1 shard
                         placement when the topology fingerprint matches,
                         reassemble + reshard otherwise (elastic restore).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Tuple

import jax
import numpy as np

from repro.core.backends import DirtyTrackingMixin, JAX_BACKEND_FEATURES
from repro.core.lock import DeviceLock
from repro.core.plugins import HookContext, Plugin
from repro.core.topology import (resolve_sharding, sharding_descriptor)
from repro.serialization.pack import dtype_to_str, dtype_from_str

PyTree = Any


# ---------------------------------------------------------------- paths
def _key_str(path) -> str:
    from jax.tree_util import (DictKey, FlattenedIndexKey, GetAttrKey,
                               SequenceKey)
    parts = []
    for k in path:
        if isinstance(k, DictKey):
            parts.append(str(k.key))
        elif isinstance(k, GetAttrKey):
            parts.append(k.name)
        elif isinstance(k, SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, FlattenedIndexKey):
            parts.append(str(k.key))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _index_to_json(index: Tuple[slice, ...], shape) -> list:
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def _index_from_json(j) -> Tuple[slice, ...]:
    return tuple(slice(a, b) for a, b in j)


# ---------------------------------------------------------------- capture
def capture_array(arr: jax.Array) -> Dict[str, Any]:
    """Snapshot one device array into host memory (shards deduped)."""
    shards = []
    for sh in arr.addressable_shards:
        if sh.replica_id != 0:
            continue
        shards.append({
            "index": _index_to_json(sh.index, arr.shape),
            "data": np.asarray(sh.data),
        })
    return {
        "kind": "device_array",
        "shape": [int(s) for s in arr.shape],
        "dtype": dtype_to_str(arr.dtype),
        "sharding": sharding_descriptor(arr),
        "shards": shards,
    }


def capture_pytree(tree: PyTree) -> Dict[str, Dict[str, Any]]:
    """name(path) -> captured entry.  Host (non-jax) leaves pass through.

    Two passes: the first starts every shard's device→host transfer
    asynchronously, the second materializes host ndarrays (by which time
    the copies have been overlapping each other — the double-buffered
    capture of the pipelined data plane): while one shard's bytes are
    turned into a host ndarray, the next shards' copies are in flight, so
    the frozen window shrinks to roughly the copy itself."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for _, leaf in flat:
        if isinstance(leaf, jax.Array):
            for sh in leaf.addressable_shards:
                if sh.replica_id == 0:
                    sh.data.copy_to_host_async()
    out: Dict[str, Dict[str, Any]] = {}
    for path, leaf in flat:
        key = _key_str(path)
        if isinstance(leaf, jax.Array):
            out[key] = capture_array(leaf)
        elif isinstance(leaf, np.ndarray):
            out[key] = {"kind": "np", "data": leaf}
        else:
            out[key] = {"kind": "host", "value": leaf}
    return out


def assemble_global(entry: Dict[str, Any]) -> np.ndarray:
    """Reassemble the full logical array from saved shards."""
    shape = tuple(entry["shape"])
    out = np.empty(shape, dtype=dtype_from_str(entry["dtype"]))
    for sh in entry["shards"]:
        idx = _index_from_json(sh["index"])
        piece_shape = tuple(s.stop - s.start for s in idx)
        out[idx] = np.asarray(sh["data"]).reshape(piece_shape)
    return out


def restore_array(entry: Dict[str, Any], target_mesh=None,
                  target_sharding=None) -> jax.Array:
    """Rebuild one device array.

    Fast path: the target sharding's shard indices match the saved shard
    index set exactly — place each saved buffer on its device directly.
    Slow (elastic) path: reassemble the global array and device_put with
    the new layout.  A leaf with neither a target sharding nor a saved
    named layout that maps onto `target_mesh` lands on the default
    device.
    """
    shape = tuple(entry["shape"])
    dtype_from_str(entry["dtype"])      # validates the stored dtype
    sharding = target_sharding
    if sharding is None:
        sharding = resolve_sharding(entry["sharding"], target_mesh)

    if sharding is None:
        return jax.device_put(assemble_global(entry))

    saved = {tuple(map(tuple, sh["index"])): sh["data"]
             for sh in entry["shards"]}
    wanted = {dev: tuple(map(tuple, _index_to_json(idx, shape)))
              for dev, idx in sharding.devices_indices_map(shape).items()}
    if all(key in saved for key in wanted.values()):
        pieces = [jax.device_put(saved[key], dev)
                  for dev, key in wanted.items()]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, pieces)
    # elastic / mismatched layout: reassemble then reshard
    return jax.device_put(assemble_global(entry), sharding)


# ---------------------------------------------------------------- plugin
class DevicePlugin(DirtyTrackingMixin, Plugin):
    """The "jax" device backend (see ``repro.core.backends``)."""

    name = "device"
    api_version = 1
    features = JAX_BACKEND_FEATURES

    def __init__(self, lock_timeout_s: float = 10.0,
                 restore_threads: int = 0):
        self.lock = DeviceLock(lock_timeout_s)
        self.restore_threads = restore_threads
        self.streams = None

    def capture_entry(self, leaf) -> Dict[str, Any]:
        """Single-leaf capture for the concurrent speculation loop.
        Raises if the leaf was donated away (deleted) — the engine notes
        it dirty and re-captures the live value at the validate pause."""
        if isinstance(leaf, jax.Array):
            return capture_array(leaf)
        if isinstance(leaf, np.ndarray):
            return {"kind": "np", "data": leaf}
        return {"kind": "host", "value": leaf}

    # --- dump ---
    def pause_devices(self, ctx: HookContext) -> None:
        roots = getattr(ctx, "roots", {})
        arrays = [l for l in jax.tree.leaves(roots)
                  if isinstance(l, jax.Array)]
        t = self.lock.lock(arrays)
        ctx.stats["lock_s"] = t
        self.drain_streams()       # CRAC boundary: may raise UnsafeOp
        # leftover-reference detection (NVML analogue, paper §4.4)
        root_ids = {id(a) for a in arrays}
        leftover = 0
        for a in jax.live_arrays():
            if id(a) not in root_ids and not a.is_deleted():
                leftover += a.nbytes
        ctx.stats["leftover_device_bytes"] = float(leftover)
        if leftover:
            ctx.warnings.append(
                f"{leftover} bytes of live device arrays outside the "
                f"registered roots (jit-cache constants / temporaries); "
                f"these are re-creatable and excluded from the image")

    def checkpoint_devices(self, ctx: HookContext) -> None:
        t0 = time.perf_counter()
        dev_bytes = 0
        for name, tree in getattr(ctx, "roots", {}).items():
            cap = capture_pytree(tree)
            ctx.device_snapshot[name] = cap
            for e in cap.values():
                if e["kind"] == "device_array":
                    dev_bytes += sum(s["data"].nbytes for s in e["shards"])
        ctx.stats["device_to_host_s"] = time.perf_counter() - t0
        ctx.stats["capture_s"] = ctx.stats["device_to_host_s"]
        ctx.stats["device_bytes"] = float(dev_bytes)

    # --- restore ---
    def update_topology_map(self, ctx: HookContext) -> None:
        from repro.core.topology import compatibility, mesh_fingerprint
        saved = ctx.manifest.get("topology", {})
        target = mesh_fingerprint(ctx.target_mesh)
        ctx.topology_map["mode"] = compatibility(saved, target)
        ctx.topology_map["target"] = target

    def _flat_shardings(self, ctx: HookContext, state: str) -> Dict[str, Any]:
        cache = getattr(ctx, "_flat_sh_cache", None)
        if cache is None:
            cache = ctx._flat_sh_cache = {}
        if state not in cache:
            flat: Dict[str, Any] = {}
            shardings = ctx.target_shardings.get(state)
            if shardings is not None:
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                        shardings)[0]:
                    flat[_key_str(path)] = leaf
            cache[state] = flat
        return cache[state]

    def _place_entry(self, ctx: HookContext, reader, state: str,
                     path: str):
        """Load + rebuild one logical leaf — the unit the lazy
        materializer streams, so arrays come back incrementally as their
        shards land."""
        entry = reader.load_entry(state, path)
        if entry["kind"] == "device_array":
            return restore_array(entry, ctx.target_mesh,
                                 self._flat_shardings(ctx, state).get(path))
        if entry["kind"] == "np":
            return entry["data"]
        return entry["value"]

    def resume_devices_late(self, ctx: HookContext) -> None:
        """host→device restore, with on-demand parallel entry loading (the
        paper cites this optimization from Yang et al. SoCC'24): worker
        threads stream pack entries from storage while the main thread
        places shards on devices.

        Lazy mode (resume-before-read): only the critical set is placed
        here; the rest of the image is handed to a LazyMaterializer the
        engine starts after the job is unlocked, and arrays rebuild
        incrementally as their shards land."""
        t0 = time.perf_counter()
        reader = ctx.reader
        threads = getattr(ctx, "restore_threads", 0) or self.restore_threads
        if getattr(ctx, "lazy", False):
            from repro.core.lazy import resume_with_schedule
            resume_with_schedule(
                ctx, lambda r, s, p: self._place_entry(ctx, r, s, p),
                threads)
            self.lock.unlock()                        # resume on criticals
            ctx.stats["host_to_device_s"] = time.perf_counter() - t0
            ctx.stats["place_s"] = ctx.stats.get("place_critical_s", 0.0)
            return
        place_s = 0.0
        for name in reader.state_names():
            flat_sh = self._flat_shardings(ctx, name)
            keys = reader.entry_names(name)
            if threads > 1 and len(keys) > 1:
                from concurrent.futures import ThreadPoolExecutor
                with ThreadPoolExecutor(max_workers=threads) as ex:
                    entries = list(ex.map(
                        lambda k: reader.load_entry(name, k), keys))
            else:
                entries = [reader.load_entry(name, k) for k in keys]
            restored: Dict[str, Any] = {}
            t_place = time.perf_counter()
            for key, entry in zip(keys, entries):
                if entry["kind"] == "device_array":
                    arr = restore_array(entry, ctx.target_mesh,
                                        flat_sh.get(key))
                elif entry["kind"] == "np":
                    arr = entry["data"]
                else:
                    arr = entry["value"]
                restored[key] = arr
            place_s += time.perf_counter() - t_place
            ctx.restored[name] = _unflatten_paths(restored)
        self.lock.unlock()
        ctx.stats["host_to_device_s"] = time.perf_counter() - t0
        ctx.stats["place_s"] = place_s


def _unflatten_paths(flat: Dict[str, Any]) -> Dict[str, Any]:
    """'a/b/c' -> nested dicts (CRIU-image-style raw view of the tree)."""
    out: Dict[str, Any] = {}
    for key, val in flat.items():
        node = out
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return out


def flatten_with_paths(tree: PyTree) -> Dict[str, Any]:
    return {_key_str(p): l
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}
