"""Preflight — the `criu check` analogue.

``criu check`` validates that the kernel supports everything a dump/restore
will need *before* anyone trusts it with a workload.  Our equivalents are
runtime-library probes: JAX version and device availability, the
serialization stack (msgpack / zlib / zstd), and the
device-backend registry.  ``capabilities()`` reports; ``check()`` judges.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Any, Dict, List, Optional


def capabilities() -> Dict[str, Any]:
    """Structured report of what this environment supports."""
    import jax

    from repro.core.backends import available_backends
    from repro.core.plugins import PLUGIN_API_VERSION

    try:
        devices = jax.devices()
        platform = devices[0].platform if devices else None
        device_count = len(devices)
    except Exception:                                  # pragma: no cover
        platform, device_count = None, 0

    try:
        import msgpack
        msgpack_version = ".".join(map(str, msgpack.version))
    except Exception:                                  # pragma: no cover
        msgpack_version = None

    try:
        import zstandard
        zstd_available = True
    except Exception:
        zstd_available = False

    return {
        "plugin_api_version": PLUGIN_API_VERSION,
        "jax": {
            "version": jax.__version__,
            "platform": platform,
            "device_count": device_count,
            "process_count": jax.process_count(),
        },
        "serialization": {
            "msgpack": msgpack_version,
            "zlib": True,                     # stdlib, always present
            "zstd": zstd_available,
        },
        "backends": available_backends(),
        "modes": ["sync", "async"],
        "pack_formats": [1, 2],
        "features": {
            "incremental": True,
            "compression": True,
            "replication": True,
            "elastic_restore": True,
            "parallel_restore": True,
            "chunked_packs": True,        # pack v2: per-chunk CRC + codec
            "striped_io": True,           # N pack files/host, appender each
            "pipelined_writer": True,     # capture → compress → write stages
            "chunk_dedup": True,          # incremental reuse at chunk grain
            "delta_transfer": True,       # CAS have/want cross-host ship
            "content_addressed_store": True,   # repro.transfer.ChunkStore
            "migration": True,            # orchestrator migrate scenario
        },
        "transfer_modes": ["copy", "delta"],
    }


@dataclasses.dataclass
class CheckReport:
    ok: bool
    problems: List[str]
    warnings: List[str]
    capabilities: Dict[str, Any]

    def summary(self) -> str:
        lines = []
        status = "OK" if self.ok else "FAIL"
        lines.append(f"repro check: {status}")
        for p in self.problems:
            lines.append(f"  problem: {p}")
        for w in self.warnings:
            lines.append(f"  warning: {w}")
        return "\n".join(lines)


def check(run_dir: Optional[str] = None, options=None) -> CheckReport:
    """Validate that checkpoint/restore can work here (`criu check`).

    Probes the runtime (not just imports): builds a trivial mesh, round-
    trips a msgpack blob, and — when `run_dir` is given — proves the image
    directory is writable.  Returns a report instead of raising so
    schedulers can surface every problem at once.
    """
    problems: List[str] = []
    warns: List[str] = []

    caps = capabilities()
    if caps["jax"]["device_count"] == 0:
        problems.append("no JAX devices visible")
    if not caps["serialization"]["msgpack"]:
        problems.append("msgpack unavailable (host-state blobs need it)")
    if not caps["serialization"]["zstd"]:
        warns.append("zstandard not installed; compress=True falls "
                     "back to zlib")
    if "jax" not in caps["backends"]:
        problems.append("no 'jax' device backend registered")

    # runtime probes, not just version strings
    try:
        from repro.launch.mesh import make_mesh
        make_mesh((1,), ("data",))
    except Exception as e:
        problems.append(f"mesh construction failed: {e}")
    try:
        from repro.core.snapshot_io import pack_host_blob, unpack_host_blob
        if unpack_host_blob(pack_host_blob({"probe": 1}))["probe"] != 1:
            problems.append("msgpack round-trip corrupted data")
    except Exception as e:
        problems.append(f"msgpack round-trip failed: {e}")

    if options is not None:
        try:
            options.validate()
        except Exception as e:
            problems.append(f"invalid options: {e}")

    if run_dir is not None:
        try:
            os.makedirs(run_dir, exist_ok=True)
            with tempfile.NamedTemporaryFile(dir=run_dir, prefix=".check"):
                pass
        except Exception as e:
            problems.append(f"run_dir {run_dir!r} not writable: {e}")

    return CheckReport(ok=not problems, problems=problems,
                       warnings=warns, capabilities=caps)
