"""Persistent AOT executable cache: serialize compiled XLA programs to
disk so a restarted process deserializes instead of recompiling
(docs/PERFORMANCE.md §Superstep & AOT executable cache).

Cold-start after a gang restart (tools/launch.py --max-restarts) is a
production SLO: today every rank pays the full trace + XLA compile of its
step/updater programs again — minutes for model-sized programs — before
the first post-restart step dispatches.  This module closes that gap with
ahead-of-time lowering at the jit sites that dominate that wall
(``DataParallelStep`` single-step and superstep executables,
``FusedUpdater`` fused-apply groups): the site lowers explicitly
(``jax.jit(...).lower(*args).compile()``), the compiled executable is
serialized via ``jax.experimental.serialize_executable`` (verified
working on the pinned jax) under ``MX_EXECUTABLE_CACHE_DIR``, and a
restarted process loads the bytes back in milliseconds.

Cache key contract (the reason PR 8 made ``memwatch.fingerprint``
restart-stable): an entry is addressed by

    (memwatch.fingerprint(parts), jax.__version__, platform, mesh shape)

— structural program identity only, never object ids, so the same
program in a restarted process maps to the same entry; a jax upgrade, a
different backend, or a different mesh shape silently misses instead of
loading an incompatible executable.

Failure posture: the cache is an OPTIMIZATION and must never take a
training run down.  Corrupt / truncated / version-mismatched entries,
serialization not supported for a program, unwritable cache directories —
every failure falls back to the normal compile path (logged at debug/
warning, surfaced as ``cache_corrupt`` on the compile telemetry event
where applicable).  ``MX_EXECUTABLE_CACHE=0`` is the kill switch: no
loads, no stores, byte-for-byte the pre-cache behavior.
"""
from __future__ import annotations

import logging
import os
import pickle
import time
from typing import Any, Dict, Optional, Tuple

__all__ = ["enabled", "cache_dir", "cache_key", "entry_path",
           "get_or_compile", "load", "store"]

_LOG = logging.getLogger("mxnet_tpu.aot_cache")

# bumped whenever the on-disk layout changes; a mismatch is a miss
_MAGIC = "MXAOT1"


def enabled() -> bool:
    """AOT persistence is on when ``MX_EXECUTABLE_CACHE_DIR`` names a
    directory and the ``MX_EXECUTABLE_CACHE`` kill switch isn't 0."""
    if not os.environ.get("MX_EXECUTABLE_CACHE_DIR"):
        return False
    return os.environ.get("MX_EXECUTABLE_CACHE", "1").lower() not in (
        "0", "false", "off")


def cache_dir() -> Optional[str]:
    return os.environ.get("MX_EXECUTABLE_CACHE_DIR") or None


def cache_key(fingerprint: str, platform: str,
              mesh_shape: Tuple = (), device_ids: Tuple = ()) -> str:
    """Filename-safe entry key: program fingerprint x jax version x
    backend platform x mesh shape x device assignment.  The fingerprint
    already encodes structural identity (shapes/dtypes/static hypers);
    version/platform/mesh ride alongside explicitly so an incompatible
    executable can never be addressed, only missed.  ``device_ids`` (the
    mesh's global device ids) matter because the serialized executable
    embeds its device assignment: in a gang where ranks run LOCAL
    per-rank meshes, rank 1's program targets global device 1 — rank 0's
    entry would deserialize to an assignment with no local devices.
    Ranks sharing one global SPMD mesh share one key (identical
    assignment), which is the useful sharing."""
    import hashlib

    import jax

    env = hashlib.sha256(
        repr((jax.__version__, platform, tuple(mesh_shape),
              tuple(device_ids))).encode()
    ).hexdigest()[:8]
    return f"{fingerprint}-{env}"


def entry_path(key: str) -> Optional[str]:
    d = cache_dir()
    if d is None:
        return None
    return os.path.join(d, f"{key}.jexec")


def store(key: str, compiled, meta: Optional[Dict[str, Any]] = None) -> bool:
    """Serialize ``compiled`` (a jax.stages.Compiled) under ``key``.
    Atomic (tmp + rename) so a concurrently-restarting rank never reads a
    torn entry; best-effort — failures are logged, never raised."""
    path = entry_path(key)
    if path is None:
        return False
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        import jax

        blob = pickle.dumps({
            "magic": _MAGIC,
            "jax": jax.__version__,
            "key": key,
            "meta": dict(meta or {}),
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        })
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except Exception as e:  # the cache must never take training down
        _LOG.warning("aot_cache: failed to store %s: %s", key, e)
        return False


def load(key: str, platform: Optional[str] = None, device_ids: Tuple = ()):
    """Deserialize the entry under ``key`` -> (loaded_executable, info)
    or (None, info).  ``info['cache_corrupt']`` marks an entry that
    existed but could not be loaded (truncated, garbled, wrong version) —
    the caller falls back to a fresh compile, which overwrites it.

    ``device_ids`` are the devices the executable was compiled for (the
    ids :func:`cache_key` hashes): it loads onto exactly those, where
    jax's default is every device of the backend."""
    info: Dict[str, Any] = {}
    path = entry_path(key)
    if path is None or not os.path.exists(path):
        return None, info
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            rec = pickle.load(f)
        import jax

        if (not isinstance(rec, dict) or rec.get("magic") != _MAGIC
                or rec.get("jax") != jax.__version__
                or rec.get("key") != key):
            raise ValueError("entry metadata mismatch")
        from jax.experimental import serialize_executable as se

        by_id = {int(d.id): d for d in jax.devices(platform)}
        loaded = se.deserialize_and_load(
            rec["payload"], rec["in_tree"], rec["out_tree"],
            backend=platform,
            execution_devices=[by_id[i] for i in device_ids] or None)
        info["cache_hit"] = True
        info["deserialize_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3)
        # caller-supplied meta rides back out: sites whose executables
        # need structural facts the python fn only yields at trace time
        # (CachedOp's output treedef/aux binding) restore them from here
        # instead of paying the trace a cache hit exists to skip
        info["meta"] = rec.get("meta") or {}
        return loaded, info
    except Exception as e:
        # torn write, partial disk, version drift, pickle garbage: all
        # fall back to a fresh compile (which re-stores a clean entry)
        _LOG.warning("aot_cache: corrupt/unloadable entry %s (%s); "
                     "falling back to fresh compile", key, e)
        info["cache_corrupt"] = True
        return None, info


def get_or_compile(jitted, args, fingerprint: str, platform: str,
                   mesh_shape: Tuple = (), device_ids: Tuple = (),
                   meta_fn=None):
    """The jit-site entry point: resolve ``fingerprint`` to a compiled
    executable — deserialized from the persistent cache when warm, else
    compiled ahead-of-time (``jitted.lower(*args).compile()``) and
    stored.  Returns ``(compiled_or_None, info)``; ``None`` means the
    cache is disabled or AOT failed entirely and the caller should fall
    back to calling ``jitted`` directly (the plain jit path).

    ``info`` feeds the compile telemetry event: ``cache_hit`` +
    ``deserialize_ms`` on a warm load, ``cache_hit=False`` (+ optional
    ``cache_corrupt``) after a fresh AOT compile.

    ``meta_fn`` (optional, zero-arg) supplies extra entry metadata and
    is called AFTER the fresh compile — i.e. after ``jitted`` traced,
    so structural facts the trace produces as side effects can be
    captured; on a warm load the stored metadata returns in
    ``info['meta']`` instead."""
    if not enabled():
        return None, {}
    try:
        key = cache_key(fingerprint, platform, mesh_shape, device_ids)
        compiled, info = load(key, platform, device_ids)
        if meta_fn is None:
            # only sites that persist structural meta consume it; the
            # others forward info verbatim into compile telemetry
            # events, which must not grow a redundant meta blob
            info.pop("meta", None)
        if compiled is not None:
            return compiled, info
        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        info["cache_hit"] = False
        info["aot_compile_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        meta = {"fingerprint": fingerprint, "platform": platform,
                "mesh_shape": tuple(mesh_shape)}
        if meta_fn is not None:
            meta.update(meta_fn() or {})
        store(key, compiled, meta=meta)
        return compiled, info
    except Exception as e:
        _LOG.warning("aot_cache: AOT compile/load failed for %s (%s); "
                     "falling back to plain jit dispatch", fingerprint, e)
        return None, {}
