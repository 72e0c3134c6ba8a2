"""Multi-process (multi-host) distributed backend.

Reference parity: ps-lite's scheduler rendezvous + ZMQ data plane
(3rdparty/ps-lite, src/kvstore/kvstore_dist.h worker side,
kvstore_dist_server.h server side) and tools/launch.py's DMLC_* env
contract.  TPU-native design (SURVEY §2.4, §5.8): the rendezvous is
jax.distributed.initialize (coordination service), and the data plane is a
COMPILED XLA collective over the global device mesh — gradients are summed
by `psum` riding DCN (Gloo on CPU hosts, ICI/DCN on pods), never staged
through host memory the way a parameter server would.

Environment contract (reference tools/launch.py exports DMLC_*; both
spellings are honored so reference launch scripts work unchanged):

  MX_COORDINATOR      / DMLC_PS_ROOT_URI + DMLC_PS_ROOT_PORT
  MX_NUM_PROCS        / DMLC_NUM_WORKER
  MX_PROC_ID          / DMLC_WORKER_ID
"""
from __future__ import annotations

import os
import time
from typing import Dict, Optional, Tuple

import numpy as np

from ..base import MXNetError

__all__ = ["init_from_env", "is_initialized", "allreduce_sum",
           "process_index", "process_count", "bucket_cap_bytes",
           "flatten_bucket", "unflatten_bucket"]

_initialized = False


def _env(*names, default=None):
    for n in names:
        v = os.environ.get(n)
        if v is not None:
            return v
    return default


def _jax_distributed_active() -> bool:
    """True when jax.distributed.initialize already ran (by us or by the
    user's own pod-startup code)."""
    try:
        from jax._src import distributed as _jd

        return _jd.global_state.client is not None
    except Exception:
        return False


def init_from_env(force_cpu: Optional[bool] = None) -> bool:
    """Connect this process to the coordination service if the launcher env
    is present (reference: ps::Postoffice::Start reading DMLC_ROLE etc.).

    Returns True when running multi-process after the call.  Idempotent,
    and treats a distributed runtime that the USER already initialized
    (conventional on pod startup) as success.

    jax requires this to run before any computation initializes the
    backends — mxnet_tpu/__init__ therefore calls this at import time when
    the launcher env is present; the KVStore constructor is only a
    fallback for exotic import orders.
    """
    global _initialized
    import jax

    if _initialized or _jax_distributed_active():
        _initialized = True
        return jax.process_count() > 1
    coord = _env("MX_COORDINATOR")
    if coord is None:
        uri = _env("DMLC_PS_ROOT_URI")
        port = _env("DMLC_PS_ROOT_PORT")
        coord = f"{uri}:{port}" if uri and port else None
    n = _env("MX_NUM_PROCS", "DMLC_NUM_WORKER")
    rank = _env("MX_PROC_ID", "DMLC_WORKER_ID")
    if coord is None or n is None or rank is None:
        return False  # single-process
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise MXNetError(
            "the distributed launcher env (MX_COORDINATOR/MX_NUM_PROCS) is "
            "set, but jax backends were already initialized before the "
            "rendezvous could run.  Import mxnet_tpu (or create the dist "
            "kvstore) BEFORE running any computation, or call "
            "jax.distributed.initialize() yourself at program start.")
    if force_cpu or (force_cpu is None and _env("MX_FORCE_CPU") == "1"):
        jax.config.update("jax_platforms", "cpu")
    # CPU hosts need an explicit cross-process collectives implementation:
    # the default ("none") makes every multiprocess computation fail with
    # "Multiprocess computations aren't implemented on the CPU backend".
    # Harmless on TPU (the flag only affects CPU client creation).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _initialize_with_retry(coord, int(n), int(rank))
    _initialized = True
    return jax.process_count() > 1


def _initialize_with_retry(coord: str, n: int, rank: int) -> None:
    """jax.distributed.initialize with exponential-backoff retries up to
    MX_RENDEZVOUS_TIMEOUT seconds (default 300).

    After a supervised gang restart (tools/launch.py --max-restarts) the
    re-spawned ranks race the new coordinator: a non-zero rank can dial
    before rank 0's coordination service is listening, and a too-fast
    restart can find the port still in TIME_WAIT — both surface as an
    immediate initialize() error that a bounded retry absorbs."""
    import jax

    import logging

    from .. import fault
    from .. import telemetry

    timeout = float(_env("MX_RENDEZVOUS_TIMEOUT", default="300"))
    deadline = time.monotonic() + timeout
    delay = 0.5
    retries = 0
    while True:
        try:
            # chaos harness: `crash-rendezvous` dies HERE — the elastic
            # re-rendezvous failure shape (a re-admitted host that dials
            # the fresh coordinator and drops dead)
            fault.on_rendezvous()
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=n, process_id=rank,
                initialization_timeout=max(
                    10, int(deadline - time.monotonic())))
            telemetry.record("rendezvous", coordinator=coord, nproc=n,
                             retries=retries)
            _record_resize(n)
            return
        except (TypeError, ValueError):
            raise  # misconfiguration, deterministic — fail fast, no retry
        except Exception as e:
            # jax assigns global_state.client BEFORE client.connect(), so
            # a failed connect leaves a half-initialized client (and, on
            # rank 0, a live coordination service) behind; without this
            # teardown the next attempt dies with "initialize should only
            # be called once" — and that stale client must NOT be taken
            # as rendezvous success.
            try:
                jax.distributed.shutdown()
            except Exception:
                # best-effort teardown of the half-initialized client while
                # already on the retry path — the real error is re-raised
                # or retried below
                pass
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise MXNetError(
                    f"rendezvous with coordinator {coord} (rank {rank}/{n}) "
                    f"failed after {timeout:.0f}s — set MX_RENDEZVOUS_TIMEOUT "
                    f"to extend; last error: {e}") from e
            logging.getLogger("mxnet_tpu.dist").warning(
                "rendezvous with %s failed (%s); retrying for another "
                "%.0fs", coord, e, remaining)
            retries += 1
            telemetry.record("rendezvous_retry", coordinator=coord,
                             retries=retries, error=str(e)[:200])
            time.sleep(min(delay, remaining))
            delay = min(delay * 2, 10.0)


def _record_resize(n: int) -> None:
    """One telemetry ``resize`` event when this incarnation follows an
    elastic world-size change (tools/launch.py --elastic exports
    MX_PREV_NUM_PROCS alongside the reduced/grown MX_NUM_PROCS).  The
    event marks the segment boundary trace_report/mem_report use to keep
    the post-resize recompile wall and the restart dead-time out of the
    straggler/leak verdicts."""
    from .. import telemetry

    prev = _env("MX_PREV_NUM_PROCS")
    try:
        prev_n = int(prev) if prev else None
    except ValueError:
        return
    if prev_n is not None and prev_n != n:
        telemetry.record(
            "resize", old_world=prev_n, new_world=n,
            restart=int(_env("MX_RESTART_COUNT", default="0") or 0))


def is_initialized() -> bool:
    return _initialized


def process_index() -> int:
    import jax

    try:
        return jax.process_index()
    except Exception:
        return 0


def process_count() -> int:
    import jax

    try:
        return jax.process_count()
    except Exception:
        return 1


# ---------------------------------------------------------------------------
# gradient bucketing (docs/PERFORMANCE.md)
#
# Coalescing many small per-param gradients into size-capped flat buckets
# is what turns an O(n_params) stream of sub-megabyte collectives into
# O(total_bytes / cap) wire-efficient ones.  The flatten/unflatten pair
# lives here because BOTH reduction planes ride it: the intra-host device
# reduce (kvstore._reduce over ICI) and this module's cross-host DCN
# allreduce.  Each is one jitted dispatch per bucket; jax's signature
# cache makes repeat steps free.
# ---------------------------------------------------------------------------
_BUCKET_MB_DEFAULT = 32.0


def bucket_cap_bytes() -> int:
    """Gradient-allreduce bucket cap in bytes (MX_ALLREDUCE_BUCKET_MB,
    default 32 MB).  0 (or any non-positive/garbled value) disables
    bucketing entirely — the per-param pushpull kill switch."""
    raw = os.environ.get("MX_ALLREDUCE_BUCKET_MB")
    try:
        mb = float(raw) if raw is not None else _BUCKET_MB_DEFAULT
    except (TypeError, ValueError):
        return 0
    return int(mb * (1 << 20)) if mb > 0 else 0


_flatten_jit = None
_unflatten_cache: Dict[Tuple, object] = {}


def flatten_bucket(arrs):
    """Concatenate same-dtype jax arrays into one flat buffer — a single
    jitted dispatch regardless of how many gradients the bucket holds."""
    global _flatten_jit
    if _flatten_jit is None:
        import jax
        import jax.numpy as jnp

        _flatten_jit = jax.jit(
            lambda *xs: jnp.concatenate([x.reshape(-1) for x in xs]))
    return _flatten_jit(*arrs)


def unflatten_bucket(flat, shapes):
    """Split a reduced flat bucket back into the original shapes (one
    jitted dispatch; executables cached per bucket layout)."""
    shapes = tuple(tuple(int(d) for d in s) for s in shapes)
    fn = _unflatten_cache.get(shapes)
    if fn is None:
        import jax
        import jax.numpy as jnp

        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        offsets = list(np.cumsum(sizes)[:-1])

        def split(buf):
            parts = jnp.split(buf, offsets) if offsets else [buf]
            return tuple(p.reshape(s) for p, s in zip(parts, shapes))

        fn = _unflatten_cache[shapes] = jax.jit(split)
    return fn(flat)


# ---------------------------------------------------------------------------
# compiled global allreduce
# ---------------------------------------------------------------------------
# (mesh, my lead device, jitted reducer) — built once; jax.jit's own cache
# handles per-shape/dtype specialization
_allreduce_state = None
# (shape, dtype) pairs whose reducer specialization already compiled —
# telemetry uses this to tag first-use collective events as compile
_allreduce_seen: set = set()


def _get_allreduce_state():
    global _allreduce_state
    if _allreduce_state is None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        by_proc: Dict[int, object] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        leads = [by_proc[i] for i in sorted(by_proc)]
        mesh = Mesh(np.array(leads), ("hosts",))
        reducer = jax.jit(lambda a: a.sum(axis=0),
                          out_shardings=NamedSharding(mesh, P()))
        _allreduce_state = (mesh, leads[process_index()], reducer)
    return _allreduce_state


def allreduce_sum(arr):
    """Sum a per-process jax/numpy array across all processes; returns the
    (replicated) result as a jax array on this process's lead device.

    Compiled path: the per-host contributions form ONE global array sharded
    over the 'hosts' mesh axis; a jitted sum over that axis lowers to an
    XLA all-reduce on the wire (reference equivalent being replaced:
    kvstore_dist_server.h DataHandleEx server-side aggregation ~L200).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = process_count()
    if n == 1:
        return jax.numpy.asarray(arr)
    mesh, lead, reducer = _get_allreduce_state()
    local = jax.numpy.asarray(arr)
    garr = jax.make_array_from_single_device_arrays(
        (n,) + tuple(local.shape),
        NamedSharding(mesh, P("hosts")),
        [jax.device_put(local[None], lead)])
    from .. import telemetry

    t0 = time.perf_counter()
    out = reducer(garr)
    if telemetry.enabled():
        # the shared reducer jit re-specializes per (shape, dtype); tag
        # each first use so compile time stays out of the comm aggregates
        shape_key = (tuple(local.shape), str(local.dtype))
        traced = shape_key not in _allreduce_seen
        _allreduce_seen.add(shape_key)
        telemetry.record_collective("global_allreduce",
                                    nbytes=int(local.nbytes),
                                    wall_s=time.perf_counter() - t0,
                                    nproc=n, traced=traced)
    return out.addressable_shards[0].data
