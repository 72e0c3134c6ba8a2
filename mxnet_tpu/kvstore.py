"""KVStore: the data-parallel aggregation facade.

Reference parity: python/mxnet/kvstore.py over src/kvstore/ —
KVStoreLocal (kvstore_local.h ~L200), CommDevice reduce (comm.h ~L500),
KVStoreNCCL (kvstore_nccl.h), KVStoreDist (kvstore_dist.h).

TPU-native mapping (SURVEY §2.3/§5.8):
  * 'local' / 'device' / 'nccl'  -> single-process aggregation across the
    local device list.  The hand-rolled tree reduce / RCCL rings of the
    reference are unnecessary: the fused pjit training-step path
    (mxnet_tpu.parallel) emits XLA ICI collectives; this eager facade sums
    on the lead device, preserving exact KVStore push/pull semantics.
  * 'dist_sync' / 'dist_sync_device' -> same API over a multi-host program
    (jax.distributed); gradients are globally reduced; servers do not exist
    as processes — the "server-side optimizer" (update_on_kvstore) runs
    identically on every host, which is numerically equivalent to the
    reference's sync PS protocol.
  * 'dist_async' -> unsupported by design: async parameter serving has no
    SPMD analog (documented divergence).
"""
from __future__ import annotations

import pickle
from typing import Any, Dict, List, Optional, Union

from . import telemetry
from .base import MXNetError

__all__ = ["KVStore", "create"]


def _as_list(x):
    return x if isinstance(x, (list, tuple)) else [x]


class KVStore:
    """Key-value store for parameter synchronization."""

    def __init__(self, kv_type: str = "local"):
        self._type = kv_type
        self._store: Dict[Any, Any] = {}
        self._updater = None
        self._optimizer = None
        self._compression_params = None
        self._psum_cache: Dict[Any, Any] = {}
        self._psum_seen: set = set()
        if kv_type.startswith("dist"):
            # rendezvous with the coordination service when launched by
            # tools/launch.py (reference: ps::Postoffice::Start on first
            # KVStoreDist construction)
            from .parallel import dist

            dist.init_from_env()

    # ------------------------------------------------------------------
    @property
    def type(self) -> str:
        return self._type

    @property
    def rank(self) -> int:
        if self._type.startswith("dist"):
            from .parallel import dist

            return dist.process_index()
        return 0

    @property
    def num_workers(self) -> int:
        if self._type.startswith("dist"):
            from .parallel import dist

            return dist.process_count()
        return 1

    # ------------------------------------------------------------------
    def init(self, key, value) -> None:
        keys, values = self._key_value(key, value)
        dist_bcast = self._type.startswith("dist") and self.num_workers > 1
        for k, v in zip(keys, values):
            vals = _as_list(v)
            init_val = vals[0].copy()
            if dist_bcast:
                # reference contract (KVStoreDist): only rank 0's init value
                # reaches the store; every worker starts from the SAME
                # parameters.  Broadcast = allreduce of (rank0 ? v : 0).
                if self.rank != 0:
                    init_val = init_val * 0
                init_val = self._global_sum(init_val)
            self._store[k] = init_val

    def push(self, key, value, priority: int = 0) -> None:
        keys, values = self._key_value(key, value)
        for k, v in zip(keys, values):
            merged = self._reduce(_as_list(v))
            if self._type.startswith("dist") and self.num_workers > 1:
                merged = self._global_sum(merged)
            self._store_merged([(k, merged)])

    def pull(self, key, out=None, priority: int = 0,
             ignore_sparse: bool = True) -> None:
        keys, outs = self._key_value(key, out)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized")
            src = self._store[k]
            for dst in _as_list(o):
                dst._set_data(self._to_ctx(src, dst.context))

    def pushpull(self, key, value, out=None, priority: int = 0) -> None:
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)

    # ------------------------------------------------------------------
    # bucketed gradient aggregation (docs/PERFORMANCE.md)
    # ------------------------------------------------------------------
    def push_bucketed(self, key, value, priority: int = 0) -> int:
        """Push many keys at once, coalescing their values into size-capped
        flat buckets (MX_ALLREDUCE_BUCKET_MB, default 32) so ONE collective
        moves many gradients instead of one per key.  Store contents after
        the call are exactly what per-key ``push`` would have produced
        (unflatten restores every key before it reaches the store or the
        updater), so ``pull`` semantics are unchanged.

        Returns the number of flat buckets reduced; 0 means everything fell
        back to per-key pushes (bucketing disabled, or sparse/ragged
        values).  When the installed updater is a ``FusedUpdater`` the
        server-side optimizer also applies in one jitted call for the whole
        batch rather than once per key.
        """
        from .parallel.dist import bucket_cap_bytes

        keys, values = self._key_value(key, value)
        cap = bucket_cap_bytes()
        if cap <= 0:
            for k, v in zip(keys, values):
                self.push(k, v, priority)
            return 0
        from .ndarray.sparse import BaseSparseNDArray

        with telemetry.span("push_bucketed", n_keys=len(keys)):
            groups: Dict[Any, List] = {}  # (ctx tuple, dtype) -> [(k, vals)]
            fallback: List = []
            for k, v in zip(keys, values):
                vals = _as_list(v)
                lead = vals[0]
                if (any(isinstance(x, BaseSparseNDArray) for x in vals)
                        or any(x._data.dtype != lead._data.dtype
                               or x.shape != lead.shape for x in vals[1:])):
                    fallback.append((k, vals))
                    continue
                gkey = (tuple(x.context for x in vals), str(lead._data.dtype))
                groups.setdefault(gkey, []).append((k, vals))
            n_buckets = 0
            merged_kv: List = []  # (k, merged NDArray) in caller key order
            for (_ctxs, _dt), items in groups.items():
                bucket: List = []
                nbytes = 0
                for k, vals in items:
                    sz = int(vals[0].size) * vals[0]._data.dtype.itemsize
                    if bucket and nbytes + sz > cap:
                        merged_kv.extend(self._reduce_bucket(bucket))
                        n_buckets += 1
                        bucket, nbytes = [], 0
                    bucket.append((k, vals))
                    nbytes += sz
                if bucket:
                    merged_kv.extend(self._reduce_bucket(bucket))
                    n_buckets += 1
            self._store_merged(merged_kv)
            for k, vals in fallback:
                self.push(k, vals, priority)
            return n_buckets

    def _reduce_bucket(self, bucket) -> List:
        """Reduce one flat bucket across devices (and hosts for dist_*);
        returns the per-key merged values, unflattened."""
        from .ndarray import NDArray
        from .parallel.dist import flatten_bucket, unflatten_bucket

        shapes = [tuple(vals[0].shape) for _k, vals in bucket]
        if len(bucket) == 1:
            # a bucket of one key gains nothing from the flatten round-trip
            k, vals = bucket[0]
            with telemetry.span("bucket_collective", paired=True, n_keys=1):
                merged = self._reduce(vals)
                if self._type.startswith("dist") and self.num_workers > 1:
                    merged = self._global_sum(merged)
            return [(k, merged)]
        ndev = len(bucket[0][1])
        with telemetry.span("bucket_flatten", n_keys=len(bucket)):
            flats = []
            for d in range(ndev):
                flat = flatten_bucket([vals[d]._data for _k, vals in bucket])
                flats.append(NDArray(flat, ctx=bucket[0][1][d].context))
        with telemetry.span("bucket_collective", paired=True,
                            n_keys=len(bucket)):
            merged = self._reduce(flats)
            if self._type.startswith("dist") and self.num_workers > 1:
                merged = self._global_sum(merged)
        with telemetry.span("bucket_unflatten", n_keys=len(bucket)):
            segments = unflatten_bucket(merged._data, shapes)
            out = [(k, NDArray(seg, ctx=merged.context))
                   for (k, _vals), seg in zip(bucket, segments)]
        return out

    def _store_merged(self, merged_kv) -> None:
        """The tail of ``push`` for already-reduced values: store them, or
        hand them to the server-side optimizer — batched through the fused
        updater when several keys arrive at once (the bucketed path)."""
        if self._updater is None:
            for k, merged in merged_kv:
                self._store[k] = merged
            return
        entries = []
        for k, merged in merged_kv:
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized")
            # the updater computes eagerly on one device — localize BOTH
            # operands (a mesh-replicated merge from a collective reduce,
            # and a store value left replicated by an earlier non-updater
            # push) so eager ops don't mix device sets
            ctx = self._store[k].context
            merged = self._localize(merged, ctx)
            self._store[k] = self._localize(self._store[k], ctx)
            entries.append((self._updater_key(k), merged, self._store[k]))
        apply_batch = None
        if len(entries) > 1:
            from .optimizer.fused import FusedUpdater

            # scope the batched fast path to the type that defines it — a
            # user updater installed via set_updater may coincidentally
            # have an `apply` with a different contract
            if isinstance(self._updater, FusedUpdater):
                apply_batch = self._updater.apply
        if apply_batch is not None:
            # donate=False: pulled store values alias into caller arrays
            apply_batch(entries)
        else:
            for uk, merged, stored in entries:
                self._updater(uk, merged, stored)

    def broadcast(self, key, value, out, priority: int = 0) -> None:
        self.init(key, value)
        self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None) -> None:
        # sparse storage is emulated densely (SURVEY §7.3 item 8)
        self.pull(key, out, priority)

    # ------------------------------------------------------------------
    def set_updater(self, updater) -> None:
        self._updater = updater

    def set_optimizer(self, optimizer) -> None:
        """Install a server-side optimizer (reference: _send_command_to_servers
        pickles it; here the 'server' is this process — and every host in the
        dist_sync case, which the sync protocol makes equivalent)."""
        from . import optimizer as opt_mod

        # round-trip through pickle to mirror the reference's serialization
        # boundary (catches unpicklable user optimizers early)
        optimizer = pickle.loads(pickle.dumps(optimizer))
        self._optimizer = optimizer
        self._updater = opt_mod.get_updater(optimizer)

    def set_gradient_compression(self, compression_params: Dict) -> None:
        # DCN/ICI collectives don't need 2-bit compression; accepted for API
        # compatibility (reference: gradient_compression.cc)
        self._compression_params = compression_params

    # ------------------------------------------------------------------
    def barrier(self) -> None:
        if self._type.startswith("dist"):
            from .parallel import host_barrier

            host_barrier()

    def save_optimizer_states(self, fname: str, dump_optimizer: bool = False) -> None:
        if self._updater is None:
            raise MXNetError("no updater installed")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname: str) -> None:
        if self._updater is None:
            raise MXNetError("no updater installed")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    # ------------------------------------------------------------------
    def _key_value(self, key, value):
        if isinstance(key, (list, tuple)):
            if value is None:
                return list(key), [None] * len(key)
            return list(key), list(value)
        return [key], [value]

    @staticmethod
    def _updater_key(k):
        return int(k) if isinstance(k, str) and k.isdigit() else k

    def _reduce(self, vals: List):
        """Sum a per-device gradient list (CommDevice::Reduce).

        When the values live on DISTINCT devices, the sum runs as a
        compiled all-reduce (shard_map psum over a one-axis mesh of those
        devices) and the result is left replicated across them — on TPU
        the traffic rides ICI and a subsequent pull() to any contributing
        device is a local-shard fetch, not a broadcast.  This removes the
        r3-flagged lead-device funnel (all grads staged through one HBM).
        Single-device / duplicated-device lists keep the simple
        sum-on-lead path.

        Sparse values densify first: per-worker nnz/rows differ, so the
        collective needs the full logical shape (the reference's dist
        row_sparse key encoding is a documented non-goal; dense aggregation
        is correct, just not compact)."""
        from .ndarray.sparse import BaseSparseNDArray

        vals = [v.todense() if isinstance(v, BaseSparseNDArray) else v
                for v in vals]
        if len(vals) == 1:
            return vals[0].copy()
        lead = vals[0].context
        import jax

        devices = [v.context.jax_device for v in vals]
        if len(set(devices)) == len(vals):
            return self._reduce_collective(vals, devices)
        total = vals[0]._data
        for v in vals[1:]:
            arr = v._data
            if v.context != lead:
                arr = jax.device_put(arr, lead.jax_device)
            total = total + arr
        from .ndarray import NDArray

        return NDArray(total, ctx=lead)

    def _reduce_collective(self, vals: List, devices: List):
        """All-reduce across distinct devices; result replicated on all."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from .ndarray import NDArray

        shape = tuple(vals[0].shape)
        key = (tuple(devices), len(shape))
        entry = self._psum_cache.get(key)
        cold = entry is None
        if entry is None:
            mesh = Mesh(np.array(devices), ("kv",))
            fn = jax.jit(jax.shard_map(
                lambda x: jax.lax.psum(x, "kv")[0],
                mesh=mesh, in_specs=P("kv"),
                out_specs=P(*([None] * len(shape)))))
            entry = self._psum_cache[key] = (mesh, fn)
        mesh, fn = entry
        # pin FIRST, then expand: uncommitted arrays (made under
        # jax.default_device) would otherwise bounce through the default
        # device during the expand_dims dispatch — re-creating the funnel
        parts = [jnp.expand_dims(jax.device_put(v._data, d), 0)
                 for v, d in zip(vals, devices)]
        stacked = jax.make_array_from_single_device_arrays(
            (len(vals),) + shape, NamedSharding(mesh, P("kv")), parts)
        import time as _time

        from . import telemetry

        t0 = _time.perf_counter()
        reduced = fn(stacked)  # replicated over the kv mesh
        if telemetry.enabled():
            # cold = this (devices, ndim) program was jit-built above;
            # jax also re-specializes per concrete shape — approximate
            # that with a per-shape first-use check so compile time never
            # pollutes the comm aggregates
            shape_key = (key, shape, str(vals[0]._data.dtype))
            traced = cold or shape_key not in self._psum_seen
            self._psum_seen.add(shape_key)
            telemetry.record_collective(
                "device_allreduce",
                nbytes=int(np.prod(shape)) * vals[0]._data.dtype.itemsize,
                wall_s=_time.perf_counter() - t0, ndev=len(vals),
                traced=traced)
            if traced:
                # one compile event per specialized psum executable
                from . import memwatch

                memwatch.note_compile(
                    "KVStore.device_allreduce",
                    ("kvstore_psum", len(devices), shape,
                     str(vals[0]._data.dtype)),
                    wall_s=_time.perf_counter() - t0, site="kvstore",
                    jitted=fn, args=(memwatch.shape_structs(stacked),),
                    ndev=len(devices))
        return NDArray(reduced, ctx=vals[0].context)

    def _global_sum(self, nd):
        from .parallel import global_allreduce

        return global_allreduce(nd)

    def _localize(self, nd, ctx):
        """A single-device NDArray on ctx, fetching the local shard when
        the value is mesh-replicated (collective _reduce output)."""
        from .ndarray import NDArray

        return NDArray(self._to_ctx(nd, ctx), ctx=ctx)

    def _to_ctx(self, nd, ctx):
        import jax

        arr = nd._data
        multi = len(getattr(arr, "sharding", None).device_set) > 1 \
            if hasattr(arr, "sharding") else False
        if nd.context == ctx and not multi:
            return arr
        # replicated-over-mesh values: device_put to a member device is a
        # local-shard fetch (no cross-device traffic)
        return jax.device_put(arr, ctx.jax_device)


def create(name: str = "local") -> KVStore:
    """Create a KVStore (reference: kvstore.cc factory ~L30)."""
    if not isinstance(name, str):
        raise MXNetError("name must be a string")
    kv_type = name.lower()
    if kv_type in ("local", "local_allreduce_cpu", "local_allreduce_device",
                   "device", "nccl"):
        return KVStore("device" if kv_type != "local" else "local")
    if kv_type in ("dist_sync", "dist_sync_device", "dist_device_sync"):
        return KVStore(kv_type)
    if kv_type == "dist_async":
        raise MXNetError(
            "dist_async is not supported on TPU: asynchronous parameter "
            "serving has no SPMD analog (see SURVEY §2.3); use dist_sync")
    raise MXNetError(f"unknown KVStore type {name!r}")
