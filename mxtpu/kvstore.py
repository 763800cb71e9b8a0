"""KVStore: parameter synchronization API over XLA collectives.

Parity: include/mxnet/kvstore.h:45-60 + python/mxnet/kvstore.py (Init/Push/Pull,
set_updater/set_optimizer, rank/num_workers, Barrier) and the Comm/KVStoreLocal/
KVStoreDist stack (SURVEY.md §2.4). TPU-native mapping (SURVEY.md §5 'Distributed
communication backend'):

  * 'local'/'device': single-process multi-device — Push aggregates per-key
    gradients (the CommCPU/CommDevice tree-reduce collapses into one jnp add-N
    on device; XLA fuses it), the updater runs once, Pull broadcasts. No P2P
    plumbing needed: device copies ride ICI via device_put.
  * 'dist_sync'/'dist_device_sync': multi-host — rank/num_workers come from
    jax.distributed (process_index/count); cross-host aggregation uses a psum
    over the global mesh (see mxtpu.parallel) instead of ps-lite ZPush/ZPull;
    there is no separate server role — optimizer state lives replicated (or
    sharded, see parallel.dp) on workers. ``set_optimizer`` therefore runs
    the optimizer locally-after-allreduce, which is bitwise the sync-server
    semantics of kvstore_dist_server.h:175 ApplyUpdates.
  * 'dist_async': synchronous collectives cannot express async staleness, so
    on a jax.distributed job process 0 hosts the TCP parameter server
    in-process (async mode: every push applies immediately, pulls return the
    latest state, no cross-worker barrier — kvstore_dist_server.h:164-300
    semantics) and workers connect over DCN. Under tools/launch.py the
    classic external server processes are used instead.
"""
from __future__ import annotations

import pickle
import threading as _threading

import jax
import numpy as _np

import os as _os

from .analysis import concurrency as _conc
from .base import MXNetError
# private aliases: mxtpu.kvstore is a directly-documented module, and a
# bare RetryPolicy import would duplicate its class doc onto the
# generated kvstore API page
from .faults import RetryPolicy as _RetryPolicy
from .faults import env_attempts as _env_attempts
from .faults import injection as _faults
from .ndarray import NDArray, zeros
from . import optimizer as opt
from . import telemetry as _tel
from .telemetry import tracing as _tracing

__all__ = ["KVStore", "create"]


def _nbytes(arr):
    """Payload size of an NDArray/array-like (shape x itemsize)."""
    try:
        shape = arr.shape
        return int(_np.prod(shape)) * _np.dtype(arr.dtype).itemsize \
            if shape else _np.dtype(arr.dtype).itemsize
    except Exception:
        return 0


def _is_dist():
    try:
        return jax.process_count() > 1
    except Exception:
        return False


class KVStore:
    def __init__(self, kind="local"):
        self._kind = kind
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._barrier_count = 0
        self._client = None
        self._env = None
        # transient transport errors (socket resets, IO hiccups — and
        # the injected faults that model them) retry through the shared
        # policy instead of killing the training step; the per-KEY
        # transport head is retried, so an already-applied key is never
        # re-pushed. MXTPU_KVSTORE_RETRIES counts retries AFTER the
        # first attempt (the MXTPU_ELASTIC_RETRIES convention).
        attempts = _env_attempts("MXTPU_KVSTORE_RETRIES", 2)
        try:
            backoff = float(_os.environ.get("MXTPU_KVSTORE_BACKOFF_S",
                                            "0.05"))
        except ValueError:
            backoff = 0.05
        self._push_retry = _RetryPolicy(
            "kvstore.push", max_attempts=attempts, backoff_s=backoff,
            backoff_cap_s=1.0)
        self._pull_retry = _RetryPolicy(
            "kvstore.pull", max_attempts=attempts, backoff_s=backoff,
            backoff_cap_s=1.0)
        if kind.startswith("dist"):
            from . import kvstore_server as kvs

            env = kvs.cluster_env()
            if env is not None and env["role"] == "worker":
                # ps-style transport (tools/launch.py cluster). On real
                # multi-host TPU (jax.process_count() > 1) the psum path
                # below is used instead and this client only carries
                # control traffic.
                self._env = env
                # heartbeat = ps-lite liveness role (kvstore.h:328)
                self._connect_worker(kvs, env["uri"], env["port"],
                                     env["worker_id"],
                                     async_mode="async" in kind)
            elif "async" in kind and _is_dist():
                # dist_async ON the jax.distributed path (VERDICT r3 #8):
                # synchronous psum cannot reproduce the reference's async
                # staleness semantics (kvstore_dist_server.h:164-300 —
                # every push applies immediately, no cross-worker wait), so
                # process 0 hosts the TCP parameter server in-process and
                # every rank connects over DCN. Push/pull then have NO
                # cross-worker barrier: a fast worker's updates land and
                # are visible to slow workers' pulls immediately.
                self._start_async_over_distributed(kvs)

    def _start_async_over_distributed(self, kvs):
        """Bring up the async parameter server for a jax.distributed job:
        rank 0 serves (KVServer thread, async mode), everyone connects.
        The server address defaults to the coordinator's host with port
        coordinator+1000; override with MXTPU_ASYNC_PS_URI/PORT when the
        coordinator host is not reachable from workers on that port."""
        import os

        coord = None
        try:
            from jax._src.distributed import global_state
            coord = global_state.coordinator_address
        except Exception:
            coord = None
        host = os.environ.get("MXTPU_ASYNC_PS_URI")
        port = os.environ.get("MXTPU_ASYNC_PS_PORT")
        if coord:
            # rsplit + bracket-strip: coordinator may be IPv6 ([::1]:1234)
            chost, cport = coord.rsplit(":", 1)
            chost = chost.strip("[]")
            host = host or chost
            port = int(port) if port else int(cport) + 1000
        elif host is None or port is None:
            raise MXNetError(
                "dist_async over jax.distributed: cannot resolve the "
                "coordinator address from this jax version — set "
                "MXTPU_ASYNC_PS_URI and MXTPU_ASYNC_PS_PORT to a "
                "host:port reachable from every worker")
        else:
            port = int(port)
        n = jax.process_count()
        if jax.process_index() == 0:
            # bind on all interfaces so cross-host workers reach us
            self._server = kvs.KVServer(port, n, host="0.0.0.0")
            self._server.sync_mode = False
            self._server.run_in_thread()
        self._connect_worker(kvs, host, port, jax.process_index(),
                             async_mode=True)

    def _connect_worker(self, kvs, host, port, rank, async_mode):
        """Shared client bring-up: connect, heartbeat, mode, barrier."""
        self._client = kvs.KVClient(host, port)
        self._client.start_heartbeat(rank)
        if async_mode:
            self._client.send_command("sync_mode", False)
        self._client.barrier()

    # ------------------------------------------------ identity
    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        if self._env is not None:
            return self._env["worker_id"]
        if self._kind.startswith("dist"):
            try:
                return jax.process_index()
            except Exception:
                return 0
        return 0

    @property
    def num_workers(self):
        if self._env is not None:
            return self._env["num_workers"]
        if self._kind.startswith("dist"):
            try:
                return jax.process_count()
            except Exception:
                return 1
        return 1

    # ------------------------------------------------ core ops
    def init(self, key, value):
        keys, values = self._normalize(key, value)
        for k, v in zip(keys, values):
            arr = v[0] if isinstance(v, list) else v
            if self._client is not None:
                # lowest rank wins server-side = rank0 init semantics
                # (KVStoreDist::Init + Barrier, kvstore_dist.h)
                self._client.init(k, arr.asnumpy(), rank=self.rank)
                self._client.barrier()
            self._store[k] = arr.copy()

    # ------------------------------------------------ mesh veneer
    # With an active SPMD mesh (mxtpu.sharding), 'local'/'device' stores
    # become a thin veneer over the mesh path: push aggregation runs as
    # ONE jitted all-reduce over the mesh (GSPMD collectives over ICI)
    # and pull hands each device its addressable shard of the replicated
    # result zero-copy. The host loop below stays as the fallback for
    # value lists that don't line up with the mesh (different device
    # set, single device, non-jax values). MXTPU_KVSTORE_MESH=0 opts out.

    # jitted sum per mesh, keyed by the mesh's STABLE identity (axis
    # layout + device ids, not id(mesh) — a leaked id would both re-jit
    # per push and pin dead meshes); guarded by a class lock since
    # pushes can race from several fit threads
    _MESH_SUM_FNS = {}
    _MESH_SUM_LOCK = _conc.lock("KVStore", "_MESH_SUM_LOCK")

    @staticmethod
    def _mesh_key(mesh):
        return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
                tuple(d.id for d in mesh.devices.flat))

    def _mesh_align(self, vlist):
        """Per-mesh-device arrays in mesh order when ``vlist`` covers
        exactly the active mesh's devices; None otherwise."""
        import os
        if os.environ.get("MXTPU_KVSTORE_MESH", "1") == "0":
            return None, None
        from . import sharding as _sharding
        mctx = _sharding.current()
        if mctx is None:
            return None, None
        # the row-shard trick below (one (1,)+shape row per device under
        # P(data)) is only shape-correct on a 1-D data mesh — on a
        # data×tp mesh the expected shard holds n/n_data rows, so fall
        # back to the host loop rather than hand jax mis-shaped shards
        if mctx.mesh.axis_names != (mctx.layout.data_axis,):
            return None, None
        devices = mctx.devices
        if len(vlist) != len(devices) or len(devices) < 2:
            return None, None
        by_dev = {}
        for v in vlist:
            data = getattr(v, "_data", None)
            if not isinstance(data, jax.Array):
                return None, None
            devs = getattr(data, "devices", lambda: set())()
            if len(devs) != 1:
                return None, None
            by_dev[next(iter(devs))] = data
        if set(by_dev) != set(devices):
            return None, None
        return [by_dev[d] for d in devices], mctx

    def _mesh_merge(self, ordered, mctx, ctx_out):
        """All-reduce ``ordered`` (one committed array per mesh device,
        mesh order) into a mesh-replicated NDArray: the per-device
        buffers become row-shards of ONE global array and a jitted
        sum-over-rows with replicated out_sharding lowers to the
        collective — no host hop, no per-device copy loop."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = mctx.mesh
        shape = tuple(ordered[0].shape)
        rows = [a.reshape((1,) + shape) for a in ordered]
        sharding = NamedSharding(mesh, P(mctx.layout.data_axis))
        global_arr = jax.make_array_from_single_device_arrays(
            (len(rows),) + shape, sharding, rows)
        key = self._mesh_key(mesh)
        with self._MESH_SUM_LOCK:
            fn = self._MESH_SUM_FNS.get(key)
            if fn is None:
                fn = jax.jit(lambda x: x.sum(0),
                             out_shardings=NamedSharding(mesh, P()))
                self._MESH_SUM_FNS[key] = fn
        _tel.counter("kvstore_mesh_allreduce",
                     help="push aggregations lowered to mesh "
                          "collectives instead of the host loop").inc()
        return NDArray(fn(global_arr), ctx_out)

    def _local_merge(self, vlist):
        """Reduce a per-device value list (the CommCPU/CommDevice
        tree-reduce role, comm.h:90/:462): one mesh collective when the
        list lines up with the active mesh, else the host loop onto the
        first device."""
        merged = vlist[0]
        if len(vlist) > 1:
            ordered, mctx = self._mesh_align(vlist)
            if ordered is not None:
                return self._mesh_merge(ordered, mctx, vlist[0].context)
            dev = vlist[0].context.jax_device
            acc = vlist[0]._data
            for x in vlist[1:]:
                acc = acc + jax.device_put(x._data, dev)
            merged = NDArray(acc, vlist[0].context)
        return merged

    def push(self, key, value, priority=0):
        """Aggregate pushed values per key; run updater if set, else assign-sum
        (parity KVStoreLocal::PushImpl kvstore_local.h:149; dist path
        KVStoreDist::Push_ kvstore_dist.h:256)."""
        with _tracing.span("kvstore.push", category="kvstore") as sp:
            self._push_impl(key, value, priority)
        _tel.histogram("kvstore_push_ms",
                       help="per push() call latency").observe(
            sp.duration_ms)

    def _push_impl(self, key, value, priority):
        bytes_pushed = _tel.counter("kvstore_push_bytes",
                                    help="aggregated gradient bytes pushed")
        keys, values = self._normalize(key, value)
        for k, v in zip(keys, values):
            vlist = v if isinstance(v, list) else [v]
            merged = self._local_merge(vlist)
            bytes_pushed.inc(_nbytes(merged))
            # ONLY the transport head is retried: the ps-client push is
            # an at-least-once wire op. The collective (every host must
            # issue it exactly once or peers hang) and the updater's
            # in-place mutation of the store run OUTSIDE the retry —
            # re-running either after a partial success would desync
            # or double-apply.
            if self._client is not None:
                self._push_retry.call(self._push_transport, k, merged)
                continue
            self._push_retry.call(_faults.point, "kvstore.push")
            self._apply_push(k, merged)

    def _push_transport(self, k, merged):
        _faults.point("kvstore.push")
        self._client.push(k, merged.asnumpy())

    def _apply_push(self, k, merged):
        if self._kind.startswith("dist") and _is_dist():
            # real multi-host path: all-reduce over DCN/ICI replaces the
            # worker->server hop entirely
            from jax.experimental import multihost_utils as mhu
            gathered = mhu.process_allgather(merged._data)
            merged = NDArray(gathered.sum(axis=0), merged.context)
        if k not in self._store:
            self._store[k] = merged.copy()
            return
        if self._updater is not None:
            if getattr(merged._data, "sharding", None) is not None and \
                    len(merged._data.devices()) > 1:
                # the updater runs the optimizer on the store's own
                # single-device array — hand it a single-device view
                # of the mesh-replicated aggregate (its local shard,
                # so this is a no-copy reinterpret)
                merged = NDArray(self._shard_for(
                    merged._data, self._store[k].context.jax_device),
                    self._store[k].context)
            self._updater(self._key_int(k), merged, self._store[k])
        else:
            self._store[k]._data = merged._data

    def pull(self, key, out=None, priority=0):
        with _tracing.span("kvstore.pull", category="kvstore") as sp:
            self._pull_impl(key, out, priority)
        _tel.histogram("kvstore_pull_ms",
                       help="per pull() call latency").observe(
            sp.duration_ms)

    def _pull_impl(self, key, out, priority):
        if out is None:
            raise MXNetError("pull: out is required")
        bytes_pulled = _tel.counter("kvstore_pull_bytes",
                                    help="weight bytes pulled to devices")
        keys, outs = self._normalize(key, out)
        for k, o in zip(keys, outs):
            # same split as push: retry the transport read, distribute
            # the result to the outs exactly once
            if self._client is not None:
                import jax.numpy as jnp
                src_np = self._pull_retry.call(self._pull_transport, k)
                olist = o if isinstance(o, list) else [o]
                for dst in olist:
                    dst._data = jax.device_put(jnp.asarray(src_np),
                                               dst.context.jax_device)
                    bytes_pulled.inc(_nbytes(dst))
                continue
            self._pull_retry.call(_faults.point, "kvstore.pull")
            src = self._store[k]
            olist = o if isinstance(o, list) else [o]
            for dst in olist:
                dst._data = self._shard_for(src._data,
                                            dst.context.jax_device)
                bytes_pulled.inc(_nbytes(dst))

    def _pull_transport(self, k):
        _faults.point("kvstore.pull")
        return self._client.pull(k)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows (parity KVStore::PullRowSparse,
        kvstore_local.h PullRowSparseImpl). If ``out`` is row_sparse the
        result keeps sparse storage; dense outs get the full weight."""
        import numpy as _np
        from .ndarray.sparse import RowSparseNDArray

        if out is None or row_ids is None:
            raise MXNetError("row_sparse_pull requires out and row_ids")
        keys, outs = self._normalize(key, out)
        rids = row_ids if isinstance(row_ids, list) else [row_ids]
        for k, o in zip(keys, outs):
            olist = o if isinstance(o, list) else [o]
            rlist = rids if len(rids) == len(olist) else rids * len(olist)
            for dst, rid in zip(olist, rlist):
                if isinstance(dst, RowSparseNDArray):
                    rows = _np.unique(
                        rid.asnumpy().astype(_np.int64).reshape(-1))
                    if self._client is not None:
                        # dist path: ship ONLY the requested rows from the
                        # server (KVStoreDist::PullRowSparse_ semantics)
                        gathered = jax.numpy.asarray(
                            self._client.pull_rows(k, rows))
                    else:
                        gathered = self._store[k]._data[rows]
                    dst._sp_data = gathered
                    dst._sp_indices = jax.numpy.asarray(rows)
                    dst._dense_cache = None
                else:
                    src = self._store[k]
                    dst._data = jax.device_put(src._data,
                                               dst.context.jax_device)

    @staticmethod
    def _shard_for(src, device):
        """A single-device array of ``src`` on ``device``. When ``src``
        is mesh-replicated and ``device`` holds one of its shards, the
        shard IS the value — handed out zero-copy (the veneer's pull
        path); otherwise a plain device_put transfer."""
        if isinstance(src, jax.Array) and len(src.devices()) > 1:
            for sh in src.addressable_shards:
                if sh.device == device and \
                        tuple(sh.data.shape) == tuple(src.shape):
                    return sh.data
        return jax.device_put(src, device)

    # ------------------------------------------------ updater / optimizer
    def set_updater(self, updater):
        self._updater = updater

    def set_optimizer(self, optimizer):
        """Parity kvstore.py:349: in ps-transport dist mode the optimizer is
        pickled to the server (the reference's exact mechanism); otherwise it
        runs worker-side after aggregation — the same sync semantics."""
        self._optimizer = optimizer
        if self._client is not None:
            # every worker sends (idempotent server-side); the socket's FIFO
            # order guarantees this precedes the worker's own pushes, and a
            # sync merge completes only after ALL workers pushed, so the
            # updater is installed before the first ApplyUpdates.
            self._client.send_command("set_optimizer",
                                      pickle.dumps(optimizer))
            return
        self._updater = opt.get_updater(optimizer)

    # ------------------------------------------------ cluster control
    def barrier(self):
        self._barrier_count += 1
        if self._client is not None:
            self._client.barrier()
            return
        if self._kind.startswith("dist") and _is_dist():
            # all-host sync point via a tiny global psum
            from .parallel import host_barrier
            host_barrier()

    def send_command_to_servers(self, head, body):
        if self._client is not None:
            self._client.send_command(head, body)

    def num_dead_node(self, node_id=0, timeout=60):
        """Workers the server marks dead — silent for > ``timeout`` sec
        after their heartbeat started, excluding clean shutdowns. Parity:
        include/mxnet/kvstore.h:328 get_num_dead_node (node_id kept for
        signature parity; this transport has one worker group)."""
        del node_id
        if self._client is not None:
            return self._client.num_dead_node(timeout)
        return 0

    def close(self):
        """Stop the worker's server connection (sends STOP; the server
        exits after all workers stop — barrier_before_exit role)."""
        if self._client is not None:
            self._client.stop()
            self._client = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("optimizer is not set")
        payload = self._updater.get_states()
        if dump_optimizer:
            payload = pickle.dumps((payload, self._optimizer))
        with open(fname, "wb") as f:
            f.write(payload)

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("optimizer is not set")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    # ------------------------------------------------ helpers
    @staticmethod
    def _key_int(k):
        try:
            return int(k)
        except (TypeError, ValueError):
            return k

    @staticmethod
    def _normalize(key, value):
        if isinstance(key, (str, int)):
            return [key], [value]
        assert len(key) == len(value)
        return list(key), list(value)


def create(name="local"):
    """Factory (parity KVStore::Create src/kvstore/kvstore.cc:34-59)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    valid = ("local", "device", "local_allreduce_cpu", "local_allreduce_device",
             "dist_sync", "dist_device_sync", "dist_async", "dist_sync_device",
             "nccl")
    if name not in valid:
        raise MXNetError("Unknown KVStore type %s" % name)
    return KVStore(name)
