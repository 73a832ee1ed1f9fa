"""XLA collective group — the TPU-native replacement for the reference's
NCCL group (ref: python/ray/util/collective/collective_group/
nccl_collective_group.py, 836 LoC of cupy/NCCL machinery).

Design: collectives lower to XLA collective ops (psum / all_gather /
psum_scatter) over a ``jax.sharding.Mesh``, executed as cached jitted
``shard_map`` programs, so repeated calls hit the XLA executable cache and
ride ICI inside a slice (DCN across slices when the group is federated via
jax.distributed).  Two membership modes share one code path:

* **in-process** (jax.process_count() == 1): group members are this
  process's devices — the natural single-controller TPU mode.  The
  ``*_multidevice`` verbs (parity with the reference's ``*_multigpu``) run
  real multi-device collectives over the local mesh; the per-rank verbs
  degenerate to world_size == 1.
* **federated** (multi-host): each member process contributes devices to a
  global mesh; the jax.distributed coordinator rendezvous rides the GCS KV
  (replacing the named-actor NCCLUniqueID store,
  nccl_collective_group.py:29-78).

Block protocol: per-member tensors of shape S are stacked into a global
array of shape (n, *S) sharded one block per device; kernels see (1, *S)
blocks and return (k, *S') blocks that concatenate over the mesh axis.
"""

from __future__ import annotations

import functools
import logging

import numpy as np

from ant_ray_tpu.util.collective import types
from ant_ray_tpu.util.collective.collective_group.base import BaseGroup

logger = logging.getLogger(__name__)


def _jax():
    from ant_ray_tpu._private.jax_utils import import_jax  # noqa: PLC0415

    return import_jax()


class XLAGroup(BaseGroup):
    def __init__(self, world_size: int, rank: int, group_name: str,
                 devices=None):
        super().__init__(world_size, rank, group_name)
        jax = _jax()
        # Mesh-based verbs need one process per rank (jax.distributed);
        # the KV-mailbox p2p verbs (send/recv) work without it, so the
        # check is deferred to the verbs that actually need the mesh.
        self._federated_ok = (world_size <= 1
                              or jax.process_count() >= world_size)
        self._devices = (list(devices) if devices is not None
                         else list(jax.devices()))
        # One representative device per member process for per-rank verbs.
        by_proc: dict[int, list] = {}
        for d in self._devices:
            by_proc.setdefault(d.process_index, []).append(d)
        self._rank_devices = [
            sorted(devs, key=lambda d: d.id)[0]
            for _proc, devs in sorted(by_proc.items())
        ]
        self._local_devices = [d for d in self._devices
                               if d.process_index == jax.process_index()]

    @classmethod
    def backend(cls):
        return "xla"

    @property
    def local_device_count(self) -> int:
        return len(self._local_devices)

    # ------------------------------------------------------------ compile

    @functools.lru_cache(maxsize=256)  # noqa: B019 — cache dies with group
    def _compiled(self, verb: str, shape: tuple, dtype: str, n_dev: int,
                  extra):
        jax = _jax()
        from jax import shard_map  # noqa: PLC0415
        from jax.sharding import Mesh, NamedSharding  # noqa: PLC0415
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        devices = (self._rank_devices if n_dev == len(self._rank_devices)
                   else self._devices)
        if verb.startswith("hier_"):
            return self._compile_hierarchical(verb, shape, n_dev, extra,
                                              devices)
        if verb.endswith("_q8"):
            return self._compile_q8(verb, shape, n_dev, extra, devices)
        mesh = Mesh(np.array(devices[:n_dev]), ("world",))
        axis = "world"

        def op(x):
            # x: this device's block, shape (1, *S)
            if verb.endswith("_accf32"):
                # Reduced-precision transport bucket (fusion.py): the
                # operand arrived in the narrow wire dtype; accumulate
                # at float32 (EQuARX-style) and return float32 — the
                # unpack stage restores the leaf dtype.
                import jax.numpy as jnp  # noqa: PLC0415

                return op_base(verb[:-len("_accf32")],
                               x.astype(jnp.float32))
            return op_base(verb, x)

        def op_base(verb, x):
            if verb == "allreduce_sum":
                return jax.lax.psum(x, axis)
            if verb == "allreduce_min":
                return jax.lax.pmin(x, axis)
            if verb == "allreduce_max":
                return jax.lax.pmax(x, axis)
            if verb == "allreduce_average":
                return jax.lax.pmean(x, axis)
            if verb == "broadcast":
                return jax.lax.all_gather(x[0], axis)[extra][None]
            if verb == "allgather":
                # out block: (n, *S) — every device gets the full gather
                return jax.lax.all_gather(x[0], axis)
            if verb == "reducescatter_sum":
                # x[0]: (d0, *rest) with d0 % n == 0 → (d0/n, *rest)
                return jax.lax.psum_scatter(x[0], axis, tiled=True)
            if verb.startswith("reducescatter_"):
                # MIN/MAX/AVERAGE: no fused XLA op — gather, reduce
                # locally, keep this rank's tile.
                g = jax.lax.all_gather(x[0], axis)   # (n, d0, *rest)
                red = {"min": g.min(axis=0), "max": g.max(axis=0),
                       "average": g.mean(axis=0)}[verb.split("_", 1)[1]]
                tile = red.shape[0] // n_dev
                index = jax.lax.axis_index(axis)
                return jax.lax.dynamic_slice_in_dim(
                    red, index * tile, tile, axis=0)
            raise ValueError(verb)

        fn = shard_map(op, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
        return jax.jit(fn), mesh, NamedSharding(mesh, P(axis))

    def _compile_q8(self, verb: str, shape: tuple, n_dev: int, extra,
                    devices):
        """Blockwise-int8 quantized allreduce (EQuARX-style): the
        all_gather moves int8 codes plus the float32 scale sidecar —
        the only bytes on the wire — and every rank dequantizes and
        accumulates at float32.  ``verb`` is ``allreduce_{sum,average}_q8``,
        ``extra`` is ``(block, n_blocks)``; one compiled program per
        bucket shape rides the same ``_compiled`` LRU as the plain
        verbs."""
        jax = _jax()
        import jax.numpy as jnp  # noqa: PLC0415
        from jax import shard_map  # noqa: PLC0415
        from jax.sharding import Mesh, NamedSharding  # noqa: PLC0415
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        block, _n_blocks = extra
        size = shape[0]
        average = verb.startswith("allreduce_average")
        mesh = Mesh(np.array(devices[:n_dev]), ("world",))
        axis = "world"

        def op(q, s):
            # q: (1, size) int8, s: (1, n_blocks) float32
            qg = jax.lax.all_gather(q[0], axis)      # (n, size) — wire
            sg = jax.lax.all_gather(s[0], axis)      # (n, n_blocks)
            scale = jnp.repeat(sg, block, axis=1)[:, :size]
            out = (qg.astype(jnp.float32) * scale).sum(axis=0)
            if average:
                out = out / n_dev
            return out[None]

        fn = shard_map(op, mesh=mesh, in_specs=(P(axis), P(axis)),
                          out_specs=P(axis))
        return jax.jit(fn), mesh, NamedSharding(mesh, P(axis))

    def _compile_hierarchical(self, verb: str, shape: tuple, n_dev: int,
                              extra, devices):
        """Two-level allreduce over a (slice, intra) mesh: reduce-
        scatter within each slice (ICI), psum across slices (the DCN
        exchange — each chunk crosses slice boundaries ONCE per slice,
        so cross-slice traffic scales with num_slices, not world size),
        then all_gather within the slice to rebuild the bucket.
        ``verb`` is ``hier_allreduce_{sum,average}[_accf32]``; ``extra``
        is the SliceTopology's rank partition (must be the regular
        contiguous layout matching device order)."""
        jax = _jax()
        import jax.numpy as jnp  # noqa: PLC0415
        from jax import shard_map  # noqa: PLC0415
        from jax.sharding import Mesh, NamedSharding  # noqa: PLC0415
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        slices = extra
        num_slices = len(slices)
        per = n_dev // num_slices
        size = shape[0]
        accf32 = verb.endswith("_accf32")
        average = "allreduce_average" in verb
        mesh = Mesh(np.array(devices[:n_dev]).reshape(num_slices, per),
                    ("slice", "intra"))

        def op(x):
            y = x[0, 0]                               # (size,)
            if accf32:
                y = y.astype(jnp.float32)
            if per > 1 and size % per == 0:
                y = jax.lax.psum_scatter(y, "intra", tiled=True)
                y = jax.lax.psum(y, "slice")
                y = jax.lax.all_gather(y, "intra", tiled=True)
            else:
                # Odd-sized bucket: no clean scatter tiling — reduce
                # whole within the slice, then across slices.
                y = jax.lax.psum(y, "intra")
                y = jax.lax.psum(y, "slice")
            if average:
                y = y / n_dev
            return y[None, None]

        spec = P("slice", "intra")
        fn = shard_map(op, mesh=mesh, in_specs=spec, out_specs=spec)
        return jax.jit(fn), mesh, NamedSharding(mesh, spec)

    # ------------------------------------------------------------ runners

    def _run_multidevice(self, verb: str, tensors: list, extra=None) -> list:
        """tensors: one per local device → list of per-device out blocks."""
        jax = _jax()
        n = len(tensors)
        if n != len(self._local_devices):
            raise ValueError(
                f"expected one tensor per local device "
                f"({len(self._local_devices)}), got {n}")
        t0 = np.asarray(tensors[0])
        jitted, mesh, sharding = self._compiled(
            verb, tuple(t0.shape), str(t0.dtype), len(self._devices), extra)
        mesh_devices = list(mesh.devices.flat)
        local_order = [d for d in mesh_devices if d in self._local_devices]
        shards = [
            jax.device_put(np.asarray(t)[None], d)
            for t, d in zip(tensors, local_order)
        ]
        global_shape = (len(self._devices),) + tuple(t0.shape)
        arr = jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards)
        out = jitted(arr)
        by_device = {s.device: s.data for s in out.addressable_shards}
        return [by_device[d] for d in local_order]

    def _stage_rank_verb(self, verb: str, tensor, extra=None):
        """Transfer stage of a per-rank verb: compile-cache lookup plus
        async-dispatched host→device ``device_put``.  Split from the
        execute stage so the fused coalesced path can issue bucket
        k+1's transfer while bucket k's collective runs."""
        jax = _jax()
        if not self._federated_ok:
            raise RuntimeError(
                f"xla group {self._group_name!r} needs "
                f"{self._world_size} federated processes but "
                f"jax.process_count() == {jax.process_count()}. "
                "Initialize jax.distributed before using mesh "
                "collectives (send/recv work without it).")
        t = np.asarray(tensor)
        jitted, mesh, sharding = self._compiled(
            verb, tuple(t.shape), str(t.dtype), len(self._rank_devices),
            extra)
        shard = jax.device_put(t[None], self._rank_devices[self._rank])
        arr = jax.make_array_from_single_device_arrays(
            (self._world_size,) + t.shape, sharding, [shard])
        return jitted, arr

    def _run_rank_verb(self, verb: str, tensor, extra=None):
        """One tensor per member process; returns this rank's out block."""
        jitted, arr = self._stage_rank_verb(verb, tensor, extra)
        return jitted(arr).addressable_shards[0].data

    _REDUCE_VERBS = {
        types.ReduceOp.SUM: "allreduce_sum",
        types.ReduceOp.MIN: "allreduce_min",
        types.ReduceOp.MAX: "allreduce_max",
        types.ReduceOp.AVERAGE: "allreduce_average",
    }

    def _reduce_verb(self, op: types.ReduceOp) -> str:
        verb = self._REDUCE_VERBS.get(op)
        if verb is None:
            raise NotImplementedError(
                f"{op} is not supported by the xla backend; allgather and "
                "reduce locally instead")
        return verb

    # ------------------------------------------------------------ verbs

    def allreduce(self, tensors, opts: types.AllReduceOptions):
        if self._world_size == 1:
            return [tensors[0]]
        block = self._run_rank_verb(self._reduce_verb(opts.reduce_op),
                                    tensors[0])
        return [block[0]]

    def allreduce_coalesced(self, tensors,
                            opts: types.AllReduceCoalescedOptions):
        """Fused path: one compiled shard_map collective per *bucket*
        shape (reusing the ``_compiled`` LRU) instead of one per
        tensor, with bucket k+1's host→HBM transfer pipelined against
        bucket k's collective.  Runs the compiled program even at
        world_size == 1 (psum over a 1-device mesh is identity) so the
        bucketed compile-cache behavior is identical at any scale."""
        from ant_ray_tpu.util.collective import fusion  # noqa: PLC0415

        if getattr(self, "_fusion_stats", None) is None:
            self._fusion_stats = fusion.FusionStats()

        def transfer(flat, bucket):
            return self.bucket_transfer(flat, bucket, opts)

        def reduce_bucket(staged, bucket):
            return self.bucket_reduce(staged, bucket, opts)

        return fusion.run_coalesced(tensors, opts, transfer_fn=transfer,
                                    collective_fn=reduce_bucket,
                                    stats=self._fusion_stats)

    # ---- per-bucket stages (driven by run_coalesced AND GradientSyncer)

    def _hier_topology(self, opts):
        """The validated hierarchy for this group, or None.  The xla
        mesh reshape needs the regular contiguous rank→slice layout
        (rank i on mesh cell (i // per, i % per)); anything else falls
        back to the flat ring."""
        from ant_ray_tpu.util.collective.types import SliceTopology  # noqa: PLC0415

        topo = getattr(opts, "hierarchy", None)
        if topo is None:
            return None
        world = self._world_size
        if world % max(1, topo.num_slices) != 0:
            return None
        if topo.slices != SliceTopology.regular(
                world, topo.num_slices).slices:
            return None
        return topo

    def bucket_transfer(self, flat, bucket,
                        opts: types.AllReduceCoalescedOptions):
        """Transfer stage of one fused bucket: compile-cache lookup +
        host→HBM ``device_put``.  Picks the wire program — plain,
        ``_accf32`` (narrow-float transport, f32 accumulate), ``_q8``
        (blockwise int8 + scale sidecar), or ``hier_*`` (two-level
        slice schedule; quantized buckets keep the flat q8 exchange)."""
        jax = _jax()
        from ant_ray_tpu.util.collective import fusion  # noqa: PLC0415

        verb = self._reduce_verb(opts.reduce_op)
        if bucket.transport_dtype == "int8":
            q, scales = flat
            jitted, arr_q = self._stage_rank_operand(
                verb + "_q8", q,
                key_shape=tuple(q.shape),
                key_dtype="int8",
                extra=(fusion.QUANT_BLOCK,
                       fusion.quant_blocks(bucket.size)))
            _jit2, arr_s = self._stage_rank_operand(
                verb + "_q8", scales,
                key_shape=tuple(q.shape), key_dtype="int8",
                extra=(fusion.QUANT_BLOCK,
                       fusion.quant_blocks(bucket.size)),
                operand_index=1)
            return ("q8", jitted, (arr_q, arr_s), self._world_size)
        topo = self._hier_topology(opts)
        if topo is not None:
            t = np.asarray(flat)
            wire_verb = "hier_" + verb + (
                "_accf32" if bucket.transport_dtype != bucket.dtype
                else "")
            jitted, mesh, sharding = self._compiled(
                wire_verb, tuple(t.shape), str(t.dtype),
                len(self._rank_devices), topo.slices)
            per = self._world_size // topo.num_slices
            shard = jax.device_put(t[None, None],
                                   self._rank_devices[self._rank])
            arr = jax.make_array_from_single_device_arrays(
                (topo.num_slices, per) + t.shape, sharding, [shard])
            return ("hier", jitted, (arr,), topo.num_slices)
        wire_verb = verb + ("_accf32"
                            if bucket.transport_dtype != bucket.dtype
                            else "")
        jitted, arr = self._stage_rank_verb(wire_verb, flat)
        return ("flat", jitted, (arr,), self._world_size)

    def _stage_rank_operand(self, verb: str, tensor, *, key_shape,
                            key_dtype, extra, operand_index: int = 0):
        """Stage one operand of a (possibly multi-input) compiled verb:
        the LRU key is pinned to the BUCKET's shape/dtype so sidecar
        operands (q8 scales) do not mint extra cache entries."""
        jax = _jax()
        if not self._federated_ok:
            raise RuntimeError(
                f"xla group {self._group_name!r} needs "
                f"{self._world_size} federated processes but "
                f"jax.process_count() == {jax.process_count()}.")
        t = np.asarray(tensor)
        jitted, mesh, sharding = self._compiled(
            verb, key_shape, key_dtype, len(self._rank_devices), extra)
        shard = jax.device_put(t[None], self._rank_devices[self._rank])
        arr = jax.make_array_from_single_device_arrays(
            (self._world_size,) + t.shape, sharding, [shard])
        return jitted, arr

    def bucket_reduce(self, staged, bucket,
                      opts: types.AllReduceCoalescedOptions):
        from ant_ray_tpu.util.collective import fusion  # noqa: PLC0415

        if getattr(self, "_fusion_stats", None) is None:
            self._fusion_stats = fusion.FusionStats()
        kind, jitted, args, dcn = staged
        out = jitted(*args)
        block = out.addressable_shards[0].data
        self._fusion_stats.dcn_participants += dcn
        if kind == "hier":
            return block[0, 0]
        return block[0]

    def barrier(self, opts: types.BarrierOptions):
        if self._world_size > 1:
            self._run_rank_verb("allreduce_sum", np.zeros((1,), np.float32))

    def reduce(self, tensors, opts: types.ReduceOptions):
        # The SPMD collective gives every rank the reduction; the
        # reference contract is "result lands on root_rank, other
        # buffers untouched" — so non-roots hand back their input.
        reduced = self.allreduce(
            tensors, types.AllReduceOptions(reduce_op=opts.reduce_op))
        if self._world_size > 1 and self._rank != opts.root_rank:
            return [tensors[0]]
        return reduced

    def broadcast(self, tensors, opts: types.BroadcastOptions):
        if self._world_size == 1:
            return [tensors[0]]
        block = self._run_rank_verb("broadcast", tensors[0],
                                    extra=opts.root_rank)
        return [block[0]]

    def allgather(self, tensors, opts: types.AllGatherOptions):
        if self._world_size == 1:
            return [[tensors[0]]]
        block = self._run_rank_verb("allgather", tensors[0])
        return [[block[i] for i in range(self._world_size)]]

    _SCATTER_VERBS = {
        types.ReduceOp.SUM: "reducescatter_sum",
        types.ReduceOp.MIN: "reducescatter_min",
        types.ReduceOp.MAX: "reducescatter_max",
        types.ReduceOp.AVERAGE: "reducescatter_average",
    }

    def _scatter_verb(self, op: types.ReduceOp, tensor, n: int) -> str:
        verb = self._SCATTER_VERBS.get(op)
        if verb is None:
            raise NotImplementedError(
                f"{op} is not supported by xla reducescatter")
        d0 = np.asarray(tensor).shape[0]
        if d0 % n != 0:
            raise ValueError(
                f"reducescatter leading dim {d0} not divisible by "
                f"group size {n}")
        return verb

    def reducescatter(self, tensors, opts: types.ReduceScatterOptions):
        if self._world_size == 1:
            return [tensors[0]]
        verb = self._scatter_verb(opts.reduce_op, tensors[0],
                                  self._world_size)
        block = self._run_rank_verb(verb, tensors[0])
        return [block]

    # ---- multi-device variants (parity: reference *_multigpu verbs)

    def allreduce_multidevice(self, tensors: list,
                              opts: types.AllReduceOptions):
        blocks = self._run_multidevice(self._reduce_verb(opts.reduce_op),
                                       tensors)
        return [b[0] for b in blocks]

    def broadcast_multidevice(self, tensors: list,
                              opts: types.BroadcastOptions):
        blocks = self._run_multidevice("broadcast", tensors,
                                       extra=opts.root_rank)
        return [b[0] for b in blocks]

    def allgather_multidevice(self, tensors: list,
                              opts: types.AllGatherOptions):
        blocks = self._run_multidevice("allgather", tensors)
        return [[b[i] for i in range(len(self._devices))] for b in blocks]

    def reducescatter_multidevice(self, tensors: list,
                                  opts: types.ReduceScatterOptions):
        verb = self._scatter_verb(opts.reduce_op, tensors[0],
                                  len(self._devices))
        return self._run_multidevice(verb, tensors)

    # ---- p2p
    # Host-level point-to-point rides the control plane through GCS KV
    # mailboxes (ICI p2p belongs to compiled step-graph channels / the
    # ppermute inside sharded programs).  Each (src → dst) pair keeps a
    # sequence so repeated sends pair with recvs in order, matching the
    # reference's NCCL send/recv contract
    # (ref: collective.py:601,664).

    def _mailbox_key(self, src: int, dst: int, seq: int) -> str:
        return (f"collective_p2p:{self._group_name}:"
                f"{src}->{dst}:{seq}")

    def send(self, tensors, opts: types.SendOptions):
        import pickle  # noqa: PLC0415
        import time as _time  # noqa: PLC0415

        from ant_ray_tpu._private.worker import global_worker  # noqa: PLC0415

        seq_attr = f"_send_seq_{opts.dst_rank}"
        attempt_attr = f"_send_attempt_{opts.dst_rank}"
        seq = getattr(self, seq_attr, 0)
        attempt = getattr(self, attempt_attr, 0)
        key = self._mailbox_key(self._rank, opts.dst_rank, seq) \
            + f"#a{attempt}"
        blob = pickle.dumps(np.asarray(tensors[0]), protocol=5)
        gcs = global_worker.runtime._gcs
        # Exchange protocol (retry-safe): the outcome of each
        # (seq, attempt) is decided exactly once by a put-if-absent race
        # on an arbitration key — "delivered" (receiver claims after
        # reading the blob) vs "withdrawn" (sender claims at its
        # deadline).  Every operation either is idempotent (KVGet,
        # re-KVPut of the same value) or resolves ambiguity by
        # re-reading the arbitration key, so an RPC connection retry can
        # never lose a message or desync the pair.  A withdrawn attempt
        # stays decided (deciding it twice is what reintroduces the
        # race); both sides move to attempt+1, so an application retry
        # of a timed-out send starts fresh.  Keys two sequences back are
        # garbage-collected here — by the time seq N+2 is sent, the
        # receiver has fully finished seq N.
        arb = key + ":arb"
        if seq >= 2:
            prefix = self._mailbox_key(self._rank, opts.dst_rank, seq - 2)
            for stale in gcs.call("KVKeys", {"prefix": prefix},
                                  retries=3) or []:
                gcs.call("KVDel", {"key": stale}, retries=3)
        gcs.call("KVPut", {"key": key, "value": blob}, retries=3)
        deadline = _time.monotonic() + opts.timeout_ms / 1000.0
        poll = 0.002
        while _time.monotonic() < deadline:
            if gcs.call("KVGet", {"key": arb}, retries=3) == b"delivered":
                setattr(self, seq_attr, seq + 1)
                setattr(self, attempt_attr, 0)
                return
            _time.sleep(poll)
            poll = min(poll * 2, 0.05)  # backoff: bounded GCS RPC rate
        gcs.call("KVPut", {"key": arb, "value": b"withdrawn",
                           "overwrite": False}, retries=3)
        if gcs.call("KVGet", {"key": arb}, retries=3) == b"delivered":
            setattr(self, seq_attr, seq + 1)  # receiver won at the wire
            setattr(self, attempt_attr, 0)
            return
        setattr(self, attempt_attr, attempt + 1)  # retry starts fresh
        raise TimeoutError(
            f"send to rank {opts.dst_rank} not consumed in time")

    def recv(self, tensors, opts: types.RecvOptions):
        import pickle  # noqa: PLC0415
        import time as _time  # noqa: PLC0415

        from ant_ray_tpu._private.worker import global_worker  # noqa: PLC0415

        seq_attr = f"_recv_seq_{opts.src_rank}"
        attempt_attr = f"_recv_attempt_{opts.src_rank}"
        seq = getattr(self, seq_attr, 0)
        attempt = getattr(self, attempt_attr, 0)
        gcs = global_worker.runtime._gcs
        deadline = _time.monotonic() + opts.timeout_ms / 1000.0
        poll = 0.002
        while _time.monotonic() < deadline:
            key = self._mailbox_key(opts.src_rank, self._rank, seq) \
                + f"#a{attempt}"
            arb = key + ":arb"
            blob = gcs.call("KVGet", {"key": key}, retries=3)
            if blob is not None:
                # Claim delivery via put-if-absent on the arbitration
                # key; on a lost reply the re-read below resolves who
                # won (see the protocol note in send()).
                won = gcs.call("KVPut", {"key": arb, "value": b"delivered",
                                         "overwrite": False}, retries=3)
                verdict = (b"delivered" if won else
                           gcs.call("KVGet", {"key": arb}, retries=3))
                if verdict == b"delivered":
                    setattr(self, seq_attr, seq + 1)  # success only
                    setattr(self, attempt_attr, 0)
                    return [pickle.loads(blob)]
                # "withdrawn": the sender gave up on this attempt; its
                # retry (if any) arrives at attempt+1 — move with it.
                attempt += 1
                setattr(self, attempt_attr, attempt)
            _time.sleep(poll)
            poll = min(poll * 2, 0.05)  # backoff: bounded GCS RPC rate
        raise TimeoutError(
            f"recv from rank {opts.src_rank} timed out")

    def destroy_group(self):
        self._compiled.cache_clear()
