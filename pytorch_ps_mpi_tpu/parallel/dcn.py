"""Multi-process async parameter server over native shared memory.

The cross-process face of AsySG-InCon (the in-XLA single-program form
lives in ``async_ps.py``): a server process owns the parameters and
applies gradient updates in arrival order; worker processes read the
latest published snapshot whenever they like (inconsistent reads) and push
gradients tagged with the version they used. Transport is the C++
``native/psqueue.cpp`` segment (seqlock parameter board + per-worker
gradient mailboxes) — the role mpi4py's nonblocking collectives played for
the reference (``mpi_comms.py:88,132``), with staleness bounded by the
server dropping gradients older than ``max_staleness`` versions.

Across real pod slices the same server loop runs on each slice controller
with DCN transfers in place of shm; this module is the single-host
(multi-process) instantiation and the protocol reference.
"""

from __future__ import annotations

import ctypes
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from pytorch_ps_mpi_tpu.telemetry import PSServerTelemetry, span

PyTree = Any

_lib: Optional[ctypes.CDLL] = None


def get_lib() -> Optional[ctypes.CDLL]:
    """Build (once) and load native/psqueue.cpp; None without a toolchain."""
    global _lib
    if _lib is not None:
        return _lib
    from pytorch_ps_mpi_tpu.utils.native import build_and_load

    lib = build_and_load("psqueue.cpp")
    if lib is None:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.psq_create.restype = ctypes.c_void_p
    lib.psq_create.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                               ctypes.c_uint64, ctypes.c_uint64]
    lib.psq_open.restype = ctypes.c_void_p
    lib.psq_open.argtypes = [ctypes.c_char_p]
    lib.psq_close.argtypes = [ctypes.c_void_p]
    lib.psq_n_workers.restype = ctypes.c_uint32
    lib.psq_n_workers.argtypes = [ctypes.c_void_p]
    lib.psq_publish_params.restype = ctypes.c_int
    lib.psq_publish_params.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64,
                                       ctypes.c_uint64]
    lib.psq_read_params.restype = ctypes.c_int64
    lib.psq_read_params.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64,
                                    ctypes.POINTER(ctypes.c_uint64)]
    lib.psq_push_grad.restype = ctypes.c_int
    lib.psq_push_grad.argtypes = [ctypes.c_void_p, ctypes.c_uint32, u8p,
                                  ctypes.c_uint64, ctypes.c_uint64]
    lib.psq_pop_grad.restype = ctypes.c_int64
    lib.psq_pop_grad.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64,
                                 ctypes.POINTER(ctypes.c_uint32),
                                 ctypes.POINTER(ctypes.c_uint64),
                                 ctypes.POINTER(ctypes.c_uint32)]
    lib.psq_grad_pending.restype = ctypes.c_int
    lib.psq_grad_pending.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.psq_reset_slot.restype = ctypes.c_int
    lib.psq_reset_slot.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.psq_params_version.restype = ctypes.c_uint64
    lib.psq_params_version.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _flat_size(template: PyTree) -> int:
    import jax

    return sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(template))


def _flatten(tree: PyTree) -> np.ndarray:
    import jax

    return np.concatenate(
        [np.asarray(x, np.float32).reshape(-1) for x in jax.tree.leaves(tree)]
    ) if jax.tree.leaves(tree) else np.zeros(0, np.float32)


def _unflatten(flat: np.ndarray, template: PyTree) -> PyTree:
    import jax

    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for leaf in leaves:
        n = int(np.prod(np.shape(leaf)))
        out.append(flat[off : off + n].reshape(np.shape(leaf)).astype(np.float32))
        off += n
    return jax.tree.unflatten(treedef, out)


class CodecWire:
    """Fixed-spec byte wire for codec payloads over the shm mailboxes.

    The reference's codec placement — encode before send, decode on
    receive (``ps.py:94,166``) — applied to the async PS path: the worker
    encodes on device and ships the payload *bytes*; the server decodes
    back to a gradient. Because payload shapes are static, the wire spec
    (unit shapes/dtypes/order) is fixed at construction — the reference's
    per-message two-phase size exchange (``mpi_comms.py:144-174``)
    collapses to a one-time agreement, and the mailbox slot is sized to
    the spec exactly (no ``max_bytes`` high-water growth).

    ``bucket_mb > 0`` with a ``Codec.bucketable`` codec makes the wire
    UNIT a dtype-grouped flat bucket (``bucketing.BucketPlan``) instead
    of a pytree leaf: one push then ships a handful of contiguous
    ~MB-scale payload buffers instead of hundreds of per-leaf fragments
    (fewer per-unit scale/index sidecars on the wire, one big memcpy per
    unit on each end). Worker and server MUST agree on ``bucket_mb`` —
    it joins the codec config in the one-time wire agreement and should
    come from the same config source on both ends (``async_train`` plumbs
    ``cfg["bucket_mb"]`` to server and workers alike). The ``poll_grad``
    size check catches a mismatch whenever it changes total wire bytes
    (any codec with per-unit sidecars); like a same-size codec-config
    disagreement, a mismatch that preserves the byte count (identity
    codec over a mixed-dtype tree) is NOT detectable from the frame
    alone — single-source the config.

    The byte packing itself is double-buffered and chunked:
    ``encode_to_bytes`` first starts ASYNC device→host transfers for
    every payload array, then packs them into one of two preallocated
    ping-pong wire buffers — the DMA of payload *k+1* overlaps the host
    memcpy of payload *k* (serialization overlapping I/O), and the
    ping-pong lets a transport still draining buffer A (kernel socket
    buffer, shm seqlock reader) coexist with the next step encoding into
    buffer B. No ``b"".join`` double copy anywhere on the path.
    """

    def __init__(self, code, template: PyTree, seed: int = 0,
                 bucket_mb: float = 0.0):
        import jax
        import jax.numpy as jnp

        from pytorch_ps_mpi_tpu.bucketing import plan_buckets

        self.code = code
        leaves, self.treedef = jax.tree.flatten(template)
        self.plan = (
            plan_buckets(template, bucket_mb)
            if (bucket_mb > 0 and getattr(code, "bucketable", False))
            else None
        )
        if self.plan is not None:
            # wire units are flat dtype-grouped buckets
            self.shapes = [(b.size,) for b in self.plan.buckets]
            self.dtypes = [np.dtype(b.dtype) for b in self.plan.buckets]
        else:
            self.shapes = [tuple(np.shape(l)) for l in leaves]
            self.dtypes = [np.asarray(l).dtype for l in leaves]

        def one_struct(shape, dtype):
            return jax.eval_shape(
                lambda: code.encode(
                    jnp.zeros(shape, dtype),
                    code.init_state(shape, dtype),
                    jax.random.key(0) if code.needs_rng else None,
                )
            )[0]

        self._payload_structs = [
            one_struct(s, d) for s, d in zip(self.shapes, self.dtypes)
        ]
        self._flat_specs = [  # (shape, dtype) in wire order
            (tuple(x.shape), np.dtype(x.dtype))
            for ps in self._payload_structs
            for x in jax.tree.leaves(ps)
        ]
        self.wire_bytes = sum(
            int(np.prod(s)) * d.itemsize if s else d.itemsize
            for s, d in self._flat_specs
        )
        self.raw_bytes = _flat_size(template) * 4
        self._states = [
            code.init_state(s, d) for s, d in zip(self.shapes, self.dtypes)
        ]
        self._rng = jax.random.key(seed)
        # ping-pong wire buffers, preallocated once to the exact spec
        self._send_bufs = [
            np.empty(self.wire_bytes, np.uint8),
            np.empty(self.wire_bytes, np.uint8),
        ]
        self._send_idx = 0
        plan = self.plan

        def enc_all(grad_leaves, states, keys):
            units = (
                plan.pack_leaves(grad_leaves) if plan is not None
                else grad_leaves
            )
            payloads, new_states = [], []
            for i, (g, st) in enumerate(zip(units, states)):
                k = keys[i] if keys is not None else None
                p, s2 = code.encode(g, st, k)
                payloads.append(p)
                new_states.append(s2)
            return payloads, new_states

        def dec_all(payloads):
            units = [
                code.decode(p, s, d)
                for p, s, d in zip(payloads, self.shapes, self.dtypes)
            ]
            return (
                plan.unpack_leaves(units) if plan is not None else units
            )

        self._enc = jax.jit(enc_all)
        self._dec = jax.jit(dec_all)

    def encode_to_bytes(self, grad_tree: PyTree) -> np.ndarray:
        """Encode + pack into one contiguous preallocated wire buffer
        (a uint8 ndarray of exactly ``wire_bytes``; bytes-like for every
        transport). The returned buffer stays valid until the NEXT-next
        call (two-deep ping-pong)."""
        import jax

        grad_leaves = self.treedef.flatten_up_to(grad_tree)
        keys = None
        if self.code.needs_rng:
            self._rng, sub = jax.random.split(self._rng)
            keys = list(jax.random.split(sub, len(self.shapes)))
        payloads, self._states = self._enc(grad_leaves, self._states, keys)
        flat = [x for p in payloads for x in jax.tree.leaves(p)]
        # start all device->host DMAs before touching any bytes: the
        # transfer of payload k+1 overlaps the memcpy of payload k below
        for x in flat:
            copy_async = getattr(x, "copy_to_host_async", None)
            if copy_async is not None:
                try:
                    copy_async()
                except Exception:
                    pass  # backend without async host copies
        from pytorch_ps_mpi_tpu.utils.serialization import pack_arrays_into

        buf = self._send_bufs[self._send_idx]
        self._send_idx ^= 1
        pack_arrays_into(buf, flat)
        return buf

    def probe_fidelity(self, grad_tree: PyTree) -> Dict[str, Any]:
        """Online codec-fidelity probe on the LARGEST wire unit (the
        sampled bucket, or the biggest leaf on the per-leaf wire):
        decode-after-encode relative L2 error, cosine similarity, and
        achieved bits-per-parameter via ``Codec.fidelity_probe``.
        Read-only — the wire's codec states and PRNG stream are
        untouched (the probe folds its own fixed key), so probing at any
        cadence never perturbs what actually ships."""
        import jax

        grad_leaves = self.treedef.flatten_up_to(grad_tree)
        units = (
            self.plan.pack_leaves(grad_leaves) if self.plan is not None
            else grad_leaves
        )
        i = max(range(len(units)),
                key=lambda j: int(np.prod(self.shapes[j]) or 1))
        rng = jax.random.key(0x9E3779B9) if self.code.needs_rng else None
        out = self.code.fidelity_probe(units[i], self._states[i], rng)
        out["unit"] = i
        out["codec"] = type(self.code).__name__
        return out

    def payloads_from_bytes(self, buf) -> list:
        """Parse a wire buffer into the per-unit payload pytrees as
        ZERO-COPY numpy views (valid only while ``buf`` is — consumers
        that retain anything must copy)."""
        import jax

        from pytorch_ps_mpi_tpu.utils.serialization import read_arrays

        arrays = read_arrays(buf, self._flat_specs, copy=False)
        payloads, i = [], 0
        for ps in self._payload_structs:
            struct = jax.tree.structure(ps)
            payloads.append(
                jax.tree.unflatten(struct, arrays[i:i + struct.num_leaves])
            )
            i += struct.num_leaves
        return payloads

    def decode_from_bytes(self, buf) -> PyTree:
        """Decode a wire buffer (``bytes``, ``bytearray``, ``memoryview``
        or uint8 ndarray) back into the template-structured gradient tree.
        Payload arrays are zero-copy views through one ``memoryview`` —
        the device transfer inside the jitted decode is the only copy.
        A buffer shorter than the wire spec raises a clear ValueError."""
        import jax

        decoded = self._dec(self.payloads_from_bytes(buf))
        return jax.tree.unflatten(
            self.treedef, [np.asarray(x) for x in decoded]
        )

    @property
    def agg_supported(self) -> bool:
        """True when EVERY wire unit can aggregate in the compressed
        domain (``Codec.supports_aggregate`` + the per-unit
        ``can_aggregate`` refinement). False means the serve loop keeps
        the decode-sum path — the automatic fallback."""
        return bool(getattr(self.code, "supports_aggregate", False)) and all(
            self.code.can_aggregate(s, d)
            for s, d in zip(self.shapes, self.dtypes)
        )

    def agg_begin(self) -> "WireAggregator":
        """Fresh compressed-domain accumulator for one aggregation round
        (one published version). Fold every composing push's payload
        bytes in, then ``finalize()`` for the ONE decode."""
        return WireAggregator(self)

    def payload_finite(self, buf) -> bool:
        """Cheap payload-level non-finite screen: checks only the FLOAT
        leaves of the wire payload (scales, norms, sparse values — for
        int8 that is one scalar per unit). A payload whose float leaves
        are finite decodes to a finite gradient for every registered
        codec, so this is the aggregation path's stand-in for the
        decoded-tree check the numerics monitor runs. Float-ness is
        decided by an UPCAST probe, not ``dtype.kind``: the ml_dtypes
        wire types (bf16's numpy dtype has kind 'V', not 'f') must be
        screened — they are exactly the payloads an identity/bf16 wire
        carries."""
        import jax

        for p in self.payloads_from_bytes(buf):
            for leaf in jax.tree.leaves(p):
                if leaf.dtype.kind in "iub":
                    continue  # integer payload domain (q, indices, votes)
                if not np.all(np.isfinite(np.asarray(leaf, np.float32))):
                    return False
        return True


class WireAggregator:
    """One aggregation round's compressed accumulator over a
    :class:`CodecWire`: ``fold`` ingests one push's payload bytes per
    call (host-side numpy, no jit dispatch, no tree rebuild — the
    per-push cost is a function of PAYLOAD size), ``finalize`` performs
    exactly one decode and returns the summed gradient tree. The
    serve-loop half of the THC/SparCML recipe; the SPMD half lives in
    ``ps.decode_sum_payloads``."""

    def __init__(self, wire: "CodecWire"):
        self.wire = wire
        code = wire.code
        self._accs = [
            code.agg_init(s, d) for s, d in zip(wire.shapes, wire.dtypes)
        ]
        self.frames = 0

    def fold(self, buf) -> None:
        """Fold one push's payload bytes (any bytes-like of exactly
        ``wire.wire_bytes``) into the accumulator. The parse is
        zero-copy; codec folds copy only what they retain."""
        payloads = self.wire.payloads_from_bytes(buf)
        code = self.wire.code
        for acc, p in zip(self._accs, payloads):
            code.agg_fold(acc, p)
        self.frames += 1

    def finalize(self) -> PyTree:
        """The ONE decode per published version: per-unit finalize,
        bucket unpack (when the wire is bucketed), tree rebuild. Returns
        the SUM over folded pushes."""
        import jax

        wire = self.wire
        code = wire.code
        units = [
            np.asarray(code.agg_finalize(acc, s, d))
            for acc, s, d in zip(self._accs, wire.shapes, wire.dtypes)
        ]
        if wire.plan is not None:
            units = [np.asarray(x) for x in wire.plan.unpack_leaves(units)]
        return jax.tree.unflatten(wire.treedef, units)

    def __del__(self):
        # an abandoned round (degraded sync, dropped worker set) must
        # hand its pooled sparse buffers back, or the pool stays cold
        # and every later round pays the fresh-zeros allocation
        try:
            from pytorch_ps_mpi_tpu.codecs.base import sparse_agg_release

            for acc in self._accs:
                if isinstance(acc, dict):
                    sparse_agg_release(acc)
        except Exception:
            pass  # interpreter teardown


def _renegotiate_common(server, code, bucket_mb: float = 0.0) -> None:
    """The shared server half of a codec/bucket_mb renegotiation (shm
    and TCP): build the new wire, keep the old epoch accepted, make the
    new fingerprint current. The epoch bump is executed entirely through
    the PR 3 frame handshake — the fingerprint IS the epoch
    discriminator, so no transport protocol change is needed."""
    if not server.frame:
        raise RuntimeError("wire renegotiation requires frame_check "
                           "(the fingerprint is the epoch handshake)")
    if server.wire is None:
        raise RuntimeError("wire renegotiation requires a codec wire")
    if getattr(server, "tree_slots", 0):
        raise RuntimeError("wire renegotiation is not supported on tree "
                           "wires (the hop codec is the tree's own "
                           "agreement)")
    if getattr(server, "agg_mode", 0.0):
        raise RuntimeError("suspend compressed-domain aggregation before "
                           "renegotiating (mixed-epoch payloads cannot "
                           "share one accumulator)")
    from pytorch_ps_mpi_tpu.resilience import frames as _frames

    new_wire = CodecWire(code, server.template, bucket_mb=bucket_mb)
    new_frame = new_wire.wire_bytes + _frames.HEADER_BYTES
    # the cap is the BOOT wire's frame size, latched at the first
    # renegotiation (when server.wire IS still the boot wire) — not the
    # receive buffer, which on TCP is sized to max(snapshot, frame) and
    # would admit entries every WORKER's boot-sized frame buffer must
    # then decline (a fleet-wide silent config rejection after retire)
    cap = server.__dict__.setdefault(
        "_reneg_frame_cap", server._expected_payload + _frames.HEADER_BYTES)
    if new_frame > cap:
        raise ValueError(
            f"renegotiated wire needs {new_frame} B frames but the "
            f"boot wire (and every worker's frame buffer) was sized "
            f"for {cap} B — ladder entries must not exceed the boot "
            "wire's payload size")
    table = server.__dict__.setdefault("_epoch_table", {})
    table[server._fingerprint] = {
        "wire": server.wire,
        "expected": server._expected_payload,
        "epoch": getattr(server, "_epoch", 0),
    }
    while len(table) > 2:  # at most two retiring epochs in flight
        table.pop(next(iter(table)))
    server._epoch = getattr(server, "_epoch", 0) + 1
    server.wire = new_wire
    server._fingerprint = _frames.wire_fingerprint(
        new_wire, server.template)
    server._expected_payload = new_wire.wire_bytes
    server._wire_payload_bytes = new_wire.wire_bytes
    server._epoch_transition = True


def _worker_renegotiate_common(worker, code,
                               bucket_mb: float = 0.0) -> bool:
    """The shared worker half of a renegotiation: rebuild the codec
    wire (same per-worker seed, so stochastic codecs keep distinct
    streams) and recompute the fingerprint. Returns False — declining,
    never raising — when this worker cannot switch (unframed wire, no
    codec, tree trailer wire, or a payload the boot-sized frame buffer
    cannot hold); a declining worker keeps pushing its old epoch, which
    the server consumes until that epoch retires."""
    if (not getattr(worker, "frame", False) or worker.wire is None
            or getattr(worker, "tree_slots", 0)):
        return False
    from pytorch_ps_mpi_tpu.resilience import frames as _frames

    new_wire = CodecWire(code, worker.template,
                         seed=getattr(worker, "_seed", 0),
                         bucket_mb=bucket_mb)
    if (_frames.HEADER_BYTES + new_wire.wire_bytes
            > worker._frame_buf.nbytes):
        return False
    worker.wire = new_wire
    worker._fingerprint = _frames.wire_fingerprint(
        new_wire, worker.template)
    return True


class ShmPSServer(PSServerTelemetry):
    """Owns params; publishes snapshots, consumes gradients in arrival
    order (the PS side of the reference's rank-0 loop, README.md:61-77).
    With ``code=`` the mailboxes carry encoded payload bytes (see
    :class:`CodecWire`) and the server decodes on receive.

    Telemetry (:class:`PSServerTelemetry`): ``metrics()`` returns the
    canonical schema shared with ``TcpPSServer`` — the reference's
    ``msg_bytes``/``packaged_bytes`` pair (``ps.py:135-136``) measured
    on the live async path — ``prometheus_text()`` is the in-process
    scrape method, and ``start_metrics_http()`` serves the same registry
    (plus the ``/health`` diagnosis JSON) over HTTP: the endpoint only
    renders Python state on a daemon thread, so the shm transport gets
    the same ops surface as TCP."""

    def __init__(self, name: str, num_workers: int, template: PyTree,
                 max_staleness: int = 4, code=None, bucket_mb: float = 0.0,
                 frame: bool = False, tree_slots: int = 0):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native psqueue unavailable (no g++?)")
        self._lib = lib
        self.template = template
        self.num_workers = num_workers
        self.max_staleness = max_staleness
        # bucket_mb is part of the one-time wire agreement: every worker
        # must be constructed with the same value (the poll-side size
        # check catches disagreement loudly)
        self.wire = (
            CodecWire(code, template, bucket_mb=bucket_mb)
            if code is not None else None
        )
        nbytes = _flat_size(template) * 4
        payload_bytes = self.wire.wire_bytes if self.wire else nbytes
        # tree_slots > 0: aggregation-tree parent — every push carries a
        # fixed-size composed-lineage trailer (parallel.tree; needs
        # frames, the trailer rides inside the CRC'd frame payload)
        self.tree_slots = int(tree_slots)
        self.tree_composed = 0
        self._wire_payload_bytes = payload_bytes
        if self.tree_slots:
            if not frame:
                raise ValueError("tree_slots requires frame=True (the "
                                 "lineage trailer rides the framed wire)")
            import collections as _collections

            from pytorch_ps_mpi_tpu.resilience import frames as _fr

            payload_bytes += _fr.trailer_bytes(self.tree_slots)
            self._composed_queue = _collections.deque()
        self._expected_payload = payload_bytes
        # frame=True: every push carries a self-verifying header (magic +
        # CRC32 + config fingerprint, resilience.frames) and a bad frame
        # becomes a counted per-worker rejection instead of a crash or a
        # silent mis-decode. Joins the one-time wire agreement: server
        # and every worker must agree on it (cfg["frame_check"]).
        self.frame = bool(frame)
        if self.frame:
            from pytorch_ps_mpi_tpu.resilience import frames as _frames

            self._frames = _frames
            self._fingerprint = _frames.wire_fingerprint(
                self.wire, template, tree_slots=self.tree_slots)
            grad_slot = payload_bytes + _frames.HEADER_BYTES
        else:
            grad_slot = payload_bytes
        self._h = lib.psq_create(name.encode(), num_workers, nbytes, grad_slot)
        if not self._h:
            raise RuntimeError(f"psq_create({name}) failed")
        self.version = 0
        if self.frame:
            self._grad_buf = np.empty(grad_slot, np.uint8)
        elif self.wire:
            self._grad_buf = np.empty(self.wire.wire_bytes, np.uint8)
        else:
            self._grad_buf = np.empty(_flat_size(template), np.float32)
        self.stale_drops = 0
        self.staleness_seen: Dict[int, int] = {}
        self.grads_received = 0
        self.bytes_received = 0
        # failure/straggler detection (absent in the reference, SURVEY
        # §5.3: MPI aborted the whole job; here the server observes)
        self.last_seen: Dict[int, float] = {}
        self._t0 = time.time()
        # uptime anchor for the canonical ts/uptime_s keys: monotonic,
        # per server GENERATION (a supervisor restart resets it)
        self._t0_mono = time.monotonic()

    def publish(self, params: PyTree) -> None:
        self.publish_flat(_flatten(params))

    def publish_flat(self, flat: np.ndarray) -> None:
        """Publish a pre-flattened f32 snapshot (the serving-core path:
        one flatten feeds the transport AND the snapshot ring)."""
        flat = np.ascontiguousarray(flat, np.float32)
        self.version += 1
        rc = self._lib.psq_publish_params(
            self._h, _u8(flat.view(np.uint8)), flat.nbytes, self.version
        )
        if rc != 0:
            raise RuntimeError("psq_publish_params failed")

    def _decode_payload(self, payload: np.ndarray,
                        wire=None) -> PyTree:
        """Payload bytes (a view into the receive buffer) → gradient
        tree; shared by the framed and legacy poll paths. Counted in
        ``decodes_done`` — the numerator of ``decodes_per_publish``.
        ``wire`` overrides the server's current wire — the old-epoch
        decode path during a codec renegotiation transition."""
        self.decodes_done += 1
        wire = wire if wire is not None else self.wire
        if wire:
            # zero-copy: decode reads the receive buffer through a
            # memoryview; the jitted decode's device transfer is the copy
            return wire.decode_from_bytes(payload)
        flat = np.frombuffer(payload, np.float32).copy()
        return _unflatten(flat, self.template)

    def renegotiate_wire(self, code, bucket_mb: float = 0.0) -> None:
        """Install a NEW codec wire as the current epoch (the
        controller's codec/bucket_mb renegotiation). The old epoch's
        wire stays in ``_epoch_table`` so in-flight old-fingerprint
        frames are consumed — decoded with their own wire — instead of
        rejected; :meth:`finish_renegotiation` retires it once the
        fleet has switched. The new wire's framed payload must fit the
        boot-sized transport buffers (mailbox slots are sized once at
        creation), so a ladder can only move between the boot config
        and anything smaller."""
        _renegotiate_common(self, code, bucket_mb)

    def finish_renegotiation(self) -> None:
        """Retire every old epoch: frames carrying a retired fingerprint
        become counted ``"config"`` rejections again (the pre-transition
        behavior for config drift)."""
        self._epoch_table = {}
        self._epoch_transition = False

    def _poll_grad_framed(self, raw: bool = False
                          ) -> Optional[Tuple[int, int, PyTree]]:
        """Frame-checking poll — the shared ``frames.framed_poll`` loop
        (validate → reject-and-count → bounded staleness → decode) over
        this transport's mailbox pop."""
        worker = ctypes.c_uint32()
        version = ctypes.c_uint64()
        cursor = getattr(self, "_cursor", None)
        if cursor is None:
            cursor = self._cursor = ctypes.c_uint32(0)

        def pop_once():
            n = self._lib.psq_pop_grad(
                self._h, _u8(self._grad_buf.view(np.uint8)),
                self._grad_buf.nbytes,
                ctypes.byref(worker), ctypes.byref(version),
                ctypes.byref(cursor),
            )
            return int(n), int(worker.value), int(version.value)

        return self._frames.framed_poll(self, pop_once, raw=raw)

    def poll_grad(self, raw: bool = False
                  ) -> Optional[Tuple[int, int, PyTree]]:
        """One pending gradient as (worker, version, grad_tree), or None.
        Gradients staler than max_staleness are dropped (bounded
        staleness), counted in ``stale_drops``. ``raw=True`` (the
        homomorphic-aggregation mode) skips the decode and returns the
        validated payload BYTES as a view into the receive buffer —
        copy or fold before the next poll."""
        if raw and not self.wire:
            # without a codec wire the receive buffer is f32-typed and
            # there is no payload format to hand back — a [:n] slice
            # would be a silently mis-sized view, not bytes
            raise ValueError("poll_grad(raw=True) needs a codec wire")
        if self.frame:
            return self._poll_grad_framed(raw=raw)
        worker = ctypes.c_uint32()
        version = ctypes.c_uint64()
        cursor = getattr(self, "_cursor", None)
        if cursor is None:
            cursor = self._cursor = ctypes.c_uint32(0)
        while True:  # iterative stale drain — a deep backlog of stale
            # gradients (one slow worker after a long server pause) must
            # not grow the Python stack
            n = self._lib.psq_pop_grad(
                self._h, _u8(self._grad_buf.view(np.uint8)),
                self._grad_buf.nbytes,
                ctypes.byref(worker), ctypes.byref(version),
                ctypes.byref(cursor),
            )
            if n <= 0:
                return None
            # clamp at 0: a future version (worker outliving a server
            # restart) is simply fresh; a negative key would corrupt the
            # histogram and dodge the drop check
            staleness = max(0, self.version - int(version.value))
            self.staleness_seen[staleness] = (
                self.staleness_seen.get(staleness, 0) + 1
            )
            self.last_seen[int(worker.value)] = time.time()
            self.grads_received += 1
            self.bytes_received += int(n)
            if staleness <= self.max_staleness:
                break
            self.stale_drops += 1
        expected = self.wire.wire_bytes if self.wire else _flat_size(self.template) * 4
        if int(n) != expected:
            # the wire spec is a one-time agreement — enforce it, or a
            # worker running a different codec config would crash the
            # decode (short payload) or silently corrupt gradients
            # (same-size different layout)
            raise RuntimeError(
                f"payload size {n} != wire spec {expected} bytes: worker "
                "and server codec configs disagree"
            )
        if raw:
            # aggregation mode (codec wire only): the validated payload
            # bytes, a view into the receive buffer
            grad = self._grad_buf[:n]
        elif self.wire:
            grad = self._decode_payload(self._grad_buf[:n])
        else:
            # the no-codec receive buffer is f32-typed: slice elements
            grad = self._decode_payload(self._grad_buf[: n // 4])
        return int(worker.value), int(version.value), grad

    def reset_worker_slot(self, worker: int) -> None:
        """Elastic replacement of a CRASHED worker: forcibly empty its
        mailbox (a process killed while its slot was in the WRITING state
        of the EMPTY/WRITING/FULL machine leaves it wedged, so a
        replacement could never push). Call only after confirming the
        previous owner is dead — a half-written payload is discarded,
        which the async protocol tolerates (one lost gradient). Also
        restarts the worker's liveness clock so ``stragglers()`` gives
        the replacement its startup grace instead of instantly re-
        flagging the id it inherits."""
        rc = self._lib.psq_reset_slot(self._h, worker)
        if rc != 0:
            raise ValueError(f"psq_reset_slot({worker}) -> {rc}")
        self.last_seen[int(worker)] = time.time()

    def stragglers(self, timeout: float) -> Dict[int, float]:
        """Workers with no sign of life for ``timeout`` seconds: no
        gradient consumed from them recently AND nothing pending in their
        mailbox (a pushed-but-unpolled gradient counts as alive, so server
        polling pauses don't misreport healthy workers). Never-seen
        workers age from server start. The failure-detection surface the
        reference lacked (its MPI default killed the whole job on any rank
        failure, SURVEY §5.3); the async protocol tolerates stragglers by
        design — this makes them observable."""
        now = time.time()
        out = {}
        for w in range(self.num_workers):
            if self._lib.psq_grad_pending(self._h, w) == 1:
                continue  # pushed, awaiting consumption: alive
            age = now - self.last_seen.get(w, self._t0)
            if age > timeout:
                out[w] = age
        return out

    def close(self):
        # the /metrics + /health endpoint (PSServerTelemetry mixin) dies
        # with the server — a supervisor restart can never leak a socket;
        # the serving core's read tier follows the same rule, and the
        # observability plane (profiler thread, TSDB flush, fleet
        # registration) is torn down the same way
        self.close_observability()
        self.close_metrics_http()
        sc = getattr(self, "serving_core", None)
        if sc is not None:
            sc.close()
        if self._h:
            self._lib.psq_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ShmPSWorker:
    """Reads the latest params whenever it likes; pushes version-tagged
    gradients (the worker side of AsySG-InCon's inconsistent reads)."""

    def __init__(self, name: str, worker_id: int, template: PyTree,
                 timeout: float = 30.0, code=None, seed: int = 0,
                 bucket_mb: float = 0.0, frame: bool = False,
                 cached_reads: bool = False, tree_slots: int = 0):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native psqueue unavailable (no g++?)")
        self._lib = lib
        deadline = time.time() + timeout
        self._h = None
        while time.time() < deadline:
            h = lib.psq_open(name.encode())
            if h:
                self._h = h
                break
            time.sleep(0.05)
        if not self._h:
            raise TimeoutError(f"psq_open({name}) timed out")
        self.worker_id = worker_id
        self.template = template
        # worker's wire must agree with the server's (same codec config
        # AND bucket_mb); stochastic codecs get a per-worker PRNG stream
        self._seed = seed + worker_id  # re-used by renegotiate()
        self.wire = (
            CodecWire(code, template, seed=self._seed,
                      bucket_mb=bucket_mb)
            if code is not None else None
        )
        # frame must match the server's (wire agreement); the fingerprint
        # is computed from THIS side's config — drift fails the compare
        self.frame = bool(frame)
        self._tamper = None  # one-shot outgoing-bytes hook (fault injection)
        self._wire_delay_s = 0.0  # one-shot post-seal delay (wire_delay)
        # monotonic push sequence for the frame trace ID — the fallback
        # when the caller doesn't pass an explicit lineage=(step, seq)
        self._auto_seq = 0
        # tree_slots > 0: pushes to an aggregation-tree parent carry a
        # fixed-capacity composed-lineage trailer (default: self)
        self.tree_slots = int(tree_slots)
        if self.tree_slots and not self.frame:
            raise ValueError("tree_slots requires frame=True")
        if self.frame:
            from pytorch_ps_mpi_tpu.resilience import frames as _frames

            self._frames = _frames
            self._fingerprint = _frames.wire_fingerprint(
                self.wire, template, tree_slots=self.tree_slots)
            payload_bytes = (self.wire.wire_bytes if self.wire
                             else _flat_size(template) * 4)
            self._frame_buf = np.empty(
                _frames.HEADER_BYTES + payload_bytes
                + _frames.trailer_bytes(self.tree_slots), np.uint8
            )
        self._param_buf = np.empty(_flat_size(template), np.float32)
        # version-conditional read cache (OPT-IN here, unlike TCP where
        # it defaults on): when the published version is unchanged (one
        # atomic peek — psq_params_version) the full seqlock copy +
        # unflatten is skipped and the cached tree returned, counted in
        # reads_not_modified. Off by default because a shm read is
        # already just a local memcpy — making it ~free changes the
        # pacing of tight read→push training loops (more same-version
        # pushes between publishes), whereas on TCP the request/reply
        # RTT still paces the reader and only the payload is saved.
        self.cached_reads = bool(cached_reads)
        self._cached_tree: Optional[PyTree] = None
        self._cached_version = 0
        self.reads_total = 0
        self.reads_not_modified = 0

    def read_params(self, timeout: float = 30.0) -> Tuple[PyTree, int]:
        """Latest published snapshot (blocks until the server's first
        publish; after that, never blocks on the writer — seqlock).
        With ``cached_reads=True`` (opt-in — see the constructor note)
        an unchanged version costs one atomic load instead of a full
        snapshot copy, and the SAME cached tree object is returned —
        callers opting in must not mutate it."""
        self.reads_total += 1
        if self.cached_reads and self._cached_tree is not None:
            v = int(self._lib.psq_params_version(self._h))
            if v == self._cached_version and v > 0:
                self.reads_not_modified += 1
                return self._cached_tree, v
        version = ctypes.c_uint64()
        deadline = time.time() + timeout
        while True:
            n = self._lib.psq_read_params(
                self._h, _u8(self._param_buf.view(np.uint8)),
                self._param_buf.nbytes, ctypes.byref(version),
            )
            if n == -2:
                # seqlock starved (server republishing faster than this
                # reader gets scheduled) — retriable until the deadline
                if time.time() > deadline:
                    raise TimeoutError("psq_read_params starved (seqlock)")
                time.sleep(0.01)
                continue
            if n < 0:
                raise RuntimeError(f"psq_read_params -> {n}")
            if version.value > 0:
                break
            if time.time() > deadline:
                raise TimeoutError("no parameter snapshot published yet")
            time.sleep(0.002)
        tree = _unflatten(self._param_buf[: n // 4].copy(), self.template)
        if self.cached_reads:
            self._cached_tree, self._cached_version = tree, int(version.value)
        return tree, int(version.value)

    def push_grad(self, grad: PyTree, version: int,
                  timeout: float = 30.0,
                  lineage: Optional[Tuple[int, int]] = None,
                  composed=None) -> None:
        """``lineage=(step, seq)`` stamps the push's trace ID into the
        v2 frame header (worker id travels in the transport); without it
        a per-transport auto-incrementing seq is used. Ignored on the
        unframed wire — there is nowhere to carry it. On a tree wire,
        ``composed`` lists the constituent trace IDs for the lineage
        trailer (default: this worker itself)."""
        with span("wire.encode"):
            if self.wire:
                # encode-before-send (reference ps.py:94): only payload
                # bytes ever enter the mailbox. encode_to_bytes hands back
                # its preallocated ping-pong buffer — valid through this
                # push's retry loop, no defensive copy needed.
                flat = self.wire.encode_to_bytes(grad)
            else:
                flat = _flatten(grad)
        with span("wire.send"):
            self.push_payload(flat, version, timeout=timeout,
                              lineage=lineage, composed=composed)

    def push_payload(self, flat: np.ndarray, version: int,
                     timeout: float = 30.0,
                     lineage: Optional[Tuple[int, int]] = None,
                     composed=None) -> None:
        """Push pre-encoded payload bytes — the tree leader's hop path
        (it encodes explicitly so error feedback can decode the exact
        payload that shipped)."""
        if self.frame:
            step, seq = lineage if lineage is not None else (0, self._auto_seq)
            self._auto_seq += 1
            if self.tree_slots and composed is None:
                composed = [(self.worker_id, step, seq, time.time())]
            flat = self._frames.seal_frame(self._frame_buf, flat,
                                           self._fingerprint,
                                           step=step, seq=seq,
                                           composed=composed,
                                           tree_slots=self.tree_slots)
        if self._tamper is not None:
            # fault injection: corrupt the outgoing bytes AFTER sealing,
            # so the CRC no longer matches what travels
            t, self._tamper = self._tamper, None
            t(flat.view(np.uint8))
        d, self._wire_delay_s = self._wire_delay_s, 0.0
        if d:
            # fault injection (kind "wire_delay"): emulated wire latency
            # — the frame is sealed (send_wall stamped at the encode
            # site) but the bytes travel late, exactly the window the
            # lineage wire stage measures
            time.sleep(d)
        deadline = time.time() + timeout
        while time.time() < deadline:
            rc = self._lib.psq_push_grad(
                self._h, self.worker_id, _u8(flat.view(np.uint8)),
                flat.nbytes, version,
            )
            if rc == 1:
                return
            if rc < 0:
                raise RuntimeError("psq_push_grad failed")
            time.sleep(0.002)  # mailbox full: server hasn't consumed yet
        raise TimeoutError("push_grad timed out")

    def renegotiate(self, code, bucket_mb: float = 0.0) -> bool:
        """Switch this worker's wire to a renegotiated codec epoch (the
        controller published it via ``control-epoch.json``). Returns
        False when declined — see :func:`_worker_renegotiate_common`."""
        return _worker_renegotiate_common(self, code, bucket_mb=bucket_mb)

    def close(self):
        if self._h:
            self._lib.psq_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
