"""Cross-host async parameter server over native TCP (the DCN role).

The second transport for the AsySG-InCon protocol: ``dcn.py`` moves bytes
between co-hosted processes through shared memory; this module moves the
same bytes between HOSTS through ``native/tcpps.cpp`` — the deployment
shape the reference got from MPI over Ethernet/IB (reference
``README.md:19-23``, ``mpi_comms.py:88,132``), realized as the plain TCP
a TPU pod's data-center network exposes to host code. On a pod, the
server runs on one slice's controller and workers on other slices'
controllers; each host's in-XLA compute path (jit/pjit over its own
chips) is unchanged.

:class:`TcpPSServer` / :class:`TcpPSWorker` present the same surface as
``ShmPSServer`` / ``ShmPSWorker`` — ``publish`` / ``poll_grad`` /
``metrics`` / ``stragglers`` and ``read_params`` / ``push_grad`` — so
``async_train.serve`` and ``async_train.worker_main`` run over either
transport unmodified (``cfg["transport"] = "shm" | "tcp"``). Semantics
preserved across the swap:

- inconsistent reads: a worker gets the latest snapshot whenever it asks;
  no barrier, concurrent workers may see different versions;
- bounded staleness: the server drops gradients older than
  ``max_staleness`` versions, counted in ``stale_drops``;
- push back-pressure: a push is acknowledged by the server, so a worker
  has at most one unacknowledged gradient in flight (the shm single-slot
  mailbox's property, carried by protocol instead of memory layout);
- codec wire: with ``code=`` only encoded payload BYTES travel
  (``CodecWire``), decoded server-side — encode-before-send, reference
  ``ps.py:94,166``.

What TCP adds over shm: worker crash == socket EOF, an explicit liveness
signal (``connected``), and elastic replacement is just a reconnect — no
``reset_worker_slot`` surgery needed.
"""

from __future__ import annotations

import ctypes
import time
from typing import Dict, Optional, Tuple

import numpy as np

from pytorch_ps_mpi_tpu.parallel.dcn import (
    CodecWire,
    PyTree,
    _flat_size,
    _flatten,
    _u8,
    _unflatten,
)
from pytorch_ps_mpi_tpu.telemetry import PSServerTelemetry, span

_lib: Optional[ctypes.CDLL] = None


class _BatchMeta(ctypes.Structure):
    """Mirror of native/tcpps.cpp BatchMeta (48 bytes, packed)."""

    _pack_ = 1
    _fields_ = [
        ("worker", ctypes.c_uint32),
        ("status", ctypes.c_uint32),
        ("version", ctypes.c_uint64),
        ("off", ctypes.c_uint64),
        ("len", ctypes.c_uint64),
        ("step", ctypes.c_uint32),
        ("seq", ctypes.c_uint32),
        ("send_wall", ctypes.c_double),
    ]


assert ctypes.sizeof(_BatchMeta) == 48


class _HopStamp(ctypes.Structure):
    """Mirror of native/tcpps.cpp HopStamp (32 bytes, packed) — one
    per-frame validate/ingest stamp from the batched pop, drained by the
    hop-anatomy plane through ``tps_hop_stamps_drain`` (pump-owning
    thread only). Size-checked at load via ``tps_abi_hop_stamp_bytes``
    and diffed field-for-field by the psanalyze ABI-drift rule."""

    _pack_ = 1
    _fields_ = [
        ("t_ns", ctypes.c_uint64),
        ("validate_ns", ctypes.c_uint64),
        ("bytes", ctypes.c_uint64),
        ("worker", ctypes.c_uint32),
        ("status", ctypes.c_uint32),
    ]


assert ctypes.sizeof(_HopStamp) == 32


def get_lib() -> Optional[ctypes.CDLL]:
    """Build (once) and load native/tcpps.cpp; None without a toolchain."""
    global _lib
    if _lib is not None:
        return _lib
    from pytorch_ps_mpi_tpu.utils.native import build_and_load

    lib = build_and_load("tcpps.cpp")
    if lib is None:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.tps_server_create.restype = ctypes.c_void_p
    lib.tps_server_create.argtypes = [ctypes.c_uint16, ctypes.c_uint32,
                                      ctypes.c_uint64]
    lib.tps_server_port.restype = ctypes.c_uint16
    lib.tps_server_port.argtypes = [ctypes.c_void_p]
    lib.tps_server_publish.restype = ctypes.c_int
    lib.tps_server_publish.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64,
                                       ctypes.c_uint64]
    lib.tps_server_pump.restype = ctypes.c_int
    lib.tps_server_pump.argtypes = [ctypes.c_void_p]
    lib.tps_server_pop_grad.restype = ctypes.c_int64
    lib.tps_server_pop_grad.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.tps_server_pending.restype = ctypes.c_int
    lib.tps_server_pending.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.tps_server_connected.restype = ctypes.c_int
    lib.tps_server_connected.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.tps_server_read_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.tps_server_close.argtypes = [ctypes.c_void_p]
    lib.tps_worker_connect.restype = ctypes.c_void_p
    lib.tps_worker_connect.argtypes = [ctypes.c_char_p, ctypes.c_uint16,
                                       ctypes.c_uint32, ctypes.c_int]
    lib.tps_worker_read_params.restype = ctypes.c_int64
    lib.tps_worker_read_params.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int, ctypes.c_uint64,
    ]
    lib.tps_worker_push_grad.restype = ctypes.c_int
    lib.tps_worker_push_grad.argtypes = [ctypes.c_void_p, u8p,
                                         ctypes.c_uint64, ctypes.c_uint64,
                                         ctypes.c_int]
    lib.tps_worker_close.argtypes = [ctypes.c_void_p]
    # batched ingest + in-C++ frame validation (absent from a stale
    # cached .so built before they existed; the mtime rebuild makes this
    # guard a hand-copied-library corner case)
    try:
        lib.tps_server_set_frame_check.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.tps_server_pop_grad_batch.restype = ctypes.c_int
        lib.tps_server_pop_grad_batch.argtypes = [
            ctypes.c_void_p, u8p, ctypes.c_uint64,
            ctypes.POINTER(_BatchMeta), ctypes.c_int]
        lib._has_batch = True
    except AttributeError:
        lib._has_batch = False
    # per-frame ingest stamp ring (hop anatomy) — own probe, so a stale
    # library with batch but no ring degrades only the ring
    try:
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.tps_abi_hop_stamp_bytes.argtypes = []
        lib.tps_abi_hop_stamp_bytes.restype = ctypes.c_uint32
        lib.tps_hop_stamps_arm.argtypes = [ctypes.c_uint32]
        lib.tps_hop_stamps_arm.restype = ctypes.c_int
        lib.tps_hop_stamps_drain.argtypes = [
            ctypes.POINTER(_HopStamp), ctypes.c_uint32, u64p]
        lib.tps_hop_stamps_drain.restype = ctypes.c_uint32
        lib._has_hop_stamps = True
    except AttributeError:
        lib._has_hop_stamps = False
    _verify_abi(lib)
    _lib = lib
    return _lib


def _verify_abi(lib: ctypes.CDLL) -> None:
    """Load-time twin of psanalyze's abi-drift rule: re-read the PSF2
    wire constants from the loaded library and refuse it on any
    mismatch with ``resilience/frames.py`` — drift becomes a loud load
    failure instead of a silent mis-decode. A library predating the
    ``tps_abi_*`` exports (hand-copied; the mtime check rebuilds any
    stale cache) skips the check rather than failing every import."""
    if not hasattr(lib, "tps_abi_psf_header_bytes"):
        return
    from pytorch_ps_mpi_tpu.resilience import frames as _frames

    lib.tps_abi_psf_magic.restype = ctypes.c_uint32
    lib.tps_abi_psf_magic_v1.restype = ctypes.c_uint32
    lib.tps_abi_psf_header_bytes.restype = ctypes.c_uint32
    lib.tps_abi_batch_meta_bytes.restype = ctypes.c_uint32
    lib.tps_abi_frame_status_name.restype = ctypes.c_char_p
    lib.tps_abi_frame_status_name.argtypes = [ctypes.c_uint32]
    checks = (
        ("PSF2 header bytes", int(lib.tps_abi_psf_header_bytes()),
         _frames.HEADER_BYTES),
        ("PSF2 magic", int(lib.tps_abi_psf_magic()),
         _frames.FRAME_MAGIC),
        ("PSF1 magic", int(lib.tps_abi_psf_magic_v1()),
         _frames.FRAME_MAGIC_V1),
        ("BatchMeta bytes", int(lib.tps_abi_batch_meta_bytes()),
         ctypes.sizeof(_BatchMeta)),
    )
    if getattr(lib, "_has_hop_stamps", False):
        checks += (("HopStamp bytes", int(lib.tps_abi_hop_stamp_bytes()),
                    ctypes.sizeof(_HopStamp)),)
    for what, native_v, py_v in checks:
        if native_v != py_v:
            raise RuntimeError(
                f"native/tcpps.cpp ABI drift: {what} is {native_v} in "
                f"the loaded library but {py_v} on the Python side — "
                "rebuild native/_build or reconcile the constants")
    for code, want in _frames.BATCH_REASONS.items():
        got = lib.tps_abi_frame_status_name(code)
        got = got.decode() if got is not None else None
        if got != want:
            raise RuntimeError(
                "native/tcpps.cpp ABI drift: frame-status code "
                f"{code} is {got!r} in the loaded library but "
                f"{want!r} in frames.BATCH_REASONS")


def native_profile_stats() -> Optional[dict]:
    """The epoll-pump cycle counters (calls / events / wall ns / frames
    validated) — the native half of continuous profiling
    (telemetry.profiler). Reads the ALREADY-loaded library only (never
    triggers a build); None when unavailable or built before the
    counters existed."""
    lib = _lib
    if lib is None or not hasattr(lib, "tps_profile_stats"):
        return None
    calls = ctypes.c_uint64()
    events = ctypes.c_uint64()
    ns = ctypes.c_uint64()
    frames = ctypes.c_uint64()
    lib.tps_profile_stats(ctypes.byref(calls), ctypes.byref(events),
                          ctypes.byref(ns), ctypes.byref(frames))
    return {"pump_calls": int(calls.value),
            "pump_events": int(events.value),
            "pump_ns": int(ns.value),
            "frames_validated": int(frames.value)}


class TcpPSServer(PSServerTelemetry):
    """Owns params; serves snapshots and consumes gradients arriving over
    TCP in arrival order. Same role/surface as ``ShmPSServer``; pass
    ``port=0`` to auto-assign (read back via ``.port`` for workers).

    Telemetry (:class:`PSServerTelemetry`): ``metrics()`` returns the
    canonical schema shared with ``ShmPSServer``, and
    :meth:`start_metrics_http` serves the same registry as a
    Prometheus-text ``/metrics`` HTTP endpoint — the deployment shape
    where a scraper on another host watches the PS. There is no
    transport-drop counter in the schema: an acknowledged push is never
    discarded (a full queue back-pressures the pushing worker via its
    withheld ack), so ``stale_drops`` is the only way a consumed
    gradient can fail to be applied."""

    def __init__(self, port: int, num_workers: int, template: PyTree,
                 max_staleness: int = 4, code=None, bucket_mb: float = 0.0,
                 frame: bool = False, tree_slots: int = 0):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native tcpps unavailable (no g++?)")
        self._lib = lib
        self.template = template
        self.num_workers = num_workers
        self.max_staleness = max_staleness
        # bucket_mb joins the one-time wire agreement (same value on
        # every worker; the per-frame size check catches disagreement)
        self.wire = (
            CodecWire(code, template, bucket_mb=bucket_mb)
            if code is not None else None
        )
        nbytes = _flat_size(template) * 4
        payload_bytes = self.wire.wire_bytes if self.wire else nbytes
        # tree_slots > 0: this server is an aggregation-tree parent —
        # every push's payload additionally carries a fixed-size
        # hop-composed lineage trailer (parallel.tree; requires frames,
        # the trailer rides inside the CRC'd frame payload)
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
        # frame=True: self-verifying headers on every push (magic + CRC32
        # + config fingerprint, resilience.frames); a bad frame — size
        # mismatch from a misconfigured worker included — becomes a
        # counted per-worker rejection instead of a RuntimeError into the
        # serve loop. Joins the wire agreement (cfg["frame_check"]).
        self.frame = bool(frame)
        if self.frame:
            from pytorch_ps_mpi_tpu.resilience import frames as _frames

            self._frames = _frames
            self._fingerprint = _frames.wire_fingerprint(
                self.wire, template, tree_slots=self.tree_slots)
            grad_bytes = payload_bytes + _frames.HEADER_BYTES
        else:
            grad_bytes = payload_bytes
        # one frame must fit the larger of a snapshot or a payload
        max_msg = max(nbytes, grad_bytes)
        self._h = lib.tps_server_create(port, num_workers, max_msg)
        if not self._h:
            raise RuntimeError(f"tps_server_create(port={port}) failed")
        self.port = int(lib.tps_server_port(self._h))
        # native batched ingest (poll_grad_batch): the C++ side validates
        # each inner PSF2 frame (magic/version, size, fingerprint, CRC32)
        # and hands back only reason-coded metas + validated payload
        # views, so the per-push Python cost is bookkeeping, not parsing.
        # Armed whenever frames are on and the library has the entry
        # points; PS_NO_NATIVE is consulted per call, not here.
        self._batch_max = 0
        if self.frame and getattr(lib, "_has_batch", False):
            lib.tps_server_set_frame_check(
                self._h, self._fingerprint, payload_bytes)
            # batch buffer: up to 64 payloads, capped at ~16 MB so a
            # BERT-scale identity wire doesn't allocate gigabytes
            self._batch_max = max(1, min(64, (16 << 20) //
                                         max(payload_bytes, 1)))
            self._batch_buf = np.empty(
                self._batch_max * payload_bytes, np.uint8)
            self._batch_metas = (_BatchMeta * self._batch_max)()
        self.native_batches = 0
        self.native_batch_frames = 0
        self.version = 0
        if self.frame:
            # headroom to max_msg: a mismatched worker's oversized frame
            # (still <= max_msg or the transport closes its connection)
            # pops cleanly and is judged by the header, never a fatal -1
            self._grad_buf = np.empty(max_msg, np.uint8)
        elif self.wire:
            self._grad_buf = np.empty(self.wire.wire_bytes, np.uint8)
        else:
            self._grad_buf = np.empty(_flat_size(template), np.float32)
        self.stale_drops = 0
        self.staleness_seen: Dict[int, int] = {}
        self.grads_received = 0
        self.bytes_received = 0
        self.last_seen: Dict[int, float] = {}
        self._ever_connected: set = set()
        self._t0 = time.time()
        # uptime anchor for the canonical ts/uptime_s keys: monotonic,
        # per server GENERATION (a supervisor restart resets it)
        self._t0_mono = time.monotonic()
        # /metrics + /health HTTP: start_metrics_http / close_metrics_http
        # live on PSServerTelemetry (shared with the shm server)
        self._metrics_http = None
        # native GET_PARAMS accounting (total, not_modified) — refreshed
        # from the pump thread only (poll_grad/publish), so scrape
        # threads read a plain Python tuple, never the native handle
        self._native_read_stats = (0, 0)

    def _refresh_read_stats(self) -> None:
        total = ctypes.c_uint64()
        nm = ctypes.c_uint64()
        self._lib.tps_server_read_stats(self._h, ctypes.byref(total),
                                        ctypes.byref(nm))
        self._native_read_stats = (int(total.value), int(nm.value))

    def hop_stamps_arm(self, capacity: int) -> bool:
        """Arm (capacity > 0) or disarm (0) the native per-frame ingest
        stamp ring the hop-anatomy plane drains. Returns True when the
        ring is live. PS_NO_NATIVE keeps the pure-Python stamp fallback
        in charge; call only from the pump-owning thread (the same
        thread-affinity contract as ``tps_server_read_stats``)."""
        from pytorch_ps_mpi_tpu.utils import native as _native

        if _native.fast_path_disabled():
            return False
        if not getattr(self._lib, "_has_hop_stamps", False):
            return False
        ok = int(self._lib.tps_hop_stamps_arm(int(capacity))) == 0
        self._hop_stamps_armed = ok and capacity > 0
        return self._hop_stamps_armed

    def drain_hop_stamps(self, max_stamps: int = 4096
                         ) -> Optional[Tuple[list, int]]:
        """Batched drain of the armed stamp ring: ``([(t_ns,
        validate_ns, bytes, worker, status), ...], dropped)`` — oldest
        first, overflow-drop counter reset per drain — or None when the
        ring is unarmed/unavailable. Pump-owning thread only; callers
        mirror the result into plain Python state before any other
        thread reads it (the ``_native_read_stats`` discipline)."""
        if not getattr(self, "_hop_stamps_armed", False):
            return None
        buf = (_HopStamp * int(max_stamps))()
        dropped = ctypes.c_uint64()
        n = int(self._lib.tps_hop_stamps_drain(
            buf, int(max_stamps), ctypes.byref(dropped)))
        stamps = [(int(buf[i].t_ns), int(buf[i].validate_ns),
                   int(buf[i].bytes), int(buf[i].worker),
                   int(buf[i].status)) for i in range(n)]
        return stamps, int(dropped.value)

    def publish(self, params: PyTree) -> None:
        self.publish_flat(_flatten(params))

    def publish_flat(self, flat: np.ndarray) -> None:
        """Publish a pre-flattened f32 snapshot (the serving-core path:
        one flatten feeds the transport AND the snapshot ring)."""
        flat = np.ascontiguousarray(flat, np.float32)
        self.version += 1
        rc = self._lib.tps_server_publish(
            self._h, _u8(flat.view(np.uint8)), flat.nbytes, self.version
        )
        if rc != 0:
            raise RuntimeError("tps_server_publish failed")
        self._lib.tps_server_pump(self._h)  # serve waiting readers promptly
        self._refresh_read_stats()

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
            # zero-copy: decode reads the receive buffer via memoryview
            return wire.decode_from_bytes(payload)
        flat = np.frombuffer(payload, np.float32).copy()
        return _unflatten(flat, self.template)

    def renegotiate_wire(self, code, bucket_mb: float = 0.0) -> None:
        """Install a NEW codec wire as the current epoch (the
        controller's codec/bucket_mb renegotiation). During the
        transition the native batched-ingest fast path is bypassed —
        its in-C++ validator knows one fingerprint — and the Python
        framed poll consumes BOTH epochs; :meth:`finish_renegotiation`
        re-arms the native validator on the new fingerprint. Ladder
        entries must not exceed the boot wire's payload size (the
        transport's max_msg is fixed at bind time)."""
        from pytorch_ps_mpi_tpu.parallel.dcn import _renegotiate_common

        _renegotiate_common(self, code, bucket_mb)

    def finish_renegotiation(self) -> None:
        """Retire every old epoch and re-point the native frame
        validator (and the batch buffer sizing) at the current wire."""
        self._epoch_table = {}
        self._epoch_transition = False
        if self._batch_max:
            payload_bytes = self._expected_payload
            self._lib.tps_server_set_frame_check(
                self._h, self._fingerprint, payload_bytes)
            batch_max = max(1, min(64, (16 << 20)
                                   // max(payload_bytes, 1)))
            if batch_max * payload_bytes > self._batch_buf.nbytes:
                self._batch_buf = np.empty(
                    batch_max * payload_bytes, np.uint8)
            if batch_max != self._batch_max:
                self._batch_metas = (_BatchMeta * batch_max)()
                self._batch_max = batch_max

    def _note_connections(self) -> None:
        """Latch first-connect times: a worker's liveness clock starts
        when it first connects, not at server start — so ``stragglers``
        can tell a worker that died mid-run (ages from its last sign of
        life) from one that NEVER showed up (reported immediately)."""
        now = time.time()
        for w in range(self.num_workers):
            if w in self._ever_connected:
                continue
            if self._lib.tps_server_connected(self._h, w):
                self._ever_connected.add(w)
                self.last_seen.setdefault(w, now)

    def _poll_grad_framed(self, raw: bool = False
                          ) -> Optional[Tuple[int, int, PyTree]]:
        """Frame-checking poll — the shared ``frames.framed_poll`` loop
        (validate → reject-and-count → bounded staleness → decode, the
        fix for one misconfigured worker's size-mismatched frame killing
        the PS with a RuntimeError) over this transport's queue pop."""
        worker = ctypes.c_uint32()
        version = ctypes.c_uint64()
        self._lib.tps_server_pump(self._h)
        self._refresh_read_stats()

        def pop_once():
            n = self._lib.tps_server_pop_grad(
                self._h, _u8(self._grad_buf.view(np.uint8)),
                self._grad_buf.nbytes,
                ctypes.byref(worker), ctypes.byref(version),
            )
            if n < 0:  # unreachable: the buffer is sized to max_msg
                raise RuntimeError("tps_server_pop_grad: payload exceeds "
                                   "the transport's own frame cap")
            wid = int(worker.value)
            if n > 0:
                self._ever_connected.add(wid)
            return int(n), wid, int(version.value)

        return self._frames.framed_poll(self, pop_once, raw=raw)

    def poll_grad_batch(self, raw: bool = False) -> Optional[list]:
        """Native batched ingest: ONE pump + ONE C++ pop drains up to
        ``_batch_max`` queued pushes, each already validated (magic/
        version, size, config fingerprint, CRC32) on the native side —
        the serve loop's per-push cost drops to bookkeeping plus, in
        ``raw`` mode, handing the validated payload VIEW straight to the
        native fold. Returns the consumed ``(worker, version, grad)``
        list ([] = nothing pending), or None when the fast path is
        unavailable (frames off, stale library, or ``PS_NO_NATIVE``) —
        callers fall back to :meth:`poll_grad`. Views returned in raw
        mode alias the batch buffer: copy or fold before the NEXT
        batched pop."""
        from pytorch_ps_mpi_tpu.utils import native as _native

        if not self._batch_max or _native.fast_path_disabled():
            return None
        if getattr(self, "_epoch_transition", False):
            # mid-renegotiation: the in-C++ validator knows only one
            # fingerprint — fall back to the Python framed poll, which
            # consumes both epochs, until finish_renegotiation()
            return None
        if raw and not self.wire:
            raise ValueError("poll_grad_batch(raw=True) needs a codec wire")
        self._lib.tps_server_pump(self._h)
        self._refresh_read_stats()
        n = self._lib.tps_server_pop_grad_batch(
            self._h, _u8(self._batch_buf), self._batch_buf.nbytes,
            self._batch_metas, self._batch_max)
        if n <= 0:
            return []
        self.native_batches += 1
        self.native_batch_frames += int(n)

        def gen():
            for i in range(n):
                m = self._batch_metas[i]
                wid = int(m.worker)
                self._ever_connected.add(wid)
                payload = (self._batch_buf[int(m.off):int(m.off) + int(m.len)]
                           if not m.status else None)
                yield (wid, int(m.version), int(m.status), payload,
                       int(m.step), int(m.seq), float(m.send_wall))

        return self._frames.framed_batch_consume(self, gen(), raw=raw)

    def poll_grad(self, raw: bool = False
                  ) -> Optional[Tuple[int, int, PyTree]]:
        """One pending gradient as (worker, version, grad_tree), or None.
        Pumps the sockets, then drains stale gradients iteratively (same
        bounded-staleness discipline as the shm server). ``raw=True``
        (the homomorphic-aggregation mode) skips the decode and returns
        the validated payload BYTES as a view into the receive buffer —
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
        self._lib.tps_server_pump(self._h)
        self._refresh_read_stats()
        expected = self.wire.wire_bytes if self.wire else _flat_size(self.template) * 4
        while True:
            n = self._lib.tps_server_pop_grad(
                self._h, _u8(self._grad_buf.view(np.uint8)),
                self._grad_buf.nbytes,
                ctypes.byref(worker), ctypes.byref(version),
            )
            if n == 0:
                return None
            if n < 0:
                raise RuntimeError(
                    "tps_server_pop_grad: payload exceeds wire spec — worker "
                    "and server codec configs disagree"
                )
            if int(n) != expected:
                # same one-time wire agreement the shm path enforces — and
                # checked for EVERY popped frame, stale-dropped ones
                # included: a codec-config mismatch on a straggling worker
                # must raise loudly, not be silently absorbed by the
                # staleness drop
                raise RuntimeError(
                    f"payload size {n} != wire spec {expected} bytes: worker "
                    "and server codec configs disagree"
                )
            # clamp at 0: a version from the future (e.g. a worker that
            # outlived a server restart) is simply fresh, and a negative
            # key would corrupt the histogram and dodge the drop check
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

    def connected(self, worker: int) -> bool:
        """Transport-level liveness: does a socket claiming this worker id
        exist right now? A crashed worker's connection closes (EOF/RST) —
        the positive failure signal shm can't give (SURVEY §5.3)."""
        self._lib.tps_server_pump(self._h)
        self._note_connections()
        return bool(self._lib.tps_server_connected(self._h, worker))

    def stragglers(self, timeout: float) -> Dict[int, float]:
        """Workers silent for ``timeout`` seconds: nothing consumed from
        them recently, nothing queued from them, and (stronger than shm)
        no open connection claiming their id — so a live worker that is
        merely mid-way through one long jitted step is never flagged, and
        acting on this report (elastic replacement) only ever targets
        dead sockets. A worker that NEVER connected has no liveness clock
        to age (``last_seen`` is latched on first connect, not at server
        start) and is reported immediately, whatever ``timeout`` — its
        age is measured from server start. The trade-off: a worker wedged
        WITH its socket open is not reported; watch ``last_seen`` ages
        for that."""
        self._lib.tps_server_pump(self._h)
        self._note_connections()
        now = time.time()
        out = {}
        for w in range(self.num_workers):
            if self._lib.tps_server_pending(self._h, w) > 0:
                continue  # pushed, awaiting consumption: alive
            if self._lib.tps_server_connected(self._h, w) == 1:
                continue  # open socket: alive (maybe slow), not lost
            if w not in self._ever_connected and w not in self.last_seen:
                # missing from the start: report NOW, no silence grace
                out[w] = now - self._t0
                continue
            age = now - self.last_seen.get(w, self._t0)
            if age > timeout:
                out[w] = age
        return out

    def close(self):
        # observability plane first (profiler thread, TSDB flush, fleet
        # deregistration), then the endpoint it was served from
        self.close_observability()
        self.close_metrics_http()
        # the read tier dies with the server (same rule as the /metrics
        # endpoint): a supervisor restart can never leak its listener
        sc = getattr(self, "serving_core", None)
        if sc is not None:
            sc.close()
        if self._h:
            self._lib.tps_server_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class TcpPSWorker:
    """Connects to a :class:`TcpPSServer` (possibly on another host),
    reads the latest params whenever it likes, pushes version-tagged
    gradients. Same surface as ``ShmPSWorker``."""

    def __init__(self, host: str, port: int, worker_id: int, template: PyTree,
                 timeout: float = 30.0, code=None, seed: int = 0,
                 bucket_mb: float = 0.0, frame: bool = False,
                 cached_reads: bool = True, tree_slots: int = 0):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native tcpps unavailable (no g++?)")
        self._lib = lib
        # the native side takes a dotted-quad only; resolve hostnames here
        # so a bad name fails loudly as what it is, not as a timeout
        import socket

        try:
            addr = socket.gethostbyname(host)
        except OSError as e:
            raise RuntimeError(f"cannot resolve PS host {host!r}: {e}") from e
        self._h = lib.tps_worker_connect(
            addr.encode(), port, worker_id, int(timeout * 1000)
        )
        if not self._h:
            raise TimeoutError(
                f"tps_worker_connect({host}={addr}:{port}) timed out"
            )
        self.worker_id = worker_id
        self.template = template
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
        # tree_slots > 0: pushes to an aggregation-tree parent — every
        # frame carries a fixed-capacity composed-lineage trailer (a
        # leaf pushing directly composes only itself)
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
        # version-conditional read cache: the request carries "I have v"
        # and an unchanged snapshot comes back as a cheap zero-payload
        # not-modified reply instead of the full re-shipped snapshot —
        # the fix for read_params re-shipping identical bytes every call.
        # Only the FLAT bytes are cached; every return still builds a
        # fresh tree, so callers that mutate returned params in place
        # (legal before this cache existed) stay correct.
        self.cached_reads = bool(cached_reads)
        self._cached_flat: Optional[np.ndarray] = None
        self._cached_version = 0
        self.reads_total = 0
        self.reads_not_modified = 0

    def read_params(self, timeout: float = 30.0) -> Tuple[PyTree, int]:
        """Latest published snapshot (blocks until the server's first
        publish, then one request/reply round trip per read). With
        ``cached_reads`` (default) the request is version-conditional:
        an unchanged snapshot costs a 28-byte header reply, not the full
        payload — the tree is rebuilt locally from the cached bytes."""
        self.reads_total += 1
        version = ctypes.c_uint64()
        deadline = time.time() + timeout
        have = (self._cached_version
                if self.cached_reads and self._cached_flat is not None
                else 0)
        while True:
            left_ms = max(1, int((deadline - time.time()) * 1000))
            n = self._lib.tps_worker_read_params(
                self._h, _u8(self._param_buf.view(np.uint8)),
                self._param_buf.nbytes, ctypes.byref(version), left_ms,
                have,
            )
            if n == -4:
                # not modified: the server confirmed our cached version;
                # fresh arrays from the cached bytes (mutation-safe)
                self.reads_not_modified += 1
                return (_unflatten(self._cached_flat, self.template),
                        self._cached_version)
            if n == -2:
                raise TimeoutError("tps_worker_read_params timed out")
            if n < 0:
                raise RuntimeError(f"tps_worker_read_params -> {n}")
            if version.value > 0:
                break
            if time.time() > deadline:
                raise TimeoutError("no parameter snapshot published yet")
            time.sleep(0.002)
        flat = self._param_buf[: n // 4].copy()
        if self.cached_reads:
            self._cached_flat, self._cached_version = flat, int(version.value)
        return _unflatten(flat, self.template), int(version.value)

    def push_grad(self, grad: PyTree, version: int,
                  timeout: float = 30.0,
                  lineage: Optional[Tuple[int, int]] = None,
                  composed=None) -> None:
        """``lineage=(step, seq)`` stamps the push's trace ID into the
        v2 frame header — same contract as ``ShmPSWorker.push_grad``.
        On a tree wire (``tree_slots > 0``), ``composed`` lists the
        constituent ``(worker, step, seq, send_wall)`` trace IDs for the
        lineage trailer; default is this worker's own trace ID (the
        direct-push / fallback case)."""
        with span("wire.encode"):
            if self.wire:
                # encode_to_bytes returns its preallocated ping-pong wire
                # buffer (one contiguous bucket payload per push) — the
                # native send consumes it synchronously, no defensive copy
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
        """Push pre-encoded payload bytes (exactly ``wire.wire_bytes``,
        or the flat f32 vector on a codec-less wire). The tree leader's
        hop path: it encodes explicitly (error feedback needs the
        payload AND its decode), then ships the bytes here."""
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
            # — sealed (send_wall stamped) but traveling late, the
            # window the lineage wire stage measures
            time.sleep(d)
        rc = self._lib.tps_worker_push_grad(
            self._h, _u8(flat.view(np.uint8)), flat.nbytes, version,
            int(timeout * 1000),
        )
        if rc == -2:
            raise TimeoutError("push_grad timed out awaiting server ack")
        if rc != 1:
            raise RuntimeError(f"tps_worker_push_grad -> {rc}")

    def renegotiate(self, code, bucket_mb: float = 0.0) -> bool:
        """Switch this worker's wire to a renegotiated codec epoch (the
        controller published it via ``control-epoch.json``). Returns
        False when declined — see
        :func:`~pytorch_ps_mpi_tpu.parallel.dcn._worker_renegotiate_common`."""
        from pytorch_ps_mpi_tpu.parallel.dcn import (
            _worker_renegotiate_common,
        )

        return _worker_renegotiate_common(self, code, bucket_mb=bucket_mb)

    def close(self):
        if self._h:
            self._lib.tps_worker_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
