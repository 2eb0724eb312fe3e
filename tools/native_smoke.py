"""Native fast-path smoke gate (make native-smoke, in the default
`make test` path).

Three checks, each a hard assert:

1. **both libraries build** — ``libwirecodec.so`` (fold kernels) and
   ``libtcpps.so`` (epoll transport + batched ingest) compile from
   source and load with the fold/batch entry points bound;
2. **fold parity** — ``WireAggregator`` rounds over real ``CodecWire``
   payload bytes are BIT-IDENTICAL with the native ``wc_fold_*``
   kernels armed and with ``PS_NO_NATIVE=1`` (the numpy fallback), for
   one codec per fold family (scale-folded integer, 2-bit tern, sign
   votes, sparse scatter, block-quantized sparse, dense cast-up);
3. **batched ingest** — a live ``TcpPSServer`` drains a worker's framed
   pushes through ``poll_grad_batch`` (C++ validation, one pump+pop),
   with poll-identical accounting, and reason-counts a corrupt frame
   instead of delivering or crashing on it.
"""

from __future__ import annotations

import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PARITY_CODECS = [
    ("int8", {}),
    ("terngrad", {}),
    ("sign", {"use_pallas": False}),
    ("topk", {"k": 96}),
    ("blocktopk8", {"fraction": 0.03, "block_size": 256}),
    ("bf16", {}),
]


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        raise SystemExit(f"native_smoke: {name} failed ({detail})")


def check_build() -> None:
    rc = subprocess.call(["make", "native"], cwd=REPO,
                         stdout=subprocess.DEVNULL,
                         stderr=subprocess.STDOUT)
    check("make native builds", rc == 0, f"rc={rc}")
    from pytorch_ps_mpi_tpu.parallel import tcp
    from pytorch_ps_mpi_tpu.utils import native

    lib = native.fold_lib()
    check("wirecodec loads with fold kernels", lib is not None)
    tlib = tcp.get_lib()
    check("tcpps loads with batched ingest",
          tlib is not None and getattr(tlib, "_has_batch", False))


def _round(wire, bufs):
    import jax

    agg = wire.agg_begin()
    for b in bufs:
        agg.fold(b)
    return [np.asarray(x) for x in jax.tree.leaves(agg.finalize())]


def check_fold_parity() -> None:
    import jax

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    template = {"w": np.zeros((700, 2), np.float32),
                "b": np.zeros(333, np.float32)}
    rng = np.random.RandomState(11)
    for name, kw in PARITY_CODECS:
        wire = CodecWire(get_codec(name, **kw), template, seed=0)
        bufs = [np.copy(wire.encode_to_bytes(jax.tree.map(
            lambda x: rng.randn(*x.shape).astype(np.float32), template)))
            for _ in range(3)]
        native_out = _round(wire, bufs)
        os.environ["PS_NO_NATIVE"] = "1"
        try:
            numpy_out = _round(wire, bufs)
        finally:
            os.environ.pop("PS_NO_NATIVE", None)
        exact = all(np.array_equal(a, b)
                    for a, b in zip(native_out, numpy_out))
        check(f"fold parity bit-exact: {name}", exact)


def check_ingest() -> None:
    from pytorch_ps_mpi_tpu.parallel import tcp
    from pytorch_ps_mpi_tpu.resilience.frames import HEADER_BYTES

    template = {"w": np.zeros(64, np.float32)}
    server = tcp.TcpPSServer(0, num_workers=2, template=template,
                             frame=True, max_staleness=10**9)
    try:
        check("batched ingest armed", server._batch_max > 0)
        server.publish(template)

        def body():
            w = tcp.TcpPSWorker("127.0.0.1", server.port, 0, template,
                                frame=True)
            try:
                _, ver = w.read_params(timeout=30)
                for i in range(5):
                    w.push_grad({"w": np.full(64, float(i + 1), np.float32)},
                                ver, timeout=30)
            finally:
                w.close()

        t = threading.Thread(target=body)
        t.start()
        items = []
        deadline = time.time() + 30
        while len(items) < 5 and time.time() < deadline:
            batch = server.poll_grad_batch()
            if batch is None:
                check("fast path stays armed mid-run", False)
            items.extend(batch)
            time.sleep(0.002)
        t.join(timeout=30)
        check("batched pop drained every push", len(items) == 5
              and server.grads_received == 5
              and server.native_batch_frames == 5,
              f"items={len(items)} received={server.grads_received}")
        vals = sorted(float(np.asarray(g["w"])[0]) for _, _, g in items)
        check("payloads intact through C++ validation",
              vals == [1.0, 2.0, 3.0, 4.0, 5.0], str(vals))

        # rogue frame: valid outer transport message, garbage inner PSF2
        s = socket.create_connection(("127.0.0.1", server.port), timeout=10)
        inner = b"\xde\xad\xbe\xef" * (
            (server._expected_payload + HEADER_BYTES) // 4)
        s.sendall(struct.pack("<IB3xIQQ", 0x31535054, 1, 1, 0, 0))
        s.sendall(struct.pack("<IB3xIQQ", 0x31535054, 4, 1, 1, len(inner))
                  + inner)
        deadline = time.time() + 30
        while server.frames_rejected_total == 0 and time.time() < deadline:
            server.poll_grad_batch()
            time.sleep(0.005)
        s.close()
        check("corrupt frame reason-counted, not delivered",
              server.frames_rejected_total == 1
              and server.grads_received == 5,
              f"rejected={server.frames_rejected_total}")
    finally:
        server.close()


def main() -> int:
    print("native_smoke: build")
    check_build()
    print("native_smoke: fold parity (native vs PS_NO_NATIVE=1)")
    check_fold_parity()
    print("native_smoke: batched ingest")
    check_ingest()
    print("native_smoke: all checks green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
