"""Native read-plane smoke gate (make read-native-smoke, in the default
`make test` path).

Proves the C++ epoll read tier end to end against the Python selectors
loop it replaces, plus one follower hop — each a hard assert:

1. **build + arm** — native/tcpps.cpp builds, the ``tps_read_*`` ABI
   twin check passes, and a core with ``read_native`` on actually serves
   from the C++ tier (``serving_snapshot()["read_native"]``);
2. **wire parity** — raw PSR1 reply byte streams (header AND payload)
   from the native tier match the Python loop bit-for-bit across the
   full / delta / not-modified kinds;
3. **concurrent reads** — 24 readers' full reads through the native
   tier are all answered and every byte of them is sent;
4. **admission shedding** — a depth-1 storm through the native tier
   sheds and every reader still completes via retry-after;
5. **replica hop** — a ``FollowerLoop`` replica pulled off the native
   root re-serves bit-exact bytes with lag 0 and nonzero
   ``follower_bytes_relayed``.

Skips (exit 0, with a notice) when the toolchain is missing or
``PS_NO_NATIVE`` is set — the Python loop is the tested fallback and
the rest of `make test` already covers it.
"""

from __future__ import annotations

import os
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ELEMS = 49_000
TEMPLATE_SHAPE = {"w0": (40_000,), "w1": (9_000,)}
SERVING_KW = {"ring": 4, "admission_depth": 64, "retry_after_s": 0.005,
              "delta_bucket_mb": 0.05}


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        raise SystemExit(f"read_native_smoke: {name} failed ({detail})")


def _recv_exact(sock, n: int) -> bytes:
    out = bytearray()
    while len(out) < n:
        chunk = sock.recv(n - len(out))
        if not chunk:
            raise ConnectionError("server closed connection")
        out += chunk
    return bytes(out)


def raw_reply(port: int, have_version: int = 0) -> bytes:
    from pytorch_ps_mpi_tpu.serving import net

    with socket.create_connection(("127.0.0.1", port), timeout=20) as s:
        s.sendall(net.pack_request(have_version, True, ""))
        hdr = _recv_exact(s, net._REP.size)
        return hdr + _recv_exact(s, net._REP.unpack(hdr)[7])


def concurrent_full_reads(port: int, n_readers: int,
                          reads_each: int) -> int:
    """Concurrent full reads — every request does real work
    (have_version=0), never the not-modified fast exit. Returns how
    many were served."""
    from pytorch_ps_mpi_tpu.serving.net import ReadClient

    served = [0] * n_readers
    barrier = threading.Barrier(n_readers)

    def body(i: int) -> None:
        c = ReadClient("127.0.0.1", port, timeout=30)
        barrier.wait()
        for _ in range(reads_each):
            kind, _, _, retry_after, _ = c.request(have_version=0)
            if kind == "retry":
                time.sleep(retry_after)
                continue
            served[i] += 1
        c.close()

    threads = [threading.Thread(target=body, args=(i,))
               for i in range(n_readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return sum(served)


def main() -> int:
    from pytorch_ps_mpi_tpu.serving import (
        FollowerLoop,
        ServingCore,
        ServingReader,
    )
    from pytorch_ps_mpi_tpu.serving.native_read import get_read_lib
    from pytorch_ps_mpi_tpu.utils.native import fast_path_disabled

    if fast_path_disabled():
        print("read_native_smoke: SKIP (PS_NO_NATIVE set; the Python "
              "loop is covered by make read-smoke)")
        return 0
    if get_read_lib() is None:
        print("read_native_smoke: SKIP (no C++ toolchain; the Python "
              "loop is covered by make read-smoke)")
        return 0

    template = {k: np.zeros(s, np.float32)
                for k, s in TEMPLATE_SHAPE.items()}
    rng = np.random.RandomState(0)
    flat_v1 = rng.randn(N_ELEMS).astype(np.float32)
    flat_v2 = flat_v1.copy()
    flat_v2[rng.choice(N_ELEMS, 120, replace=False)] += 0.5

    # -- 1. build + arm ----------------------------------------------------
    nat = ServingCore(None, {"read_port": 0, "read_native": True,
                             "serving_kw": SERVING_KW}, template=template)
    py = ServingCore(None, {"read_port": 0, "read_native": False,
                            "serving_kw": SERVING_KW}, template=template)
    check("native tier armed",
          nat.serving_snapshot()["read_native"] is True
          and py.serving_snapshot()["read_native"] is False)

    # -- 2. wire parity: raw reply streams bit-for-bit ---------------------
    for core in (nat, py):
        core.publish(flat=flat_v1.copy())
        core.publish(flat=flat_v2.copy())
    for label, have in (("full", 0), ("delta", 1), ("not_modified", 2)):
        a, b = raw_reply(nat.read_port, have), raw_reply(py.read_port, have)
        check(f"reply parity: {label}", a == b,
              f"{len(a)}B native vs {len(b)}B python")

    py.close()

    # -- 3. concurrent full reads through the native tier ------------------
    n_readers, reads_each = 24, 15
    served = concurrent_full_reads(nat.read_port, n_readers, reads_each)
    st = nat.read_server.stats()
    check("native tier answered the workload",
          served == n_readers * reads_each
          and st["reads_full"] >= n_readers * reads_each,
          f"served={served} reads_full={st['reads_full']}")
    check("native zero-copy sends drained",
          st["bytes_sent"] >= n_readers * reads_each * N_ELEMS * 4,
          f"bytes_sent={st['bytes_sent']}")

    # -- 4. admission shedding on the native tier --------------------------
    # the C++ tier sheds on PENDING replies (admitted but not yet
    # drained), and parses a pipelined burst in one pass before any
    # flush: at depth 1, request #1 of a back-to-back burst is admitted
    # and the rest MUST come back as retry-after — deterministically
    from pytorch_ps_mpi_tpu.serving import net as _net

    nat.read_server.set_admission(1, 0.002)
    n_burst = 8
    with socket.create_connection(("127.0.0.1", nat.read_port),
                                  timeout=20) as s:
        s.sendall(_net.pack_request(0, True, "") * n_burst)
        kinds = []
        retry_after = 0.0
        for _ in range(n_burst):
            hdr = _recv_exact(s, _net._REP.size)
            _, kind, _, _, _, _, ra, plen = _net._REP.unpack(hdr)
            _recv_exact(s, plen)
            kinds.append(kind)
            if kind == _net.KIND_RETRY:
                retry_after = ra
        shed_replies = kinds.count(_net.KIND_RETRY)
        check("native admission shed fired (depth 1)",
              kinds[0] == _net.KIND_FULL and shed_replies >= 1,
              f"kinds={kinds}")
        check("shed replies carry the retry-after hint",
              retry_after == 0.002, f"retry_after={retry_after}")
        # honoring the hint lands: the same connection's retry is served
        time.sleep(retry_after)
        s.sendall(_net.pack_request(0, True, ""))
        hdr = _recv_exact(s, _net._REP.size)
        _, kind, _, _, _, _, _, plen = _net._REP.unpack(hdr)
        _recv_exact(s, plen)
        check("shed reader retried to completion",
              kind == _net.KIND_FULL, f"kind={kind}")
    shed_total = nat.read_server.stats()["reads_shed"]
    check("shed accounting matches the wire",
          shed_total == shed_replies, f"{shed_total} vs {shed_replies}")
    nat.read_server.set_admission(SERVING_KW["admission_depth"],
                                  SERVING_KW["retry_after_s"])

    # -- 5. follower replica hop off the native root -----------------------
    rep = ServingCore(None, {"read_port": 0, "serving_kw": SERVING_KW},
                      template=template)
    follower = FollowerLoop(rep, "127.0.0.1", nat.read_port,
                            template=template, poll_s=0.01,
                            serving_kw=SERVING_KW)
    out = follower.step()
    check("replica republished the root's latest",
          out["outcome"] == "republished" and out["version"] == 2,
          f"{out}")
    r = ServingReader("127.0.0.1", rep.read_port, template,
                      serving_kw=SERVING_KW)
    r.read_params()
    check("replica serves bit-exact bytes",
          np.array_equal(r._flat.view(np.uint32),
                         flat_v2.view(np.uint32)))
    flat_v3 = flat_v2.copy()
    flat_v3[:64] -= 0.25
    nat.publish(flat=flat_v3.copy())
    follower.step()
    _, ver = r.read_params()
    m = rep.read_metrics()
    check("delta hop through the replica is current",
          ver == 3 and np.array_equal(r._flat.view(np.uint32),
                                      flat_v3.view(np.uint32)))
    check("replica lag settled at 0",
          m["replica_lag_versions"] == 0.0,
          f"lag={m['replica_lag_versions']}")
    check("relay accounting is nonzero",
          m["follower_bytes_relayed"] > 0,
          f"relayed={m['follower_bytes_relayed']}")
    r.close()
    follower.close()
    rep.close()
    nat.close()

    print("read_native_smoke: all checks green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
