"""Per-codec encode/decode latency + wire size on the current backend.

The compression-curve evidence the reference's codings research surface
existed to produce (SURVEY §2.2): for a ResNet-18-sized flat gradient,
each codec's on-device encode+decode time and bytes on the wire.

Run: ``python benchmarks/codec_bench.py [n_elems]``.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
from pytorch_ps_mpi_tpu.utils.devtime import codec_roundtrip_seconds

CODECS = [  # (label, registry name, kwargs)
    ("identity", "identity", {}),
    ("bf16", "bf16", {}),
    ("int8", "int8", {}),
    ("qsgd", "qsgd", {"levels": 16}),
    ("sign", "sign", {}),
    ("terngrad", "terngrad", {}),
    ("topk", "topk", {"fraction": 0.01}),
    ("topk-approx", "topk", {"fraction": 0.01, "approx": True}),
    # per-block selection, no global sort
    ("blocktopk", "blocktopk", {"fraction": 0.01}),
    ("blocktopk-4k", "blocktopk", {"fraction": 0.01, "block_size": 4096}),
    ("blocktopk8", "blocktopk8", {"fraction": 0.01}),
    ("randomk", "randomk", {"fraction": 0.01}),
    ("powersgd", "powersgd", {"rank": 4}),
    ("threshold", "threshold", {"tau": 2.0, "max_fraction": 0.05}),
]

# codecs with a Pallas kernel AND a jnp fallback: measure both and report
# the Mosaic-kernel speedup (only meaningful on TPU, where
# use_pallas=True lowers through Mosaic instead of the interpreter).
# sign and terngrad use the PR 9 fused encode+pack kernels (one VMEM
# pass instead of reduce-then-pack).
PALLAS_PAIRS = ["int8", "sign", "terngrad"]


def bench_codec(name, kw, n):
    """Device seconds for one encode+decode round-trip at ``n`` elements
    (``utils/devtime.codec_roundtrip_seconds``: a fused scan with a data
    dependence, awaited by ``block_until_ready``) and its wire bytes."""
    code = get_codec(name, **kw)
    # powersgd wants a matrix view; give every codec the same 2-D shape
    shape = (n // 1024, 1024)
    t_rt = codec_roundtrip_seconds(code, shape, jnp.float32)
    bits = code.payload_bits(shape, jnp.float32)
    return t_rt, bits / 8


def main():
    enable_compilation_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 23  # ~8M ≈ ResNet18
    n = max(1024, (n // 1024) * 1024)  # benchmarked shape is (n//1024, 1024)
    raw_bytes = n * 4
    backend = jax.default_backend()
    print(f"backend={backend} device_kind={jax.devices()[0].device_kind!r} "
          f"n={n} raw={raw_bytes/1e6:.1f} MB")
    print("| codec | enc+dec ms (device) | wire MB | ratio |")
    print("|---|---|---|---|")
    rows = []
    for label, name, kw in CODECS:
        t_rt, wire = bench_codec(name, kw, n)
        print(
            f"| {label} | {t_rt*1e3:.2f} "
            f"| {wire/1e6:.2f} | {raw_bytes/wire:.1f}x |"
        )
        rows.append({"codec": label, "enc_dec_ms_device": round(t_rt * 1e3, 2),
                     "wire_mb": round(wire / 1e6, 2),
                     "ratio": round(raw_bytes / wire, 1)})
    # same table as ONE machine-readable line (markdown is for humans).
    # Size tag in binary units so distinct n never collide on one
    # metric name
    size = f"{n//2**20}M" if n >= 2**20 else f"{n//2**10}K"
    print(json.dumps({"metric": f"codec_wire_table_{size}", "n_elems": n,
                      "rows": rows, "backend": backend}), flush=True)

    if backend == "tpu":
        print()
        print("| kernel | pallas enc+dec ms | jnp enc+dec ms | speedup |")
        print("|---|---|---|---|")
        for name in PALLAS_PAIRS:
            pt, _ = bench_codec(name, {"use_pallas": True}, n)
            jt, _ = bench_codec(name, {"use_pallas": False}, n)
            print(f"| {name} | {pt*1e3:.2f} | {jt*1e3:.2f} "
                  f"| {jt / pt:.2f}x |")
        # the exact top-k Pallas selection (threshold refine + chunked
        # compaction, no full sort) must land within 2× of approx_max_k
        # at this size — lax.top_k pays a full bitonic sort
        pe, _ = bench_codec("topk", {"fraction": 0.01, "pallas": True}, n)
        ax, _ = bench_codec("topk", {"fraction": 0.01, "approx": True}, n)
        st, _ = bench_codec("topk", {"fraction": 0.01}, n)
        ratio = pe / max(ax, 1e-12)
        print(f"topk exact selection: pallas {pe*1e3:.2f} ms, "
              f"lax.top_k sort {st*1e3:.2f} ms, approx "
              f"{ax*1e3:.2f} ms — exact/approx {ratio:.2f}x (gate 2x)")
        if ratio > 2.0:
            print(f"FAIL: exact top-k Pallas encode {ratio:.1f}x over "
                  f"approx (gate 2x)")
            return 1
    else:
        print("(pallas-vs-jnp column skipped: kernels run interpreted off-TPU)")

    # threshold-compaction regression guard: an unchunked sort
    # compaction runs a bitonic network of depth log²(n) over the WHOLE
    # tensor and scales superlinearly. The chunked compaction bounds the
    # sort width, so threshold enc+dec must stay within one moderate
    # factor of top-k at any size: 10×.
    by = {r["codec"]: r["enc_dec_ms_device"] for r in rows}
    thr_ratio = by["threshold"] / max(by["topk"], 1e-9)
    print(f"threshold/topk enc+dec ratio: {thr_ratio:.2f}x (gate 10x)")
    if thr_ratio > 10.0:
        print(f"FAIL: threshold compaction regressed — enc+dec "
              f"{by['threshold']} ms is {thr_ratio:.1f}x top-k's "
              f"{by['topk']} ms (gate 10x; see ThresholdCodec chunk=)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
