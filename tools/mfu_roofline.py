"""Analytic MFU ceiling for the transformer train steps.

Decomposes a BERT/GPT train step's FLOPs by matmul class and assigns
each class an MXU ceiling from its contraction geometry (a v5e MXU tile
is 128x128: a matmul whose contraction dim K < 128 uses at most K/128
of the array; batch/output dims pad the same way), then adds a
VPU/HBM-bound share for the non-matmul ops (layernorm, softmax, gelu,
masking) that consume step time while contributing ~no MACs. The
harmonic combination gives the analytic MFU ceiling — what a PERFECT
schedule could reach at this shape — so the measured number's gap
splits into "shape-intrinsic" vs "engineering headroom".

This is an analysis tool, not a measurement: every input is a static
shape; the one empirical knob is the non-matmul time share, bracketed
[5%, 15%] from the trace-derived comm/compute splits the repo measures.

Run: ``python tools/mfu_roofline.py`` — one JSON line per config.
"""

from __future__ import annotations

import json

MXU = 128  # v5e systolic tile edge


def _tile_eff(m: int, k: int, n: int) -> float:
    """Fraction of MXU MACs doing useful work for an [m,k]x[k,n] matmul:
    each dim pads up to the 128 tile."""
    def pad(x):
        return x / (((x + MXU - 1) // MXU) * MXU)
    return pad(m) * pad(k) * pad(n)


def transformer_step(name, b, s, d, heads, ffn, vocab, layers,
                     causal=False):
    """FLOPs by matmul class for one train step (fwd + 2x bwd).

    ``causal``: the useful score/value work halves (the flash kernel
    above FLASH_MIN_SEQ skips fully-future tiles; its block matmuls keep
    the same tile geometry — scores contract K=head_dim, values pad the
    output N=head_dim — so per-tile efficiency is unchanged and only
    the volume halves). NOTE the measured-MFU convention difference: the
    benches take FLOPs from XLA's cost analysis, which counts the FULL
    s^2 matmuls on the causal-EINSUM path (masking doesn't remove
    matmul work) — compare causal rooflines to flash-path rows."""
    hd = d // heads
    rows = b * s
    attn_f = 2 * b * heads * s * s * hd * layers * (0.5 if causal else 1.0)
    classes = {
        # label: (m, k, n, flops_fwd)
        "qkv_proj": (rows, d, 3 * d, 2 * rows * d * 3 * d * layers),
        "attn_scores": (b * heads * s, hd, s, attn_f),
        "attn_values": (b * heads * s, s, hd, attn_f),
        "out_proj": (rows, d, d, 2 * rows * d * d * layers),
        "ffn": (rows, d, ffn, 2 * rows * d * ffn * 2 * layers),
        "vocab_proj": (rows, d, vocab, 2 * rows * d * vocab),
    }
    total = sum(3 * f for _, _, _, f in classes.values())  # train = 3x fwd
    # weighted harmonic mean of per-class efficiencies: time is
    # sum(share/eff); ceiling = 1/time
    t_matmul = sum(
        (3 * f / total) / _tile_eff(m, k, n)
        for m, k, n, f in classes.values()
    )
    out = {"config": name, "batch": b, "seq": s, "causal": causal,
           "train_flops": 3 * sum(f for *_, f in classes.values())}
    for label, (m, k, n, f) in classes.items():
        out[f"share_{label}"] = round(3 * f / total, 4)
        out[f"eff_{label}"] = round(_tile_eff(m, k, n), 3)
    for nonmm in (0.05, 0.10, 0.15):
        # nonmm of step time does no MACs: MFU <= (1-nonmm)/t_matmul
        out[f"mfu_ceiling_nonmatmul_{int(nonmm*100)}pct"] = round(
            (1 - nonmm) / t_matmul, 4
        )
    return out


def main():
    configs = [
        ("bert_base_b16_s128", 16, 128, 768, 12, 3072, 30522, 12, False),
        ("bert_base_b32_s128", 32, 128, 768, 12, 3072, 30522, 12, False),
        ("bert_base_b4_s512", 4, 512, 768, 12, 3072, 30522, 12, False),
        ("bert_base_b8_s512", 8, 512, 768, 12, 3072, 30522, 12, False),
        # the gpt benches run the CAUSAL model (flash kernel at s>=512)
        ("gpt2s_b8_s1024", 8, 1024, 768, 12, 3072, 50257, 12, True),
        ("gpt2s_b4_s2048", 4, 2048, 768, 12, 3072, 50257, 12, True),
    ]
    for cfg in configs:
        print(json.dumps(transformer_step(*cfg)), flush=True)


if __name__ == "__main__":
    main()
