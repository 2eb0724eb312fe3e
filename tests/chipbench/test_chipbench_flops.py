"""flops.py against numbers worked by hand, and the peaks table."""

import pytest

from chipbench import flops

# hand-worked, forward, 2 operations a multiply-add:
#   bert-base, 64 x 128 = 8,192 tokens, 12 layers, d 768, ffn 3072, vocab 30522
#     qkv    2 * 8192 * 768 * 2304 * 12            = 347,892,350,976
#     scores 2 * 64 * 12 * 128 * 128 * 64 * 12     =  19,327,352,832  (= values)
#     out    2 * 8192 * 768 * 768 * 12             = 115,964,116,992
#     ffn    2 * 8192 * 768 * 3072 * 2 * 12        = 927,712,935,936
#     vocab  2 * 8192 * 768 * 30522                = 384,055,640,064
#     sum 1,814,279,749,632; a training step is 3x = 5,442,839,248,896
#   gpt2-small, 8 x 1024 tokens, causal (scores and values at half), vocab 50257
#     scores 2 * 8 * 12 * 1024 * 1024 * 64 * 12 / 2 = 77,309,411,328 (= values)
#     vocab  2 * 8192 * 768 * 50257                 = 632,379,408,384
#     sum 2,178,567,634,944; 3x = 6,535,702,904,832
BERT = dict(rows=64, seq=128, hidden=768, heads=12, ffn=3072, vocab=30522,
            layers=12, causal=False)
GPT2 = dict(rows=8, seq=1024, hidden=768, heads=12, ffn=3072, vocab=50257,
            layers=12, causal=True)


@pytest.mark.parametrize("shape, klass, want", [
    (BERT, "qkv_proj", 347_892_350_976),
    (BERT, "attn_scores", 19_327_352_832),
    (BERT, "attn_values", 19_327_352_832),
    (BERT, "out_proj", 115_964_116_992),
    (BERT, "ffn", 927_712_935_936),
    (BERT, "vocab_proj", 384_055_640_064),
    (GPT2, "attn_scores", 77_309_411_328),
    (GPT2, "vocab_proj", 632_379_408_384),
])
def test_forward_classes(shape, klass, want):
    assert flops.transformer_forward_flops(**shape)[klass] == want


@pytest.mark.parametrize("shape, want", [
    (BERT, 5_442_839_248_896), (GPT2, 6_535_702_904_832)])
def test_train_step_is_three_forwards(shape, want):
    assert flops.transformer_train_flops(**shape) == want


def test_attention_kernel_cost_and_bound():
    # gpt2-small: one causal matmul over all heads and layers is
    # 2*8*12*1024*1024*64*12/2 = 77,309,411,328; 2 forward + 5 backward
    cost = flops.attention_kernel_cost(rows=8, seq=1024, heads=12, head_dim=64,
                                       layers=12, causal=True, dtype_bytes=2)
    assert cost["flops"] == 7 * 77_309_411_328
    # q, k, v, o: 8*12*1024*64 * 2 B * 12 layers = 150,994,944 B each; 4 + 8
    assert cost["bytes"] == 12 * 150_994_944
    peaks = flops.peaks_for("TPU v5 lite")
    least, bound = flops.roofline_seconds(cost, peaks)
    assert bound == "compute"
    assert least == pytest.approx(7 * 77_309_411_328 / 197e12)
    assert flops.roofline_seconds({"flops": 1, "bytes": 819e9}, peaks) == (
        pytest.approx(1.0), "memory")


def test_peaks_table():
    p = flops.peaks_for("TPU v5 lite")
    assert (p["flops_bf16"], p["ops_int8"], p["hbm_bytes_per_s"],
            p["ici_bits_per_s"], p["hbm_bytes"]) == (
        197e12, 393e12, 819e9, 1600e9, 16e9)
    assert p["source"]
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")
