"""The yardstick's arithmetic against hand counts: model FLOPs per token,
bytes of the fused update, and the peak table."""
import pytest

from bench import flops, peaks


def test_train_flops_hand_count():
    # d 8, 1 layer, 2 heads of 4, 1 kv head, ff 16, vocab 10, S 3
    # per token forward: q 2*8*8=128, k+v 2*2*8*4=128, o 2*8*8=128,
    # mlp 3*2*8*16=768, attention 2*2*2*4*(3+1)/2=64, head 2*8*10=160
    fwd = 128 + 128 + 128 + 768 + 64 + 160
    got = flops.train_flops_per_token(d=8, layers=1, heads=2, kv_heads=1,
                                      head_dim=4, ff=16, vocab=10, seq_len=3)
    assert got == 3 * fwd


def test_train_flops_qwen3_scale():
    got = flops.train_flops_per_token(d=1024, layers=28, heads=16,
                                      kv_heads=8, head_dim=128, ff=3072,
                                      vocab=151936, seq_len=1024)
    assert 3.8e9 < got < 4.0e9


@pytest.mark.parametrize("partner,streams", [(False, 5), (True, 6)])
def test_update_bytes_hand_count(partner, streams):
    # bf16 params, grads and one bf16 moment over buckets of 100 and 28
    got = flops.update_bytes([100, 28], param_bytes=2, grad_bytes=2,
                             moment_bytes=[2], partner=partner)
    assert got == 128 * 2 * streams


def test_update_bytes_two_moments_fp32():
    # adam-like: p r/w, g r, two fp32 moments r/w
    got = flops.update_bytes([10], param_bytes=4, grad_bytes=4,
                             moment_bytes=[4, 4], partner=False)
    assert got == 10 * (8 + 4 + 16)


def test_peaks_known_and_unknown():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_judge_leaves_out_uncompared_numbers():
    from bench import reference
    gaps = {"loss_gap": (1.0, ""), "grad_gap": (0.1, ""), "delta_gap": (0.1, "")}
    limits = {"loss_gap": None, "grad_gap": 0.2, "delta_gap": 0.2}
    assert reference.judge(gaps, limits)
    assert not reference.judge(gaps, dict(limits, grad_gap=0.05))
