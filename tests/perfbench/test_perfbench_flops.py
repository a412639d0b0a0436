"""Operations and bytes from shapes: the counts the utilization and roofline
metrics stand on."""
import json
import os

import pytest

from perfbench import flops, stats

ROOT = stats.repo_root()
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))


def test_gpt_matmul_parameters_agree_with_the_program():
    from deepspeed_tpu.models.transformer_lm import gpt2_config, num_params

    cfg = gpt2_config("gpt2-1.3b", n_positions=1024)
    embed = cfg.vocab_size * cfg.n_embd + cfg.n_positions * cfg.n_embd
    assert flops.gpt_params_matmul(24, 2048) == num_params(cfg) - embed


def test_gpt_flops_per_token_at_the_cell():
    fpt = flops.gpt_train_flops_per_token(24, 2048, 50257, 1024)
    # 6 x 1.208e9 matmul parameters + 0.302e9 attention + 0.618e9 head
    assert fpt == pytest.approx(8.17e9, rel=2e-3)
    head = 6.0 * 50257 * 2048
    repo = fpt - head   # benchmarks/_util.gpt_flops_per_token leaves it out
    assert repo == pytest.approx(7.55e9, rel=2e-3)


def test_bert_flops_per_token_at_the_cell():
    fpt = flops.bert_train_flops_per_token(24, 1024, 4096, 30522, 128, 0.15)
    assert fpt == pytest.approx(1.886e9, rel=2e-3)
    assert flops.bert_train_flops_per_token(24, 1024, 4096, 30522, 128, 0.0) \
        < fpt


@pytest.mark.parametrize("kind,matmuls", [("fwd", 2), ("bwd_dq", 3),
                                          ("bwd_dkv", 4)])
def test_flash_call_flops(kind, matmuls):
    bh, t, d = 96, 1024, 128
    full = 2.0 * bh * t * t * d * matmuls
    assert flops.flash_call_flops(kind, bh, t, d, causal=False) == full
    assert flops.flash_call_flops(kind, bh, t, d, causal=True) == full / 2
    assert flops.flash_call_bytes(kind, bh, t, d) > 0


def test_roofline_says_which_bound():
    peak = PEAK["TPU v5 lite"]
    secs, bound = flops.roofline_seconds(197e12, 1.0, peak)
    assert bound == "compute" and secs == pytest.approx(1.0)
    secs, bound = flops.roofline_seconds(1.0, 819e9, peak)
    assert bound == "memory" and secs == pytest.approx(1.0)


def test_decode_bytes_at_the_cell():
    w = flops.gpt_weight_bytes(24, 2048, 50257, 1024)
    assert w == pytest.approx(2.63e9, rel=5e-3)       # PR 21: params 2.63 GB
    assert flops.kv_bytes_per_position(24, 2048) == 196608.0
    # 16 lanes x 1024 positions: the 3.2 GB cache of the serve cell
    assert 16 * 1024 * 196608.0 == pytest.approx(3.22e9, rel=1e-2)


def test_peaks_name_their_source():
    for kind, row in PEAK.items():
        assert row["source"] and row["bf16_tflops"] > 0 \
            and row["hbm_gb_per_s"] > 0, kind
    assert json.dumps(PEAK)  # plain data
