"""Metric arithmetic: statistics, rates, the peaks table, and the corrected
count of the operations a training token requires."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmark import family
from benchmark import metrics as M

F = family.load("llama", "flops")

TINY = dict(hidden_size=8, intermediate_size=16, num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, head_dim=4, vocab_size=32)


@pytest.mark.parametrize("q", [0, 25, 50, 75, 90, 99, 100])
def test_percentile_is_numpys(q):
    xs = [5.0, 1.0, 9.0, 3.0, 3.0, 7.5, 2.25]
    assert M.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_mean_rate_and_empty_inputs():
    assert M.mean([1, 2, 6]) == 3
    assert M.mean([]) is None and M.percentile([], 50) is None
    assert M.tokens_per_s(1200, 4.0) == 300.0
    assert M.tokens_per_s(5, 0.0) is None


def answer(submit, admit_wait_ms, ttft_ms, prompt_len, n, tpot_ms):
    return {"submit_time": submit, "admit_wait_ms": admit_wait_ms, "ttft_ms": ttft_ms, "prompt_len": prompt_len,
            "tokens": [0] * n, "tpot_ms": tpot_ms}


@pytest.mark.parametrize("window, tokens", [
    ((0.0, 200.0), 1000 + 11),          # all of it inside
    ((101.5, 102.5), 500 + 1 + 5),      # half of the prefill (101..102 is 1000 tokens), the first token, half the rest
    ((101.75, 102.0), 250 + 1),         # a quarter of the prompt and the first token at its instant
    ((102.5, 200.0), 5.0),              # the last half of the ten further tokens (102..103)
    ((103.5, 104.0), 0.0),              # after it ended
])
def test_tokens_processed_credits_the_part_of_a_request_inside_the_window(window, tokens):
    # Submitted at 100, admitted at 101, first token at 102, ten more tokens 100 ms apart: done at 103.
    a = answer(100.0, 1000.0, 2000.0, 1000, 11, 100.0)
    assert M.tokens_processed([a], *window) == pytest.approx(tokens)


def test_tokens_processed_moves_smoothly_and_sums_requests():
    one_token = answer(0.0, 0.0, 500.0, 64, 1, None)  # no second token: tpot is None
    assert M.tokens_processed([one_token], 0.0, 1.0) == pytest.approx(65)
    a, b = answer(0.0, 0.0, 1000.0, 800, 5, 250.0), answer(0.0, 1000.0, 2000.0, 800, 5, 250.0)
    ends = [M.tokens_processed([a, b], 0.0, t / 10) for t in range(0, 31)]
    steps = [y - x for x, y in zip(ends, ends[1:])]
    assert ends[-1] == pytest.approx(1610) and max(steps) < 85  # a tenth of a prompt at a time, never a whole request


def test_flops_leave_out_the_input_embedding_hand_count():
    # By hand: q 8*2*4=64, k and v 8*1*4=32 each, o 8*8=64, feed-forward 3*8*16=384 -> 576 a layer;
    # two layers 1152; the output head 8*32=256. The input embedding (256 more) is a lookup.
    assert F.matmul_params(TINY) == 1152 + 256
    # Causal attention at S=10: 6 * L * S * (H*hd) = 6*2*10*8 = 960.
    assert F.train_flops_per_token(TINY, 10) == 6 * 1408 + 960
    all_params = 1408 + 32 * 8 + 2 * 2 * 8 + 8  # + embedding + norm scales
    assert F.train_flops_per_token(TINY, 10) < 6 * all_params + 960  # bench.py's 6*N over everything


def test_peaks_table_known_and_unknown(tmp_path):
    peak = M.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["int8_ops"] == 393e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in json.loads(M.PEAKS_FILE.read_text())["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        M.peaks("cpu")
    with pytest.raises(KeyError):
        M.mfu_pct(F.train_flops_per_token(TINY, 10), 100.0, "TPU v9 imaginary")


def test_mfu_is_a_share_of_the_peak():
    mistral8 = dict(hidden_size=4096, intermediate_size=14336, num_hidden_layers=8, num_attention_heads=32,
                    num_key_value_heads=8, vocab_size=32000)
    # 1.876 B matrix parameters; 12.06 GFLOP a token at 4096; 10,041 tokens/s is 61.5% of 197 TFLOP/s.
    assert F.matmul_params(mistral8) == 1_875_902_464
    assert M.mfu_pct(F.train_flops_per_token(mistral8, 4096), 10041.0, "TPU v5 lite") == pytest.approx(61.47, abs=0.05)
