"""The benchmark's arithmetic: percentiles, rates, work counts, peaks
and the miss limit."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import stats, work  # noqa: E402
from chipbench.reference import binomial_limit  # noqa: E402

SMOLLM = {"hidden_size": 960, "num_hidden_layers": 32,
          "num_attention_heads": 15, "num_key_value_heads": 5,
          "intermediate_size": 2560, "vocab_size": 49152}


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_matches_numpy_linear(q):
    xs = list(np.random.default_rng(3).exponential(size=137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_smollm_useful_flops_per_512_token_record():
    per = work.llama_prefill_flop_per_record(SMOLLM, 512)
    assert per / 1e9 == pytest.approx(338.3, abs=0.05)
    body = 2 * (361_758_720 - 47_185_920) * 512
    attn = 4 * (512 * 512 / 2) * 15 * 64 * 32
    assert per == pytest.approx(body + attn + 2 * 960 * 49152)


def test_kernel_bytes_count_reads_and_selected_writes():
    assert work.threshold_select_bytes(100, 7) == 4 * 107
    assert work.score_hist_bytes(256) == 1024


def test_peaks_table_refuses_an_unknown_device():
    assert work.peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")


@pytest.mark.parametrize("n,p", [(60, 0.05), (200, 0.05), (10, 0.3)])
def test_binomial_limit_bounds_the_tail(n, p):
    k = binomial_limit(n, p, tail=1e-6)
    rng = np.random.default_rng(0)
    draws = rng.binomial(n, p, size=200_000)
    assert (draws > k).mean() < 1e-4
    assert 0 < k <= n
