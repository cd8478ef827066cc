"""Inputs made from the seed: the same seed gives the same traffic,
corpus, tokens and weights; another seed gives others."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import deploy, harness, plugins  # noqa: E402

queries = plugins.load("drivers", "closed_loop_queries")

BIG = 2 ** 40 + 12345          # seeds wider than 32 bits
TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "vocab_size": 256,
              "initializer_range": 0.02}


@pytest.mark.parametrize("name", ["rt.solo"])
def test_client_kind_order_is_fixed_by_the_seed(name):
    t = harness.load_json(harness.BENCH_DIR / "traffic" / f"{name}.json")
    for client in range(int(t["clients"])):
        for block in range(3):
            a = queries.client_kinds(t["pattern"], BIG, client, block)
            assert a == queries.client_kinds(t["pattern"], BIG, client,
                                             block)
            assert sorted(a) == sorted(t["pattern"])


def test_client_kind_order_changes_with_the_seed():
    pattern = ["a", "a", "b", "c"] * 4
    orders = {tuple(queries.client_kinds(pattern, s, 0, 0))
              for s in range(8)}
    assert len(orders) > 1


def test_query_keys_are_fixed_by_the_seed():
    def key(seed):
        drv = queries.ClosedLoopQueries.__new__(queries.ClosedLoopQueries)
        drv.key = deploy.stream_key(seed, "queries")
        return np.asarray(drv._key(3, 17))
    assert np.array_equal(key(BIG), key(BIG))
    assert not np.array_equal(key(BIG), key(BIG + 1))


def test_corpus_is_fixed_by_the_seed():
    cfg = {"records": 4096, "alpha": 0.01, "beta": 1.0}
    a1, o1 = deploy.make_corpus(cfg, BIG)
    a2, o2 = deploy.make_corpus(cfg, BIG)
    a3, _ = deploy.make_corpus(cfg, BIG + 1)
    assert np.array_equal(a1, a2) and np.array_equal(o1, o2)
    assert not np.array_equal(a1, a3)
    assert a1.dtype == np.float32 and ((a1 >= 0) & (a1 <= 1)).all()


def test_token_batches_are_fixed_by_the_seed_and_batch():
    def batch(seed, i):
        return np.asarray(deploy.make_token_batch(seed, i, 8, 64, 256,
                                                  (7, 13, 42), 0.5))
    assert np.array_equal(batch(BIG, 5), batch(BIG, 5))
    assert not np.array_equal(batch(BIG, 5), batch(BIG, 6))
    assert not np.array_equal(batch(BIG, 5), batch(BIG + 1, 5))
    b = batch(BIG, 5)
    assert b.shape == (8, 64) and b.min() >= 0 and b.max() < 256


def test_weights_are_fixed_by_the_seed():
    w1 = deploy.make_weights(TINY_MODEL, BIG)
    w2 = deploy.make_weights(TINY_MODEL, BIG)
    w3 = deploy.make_weights(TINY_MODEL, BIG + 1)
    for k in w1:
        assert np.array_equal(np.asarray(w1[k], np.float32),
                              np.asarray(w2[k], np.float32))
    assert not np.array_equal(np.asarray(w1["wq"], np.float32),
                              np.asarray(w3["wq"], np.float32))
