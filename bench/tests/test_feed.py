"""The packed-document generator and the trainer's data source."""
import time

import numpy as np
import pytest

import feed

TRAFFIC = {"doc_log_mean": 6.0, "doc_log_sigma": 1.2, "doc_min_tokens": 16,
           "doc_max_tokens": 65536}
EOS = 511


def rows(seed, n=64, seq=1024, vocab=512):
    return feed.pack_rows(TRAFFIC, vocab=vocab, eos=EOS, seq=seq, rows=n,
                          seed=seed)


def test_same_seed_same_rows_and_large_seeds_work():
    a, b = rows(3_000_000_017), rows(3_000_000_017)
    assert a.shape == (64, 1025) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, rows(3_000_000_018))


def test_rows_all_differ_and_ids_in_range():
    r = rows(7)
    assert len({x.tobytes() for x in r}) == len(r)
    assert r.min() >= 0 and r.max() < 512


def test_documents_are_packed_with_eos_between_them():
    r = feed.pack_rows(TRAFFIC, vocab=151936, eos=151643, seq=4096, rows=256,
                       seed=5)
    lengths = []
    for row in r:
        ends = np.flatnonzero(row == 151643)
        lengths += list(np.diff(ends) - 1)
    lengths = np.asarray(lengths)
    assert lengths.min() >= 16
    # log-normal(6.0, 1.2): median ~403 tokens, mean well above it
    assert 300 < np.median(lengths) < 520
    assert lengths.mean() > 1.3 * np.median(lengths)


def test_markov_structure_makes_successors_predictable():
    r = rows(9, n=128, seq=2048)
    pairs = {}
    for row in r:
        for a, b in zip(row[:-1], row[1:]):
            pairs.setdefault(int(a), set()).add(int(b))
    # 8 preferred successors per state plus 10% jumps: far fewer distinct
    # successors per token than a uniform stream would give
    mean_succ = np.mean([len(v) for v in pairs.values()])
    assert mean_succ < 0.5 * 512


def test_generator_costs_far_under_a_millisecond_per_step():
    t0 = time.perf_counter()
    feed.pack_rows(TRAFFIC, vocab=151936, eos=151643, seq=4096, rows=256,
                   seed=1)
    per_row = (time.perf_counter() - t0) / 256
    assert per_row < 1e-3


def test_window_closes_with_a_base_exception_and_counts_repeats():
    fd = feed.Feed(rows(1, n=8, seq=16), 2, warmup=2, seconds=0.5)
    for s in range(3):
        b = fd.batch(s)
        assert b["tokens"].shape == (2, 16) and b["labels"].shape == (2, 16)
    np.testing.assert_array_equal(fd.batch(3)["tokens"][0],
                                  fd.rows[6, :-1])
    fd.batch(2)                      # a restart replays step 2
    assert fd.log.failed == 1
    time.sleep(0.6)
    with pytest.raises(feed.WindowClosed):
        fd.batch(3)
    assert not issubclass(feed.WindowClosed, Exception)
    assert fd.log.window_end - fd.log.window_start >= 0.5
    assert len(fd.log.stamps) == 4
