"""The traffic generator's invariants (CPU, no jax, milliseconds):
what repeats from run to run is fixed by the file, never by the seed."""

import collections
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import traffic as T  # noqa: E402

BUCKETS = [64, 128, 256, 512, 768, 1024]
SEEDS = [0, 7, 2**31 + 11, 2**32 + 5]
VOCAB = 50257


def load(name):
    return T.load(os.path.join(os.path.dirname(HERE), "traffic", name + ".json"))


def lengths(arrivals):
    return sorted((len(a.prompt), a.max_new_tokens) for a in arrivals)


def per_bucket(arrivals):
    tokens = collections.Counter()
    for a in arrivals:
        tokens[T.bucket_for(len(a.prompt), BUCKETS)] += len(a.prompt)
    return tokens


@pytest.mark.parametrize("seconds", [10, 45, 51])
def test_open_loop_same_seed_same_bytes(seconds):
    tr = load("chat_steady")
    a = T.open_loop_schedule(tr, SEEDS[2], seconds, VOCAB)
    b = T.open_loop_schedule(tr, SEEDS[2], seconds, VOCAB)
    assert T.schedule_bytes(a) == T.schedule_bytes(b)
    c = T.open_loop_schedule(tr, SEEDS[1], seconds, VOCAB)
    assert T.schedule_bytes(a) != T.schedule_bytes(c)


@pytest.mark.parametrize("seconds", [10, 45, 51])
def test_open_loop_every_seed_offers_the_same_work(seconds):
    tr = load("chat_steady")
    runs = [T.open_loop_schedule(tr, s, seconds, VOCAB) for s in SEEDS]
    n_pre, n_win = T.counts(tr, seconds)
    assert n_win == round(tr["rate_per_s"] * seconds)
    for part in (lambda a: a.in_window, lambda a: not a.in_window):
        picked = [[a for a in run if part(a)] for run in runs]
        assert len({len(p) for p in picked}) == 1
        assert all(lengths(p) == lengths(picked[0]) for p in picked)
        assert all(per_bucket(p) == per_bucket(picked[0]) for p in picked)
    assert sum(1 for a in runs[0] if a.in_window) == n_win
    assert sum(1 for a in runs[0] if not a.in_window) == n_pre
    # the order is what the seed changes
    assert [len(a.prompt) for a in runs[0]] != [len(a.prompt) for a in runs[1]]


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_due_times_inside_their_pacing_intervals(seed):
    tr = load("chat_steady")
    rate = tr["rate_per_s"]
    arrivals = T.open_loop_schedule(tr, seed, 45, VOCAB)
    assert [a.index for a in arrivals] == sorted(a.index for a in arrivals)
    for a in arrivals:
        assert a.index / rate <= a.due_s < (a.index + 1) / rate
        assert a.in_window == (a.due_s >= 0)
        assert all(1 <= t < VOCAB for t in a.prompt)
    assert arrivals[0].due_s >= -tr["preroll_s"]
    assert arrivals[-1].due_s < 45


@pytest.mark.parametrize("name", ["chat_steady", "docs_offline",
                                  "decode_heavy"])
def test_multiset_is_the_files_own(name):
    tr = load(name)
    ms = T.multiset(tr)
    assert ms == T.multiset(tr) and len(ms) == tr["multiset"]["size"]
    cap = tr["multiset"]["max_total"]
    assert all(p + a <= cap and a >= 1 for p, a in ms)
    lo_p, hi_p = tr["multiset"]["prompt"]["knots"][0][1], \
        tr["multiset"]["prompt"]["knots"][-1][1]
    assert all(lo_p <= p <= hi_p for p, _ in ms)
    # scaling with --seconds is repetition of the same list
    assert T.repeated(ms, 2 * len(ms) + 3) == [ms, ms, ms[:3]]
    assert T.repeated(ms, 0) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_each_repetition_is_the_whole_multiset(seed):
    tr = load("chat_steady")
    ms = sorted(T.multiset(tr))
    win = [a for a in T.open_loop_schedule(tr, seed, 45, VOCAB) if a.in_window]
    assert len(win) % len(ms) == 0      # 45 s holds whole multisets
    for k in range(0, len(win), len(ms)):
        assert lengths(win[k:k + len(ms)]) == ms


def test_chat_steady_median_lengths_and_buckets():
    ms = T.multiset(load("chat_steady"))
    assert 110 <= statistics.median(p for p, _ in ms) <= 140
    assert 110 <= statistics.median(a for _, a in ms) <= 140
    assert T.buckets_used(load("chat_steady"), BUCKETS) == [64, 128, 256, 512, 768]
    assert T.buckets_used(load("docs_offline"), BUCKETS) == [768, 1024]


@pytest.mark.parametrize("name", ["docs_offline", "decode_heavy"])
@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_each_repetition_is_the_whole_multiset(seed, name):
    tr = load(name)
    ms = sorted(T.multiset(tr))
    stream = T.closed_loop_stream(tr, seed, VOCAB)
    first = [next(stream) for _ in range(len(ms))]
    second = [next(stream) for _ in range(len(ms))]
    assert lengths(first) == ms and lengths(second) == ms
    assert [len(a.prompt) for a in first] != [len(a.prompt) for a in second]
    again = T.closed_loop_stream(tr, seed, VOCAB)
    assert T.schedule_bytes(first) == T.schedule_bytes(
        [next(again) for _ in range(len(ms))])


# ------------------------------------------------------------- decode_heavy

def test_decode_heavy_any_eight_pairs_fit_the_pool():
    """``BlockKVCache.acquire`` reserves blocks for prompt + the whole
    answer at admission; 399 of cgpt-1p3b's 400 blocks of 16 are usable. If
    the eight largest pairs fit, any eight do, and no slot ever stands empty
    waiting for blocks (the cell would measure the allocator)."""
    cfg_path = os.path.join(os.path.dirname(HERE), "configs", "cgpt-1p3b.json")
    engine = T.load(cfg_path)["engine"]
    assert (engine["max_slots"], engine["num_blocks"],
            engine["block_size"]) == (8, 400, 16)
    ms = T.multiset(load("decode_heavy"))
    blocks = sorted(-(-(p + a) // engine["block_size"]) for p, a in ms)
    assert sum(blocks[-engine["max_slots"]:]) == 393 <= engine["num_blocks"] - 1
    assert all(p + a <= engine["max_len"] for p, a in ms)


def test_decode_heavy_lengths_and_its_one_bucket():
    tr = load("decode_heavy")
    ms = T.multiset(tr)
    assert len(ms) == 16 and tr["queue_depth_slots"] == 1
    assert all(16 <= p <= 64 and 512 <= a <= 840 for p, a in ms)
    assert T.buckets_used(tr, BUCKETS) == [64]        # prefill attention bypassed
    assert "ASSUMED" in tr["lengths_why"]
    # decode is the work: 19 output tokens for every prompt token
    assert sum(a for _, a in ms) > 15 * sum(p for p, _ in ms)


@pytest.mark.parametrize("n", [8, 16, 27, 40])
def test_decode_heavy_tokens_per_bucket_do_not_depend_on_the_seed(n):
    tr = load("decode_heavy")
    firsts = []
    for seed in SEEDS:
        stream = T.closed_loop_stream(tr, seed, VOCAB)
        firsts.append([next(stream) for _ in range(n)])
    whole = n - n % 16       # whole repetitions offer the same work
    assert len({tuple(sorted(per_bucket(f[:whole]).items()))
                for f in firsts}) == 1
    assert len({tuple(lengths(f[:whole])) for f in firsts}) == 1
    if n >= 16:
        assert [len(a.prompt) for a in firsts[0]] != \
            [len(a.prompt) for a in firsts[1]]
