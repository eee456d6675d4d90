"""The load generator's arithmetic: percentiles, the plan every seed
shares, due times, and what a stalled server does to the numbers."""

import threading
import time

import numpy as np
import pytest

from _perfbench_tiny import ROOT  # noqa: F401  (puts the repo on the path)

from perfbench import loadgen

OPEN = {"loop": "open", "rate_rps": 40.0, "clients": 4, "ramp_s": 2.0,
        "src_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                    "min": 4, "max": 200},
        "trg_len": {"dist": "ratio_uniform", "low": 0.9, "high": 1.3,
                    "min": 4, "max": 250}}


def test_percentile_interpolates():
    assert loadgen.percentile([], 95) is None
    assert loadgen.percentile([7.0], 95) == 7.0
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3
    assert loadgen.percentile(list(range(101)), 95) == 95
    assert loadgen.percentile([0.0, 10.0], 95) == pytest.approx(9.5)


@pytest.mark.parametrize("seed", [0, 12345, 2 ** 31 + 17])
def test_every_seed_gets_the_same_work_in_another_order(seed):
    a = loadgen.make_plan(OPEN, 1, 30.0)
    b = loadgen.make_plan(OPEN, seed, 30.0)
    n = min(len(a["due"]), len(b["due"]))
    assert abs(len(a["due"]) - len(b["due"])) <= 1
    assert n == pytest.approx(40 * 32, abs=2)
    # the same arrivals horizon and (up to the last arrival) the same
    # lengths, in another order
    pairs = lambda p: sorted(zip(p["src_len"].tolist(),  # noqa: E731
                                 p["trg_len"].tolist()))
    assert len(set(pairs(a)) ^ set(pairs(b))) <= 4
    assert b["due"][0] == pytest.approx(-2.0)
    assert np.all(np.diff(b["due"]) > 0) and b["due"][-1] < 30.0
    if seed != 1:
        assert not np.array_equal(a["trg_len"][:50], b["trg_len"][:50])


def test_lengths_follow_the_distribution():
    p = loadgen.make_plan(OPEN, 3, 60.0)
    src, trg = p["src_len"], p["trg_len"]
    assert np.median(src) == pytest.approx(24, abs=1)
    assert src.min() >= 4 and src.max() <= 200 and src.max() > 80
    ratio = trg / src.astype(float)
    assert trg.min() >= 4 and trg.max() <= 250
    assert 0.85 <= np.median(ratio) <= 1.35


def test_summarize_times_from_the_instant_due():
    recs = [
        # on time: due 1.0, first token 1.2, 9 tokens by 2.0
        {"due": 1.0, "sent": 1.001, "first": 1.2, "last": 2.0, "tokens": 9,
         "chunks": [(1.2, 4), (1.6, 4), (2.0, 1)], "failed": False},
        # sent 3 s late behind a stall: the wait counts
        {"due": 2.0, "sent": 5.0, "first": 5.5, "last": 5.5, "tokens": 4,
         "chunks": [(5.5, 4)], "failed": False},
        # the ramp: not in the sample, but its tokens inside the window are
        {"due": -1.0, "sent": -1.0, "first": -0.5, "last": 0.5, "tokens": 8,
         "chunks": [(-0.5, 4), (0.5, 4)], "failed": False},
        # failed: counted, and misses every latency sample
        {"due": 3.0, "sent": 3.0, "first": None, "last": None, "tokens": 0,
         "chunks": [], "failed": True},
    ]
    s = loadgen.summarize(recs, 10.0)
    assert (s["attempted"], s["failed"]) == (3, 1)
    assert s["ttft_ms"] == pytest.approx([200.0, 3500.0])
    assert s["tpot_ms"] == pytest.approx([100.0])  # one chunk: no gap
    assert s["late_ms"] == pytest.approx([1.0, 3000.0, 0.0])
    # the ramp request's first chunk reached its client before the
    # window, its second inside: only the second counts
    assert s["tokens_in_window"] == 9 + 4 + 4
    assert s["tokens_per_s"] == pytest.approx(1.7)


def test_a_chunk_counts_in_the_window_it_arrived_in():
    """Tokens delivered inside ``[0, seconds)``: a chunk counts whole, at
    the instant the client held it, whenever its request was due."""
    rec = {"due": 8.0, "sent": 8.0, "first": 9.0, "last": 11.0,
           "tokens": 10, "failed": False,
           "chunks": [(0.0, 1), (9.0, 4), (10.0, 4), (11.0, 2)]}
    assert loadgen.summarize([rec], 10.0)["tokens_in_window"] == 5
    assert loadgen.summarize([rec], 10.5)["tokens_in_window"] == 9
    late = dict(rec, due=-3.0)  # not in the sample; its tokens still count
    s = loadgen.summarize([late], 10.0)
    assert (s["attempted"], s["tokens_in_window"]) == (0, 5)


class _StalledClient(object):
    """Answers every request 0.15 s late, 4 tokens a chunk."""

    def generate(self, src, src_len=None):
        def stream():
            time.sleep(0.15)
            while True:
                yield {"event": "tokens", "tokens": np.arange(4)}
        return stream()

    def close(self):
        pass


def test_a_stalled_server_shows_in_lateness_and_ttft(monkeypatch):
    """One caller, 40 requests/s offered to a server that needs 0.15 s a
    request: the open loop keeps its schedule, requests queue behind the
    stall, and both the generator's lateness and the time to first token
    (from the instant DUE) grow through the run."""
    traffic = dict(OPEN, rate_rps=40.0, clients=1, ramp_s=0.0,
                   drain_s=0.3)
    spec = {"traffic": traffic, "seed": 5, "seconds": 1.0, "vocab": 50,
            "max_length": 256, "out": None}
    gen = loadgen.Generator(spec)
    monkeypatch.setattr(loadgen._Worker, "connect",
                        lambda self: _StalledClient())
    records = gen.run(("127.0.0.1", 0), time.time() + 0.05)
    s = loadgen.summarize(records, 1.0)
    assert s["attempted"] == pytest.approx(40, abs=3)
    done = sorted((r for r in records if not r["failed"]),
                  key=lambda r: r["due"])
    assert 4 <= len(done) <= 10           # 0.15 s each, 1.3 s in all
    assert s["failed"] == s["attempted"] - len(done)
    ttft = [r["first"] - r["due"] for r in done]
    assert ttft[0] < 0.25 and ttft[-1] > 0.6 and ttft[-1] > ttft[0]
    late = [r["sent"] - r["due"] for r in done]
    assert late[0] < 0.05 and late[-1] > 0.4
    never = [r for r in records if r.get("error") == "never sent"]
    assert never, "requests no caller was free to take must be failures"
    assert not [t for t in threading.enumerate()
                if t.name.startswith("perfbench-client") and t.is_alive()
                and not t.daemon]


@pytest.mark.parametrize("seed", [1, 99, 2 ** 31 + 5])
def test_arrivals_are_poisson_whatever_the_seed(seed):
    """Nothing is laid out: the counts of arrivals a second vary as a
    Poisson process's do (variance about the mean), and so do those over
    five seconds, where bursts that last make the tail."""
    traffic = dict(OPEN, rate_rps=31.5, ramp_s=6.0)
    p = loadgen.make_plan(traffic, seed, 51.0)
    due = p["due"][p["due"] >= 0]
    assert len(due) == pytest.approx(31.5 * 51, rel=0.08)
    gaps = np.diff(p["due"])
    assert np.mean(gaps) == pytest.approx(1 / 31.5, rel=0.03)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.15)
    by_s = np.histogram(due, bins=np.arange(0, 52, 1))[0]
    assert 0.45 * by_s.mean() < by_s.var() < 2.0 * by_s.mean()
    by_5s = np.histogram(due, bins=np.arange(0, 51, 5))[0]
    assert by_5s.max() - by_5s.min() >= 15   # sd of Poisson(157) is 12.5


def test_closed_loop_callers_go_round_one_plan():
    """Four requests a caller, the same for every seed in another order;
    a caller past the end starts the plan again."""
    traffic = dict(OPEN, loop="closed", clients=320)
    a = loadgen.make_plan(traffic, 1, 51.0)
    b = loadgen.make_plan(traffic, 2 ** 31 + 9, 51.0)
    assert len(a["trg_len"]) == len(b["trg_len"]) == 1280
    assert "due" not in a
    pairs = lambda p: sorted(zip(p["src_len"].tolist(),  # noqa: E731
                                 p["trg_len"].tolist()))
    assert pairs(a) == pairs(b)
    assert not np.array_equal(a["trg_len"], b["trg_len"])
