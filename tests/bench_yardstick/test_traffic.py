"""The open-loop traffic generator and the client-side reduction: every seed
serves the same work, a latency counts from when the request was due, and
how late the generator ran is reported."""
import collections

import pytest

from benchmark.generators import open_loop_http as gen

TRAFFIC = {"rate_rps": 3.0, "lead_in_s": 2,
           "prompt_tokens": {"law": "log-uniform", "min": 32, "max": 1024},
           "output_tokens": {"law": "log-uniform", "min": 16, "max": 256}}


def test_grids():
    lengths = gen.length_grid(TRAFFIC["prompt_tokens"], 90)
    assert len(lengths) == 90 and min(lengths) >= 32 and max(lengths) <= 1024
    assert lengths == sorted(lengths)
    # log-uniform: as many lengths below the geometric middle as above it
    assert sum(1 for n in lengths if n < (32 * 1024) ** 0.5) == 45
    gaps = gen.gap_grid(90, 30.0)
    assert sum(gaps) == pytest.approx(30.0)
    assert max(gaps) / min(gaps) > 100          # exponential, not even
    with pytest.raises(ValueError):
        gen.length_grid({"law": "zipf", "min": 1, "max": 2}, 3)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 4000000007])
def test_every_seed_serves_the_same_work(seed):
    base = gen.schedule(TRAFFIC, 0, 30, vocab=50272)
    plan = gen.schedule(TRAFFIC, seed, 30, vocab=50272)

    def work(p, window):
        rows = [r for r in p if r["window"] == window]
        return (collections.Counter(len(r["prompt"]) for r in rows),
                collections.Counter(r["max_new_tokens"] for r in rows), len(rows))

    assert work(plan, True) == work(base, True)
    assert work(plan, False) == work(base, False)
    assert work(plan, True)[2] == 90 and work(plan, False)[2] == 6
    due = [r["due"] for r in plan]
    assert due == sorted(due) and due[0] == 0.0
    assert all(2.0 <= r["due"] < 32.0 for r in plan if r["window"])
    assert all(r["due"] < 2.0 for r in plan if not r["window"])
    assert [len(r["prompt"]) for r in plan] != [len(r["prompt"]) for r in base]
    assert plan == gen.schedule(TRAFFIC, seed, 30, vocab=50272)
    assert all(0 <= t < 50272 for r in plan for t in r["prompt"])


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_an_order_seed_replays_one_trace(seed):
    mix = dict(TRAFFIC, order_seed=5)
    base = gen.schedule(mix, 0, 30, vocab=50272)
    plan = gen.schedule(mix, seed, 30, vocab=50272)
    shape = lambda p: [(r["id"], r["due"], len(r["prompt"]), r["max_new_tokens"])
                       for r in p]
    assert shape(plan) == shape(base)
    assert [r["prompt"] for r in plan] != [r["prompt"] for r in base]
    assert shape(plan) != shape(gen.schedule(dict(TRAFFIC, order_seed=6), seed,
                                             30, vocab=50272))


def test_latency_counts_from_the_due_time_and_lateness_is_reported():
    plan = [{"id": "L0", "due": 0.0, "window": False, "prompt": [1], "max_new_tokens": 2},
            {"id": "W0", "due": 1.0, "window": True, "prompt": [1, 2], "max_new_tokens": 3},
            {"id": "W1", "due": 2.0, "window": True, "prompt": [1], "max_new_tokens": 2},
            {"id": "W2", "due": 2.5, "window": True, "prompt": [1], "max_new_tokens": 2}]
    rows = [{"id": "L0", "due": 0.0, "sent": 0.001, "at": [0.9, 1.1], "tokens": [5, 6], "status": "ok"},
            # sent 0.4 s late: the wait counts, 1.5 - 1.0 and not 1.5 - 1.4
            {"id": "W0", "due": 1.0, "sent": 1.4, "at": [1.5, 1.6, 1.8], "tokens": [7, 8, 9], "status": "ok"},
            {"id": "W1", "due": 2.0, "sent": 2.0, "at": [2.2, 3.5], "tokens": [1, 2], "status": "ok"},
            {"id": "W2", "due": 2.5, "sent": 2.5, "at": [2.6], "tokens": [1], "status": "error:http 504"}]
    f = gen.reduce_rows(rows, plan, lead=1.0, seconds=2.0, timeout_s=60)
    assert f["attempted"] == 3 and f["failed"] == 1
    assert f["ttft_ms"] == pytest.approx([500.0, 200.0, 60000.0])   # a failure is the worst
    assert f["tbt_ms"] == pytest.approx([100.0, 200.0, 1300.0])
    assert f["late_ms"] == pytest.approx([1.0, 400.0, 0.0, 0.0])
    # tokens inside [1, 3): L0's second, W0's three, W1's first; not the failed one's
    assert f["tokens_in_window"] == 5


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert gen.percentile(v, 90) == 90 and gen.percentile(v, 95) == 95
    assert gen.percentile(v, 50) == 50 and gen.percentile([], 90) is None
    assert gen.percentile([3.0], 90) == 3.0


def test_sample_has_the_longest_finished_request():
    plan = [{"id": "W%d" % i, "due": float(i), "window": True,
             "prompt": [1] * (10 + i), "max_new_tokens": 2} for i in range(8)]
    plan.append({"id": "L0", "due": 0.0, "window": False, "prompt": [1] * 99,
                 "max_new_tokens": 2})
    rows = [{"id": r["id"], "tokens": [4, 5], "status": "ok"} for r in plan]
    rows[7]["status"] = "never"
    sample = gen.sample_finished(rows, plan, seed=3, n=4)
    assert len(sample) == 4 and len(sample[0][0]) == 16       # W6: W7 never came
    assert sample == gen.sample_finished(rows, plan, seed=3, n=4)
    assert all(len(p) < 99 for p, _ in sample)                # no lead-in request


def test_the_fit_feed_records_the_pace_of_its_steps():
    """A far-off run says in its notes whether every step was slow or a few
    stalled: the gaps between the batches the feed handed out."""
    from benchmark.generators import fit_device_batch as fit
    assert fit._step_gaps([0.0]) is None
    got = fit._step_gaps([0.0, 0.1, 0.2, 0.3, 0.7, 0.8])
    assert got["p50"] == pytest.approx(100.0)
    assert got["max"] == pytest.approx(400.0)
    assert got["over_1.5x_p50"] == 1
