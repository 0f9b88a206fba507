"""The drivers' accounting, on the CPU without running a model: the serving
schedule, token times read at the end of each step, percentiles over every
request, TTFT from the due time, failed requests and the checked sample."""
from __future__ import annotations

import collections
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import harness as H  # noqa: E402

serve = H.driver_module("serve")
CHAT = H.traffic_file("serve.chat")
V = 151936


def sizes(reqs):
    return collections.Counter((len(r["prompt"]), r["max_new"]) for r in reqs)


def test_schedule_repeats_for_a_seed():
    a = serve.schedule(CHAT, 2**31 + 5, 40.0, V)
    b = serve.schedule(CHAT, 2**31 + 5, 40.0, V)
    assert a == b


def test_every_seed_gets_the_same_arrivals_and_sizes():
    a = serve.schedule(CHAT, 1, 40.0, V)
    b = serve.schedule(CHAT, 2, 40.0, V)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert [(len(r["prompt"]), r["max_new"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_prompt_table_weights_follow_the_log_normal():
    w = serve.prompt_weights(CHAT["prompt"])
    lengths = CHAT["prompt"]["lengths"]
    assert w.sum() == pytest.approx(1.0) and (w > 0).all()
    # the table's median bin holds the published median
    assert lengths[int((w.cumsum() >= 0.5).argmax())] == 1024
    reqs = serve.schedule(dict(CHAT, rate_per_s=100.0), 3, 100.0, 512)
    got = collections.Counter(len(r["prompt"]) for r in reqs)
    for n, share in zip(lengths, w):
        assert got[n] / len(reqs) == pytest.approx(share, abs=0.02)


def test_schedule_holds_the_tables():
    reqs = serve.schedule(CHAT, 7, 40.0, V)
    assert len(reqs) == round(CHAT["rate_per_s"] * 40.0)
    out = CHAT["output"]
    for r in reqs:
        assert len(r["prompt"]) in CHAT["prompt"]["lengths"]
        assert out["min"] <= r["max_new"] <= out["max"]
        assert len(r["prompt"]) + r["max_new"] <= CHAT["max_len"] - 2
        assert all(0 <= t < V for t in r["prompt"])
    dues = [r["due"] for r in reqs]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 40.0


def test_warmup_covers_every_length_and_slot():
    reqs = serve.warmup_requests(CHAT, CHAT["slots"], V)
    assert {len(r["prompt"]) for r in reqs} == set(CHAT["prompt"]["lengths"])
    assert len(reqs) >= CHAT["slots"]


def rec(due, first=None, times=(), admit=None, submitted=None):
    return {"due": due, "first": first, "times": list(times),
            "admit_step": admit,
            "submitted": due if submitted is None else submitted}


def test_ttft_is_taken_from_the_due_time():
    st = serve.summarize([rec(1.0, first=1.25, times=[1.25],
                              submitted=1.2, admit=1.21)], 10.0, 11.0)
    assert st["ttft"] == [pytest.approx(250.0)]
    assert st["waits"] == [pytest.approx(210.0)]
    assert st["late"] == [pytest.approx(200.0)]


def test_unanswered_requests_fail_and_sit_in_the_tail():
    recs = [rec(float(i), first=i + 0.1, times=[i + 0.1]) for i in range(9)]
    recs.append(rec(9.0))
    st = serve.summarize(recs, 10.0, 20.0)
    assert st["failed"] == 1 and st["sent"] == 10
    assert serve.percentile(st["ttft"], 95) == pytest.approx(11000.0)


def test_tails_are_over_every_request_and_gap():
    recs = [rec(0.0, first=0.1, times=[0.1, 0.2, 0.3, 5.0]),
            rec(0.0, first=0.1, times=[0.1, 0.15])]
    st = serve.summarize(recs, 10.0, 10.0)
    assert sorted(st["itl"]) == pytest.approx([50.0, 100.0, 100.0, 4700.0])
    assert serve.percentile(st["itl"], 95) == pytest.approx(4700.0)


def test_gaps_after_the_window_are_left_out():
    st = serve.summarize([rec(0.0, first=0.1, times=[0.1, 0.2, 12.0])],
                         10.0, 13.0)
    assert st["itl"] == [pytest.approx(100.0)]


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert serve.percentile(values, 90) == 90
    assert serve.percentile(values, 95) == 95
    assert serve.percentile([3.0], 90) == 3.0


class FakeServer:
    """``Server``'s public surface: one request admitted per step into the
    lowest free slot, its prefill token and then one decode token for every
    active slot."""

    def __init__(self, slots):
        self.slots = [None] * slots
        self.queue = collections.deque()
        self.requests = {}

    def submit(self, prompt, max_new):
        rid = f"req-{len(self.requests):04d}"
        req = type("Req", (), {})()
        req.req_id, req.prompt, req.max_new = rid, list(prompt), max_new
        req.generated, req.done = [], False
        self.requests[rid] = req
        self.queue.append(req)
        return rid

    def step(self):
        if self.queue and None in self.slots:
            req = self.queue.popleft()
            req.generated.append(1)
            self.slots[self.slots.index(None)] = req
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            req.generated.append(2)
            if len(req.generated) >= req.max_new:
                req.done, self.slots[i] = True, None
        return sum(r is not None for r in self.slots)

    def pending(self):
        return len(self.queue) + sum(r is not None for r in self.slots)


def test_every_token_is_stamped_at_the_end_of_its_step():
    srv = FakeServer(2)
    tr = serve.Tracker(srv, H.Spans())
    tr.submit({"prompt": [5] * 7, "max_new": 4}, 0.0)
    tr.submit({"prompt": [5] * 3, "max_new": 3}, 0.0)
    serve.drain(tr)
    a, b = tr.recs.values()
    # the prefill's token and the first decode's come out of one step
    assert a["first"] == a["times"][0] == a["times"][1]
    assert len(a["times"]) == 4 and len(b["times"]) == 3
    assert a["admit_step"] <= a["first"] < b["admit_step"] <= b["first"]
    assert (a["slot"], b["slot"]) == (0, 1)
    assert [n for _, n in tr.prefills] == [7, 3]
    # contexts of the decoded slots: the cache length each decode attends
    assert [c for _, c in tr.decodes] == [[8], [9, 4], [10, 5]]
    st = serve.summarize(list(tr.recs.values()), float("inf"), 0.0)
    assert 0.0 in st["itl"] and len(st["itl"]) == 3 + 2


def test_the_check_sample_spans_slots_and_caps_tokens():
    srv = FakeServer(8)
    tr = serve.Tracker(srv, H.Spans())
    for n in range(16):
        tr.submit({"prompt": [3] * (4 + n), "max_new": 10 + 20 * (n == 5)},
                  0.0)
    serve.drain(tr)
    done = list(tr.recs)
    check = {"requests": 6, "tokens_per_request": 8}
    sample = serve.check_sample(tr, srv, done, 2**31 + 9, check)
    slots = [tr.recs[rid]["slot"] for rid in done
             if any(tr.recs[rid]["prompt"] is p for p, _, _ in sample)]
    assert len(sample) == 6 and len(set(slots)) == 6
    prompt, gen, js = sample[0]
    assert len(prompt) == 9 and len(gen) == 30       # the longest first
    assert js == [0, 1, 2, 3, 26, 27, 28, 29]
    rows, pos, toks = serve.positions(sample)
    assert len(toks) == 6 * 8 and int(pos[4]) == len(prompt) - 1 + 26
    assert sample == serve.check_sample(tr, srv, done, 2**31 + 9, check)
