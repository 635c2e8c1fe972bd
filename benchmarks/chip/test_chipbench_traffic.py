"""The traffic generator: seeded, clipped, the same work for every seed,
and a closed loop that keeps its requests outstanding."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, traffic

MIXES = Path(__file__).resolve().parent / "traffic"


# a closed loop of 16 greedy requests, 8 of them in flight at the start
CLOSED = {"loop": "closed", "outstanding": 16, "in_flight": 8,
          "requests": 512,
          "prompt_len": {"median": 1024, "sigma": 0.5, "min": 256,
                         "max": 1535},
          "output_len": {"median": 256, "sigma": 0.5, "min": 64, "max": 512},
          "temperature": 0.0}


def mix(name):
    return traffic.load(MIXES / f"{name}.json")


def summary(plan):
    return [(i.due_s, len(i.prompt), i.max_new, i.temperature,
             i.prompt[:4].tolist()) for i in plan.items]


def test_same_seed_same_inputs_other_seed_other_content_same_work():
    m = mix("chat")
    a = traffic.generate(m, 2**31 + 3, 51, 151936)
    b = traffic.generate(m, 2**31 + 3, 51, 151936)
    c = traffic.generate(m, 12, 51, 151936)
    assert summary(a) == summary(b)
    assert summary(a) != summary(c)
    # the schedule is every seed's: arrivals, lengths, in-flight progress
    for key in (lambda i: i.due_s, lambda i: len(i.prompt),
                lambda i: i.max_new):
        assert list(map(key, a.items)) == list(map(key, c.items))
        assert list(map(key, a.in_flight)) == list(map(key, c.in_flight))
    assert [i.greedy for i in a.items] != [i.greedy for i in c.items]
    assert sum(i.greedy for i in a.items) == sum(i.greedy for i in c.items)
    assert len(a.items) == int(m["rate_per_s"] * 51)
    assert max(i.due_s for i in a.items) < 51
    assert np.all(np.diff([i.due_s for i in a.items]) > 0)


def test_lengths_respect_their_clips():
    for m in (mix("chat"), CLOSED):
        plan = traffic.generate(m, 5, 51, 1000)
        lo, hi = traffic.prompt_bounds(m)
        olo, ohi = traffic.output_bounds(m)
        assert all(lo <= len(i.prompt) <= hi for i in plan.items)
        assert all(olo <= i.max_new <= ohi for i in plan.items)
        assert all(0 <= i.prompt.min() and i.prompt.max() < 1000
                   for i in plan.items)


def test_greedy_share_rounds_the_mix_share():
    m = dict(mix("chat"), in_flight=20)
    plan = traffic.generate(m, 9, 51, 1000)
    n = len(plan.items)
    assert sum(i.greedy for i in plan.items) == round(0.25 * n)
    assert sum(i.greedy for i in plan.in_flight) == round(0.25 * 20)
    assert all(i.temperature == 0.8 for i in plan.items + plan.in_flight
               if not i.greedy)
    assert len({i.sample_seed for i in plan.items}) == n
    assert all(i.greedy for i in traffic.generate(CLOSED, 9, 51, 1000).items)


class FakeEngine:
    """Finishes each request a fixed number of steps after it arrives."""

    def __init__(self, slots=16, steps=5):
        self.slots_n, self.steps = slots, steps
        self.live, self.slots = [], []

    def submit(self, req):
        self.live.append([req, self.steps])

    @property
    def scheduler(self):
        return self.live[self.slots_n:]

    def has_work(self):
        return bool(self.live)

    def step(self):
        for entry in self.live[:self.slots_n]:
            entry[0].out_tokens.append(1)
            entry[1] -= 1
            if entry[1] == 0:
                entry[0].done, entry[0].finish_reason = True, "done"
        self.live = [e for e in self.live if not e[0].done]
        return True


def test_in_flight_requests_are_part_way_and_never_due():
    m = dict(mix("chat"), in_flight=20)
    plan = traffic.generate(m, 3, 51, 1000)
    again = traffic.generate(m, 3, 51, 1000)
    assert [len(i.prompt) for i in plan.in_flight] == \
        [len(i.prompt) for i in again.in_flight]
    assert len(plan.in_flight) == 20
    assert all(i.due_s < 0 and i.max_new >= 1 for i in plan.in_flight)
    lo, hi = traffic.prompt_bounds(m)
    grown = [len(i.prompt) - hi for i in plan.in_flight if len(i.prompt) > hi]
    assert grown and all(g < traffic.output_bounds(m)[1] for g in grown)
    load = harness.Load(FakeEngine(slots=32), plan)
    load.start()
    assert len(load.live) == 20 and load.next == 0


@pytest.mark.parametrize("outstanding", [None, 32])
def test_closed_loop_keeps_its_requests_outstanding(outstanding):
    m = CLOSED              # 16 over 8 slots, and 32 over 16
    if outstanding:
        m = dict(m, outstanding=outstanding, in_flight=outstanding // 2)
    plan = traffic.generate(m, 4, 51, 1000)
    load = harness.Load(FakeEngine(slots=plan.outstanding // 2), plan)
    load.start()
    assert len(load.live) == len(plan.in_flight)
    for _ in range(plan.outstanding - len(load.live)):
        load.send_next(0.0)
    for _ in range(40):
        load.step()
        assert len(load.live) == plan.outstanding
    assert sum(r.finish_reason == "done" for r in load.records) \
        > plan.outstanding
