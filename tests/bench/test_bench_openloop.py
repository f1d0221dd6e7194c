"""Open-loop arrivals on a fake clock: blocks are due on the schedule
whatever the system does, latency runs from the due time, and a late
generator is reported as late."""

import pytest

from benchlib import openloop


class FakeClock:
    def __init__(self):
        self.now = 100.0
        self.slept = []

    def clock(self):
        return self.now

    def sleep(self, dt):
        assert dt > 0
        self.slept.append(dt)
        self.now += dt


def test_due_times_are_fixed_by_the_schedule():
    s = openloop.Schedule(rate=4.0, t0=10.0)
    assert [s.due(k) for k in range(3)] == [10.0, 10.25, 10.5]
    assert s.count_within(1.0) == 4        # due at 10.0, .25, .5, .75
    assert s.count_within(1.01) == 5
    assert openloop.Schedule(rate=4.6, t0=0.0).count_within(45) == 207


def test_feed_hands_in_at_due_times_and_reports_lateness():
    fc = FakeClock()
    s = openloop.Schedule(rate=2.0, t0=fc.now + 1.0)
    handed = []

    def make(k):
        if k == 2:
            fc.now += 0.7          # building block 2 overruns its due time
        return f"block{k}"

    def hand_in(k, block, due, at):
        handed.append((k, block, due, at))

    late = openloop.feed(s, 4, make, hand_in, fc.clock, fc.sleep)
    assert [h[1] for h in handed] == ["block0", "block1", "block2", "block3"]
    assert [h[2] for h in handed] == [101.0, 101.5, 102.0, 102.5]
    # block 2 was due at 102.0 and handed in at 102.2; the schedule
    # does not slip: block 3 is still due, and handed in, at 102.5
    assert late == pytest.approx([0.0, 0.0, 0.2, 0.0])
    assert handed[3][3] == pytest.approx(102.5)


def test_latency_counts_the_wait_a_stall_imposes_on_later_blocks():
    s = openloop.Schedule(rate=10.0, t0=0.0)
    # the system stalls 0.5 s on block 0 and then takes 0.01 s a block:
    # blocks 1..4 were due during the stall and waited for it
    done = [0.5, 0.51, 0.52, 0.53, 0.54]
    lat = [openloop.latency(s.due(k), d) for k, d in enumerate(done)]
    assert lat == pytest.approx([0.5, 0.41, 0.32, 0.23, 0.14])
    # timed from when each block was TAKEN, four of five would read 0.01
