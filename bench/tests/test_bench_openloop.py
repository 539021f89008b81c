"""The open-loop arithmetic: streams from a seed, latency from the due
time, percentiles over every op, ops/s over the whole window."""
import gc
import time

import numpy as np
import pytest

from bench import spec
from bench.driver import CheckedClient, drive
from bench.harness import GcPauses, Run, host_stalls, max_updates_per_window
from bench.stream import GET, UPDATE, Stream, make_stream, rng_for

CFG = {"objects": 1000, "value_sizes": [8, 32]}
MIX_A = {"mix": {"get": 0.5, "update": 0.5}, "zipf_theta": 0.99}


def test_stream_has_exact_count_and_shares_and_repeats_per_seed():
    big = 2**31 + 12345
    a = make_stream(CFG, MIX_A, 1000, 2.0, rng_for(big, 2))
    b = make_stream(CFG, MIX_A, 1000, 2.0, rng_for(big, 2))
    c = make_stream(CFG, MIX_A, 1000, 2.0, rng_for(big + 1, 2))
    assert len(a) == len(c) == 2000
    assert (a.kind == GET).sum() == (a.kind == UPDATE).sum() == 1000
    assert (c.kind == GET).sum() == 1000
    assert np.array_equal(a.due, b.due) and a.values == b.values
    assert not np.array_equal(a.due, c.due)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 2
    for t in np.flatnonzero(a.kind == UPDATE):
        assert len(a.values[t]) == CFG["value_sizes"][a.ids[t] % 2]
    assert 0 <= a.ids.min() and a.ids.max() < CFG["objects"]


def test_max_updates_per_window_bounds_every_window():
    s = Stream(np.zeros(6), np.array([1, 1, 0, 1, 1, 1], np.int8),
               np.arange(6), [b"x"] * 6)
    assert max_updates_per_window(s, 3) == 3
    assert max_updates_per_window(s, 2) == 2
    assert max_updates_per_window(s, 10) == 5


class SlowStore:
    """A store whose every call takes ``delay`` seconds."""

    num_proxies = 4

    def __init__(self, delay):
        self.delay, self.data, self.calls = delay, {}, []
        self.device_dispatches = 0

    def _wait(self, kind, n):
        self.calls.append((kind, n))
        time.sleep(self.delay)

    def multi_get(self, keys, proxy_id=0):
        self._wait("get", len(keys))
        return [self.data.get(k) for k in keys]

    def multi_update(self, items, proxy_id=0):
        self._wait("update", len(items))
        self.data.update(items)
        return [True] * len(items)

    multi_set = multi_update


def _stream(due, kinds, ids):
    return Stream(np.array(due, float), np.array(kinds, np.int8),
                  np.array(ids), [b"v%d" % i for i in range(len(due))])


def test_latency_runs_from_due_time_and_counts_queueing():
    store = SlowStore(0.05)
    client = CheckedClient(store)
    # three ops due together, the fourth while the first window is served
    s = _stream([0.0, 0.0, 0.0, 0.01], [GET, GET, GET, GET], [1, 2, 3, 4])
    rec = drive(client, s, max_window=256, num_proxies=4, engine=store)
    assert store.calls == [("get", 3), ("get", 1)]
    lat = rec.done - s.due
    assert np.all(lat[:3] >= 0.05) and np.allclose(lat[:3], lat[0])
    # the late op waited for the first window, then was served
    assert rec.issue[3] >= 0.05 and lat[3] >= 0.05 + 0.05 - 0.01
    assert np.all(rec.issue >= s.due)


def test_window_splits_at_a_key_held_under_another_kind():
    store = SlowStore(0.0)
    client = CheckedClient(store)
    s = _stream([0, 0, 0, 0], [UPDATE, GET, GET, UPDATE], [7, 8, 7, 9])
    drive(client, s, max_window=256, num_proxies=4, engine=store)
    # key 7 updated, then read: the read waits for the next window
    assert store.calls[0] == ("update", 1) and store.calls[1] == ("get", 1)
    assert client.wrong_reads == 0


def _run(due, done, traffic_s, trace_bounds=None):
    due, done = np.array(due, float), np.array(done, float)
    return Run(setup_s=1.0, traffic_s=traffic_s, due=due, issue=due,
               done=done, windows=np.zeros((0, 5)), dispatches_at_open=0,
               compiles_in_window=0, recover_s=None,
               trace_bounds=trace_bounds, trace=None)


def test_percentiles_are_over_every_op_and_rate_over_the_window():
    due = np.arange(100) * 0.01
    lat = np.arange(1, 101) * 1e-3          # 1 ms .. 100 ms
    run = _run(due, due + lat, traffic_s=1.0, trace_bounds=(1.0, 1.5))
    assert spec.reader("p50_ms")(run) == pytest.approx(50.5)
    assert spec.reader("tail_p99_ms")(run) == pytest.approx(99.01)
    # the tail leaves out the ops issued once the profiler is on
    traced = _run(due, due + lat, traffic_s=1.0, trace_bounds=(0.5, 0.9))
    assert spec.reader("tail_p99_ms")(traced) == pytest.approx(49.51)
    # ops that finished after the window closed do not count as done in it
    late = (due + lat) > 1.0
    assert spec.reader("ops_per_s")(run) == pytest.approx(
        (100 - late.sum()) / 1.0)
    run2 = _run(due, due + lat, traffic_s=2.0)
    assert spec.reader("ops_per_s")(run2) == pytest.approx(50.0)


def test_an_unanswered_op_leaves_the_tail_unreported():
    run = _run([0.0, 0.1], [0.05, np.nan], traffic_s=1.0,
               trace_bounds=(1.0, 2.0))
    assert spec.reader("tail_p99_ms")(run) is None
    assert spec.reader("p50_ms")(run) is None
    untraced = _run([0.0, 0.1], [0.05, 0.15], traffic_s=1.0)
    assert spec.reader("tail_p99_ms")(untraced) is None


def test_stall_report_names_the_slowest_call_of_each_kind():
    store = SlowStore(0.01)
    client = CheckedClient(store)
    s = _stream([0.0, 0.0, 0.02, 0.03], [GET, UPDATE, GET, GET],
                [1, 2, 3, 4])
    with GcPauses() as pauses:
        rec = drive(client, s, max_window=256, num_proxies=4, engine=store)
        gc.collect()
    run = Run(setup_s=1.0, traffic_s=1.0, due=s.due, issue=rec.issue,
              done=rec.done, windows=np.array(rec.windows, float),
              dispatches_at_open=0, compiles_in_window=0, recover_s=None,
              trace_bounds=None, trace=None)
    report = host_stalls(run, rec.slowest)
    assert set(report["slowest_calls"]) == {"multi_get", "multi_update"}
    assert report["slowest_calls"]["multi_update"][2] == 1
    assert all(v[0] >= 0.01 for v in report["slowest_calls"].values())
    assert len(report["longest_windows"]) == 3
    assert report["beyond_p99"]["p99_ms"] >= 10
    assert pauses.summary()["collections"][2] >= 1
