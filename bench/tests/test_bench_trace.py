"""The trace reduction: busy union, coding kernel time and idle gaps by
host span, on a hand-built trace and on one recorded on a v5e."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def ev(name, start, end, **stats):
    return SimpleNamespace(name=name, start_ns=float(start), end_ns=float(end),
                           duration_ns=float(end - start),
                           stats=list(stats.items()))


def line(name, *events):
    return SimpleNamespace(name=name, events=list(events))


KERNEL = ('%_delta_apply_batched_call.1 = u8[4,2,4096] custom-call(%a), '
          'custom_call_target="tpu_custom_call"')


def hand_built():
    host = SimpleNamespace(name="/host:CPU", lines=[line(
        "python",
        ev("bench.wait", 0, 100),
        ev("bench.multi_update", 100, 300, ops=4),
        ev("bench.multi_get", 300, 400, ops=6),
        ev("PjitFunction(f)", 120, 130))])
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit_f(1)", 150, 200), ev("jit_g(2)", 180, 250)),
        line("XLA Ops", ev(KERNEL, 150, 190),
             ev("%copy.2 = u8[4] copy(%b)", 195, 200),
             ev("%copy.1 = u8[4] copy(%c)", 200, 240))])
    return SimpleNamespace(planes=[host, device])


def test_busy_union_kernel_time_and_idle_gaps_by_span():
    r = trace.reduce_profile(hand_built())
    assert r.window_s == pytest.approx(400e-9)
    assert r.busy_s == pytest.approx(100e-9)       # [150, 250], overlap once
    assert r.kernel_s == pytest.approx(40e-9)
    assert r.ops == 10
    idle = dict((k, v) for k, v in r.idle_gaps)
    assert idle == pytest.approx({"bench.wait": 100e-9,
                                  "bench.multi_update": 100e-9,
                                  "bench.multi_get": 100e-9})
    ops = dict((k, v) for k, v in r.device_ops)
    # an op belongs to the module that started last before it
    assert ops == pytest.approx({
        "jit_f/_delta_apply_batched_call": 40e-9, "jit_g/copy": 45e-9})


def test_a_trace_without_a_device_plane_has_no_busy_time():
    pd = hand_built()
    pd.planes = pd.planes[:1]
    r = trace.reduce_profile(pd)
    assert r.busy_s is None and r.kernel_s == 0 and r.ops == 10


def test_a_trace_without_benchmark_spans_reduces_to_nothing():
    pd = hand_built()
    pd.planes[0].lines[0].events = pd.planes[0].lines[0].events[3:]
    assert trace.reduce_profile(pd) is None


def test_trace_recorded_on_a_v5e():
    from jax.profiler import ProfileData
    want = json.loads((DATA / "rs-a.v5e.expected.json").read_text())
    raw = gzip.decompress((DATA / "rs-a.v5e.xplane.pb.gz").read_bytes())
    r = trace.reduce_profile(ProfileData.from_serialized_xspace(raw))
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.kernel_s == pytest.approx(want["kernel_s"], rel=1e-9)
    assert r.ops == want["ops"]
    assert r.breakdown() == pytest.approx(want["breakdown"]) or \
        json.loads(json.dumps(r.breakdown())) == want["breakdown"]
