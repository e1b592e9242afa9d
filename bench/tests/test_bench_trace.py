"""The trace reduction: interval arithmetic, and the reduction of a
small set of events laid out as a TPU trace is (device ops on the "XLA
Ops" line, the run's window span on the host)."""
import gzip
import os

import pytest

from bench.tests import tiny  # noqa: F401
from bench import trace as tr


def test_intervals():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                         (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def _op(s, e, name):
    return (s, e, tr.op_name(name))


def test_op_names():
    assert tr.op_name("%codec_ef_encode.25 = (u8[704512,128]) custom-call("
                      "f32[704512,128] %bitcast.8), custom_call_target="
                      "\"tpu_custom_call\"") == "codec_ef_encode"
    # an op whose operand is a kernel's output is not that kernel
    assert tr.op_name("%reshape.500 = bf16[2,11008,4096] reshape(f32[704512"
                      ",128] %codec_decode.25)") == "reshape"
    assert tr.op_name("%broadcast.36.clone = f32[8] broadcast()") == \
        "broadcast"
    assert tr.op_name("fusion.3") == "fusion"


def test_reduce():
    host = [(100, 1100, "train_window", "python"),
            (150, 400, "dispatch", "python"),
            (600, 1000, "harvest", "python")]
    planes = {
        0: [_op(0, 200, "%fusion.1 = f32[] fusion()"),  # half in the window
            _op(200, 300, "%adam_ef_moments.2 = f32[8] custom-call()"),
            _op(300, 500, "%all-to-all.3 = u8[4] all-to-all()"),
            _op(400, 450, "%fusion.4 = f32[] fusion()"),
            _op(690, 900, "%while.7 = (s32[]) while()"),   # holds .5
            _op(700, 800, "%codec_decode.5 = f32[8] custom-call()"),
            _op(800, 850, "%reshape.6 = f32[8] reshape(%codec_decode.5)")],
        1: [_op(100, 1100, "%all-gather.1 = u8[4] all-gather()")],
        2: [_op(0, 5000, "other-chip")],             # not one of ours
    }
    s = tr.reduce(host, planes, devices=2,
                  kernels=("adam_ef_moments", "codec_decode"))
    assert s["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy 100..500 and 690..900 = 610 ns; chip 1 all 1000 ns
    assert s["busy_s"] == pytest.approx(805e-9)
    assert s["kernel_s"] == pytest.approx({"adam_ef_moments": 100e-9,
                                           "codec_decode": 100e-9})
    # exposed collective: chip 0 300..400 + 450..500, chip 1 1000 ns
    assert s["collective_exposed_s"] == pytest.approx(575e-9)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["all-gather"] == pytest.approx(500e-9)
    assert ops["fusion"] == pytest.approx(75e-9)
    assert "while" not in ops                  # it holds other ops
    assert ops["reshape"] == pytest.approx(25e-9)
    # chip 0 idles 500..690 (inside no span but the window) and
    # 900..1100 (inside "harvest" up to 1000)
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(390e-9)
    assert gaps["host: harvest"] == pytest.approx(200e-9)


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "yi6b-train-1chip.xplane.pb.gz")
KERNELS = ("adam_ef_moments", "codec_ef_encode", "codec_decode")


def _covered(intervals, lo, hi):
    """Nanoseconds of [lo, hi) covered by any interval, by a sweep over
    start and end events (independent of ``trace.union``)."""
    events = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            events += [(s, 1), (e, -1)]
    events.sort()
    total, depth, last = 0, 0, None
    for t, d in events:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_chip_trace(tmp_path):
    """A trace of two train steps recorded on one v5e (``--trace 1``, a
    0.3 s window): the reduction finds the window, the chip's op line and
    the kernels by name, and its busy time agrees with a sweep over the
    same intervals."""
    with gzip.open(RECORDED) as f:
        (tmp_path / "run.xplane.pb").write_bytes(f.read())
    host, planes = tr.load(str(tmp_path))
    assert list(planes) == [0]
    lo, hi = tr.window_bounds(host)
    s = tr.reduce(host, planes, devices=1, kernels=KERNELS)
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)
    busy = _covered([(a, b) for a, b, _ in planes[0]], lo, hi)
    assert s["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert 0 < s["busy_s"] <= s["window_s"]
    for k in KERNELS:
        assert s["kernel_s"][k] > 0, k
    assert sum(s["kernel_s"].values()) < s["busy_s"]
    assert s["collective_exposed_s"] == 0.0    # one chip: no collective
    assert len(s["breakdown"]["device_ops"]) == 10
    assert sum(v for _, v in s["breakdown"]["idle_gaps"]) == \
        pytest.approx(s["window_s"] - s["busy_s"])
