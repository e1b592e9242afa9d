"""The readers of the serving session's own counters, on records built by
hand: the share of the window the session's harvest held the device
idle, and the share of decode lanes that emitted a token."""
import types

import pytest

from bench.tests import tiny  # noqa: F401  (puts the checkout on the path)
from bench import harness

STATS = {"dispatches": 232, "chunk_dispatches": 142, "admitted": 142,
         "syncs": 143, "sync_drain_ns": 27_000_000_000,
         "fetch_ns": 351_000_000, "idle_host_ns": 249_000_000,
         "decode_tokens": 14_672}


def _serve(stats, window_s=30.0, slots=64):
    return types.SimpleNamespace(kind="serve", stats=stats,
                                 window_s=window_s,
                                 traffic={"slots": slots})


def _train():
    return types.SimpleNamespace(kind="train", window_s=30.0, steps=157,
                                 tokens=157 * 2048)


def test_harvest_stall_share():
    # 0.351 s of fetch and 0.249 s of host turnaround in a 30 s window
    assert harness.reader("harvest_stall_share.serve").read(
        _serve(STATS)) == pytest.approx(100 * 0.6 / 30.0)


def test_decode_slot_use():
    # 14,672 tokens from 232 decode steps over 64 lanes
    assert harness.reader("decode_slot_use.serve").read(
        _serve(STATS)) == pytest.approx(100 * 14_672 / (232 * 64))


@pytest.mark.parametrize("name", ["harvest_stall_share.serve",
                                  "decode_slot_use.serve"])
def test_silent_without_the_counters(name):
    """A training record, and a program whose session keeps none of the
    counters (the stats of a program before they existed), read nothing."""
    reader = harness.reader(name)
    assert reader.read(_train()) is None
    old = {k: v for k, v in STATS.items()
           if k not in ("fetch_ns", "idle_host_ns", "decode_tokens",
                        "sync_drain_ns")}
    assert reader.read(_serve(old)) is None


def test_no_decode_steps_reads_nothing():
    stats = dict(STATS, dispatches=0, decode_tokens=0)
    assert harness.reader("decode_slot_use.serve").read(
        _serve(stats)) is None
