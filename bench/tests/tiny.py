"""Cells of ``BENCHMARK.json`` cut to a size a CPU test can hold: the
same files and traffic modules, with the widths, depth, vocabulary, sequence and
traffic made small."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=1, vocab_size=256)


def cell(name: str, workers: int = 1) -> harness.Cell:
    c = harness.find_cell(name)
    c.config["run"].update(SIZES)
    if c.traffic["kind"] == "train":
        c.traffic.update(seq=32, workers=workers)
    else:
        c.traffic.update(slots=3, max_seq=128, num_pages=12,
                         prefill_chunk=16, clients=4,
                         block=8,
                         prompt={"median": 20, "sigma": 0.8, "min": 4,
                                 "max": 60},
                         output={"median": 4, "sigma": 0.8, "min": 2,
                                 "max": 12},
                         check_tokens=12)
    return c


def run(c: harness.Cell, seed: int = 2 ** 31 + 7, seconds: float = 0.3):
    """One run of the cell's traffic module (no chip check), as the result line's
    fields."""
    return harness.traffic_kind(c.traffic["kind"]).run(
        c, seed, seconds, False, t_start=harness.now())
