"""``pool_fill`` on a hand-built run: owned blocks over pool slots in the
window, and nothing to read from a program without the counters."""

import pytest

from bench.lib.cell import Run
from bench.metrics import pool_fill


def test_pool_fill_reads_the_window_and_skips_a_program_without_it():
    before = {"pool_slots": 4096, "pool_blocks": 1000, "queries": 10}
    after = {"pool_slots": 4096 + 3 * 2048, "pool_blocks": 1000 + 4300,
             "queries": 40}
    run = Run(None, None, before, after, compiles=0, bytes_per_posting=1.0)
    # 4,300 owned blocks in 6,144 slots
    assert pool_fill.read(run) == pytest.approx(100 * 4300 / 6144)
    idle = Run(None, None, before, dict(before), compiles=0,
               bytes_per_posting=1.0)
    assert pool_fill.read(idle) is None
    parent = Run(None, None, {"queries": 10}, {"queries": 40}, compiles=0,
                 bytes_per_posting=1.0)
    assert pool_fill.read(parent) is None
