"""The reader of the paged cache's write programs, on a small recorded
trace with known answers: the named commit and scrub programs count, the
gathers and the model do not, and a trace without them reads nothing."""
import json
from pathlib import Path

import pytest

from harness import Layout
from trace import reduce

DATA = Path(__file__).parent / "data"


def _rec(name):
    events = json.loads((DATA / name).read_text())
    t0, t1 = events["window_ns"]
    return {"trace": reduce(events, t0, t1),
            "traced_steps": events["traced_steps"]}


def test_cache_writes_per_step_sum_the_commit_and_scrub_programs():
    read = Layout().reader("cache_write_ms_per_step")
    assert read(_rec("trace_cache.json")) == pytest.approx(
        (200 + 300 + 100) * 1e-6 / 2)


def test_cache_writes_count_in_eager_ops_too():
    read = Layout().reader("eager_ops_ms_per_step")
    assert read(_rec("trace_cache.json")) == pytest.approx(
        (1000 + 200 + 1000 + 300 + 100) * 1e-6 / 2)


def test_cache_writes_are_silent_without_the_named_programs():
    read = Layout().reader("cache_write_ms_per_step")
    assert read(_rec("trace_phases.json")) is None
    assert read({"trace": None, "traced_steps": None}) is None
