"""The readers of the engine's phase spans, on a small recorded trace with
known answers: a device idle gap is put down to the innermost
``engine.*`` phase around it."""
import json
from pathlib import Path

import pytest

from harness import Layout
from trace import breakdown, reduce

DATA = Path(__file__).parent / "data" / "trace_phases.json"


@pytest.fixture(scope="module")
def rec():
    events = json.loads(DATA.read_text())
    t0, t1 = events["window_ns"]
    return {"trace": reduce(events, t0, t1),
            "traced_steps": events["traced_steps"]}


def test_idle_gaps_carry_the_phase_label(rec):
    idle = dict(breakdown(rec["trace"])["idle_gaps"])
    assert idle == {"engine.gather": pytest.approx(2000e-9),
                    "engine.sample": pytest.approx(1000e-9),
                    "bench.wait_arrival": pytest.approx(5000e-9)}


def test_host_idle_per_step_counts_gaps_under_engine_spans(rec):
    read = Layout().reader("host_idle_ms_per_step")
    assert read(rec) == pytest.approx((2000 + 1000) * 1e-6 / 2)


def test_host_idle_per_step_is_silent_without_a_trace():
    read = Layout().reader("host_idle_ms_per_step")
    assert read({"trace": None, "traced_steps": None}) is None
