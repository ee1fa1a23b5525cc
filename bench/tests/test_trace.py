"""The trace reduction on a small recorded trace with known answers."""
import json
from pathlib import Path

import pytest

from trace import breakdown, kernel_seconds, program_name, reduce

DATA = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def reduced():
    events = json.loads(DATA.read_text())
    t0, t1 = events["window_ns"]
    return reduce(events, t0, t1)


def test_busy_is_the_union_of_ops_clipped_to_the_window(reduced):
    dev0, dev1 = reduced["devices"]
    assert dev0["busy_s"] == pytest.approx(5500e-9)
    assert dev1["busy_s"] == pytest.approx(10000e-9)
    assert reduced["busy_s"] == pytest.approx(7750e-9)
    assert reduced["window_s"] == pytest.approx(10000e-9)


def test_idle_gaps_are_labelled_by_the_innermost_host_span(reduced):
    gaps = reduced["devices"][0]["gaps"]
    assert [(label, round(s * 1e9)) for label, s in gaps] == [
        ("bench.wait_arrival", 2000), ("engine.step", 2000),
        ("engine.step", 500)]
    assert reduced["devices"][1]["gaps"] == []


def test_programs_count_whole_runs_inside_the_window(reduced):
    progs = reduced["devices"][0]["programs"]
    assert set(progs) == {"decode_step", "take"}
    assert progs["decode_step"]["seconds"] == pytest.approx(4000e-9)
    assert progs["decode_step"]["runs"] == pytest.approx([3000e-9, 1000e-9])
    assert progs["take"]["seconds"] == pytest.approx(1000e-9)


def test_kernel_time_by_name_averaged_over_devices(reduced):
    secs, count = kernel_seconds(reduced, r"fused_matmul")
    assert (secs, count) == (pytest.approx(1500e-9 / 2), 1)
    secs, count = kernel_seconds(reduced, r"^fusion\.1$")
    assert secs == pytest.approx((2000e-9 + 10000e-9) / 2)
    assert count == 2


def test_breakdown_lists_top_ops_and_idle_by_label(reduced):
    b = breakdown(reduced)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(2000e-9)]
    assert [n for n, _ in b["idle_gaps"]] == ["engine.step",
                                              "bench.wait_arrival"]
    assert b["idle_gaps"][0][1] == pytest.approx(2500e-9)


def test_program_names():
    assert program_name("jit_decode_step(12)") == "decode_step"
    assert program_name("jit_take") == "take"
    assert program_name("fusion") == "fusion"
