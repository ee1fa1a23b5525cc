"""The DeepSeek-V2 kind's readers and its reduction by name scope, on a
small recorded trace with known answers: ops are put in the program run
that holds them, named by the scope of their HLO instruction, and the
three readers divide what they should by what they should."""
import json
from pathlib import Path

import pytest

from harness import Layout
from serve_mla_moe import (best_map, hlo_scopes, reduce_by_program,
                           scope_seconds)
from work.mla_moe import (expert_weight_bytes, routed_pair_bytes,
                          routed_pair_flops, token_flops)

DATA = Path(__file__).parent / "data"
SIZES = json.loads((Path(__file__).parents[1] / "configs"
                    / "deepseek-v2-ep8-bf16.json").read_text())["model"]
PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12}


@pytest.fixture(scope="module")
def rec():
    events = json.loads((DATA / "trace_moe.json").read_text())
    t0, t1 = events["window_ns"]
    reduced = reduce_by_program(events, t0, t1)
    maps = {prog: [hlo_scopes("\n".join(lines)) for lines in shapes]
            for prog, shapes in events["hlo"].items()}
    return {"trace": reduced, "scopes": scope_seconds(reduced, maps),
            "traced_calls": events["traced_calls"], "sizes": SIZES,
            "peaks": PEAKS}


def test_ops_fall_in_the_program_run_that_holds_them(rec):
    runs = rec["trace"]["devices"][0]["program_runs"]
    assert [prog for prog, _ in runs] == ["cache_gather", "decode_step",
                                          "decode_step", "prefill_chunk"]
    assert runs[0][1] == {"fusion.1": pytest.approx(1e-6)}
    assert runs[1][1]["fusion.3"] == pytest.approx(3e-6)
    assert runs[1][1]["ragged-dot.1"] == pytest.approx(4e-6)
    # fusion.1 at 11000 lies in the second decode run
    assert runs[2][1]["fusion.1"] == pytest.approx(1e-6)
    assert runs[3][1] == {"fusion.3": pytest.approx(2e-6)}


def test_each_run_is_read_with_the_map_of_its_shape(rec):
    """The fixture's two decode shapes give ``fusion.3`` other scopes: the
    first run's ops are all in both maps and cover the first better; the
    second run's ``fusion.1`` is only in the second."""
    [_, first, second, _] = rec["trace"]["devices"][0]["program_runs"]
    events = json.loads((DATA / "trace_moe.json").read_text())
    a, b = (hlo_scopes("\n".join(lines))
            for lines in events["hlo"]["decode_step"])
    assert best_map(first[1], [a, b]) is a
    assert best_map(first[1], [b, a]) is a
    assert best_map(second[1], [a, b]) is b


def test_scopes_by_hlo_instruction(rec):
    """The kernel's custom call, named by itself, takes its user's scope;
    the loop's own event is not counted beside its body's ops."""
    s = rec["scopes"]["decode_step"]
    assert s == {"attention": pytest.approx(4e-6),
                 "kv_write": pytest.approx(3e-6),
                 "moe/experts": pytest.approx(10e-6),
                 "moe/route": pytest.approx(2e-6),
                 "moe/shared": pytest.approx(0.5e-6)}
    assert "prefill_chunk" not in rec["scopes"]        # no map given


def test_moe_ms_per_decode_step(rec):
    read = Layout().reader("moe_ms_per_decode_step")
    assert read(rec) == pytest.approx((10 + 2 + 0.5) * 1e-6 / 2 * 1e3)


def test_expert_hbm_share(rec):
    read = Layout().reader("expert_hbm_share.dsv2")
    byts = (3 * expert_weight_bytes(SIZES) + 4 * routed_pair_bytes(SIZES))
    assert read(rec) == pytest.approx(100 * byts / 10e-6 / 1e12)


def test_mfu_counts_held_pairs_and_both_paths(rec):
    read = Layout().reader("mfu.serve.dsv2")
    ops = (sum(token_flops(SIZES, c, True) for c in (10, 20, 11, 21))
           + sum(token_flops(SIZES, c, False) for c in (1, 2))
           + 6 * routed_pair_flops(SIZES))
    assert read(rec) == pytest.approx(100 * ops / 30e-6 / 1e15)


def test_readers_are_silent_without_scopes_or_routes(rec):
    bare = dict(rec, scopes=None,
                traced_calls=[{k: v for k, v in c.items()
                               if k not in ("held_pairs", "experts_touched")}
                              for c in rec["traced_calls"]])
    for name in ("moe_ms_per_decode_step", "expert_hbm_share.dsv2",
                 "mfu.serve.dsv2"):
        assert Layout().reader(name)(bare) is None
