"""The DeepSeek-V2 kind (``serve_mla_moe``) driven end to end on the CPU,
past the harness's look for a chip, at a tiny size of the configuration's
new fields: sound, it is correct and the program's routes agree with the
reference's; with a served token altered where the engine samples it, it
is not correct."""
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from harness import Layout, load_json

BENCH = Path(__file__).parents[1]
CELL_CONFIG = "deepseek-v2-ep8-bf16"
LIMITS = load_json(BENCH / "configs" / f"{CELL_CONFIG}.json")["correct"]

TINY = dict(num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
            head_dim=16, d_ff=128, vocab_size=512, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, num_experts=16, num_experts_per_tok=3,
            num_shared_experts=1, moe_d_ff=32, moe_n_group=4,
            moe_topk_group=2, experts_held=4, experts_offset=4,
            rope_yarn_original_max_pos=64)


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """A checkout whose one cell is the DeepSeek-V2 configuration cut to a
    tiny size, under a small version of its chat mix."""
    root = tmp_path_factory.mktemp("root")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = load_json(BENCH.parent / "BENCHMARK.json")
    cfg = load_json(BENCH / "configs" / f"{CELL_CONFIG}.json")
    cfg["model"].update(TINY)
    cfg["engine"].update(slots=4, max_prefill_tokens=16)
    cfg["trace"] = {"seconds": 1.0}
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny", "config": "tiny",
                          "traffic": "small", "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = ["tiny"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    mix = load_json(BENCH / "traffic" / "chat-dsv2.json")
    mix.update(rate_per_s=3.0, tail_s=1.0,
               prompt={"dist": "lognormal", "median": 16, "sigma": 0.8,
                       "min": 4, "max": 40},
               output={"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 10})
    (root / "bench/traffic/small.json").write_text(json.dumps(mix))
    peaks = load_json(BENCH / "peaks.json")
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "int8_ops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    return Layout(root)


def _run(layout, seed=2 ** 31 + 29):
    c = layout.cell("tiny")
    return layout.runner(c.config["kind"])(
        c, layout, seed, 2.0, False, time.perf_counter(), require_tpu=False)


def test_sound_run_is_correct(layout, capsys):
    result, checks = _run(layout)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                      "output_tokens_per_s", "setup_s"}
    for c in checks.values():
        assert c["value"] <= c["limit"]
    assert result["attempted"] == 6 and result["failed"] == 0
    log = capsys.readouterr().err
    line = next(x for x in log.splitlines() if x.startswith("held pairs"))
    program = int(line.split("program ")[1].split(",")[0])
    reference = int(line.split("reference ")[1].split(" ")[0])
    assert program > 0
    # the same rows routed alike, up to a near-tie
    assert abs(program - reference) <= max(2, reference // 50)


def test_altered_token_is_not_correct(layout, monkeypatch):
    from repro.serve import paged_engine

    real = paged_engine.sample_tokens
    state = {"ticks": 0}

    def altered(logits, rows, **kw):
        out = np.array(real(logits, rows, **kw))
        state["ticks"] += 1
        if state["ticks"] == 12:              # one token, mid-window
            out[0] = (out[0] + 1) % logits.shape[-1]
        return out

    monkeypatch.setattr(paged_engine, "sample_tokens", altered)
    result, checks = _run(layout)
    assert state["ticks"] > 12
    assert result["correct"] is False
    assert any(c["value"] > LIMITS[k] for k, c in checks.items())


def test_traced_programs_name_their_moe_scopes(layout):
    """The traced run's programs, compiled again at the shapes they ran
    with, name the MoE layer's scopes in their HLO."""
    from serve_mla_moe import MoeServeCell

    sc = MoeServeCell(layout.cell("tiny"), 2 ** 31 + 31, 2.0)
    sc.make_weights()
    sc.new_engine()
    sc.warm_up()
    sc.serve()
    sc.trace_span = [0.0, sc.end_s]
    maps = sc._scope_maps()
    assert set(maps) == {"decode_step", "prefill_chunk"}
    for shapes in maps.values():
        for scopes in shapes:
            assert {"moe/route", "moe/experts", "moe/shared",
                    "attention"} <= set(scopes.values())
    assert sc.counters["serve.moe_held_assignments"] > 0
