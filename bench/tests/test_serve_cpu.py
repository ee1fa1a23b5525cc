"""A serving run driven end to end on the CPU, past the harness's look for
a chip.  At a tiny size: sound, it is correct; with a served token
altered where the engine samples it, or with a decode step that leaves
the cache unchanged, it is not.  At a size the CPU still
holds in a minute: the control (the reference one precision step below
the configuration's), judged as a run is, is not correct at the
configuration's limit, and reads far above a sound run.  And the BP8 replay
matches the BP8 engine exactly where both compute the same arithmetic."""
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from harness import Layout, load_json

BENCH = Path(__file__).parents[1]
CELL_CONFIG = "danube-1p8b-bf16"
LIMITS = load_json(BENCH / "configs" / f"{CELL_CONFIG}.json")["correct"]

TINY = dict(num_layers=2, d_model=64, num_heads=8, num_kv_heads=2,
            head_dim=8, d_ff=160, vocab_size=512)
MID = dict(num_layers=8, d_model=512, num_heads=8, num_kv_heads=2,
           head_dim=64, d_ff=1536, vocab_size=8192)


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """A checkout whose cells are the benchmark's configurations cut to
    small sizes, under a small version of the chat mix."""
    root = tmp_path_factory.mktemp("root")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = load_json(BENCH.parent / "BENCHMARK.json")
    spec["configs"], spec["workloads"] = [], []
    bp8 = dict(matmul_mode="bp8_fused", kv_quant="bp8")
    for name, sizes in (("tiny", TINY), ("mid", MID),
                        ("tiny-bp8", dict(TINY, **bp8))):
        cfg = load_json(BENCH / "configs" / f"{CELL_CONFIG}.json")
        cfg["model"].update(sizes)
        cfg["engine"].update(slots=4, max_prefill_tokens=16)
        cfg["trace"] = {"seconds": 1.0}
        (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": name, "config": name,
                                  "traffic": "small", "chips": 1,
                                  "why": "test"})
    for m in spec["per_layer"]:
        m["workloads"] = [w["name"] for w in spec["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    mix = load_json(BENCH / "traffic" / "chat.json")
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


def _run(layout, cell="tiny", seed=2 ** 31 + 17):
    c = layout.cell(cell)
    return layout.runner(c.config["kind"])(
        c, layout, seed, 2.0, False, time.perf_counter(), require_tpu=False)


def _readings(layout, cell, seed):
    """(correct, widest gap, mean gap) of the served tokens and of the
    control's first choices, each judged as a run judges its tokens, at
    the configuration's limits."""
    import serve

    sc = serve.ServeCell(layout.cell(cell), seed, 3.0)
    sc.make_weights()
    sc.new_engine()
    sc.warm_up()
    sc.serve()
    sc.record_calls()
    sc.engine = None
    verdicts, _, _ = serve.judge(sc, LIMITS, control=True)
    return [(ok, n["widest_logit_gap"], n["mean_logit_gap"])
            for ok, n in verdicts]


def test_sound_run_is_correct(layout):
    result, checks = _run(layout)
    assert result["correct"] is True
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device"]
    assert set(result["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                      "output_tokens_per_s", "setup_s"}
    for c in checks.values():
        assert c["value"] <= c["limit"]
    assert result["attempted"] == 6 and result["failed"] == 0


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
    assert checks["widest_logit_gap"]["value"] > LIMITS["widest_logit_gap"]


def test_unchanged_cache_is_not_correct(layout, monkeypatch):
    """A decode step that leaves the cache as it was: the decoded tokens'
    keys and values never reach the pool."""
    from repro.serve import paged_cache

    calls = {"n": 0}

    def unchanged(self, view, rows, slot_ids, positions):
        calls["n"] += 1

    monkeypatch.setattr(paged_cache.PagedCache, "commit_decode", unchanged)
    result, checks = _run(layout)
    assert calls["n"] > 12
    assert result["correct"] is False
    assert checks["widest_logit_gap"]["value"] > LIMITS["widest_logit_gap"]


def test_control_reads_far_above_a_sound_run(layout):
    (sound_ok, sound, sound_mean), (control_ok, control, control_mean) = \
        _readings(layout, "mid", 7)
    assert sound_ok is True
    assert control_ok is False
    assert control > LIMITS["widest_logit_gap"] > sound
    assert control_mean > 3 * sound_mean


def test_bp8_replay_is_exact(layout):
    (_, sound, _), (_, control, _) = _readings(layout, "tiny-bp8", 5)
    assert sound == 0.0
    assert control > 0.0
