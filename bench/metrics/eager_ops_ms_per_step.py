"""Device time per engine step of every program other than the model's
``decode_step`` and ``prefill_chunk`` (the paged cache's gathers, commits
and scrubs, and sampling) in the traced window.  Moves ``itl_p95_ms``."""

MODEL = ("decode_step", "prefill_chunk")


def read(rec):
    if (rec["trace"] is None or not rec["trace"]["devices"]
            or not rec["traced_steps"]):
        return None
    devs = rec["trace"]["devices"]
    secs = sum(p["seconds"] for d in devs for name, p in d["programs"].items()
               if name not in MODEL)
    return secs / len(devs) / rec["traced_steps"] * 1e3
