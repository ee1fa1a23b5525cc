"""Median device time of one run of the ``decode_step`` program in the
traced window: the model.  Moves ``itl_p95_ms``."""
from harness import percentile


def read(rec):
    if rec["trace"] is None:
        return None
    runs = [r for d in rec["trace"]["devices"]
            for r in d["programs"].get("decode_step", {}).get("runs", [])]
    return percentile(runs, 50) * 1e3 if runs else None
