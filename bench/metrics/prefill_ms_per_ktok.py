"""Device time of the ``prefill_chunk`` programs in the traced window per
thousand prompt tokens they computed: the model.  Moves ``ttft_p90_ms``."""


def read(rec):
    if rec["trace"] is None or not rec["traced_calls"]:
        return None
    tokens = sum(c["rows"] * c["length"] for c in rec["traced_calls"]
                 if c["program"] == "prefill_chunk")
    secs = sum(d["programs"].get("prefill_chunk", {}).get("seconds", 0.0)
               for d in rec["trace"]["devices"])
    if not tokens or not secs:
        return None
    return secs / len(rec["trace"]["devices"]) / tokens * 1e6
