"""Device time of the ops traced under the ``moe/*`` name scopes (router,
held experts, shared experts) per run of the ``decode_step`` program in
the traced window: the MoE layers' share of a decode step.  ``None``
where the record has no time by scope.  Moves ``itl_p95_ms``."""


def read(rec):
    scopes = (rec.get("scopes") or {}).get("decode_step")
    if rec["trace"] is None or not scopes:
        return None
    devs = rec["trace"]["devices"]
    runs = sum(len(d["programs"].get("decode_step", {}).get("runs", []))
               for d in devs) / max(1, len(devs))
    if not runs:
        return None
    secs = sum(v for k, v in scopes.items() if k.startswith("moe/"))
    return secs / runs * 1e3
