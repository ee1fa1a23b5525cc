"""Device time per engine step of the paged cache's writes in the traced
window: the programs ``cache_commit_decode``, ``cache_commit_prefill`` and
``cache_scrub``, averaged over devices.  With the pool donated they update
a few cells in place, so a reading of a whole pool leaf's copy means the
in-place write did not engage.  ``None`` where the trace holds none of
them (a program whose cache writes are not these named programs).  Moves
``itl_p95_ms``."""

WRITES = ("cache_commit_decode", "cache_commit_prefill", "cache_scrub")


def read(rec):
    if (rec["trace"] is None or not rec["trace"]["devices"]
            or not rec["traced_steps"]):
        return None
    devs = rec["trace"]["devices"]
    if not any(name in d["programs"] for d in devs for name in WRITES):
        return None
    secs = sum(d["programs"][name]["seconds"] for d in devs for name in WRITES
               if name in d["programs"])
    return secs / len(devs) / rec["traced_steps"] * 1e3
