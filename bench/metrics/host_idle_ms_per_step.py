"""Device idle time in the traced window under an ``engine.*`` span, per
traced engine step: the gaps the scheduler and engine leave between the
device's work.  A gap is labelled with the innermost host span over its
midpoint; the engine's phase spans (``engine.gather``, ``engine.sample``,
...) tile ``step()``, and a program without them leaves the benchmark's
own ``engine.step`` as the label.  Moves ``output_tokens_per_s``."""


def read(rec):
    t = rec["trace"]
    if t is None or not t["devices"] or not rec["traced_steps"]:
        return None
    idle = sum(secs for d in t["devices"] for label, secs in d["gaps"]
               if label.startswith("engine."))
    return idle / len(t["devices"]) / rec["traced_steps"] * 1e3
