"""Share of the decode batch's rows that carried a request's token:
registry ``serve.decode_tokens`` over decode ticks times slots, over the
window and its drain.  Moves ``output_tokens_per_s``."""


def read(rec):
    ticks = rec["stats"]["decode_ticks"]
    if not ticks:
        return None
    return 100.0 * rec["counters"]["serve.decode_tokens"] / (ticks
                                                             * rec["slots"])
