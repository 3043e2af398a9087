"""Scheduler: live slots per decode step over the window
(``decode_slot_tokens / decode_steps``, the batcher's own counters)."""


def read(run):
    d = run.counter_delta("window")
    return d["decode_slot_tokens"] / d["decode_steps"] if d["decode_steps"] else None
