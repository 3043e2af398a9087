"""Whole step: the least time the window's model work needs at the chip's
peaks — projections at the int8 peak, decode attention and the LM head at
the bf16 peak — over the traced window, in percent.  Rows are counted as
the scheduler ran them (decode tokens, prefill chunks at the chunk size);
the LM head counts one row per emitted token; prefill attention is not
counted."""
from bench.kernels import lm_head, paged_attention, qmatmul_ternary


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    d = run.counter_delta("trace")
    calls = d["decode_steps"] + d["prefill_chunks"]
    contexts = run.decode_contexts()
    if not calls:
        return None
    rows = len(contexts) + d["prefill_chunks"] * run.chunk_size
    emitted = run.tokens_in_trace()
    parts = [qmatmul_ternary.work(run.m, calls, rows),
             paged_attention.work(run.m, contexts),
             lm_head.work(run.m, calls, emitted)]
    least = sum(p["ops"] / run.peaks[p["peak"]] for p in parts)
    return 100.0 * least / run.trace.window_s
