"""Kernels: the paged decode attention kernel's share of its roofline in
the traced window (operations at the bf16 peak or bytes at HBM
bandwidth, whichever is longer, over the kernel's device time)."""
from bench.kernels import paged_attention as K


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.op_time(K.TRACE_OPS)
    contexts = run.decode_contexts()
    if not n or not contexts:
        return None
    return run.roofline_share(K.work(run.m, contexts), secs)
