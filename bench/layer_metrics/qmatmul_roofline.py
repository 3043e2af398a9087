"""Kernels: the ternary qmatmul's share of its roofline in the traced
window — the least time the chip could take for the window's projection
work (operations at the int8 peak or bytes at HBM bandwidth, whichever is
longer) over the device time of the kernel's operations."""
from bench.kernels import qmatmul_ternary as K


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.op_time(K.TRACE_OPS)
    d = run.counter_delta("trace")
    calls = d["decode_steps"] + d["prefill_chunks"]
    if not n or not calls:
        return None
    rows = len(run.decode_contexts()) + d["prefill_chunks"] * run.chunk_size
    return run.roofline_share(K.work(run.m, calls, rows), secs)
