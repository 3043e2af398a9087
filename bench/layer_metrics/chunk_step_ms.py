"""Model step: the mean duration of the program's ``repro.step`` spans in
the traced window that carry a prefill chunk (hold a
``repro.prefill_chunk``).  Nothing only when the slice holds no such step:
a 3 s slice of the shared-doc cell holds about six, and fewer than three
in about one seed of eight, which a minimum count would leave without the
metric."""
from bench import program_spans as P


def read(run):
    spans = P.of_run(run)
    return P.chunk_step_ms(spans) if spans else None
