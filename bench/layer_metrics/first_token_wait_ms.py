"""Scheduler (admission): the mean duration of the program's
``repro.first_token`` spans in the traced window — the host blocked on an
admission's last prefill chunk and the sampling of its first token, before
it can dispatch the step's decode."""
from bench import program_spans as P


def read(run):
    spans = P.of_run(run)
    return P.first_token_wait_ms(spans) if spans else None
