"""Scheduler: the host's own work per scheduler step in the traced window
— the mean over the program's ``repro.step`` spans of the step's duration
less the time its ``repro.decode_fetch`` and ``repro.first_token`` spans
cover (where the host waits on the device).  The ``on_token`` callbacks
count as the host's work, as an operator's server would count them."""
from bench import program_spans as P


def read(run):
    spans = P.of_run(run)
    return P.host_self_ms(spans) if spans else None
