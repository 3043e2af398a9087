"""Model step: device time per decode step in the traced window — the
decode program and the sampler program that follows it, over the decode
program's executions."""


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module_time(run.programs["decode"])
    select, _ = run.trace.module_time(run.programs["select"])
    return (secs + select) / n * 1e3 if n else None
