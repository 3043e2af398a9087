"""The ternary ``qmatmul``: signed activation codes (int8) times packed
ternary weights (2 bits each), int32 accumulation, per-channel and per-row
scales, float32 out.

Per call of one projection (K, N) on M rows: 2*M*K*N integer operations;
bytes: the packed weights (K*N/4) and their scales (4*N) once per call, the
activation codes (M*K) and the float32 output (4*M*N).  Every layer calls
each projection once per prefill chunk and once per decode step.
"""
TRACE_OPS = r"ternary"


def projections(m: dict) -> list[tuple[int, int]]:
    d, h, kv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d),
            (d, f), (d, f), (f, d)]


def work(m: dict, calls: int, rows: int) -> dict:
    """``calls`` model steps (chunks and decode steps) that together
    computed ``rows`` token rows."""
    L = m["n_layers"]
    kn = projections(m)
    ops = 2 * rows * L * sum(k * n for k, n in kn)
    weight = calls * L * sum(k * n // 4 + 4 * n for k, n in kn)
    act = rows * L * sum(k + 4 * n for k, n in kn)
    return {"ops": ops, "peak": "int8_ops", "bytes": weight + act}
