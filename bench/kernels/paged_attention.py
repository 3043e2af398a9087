"""Paged decode attention over the int8 KV pool: one query token per live
slot attends over its context of c positions.

Per token and layer: 4*H*Dh*c floating-point operations (scores and the
weighted sum); bytes: the context's keys and values as int8 codes with a
float32 scale per position and KV head (2*c*KV*(Dh+4)), plus the query
and output in bfloat16 (4*H*Dh).
"""
TRACE_OPS = r"paged_attention"


def work(m: dict, contexts) -> dict:
    """``contexts``: the context length of every decode token."""
    L, h, kv, dh = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    total = sum(contexts)
    return {"ops": 4 * L * h * dh * total, "peak": "bf16_flops",
            "bytes": L * (2 * kv * (dh + 4) * total
                          + 4 * h * dh * len(contexts))}
