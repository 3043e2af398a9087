"""The bfloat16 LM head: hidden rows (R, D) times the (D, V) head.

Per call: 2*R*D*V operations; bytes: the head (2*D*V) once per call, the
rows in (2*R*D) and the logits out (2*R*V)."""
TRACE_OPS = r"^(dot|convolution|fusion)"


def vocab(m: dict) -> int:
    return -(-m["vocab"] // 512) * 512


def work(m: dict, calls: int, rows: int) -> dict:
    d, v = m["d_model"], vocab(m)
    return {"ops": 2 * rows * d * v, "peak": "bf16_flops",
            "bytes": calls * 2 * d * v + rows * 2 * (d + v)}
