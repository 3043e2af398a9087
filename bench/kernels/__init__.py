"""Work counts of the kernels the per-layer metrics judge: operations and
bytes of each logical operation, from the model's shapes and the run's
token and step counts.  One module per kernel; each names the trace
operations that implement it (``TRACE_OPS``, a regular expression) and
gives ``work(m, run)`` -> ``{"ops": ..., "peak": "int8_ops"|"bf16_flops",
"bytes": ...}``."""
