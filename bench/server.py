"""The system under test behind the drivers' interface: the program's
``PagedBatcher``, driven through ``submit`` and ``step``."""
from __future__ import annotations

import re

COUNTERS = ("decode_steps", "decode_slot_tokens", "prefill_chunks",
            "prompt_tokens", "prefix_hit_tokens", "preemptions",
            "tokens_out")


def module_pattern(jitted) -> str:
    """The trace's name for a jitted function's program: ``jit_<name>(``
    with ``<lambda>`` written ``_lambda``."""
    name = getattr(jitted, "__name__", "")
    return r"^jit_+" + re.escape(name.strip("<>_")) + r"\("


class PagedServer:
    def __init__(self, batcher):
        from repro.runtime.serving import Request, RequestOptions
        self.b = batcher
        self._request, self._options = Request, RequestOptions

    def submit(self, req, on_token):
        handle = self._request(req.rid, req.tokens[None], self._options(
            max_new=req.max_new, on_token=on_token))
        req.handle = handle
        self.b.submit(handle)

    def step(self):
        return self.b.step()

    @property
    def idle(self) -> bool:
        return self.b.idle

    @property
    def chunk_size(self) -> int:
        return self.b.chunk_size

    def programs(self) -> dict:
        """Trace-name patterns of the timed path's programs, taken from the
        batcher's own jitted functions: the decode step, the sampler that
        follows it, and the prefill chunk."""
        return {"decode": module_pattern(self.b._decode),
                "select": module_pattern(self.b._select_paged),
                "prefill": module_pattern(self.b._prefill_chunk)}

    def counters(self) -> dict:
        m = self.b.metrics
        return {k: int(getattr(m, k)) for k in COUNTERS}
