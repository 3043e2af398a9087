"""The program's host spans and the three readers built on them, on a
hand-made trace whose numbers are worked out in its header."""
import types
from pathlib import Path

import jax
import pytest

from bench import program_spans as P
from bench.layer_metrics import (chunk_step_ms, first_token_wait_ms,
                                 host_self_ms)

DATA = Path(__file__).resolve().parents[1] / "testdata"
TEXT = (DATA / "program_spans.xplane.pbtxt").read_text()


def _planes(text=TEXT):
    return jax.profiler.ProfileData.from_text_proto(text).planes


def _run(tmp_path, monkeypatch, text=TEXT):
    """A traced run whose profile is the hand-made trace, where the
    harness writes it."""
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path)
    return types.SimpleNamespace(trace=object())


def test_spans_inside_the_window_with_their_nesting():
    spans = P.from_planes(_planes())
    steps = P.steps(spans)
    assert [(s.start_ns, s.end_ns) for s in steps] == [(20e6, 180e6),
                                                       (180e6, 390e6)]
    assert [c.name for c in steps[0].children] == [
        "admit", "pre_decode", "decode", "decode_fetch", "emit"]
    admit = steps[1].children[0]
    assert admit.name == "admit"
    assert [c.name for c in admit.children] == ["prefill_chunk",
                                                "first_token"]
    # the first token before the window is left out with its step
    assert [s.start_ns for s in P.first_tokens(spans)] == [185e6]


def test_readers(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    assert host_self_ms.read(run) == pytest.approx(15.0)   # (10 + 20) / 2
    assert chunk_step_ms.read(run) == pytest.approx(210.0)
    assert first_token_wait_ms.read(run) == pytest.approx(60.0)
    assert run._program_spans            # parsed once, kept on the run


def test_readers_without_a_trace_or_without_spans(tmp_path, monkeypatch):
    for m in (host_self_ms, chunk_step_ms, first_token_wait_ms):
        assert m.read(types.SimpleNamespace(trace=None)) is None
    # a program that opens no repro spans (the parent of this change)
    bare = "\n".join(line for line in TEXT.splitlines()
                     if "repro." not in line)
    run = _run(tmp_path, monkeypatch, bare)
    for m in (host_self_ms, chunk_step_ms, first_token_wait_ms):
        assert m.read(run) is None


def test_idle_by_phase():
    idle = P.idle_by_phase(_planes())
    # gaps 10..22 (no span), 175..254 (first_token), 384..400 (the cut
    # step's admit, which lies inside the window)
    assert idle == {"none": pytest.approx(12e-3),
                    "first_token": pytest.approx(79e-3),
                    "admit": pytest.approx(16e-3)}
    s = P.summary(_planes())
    assert (s["steps"], s["chunk_steps"], s["first_tokens"]) == (2, 1, 1)
