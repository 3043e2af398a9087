"""Work counts at smollm-135m's published shapes, worked by hand."""
import json
from pathlib import Path

import pytest

from bench import run
from bench.kernels import lm_head, paged_attention, qmatmul_ternary

M = json.loads((Path(__file__).resolve().parents[1] / "configs"
                / "smollm-135m-2xT.json").read_text())["model"]


def test_qmatmul_ternary_one_decode_row():
    # per layer: wq 576x576, wk/wv 576x192, wo 576x576, gate/up 576x1536,
    # down 1536x576 -> 3,538,944 weights; 30 layers -> 106,168,320
    w = qmatmul_ternary.work(M, calls=1, rows=1)
    assert w["ops"] == 2 * 106_168_320
    assert w["peak"] == "int8_ops"
    # packed weights at 2 bits: 26,542,080 B; scales 4 * 5,184 * 30 =
    # 622,080 B; codes in 4,992 * 30 = 149,760 B; f32 out 20,736 * 30 =
    # 622,080 B
    assert w["bytes"] == 26_542_080 + 622_080 + 149_760 + 622_080


def test_qmatmul_weights_move_once_per_call():
    one = qmatmul_ternary.work(M, calls=1, rows=64)
    two = qmatmul_ternary.work(M, calls=2, rows=64)
    assert two["bytes"] - one["bytes"] == 26_542_080 + 622_080
    assert two["ops"] == one["ops"]


def test_paged_attention_contexts():
    w = paged_attention.work(M, [100, 200])
    # 4 * 30 layers * 9 heads * 64 * 300 positions
    assert w["ops"] == 20_736_000
    # per layer: K and V codes + f32 scales, 2 * 3 * (64 + 4) * 300 =
    # 122,400 B; query and output in bf16 4 * 9 * 64 * 2 = 4,608 B
    assert w["bytes"] == 30 * (122_400 + 4_608)


def test_lm_head_one_row():
    w = lm_head.work(M, calls=1, rows=1)
    assert w["ops"] == 2 * 576 * 49_152
    assert w["bytes"] == 2 * 576 * 49_152 + 2 * (576 + 49_152)


def test_roofline_share_takes_the_longer_bound():
    rd = run.RunData(peaks=run.peaks_for("TPU v5 lite"))
    # 819 MB at 819 GB/s is 1 ms; 1 GOP at 393 TOP/s is far less
    share = rd.roofline_share({"ops": 1e9, "peak": "int8_ops",
                               "bytes": 819e6}, 0.004)
    assert share == pytest.approx(25.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        run.peaks_for("TPU v4")
