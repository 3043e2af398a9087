"""The command's contract off the chip."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for kv in extra_env:
        k, v = kv.split("=", 1)
        env[k] = v
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-135m-8xT.shared-doc", "--seed", str(2**33 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            pass
    return False


def test_no_tpu_fails_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_names_only_files_it_has():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "layer_metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        # a per-layer metric is reported only where its end-to-end metric is
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]]
