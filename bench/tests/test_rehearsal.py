"""The harness loop end to end on the CPU, and ``bench/run.py``'s refusal
to run anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys

from bench import harness
import _smoke

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_rehearsal_last_line_keys():
    got = {}
    out = _smoke.run("kv", seconds=2.0, log=lambda **kw: got.update(kw))
    assert got["kv_checked"] > 0 and got["attn_rows"] > 0
    assert got["programs_lowered"] == 0        # nothing compiles in the window
    assert list(out) == KEYS
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert {"setup_s", "output_tok_s", "itl_p95_ms"} <= set(m)
    assert all(set(v) == {"value", "unit"} for v in m.values())
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(set(c) in ({"value", "max"}, {"value", "min"}) for c in out["checks"].values())
    json.dumps(out)


def test_rehearsal_traced_line_has_device_window_and_breakdown():
    out = _smoke.run("model", seconds=1.5, trace=True)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "setup_s" not in out["metrics"]


def _run_py(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2_5_3b.decode_heavy",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_a_cpu():
    p = _run_py(harness.ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_py_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
