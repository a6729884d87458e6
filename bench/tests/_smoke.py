"""A smoke-sized cell for CPU tests: the real harness, tiny shapes.

Its upper limits are the committed configurations' own, so a test that
finds ``correct`` false here finds it under the limits the benchmark runs
with; its lower limits (what must have been compared) suit its short
window."""

import json
from pathlib import Path

from bench import harness, traffic

DATA = Path(__file__).resolve().parent / "data"
CELLS = {"model": ("smoke_model.json", "smoke_closed.json", "qwen2_5_3b.json"),
         "kv": ("smoke_kvpath.json", "smoke_open.json", "qwen2_5_3b_kvpath.json")}


def spec(kind: str) -> dict:
    cfg, tr, committed = CELLS[kind]
    full = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    config = json.loads((DATA / cfg).read_text())
    config["limits"]["max"] = json.loads(
        (harness.BENCH / "configs" / committed).read_text())["limits"]["max"]
    return {"workload": {"name": f"smoke.{kind}", "chips": 1},
            "config": config,
            "traffic": traffic.load(DATA / tr),
            "end_to_end": [m for m in full["end_to_end"] if "workloads" not in m],
            "per_layer": [m for m in full["per_layer"]]}


def run(kind: str, seed: int = 2**33 + 17, seconds: float = 2.0, **kw) -> dict:
    kw.setdefault("log", lambda **_: None)
    return harness.run(f"smoke.{kind}", seed, seconds, kw.pop("trace", False),
                       spec=spec(kind), **kw)
