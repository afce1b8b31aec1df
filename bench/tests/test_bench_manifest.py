"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, traffic, reference, limits and per-layer readers by name,
and the command refuses to run off a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from jzb.manifest import Manifest, load  # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["bench"] and DOC["command"][1] == "bench/run.py"
    names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    names += [w["name"] for w in DOC["workloads"]]
    names += [c["name"] for c in DOC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in DOC["end_to_end"]} == {
        "setup_s", "p50_ms", "goodput_rps"}
    assert all(w["chips"] == 1 for w in DOC["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_cell_finds_its_files(cell):
    man = Manifest()
    w = man.cell(cell)
    cfg = man.config(w["config"])
    assert cfg["name"] == w["config"]
    entry = man.configs[w["config"]]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == []
    model = load("models", cfg["model"])
    for fn in ("init", "logits", "flops"):
        assert callable(getattr(model, fn))
    for k in cfg.get("kernels", []):
        kern = load("kernels", k)
        assert kern.TRACE_NAMES and callable(kern.bytes_moved)
    mix = man.traffic(w["traffic"])
    for key in ("rate_rps", "knee_rps", "deadline_ms", "arrivals",
                "candidates", "history", "zipf_a"):
        assert key in mix
    limits = json.loads((BENCH / "limits" / f"{w['config']}.json")
                        .read_text())
    for k in ("point_gap", "cand_gap", "rank_violations", "shed_violations",
              "shed_share", "unanswered"):
        assert limits[k]["limit"] >= 0
    e2e = {m["name"] for m in man.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = man.per_layer(cell)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e
        assert callable(man.reader(m["name"]))


def test_every_metric_lists_cells_that_report_what_it_moves():
    man = Manifest()
    for m in DOC["per_layer"]:
        assert m["workloads"]
        for cell in m["workloads"]:
            assert m["moves"] in {e["name"] for e in man.end_to_end(cell)}
    layers = {}
    for m in DOC["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert all("\n" not in layer for layer in layers)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "din.steady",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_run_exits_nonzero_off_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0 and not _printed_result(proc)
    assert "TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0 and not _printed_result(proc)
