"""The harness end to end on the CPU: a rehearsal of a cell, a cell added
from files alone, and the runs that must print no result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import correct, harness, peaks, run

CHECKOUT = harness.CHECKOUT


def _last_json(text):
    lines = [ln for ln in text.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_cpu_rehearsal_runs_a_cell_end_to_end(capsys):
    """The open-loop cell: every request due in the window is served to its
    end, so the check always has requests to compare."""
    code = run.main(
        ["--workload", "granite-8b.prefill-poisson", "--seed", str(2**31 + 5), "--seconds", "3",
         "--cpu-rehearsal", "--backend", "xla"]
    )
    assert code == 0
    line = _last_json(capsys.readouterr().out)
    assert line["rehearsal"] is True and "device" not in line
    assert line["correct"] is True
    assert set(line["readings"]) == {"setup_s", "tpot_p95_ms", "ttft_p50_ms"}
    assert list(line)[-1] == "checks"


def test_a_cell_added_from_files_alone(tmp_path):
    """A new mix, a new per-layer metric and a new cell: files and entries
    only, read from another root. The run is untraced, so of the per-layer
    metrics only those the loop counts have something to read."""
    shutil.copytree(CHECKOUT / "bench" / "configs", tmp_path / "bench" / "configs")
    shutil.copytree(CHECKOUT / "bench" / "reference", tmp_path / "bench" / "reference")
    shutil.copytree(CHECKOUT / "bench" / "generators", tmp_path / "bench" / "generators")
    shutil.copytree(CHECKOUT / "bench" / "traffic", tmp_path / "bench" / "traffic")
    shutil.copytree(CHECKOUT / "bench" / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(CHECKOUT / "bench" / "cells", tmp_path / "bench" / "cells")
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    mix = json.loads((CHECKOUT / "bench" / "traffic" / "decode-batch.json").read_text())
    mix["rehearsal"]["output"] = {"dist": "fixed", "value": 8}
    (tmp_path / "bench" / "traffic" / "short-out.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "requests_seen.count.py").write_text(
        "def read(record):\n    return float(len(record.tracked)) or None\n"
    )
    cells = json.loads((CHECKOUT / "bench" / "cells" / "granite-8b.decode-batch.json").read_text())
    (tmp_path / "bench" / "cells" / "granite-8b.short-out.json").write_text(json.dumps(cells))
    bench["workloads"].append(
        {"name": "granite-8b.short-out", "config": "granite-8b", "traffic": "short-out", "chips": 1, "why": "test"}
    )
    bench["per_layer"].append(
        {"name": "requests_seen.count", "unit": "requests", "better": "higher", "source": "program_counter",
         "layer": "scheduler", "moves": "tpot_p95_ms", "workloads": ["granite-8b.short-out"]}
    )
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append("granite-8b.short-out")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell.load(tmp_path, "granite-8b.short-out", rehearsal=True)
    assert cell.mix["rehearsal"]["output"]["value"] == 8
    out = run.run_cell(cell, 9, 3.0, traced=False, backend="xla", pk=peaks.peaks("TPU v5 lite"))
    assert correct.correct(out["cmp"], cell.limits)
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s", "tpot_p95_ms"}
    read = run.per_layer(cell, tmp_path, out["rec"])
    assert set(read) == {"requests_seen.count"} and read["requests_seen.count"]["value"] >= 1


def _script(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    args = args or ("--workload", "granite-8b.decode-batch", "--seed", "1", "--seconds", "1", "--trace", "0")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=600
    )


def test_a_run_without_a_tpu_exits_nonzero_with_no_result():
    out = _script(CHECKOUT)
    assert out.returncode != 0
    assert _last_json(out.stdout) is None
    assert "no TPU" in out.stderr


def test_a_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _script(tmp_path)
    assert out.returncode != 0
    assert _last_json(out.stdout) is None
