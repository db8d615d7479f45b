"""A cell, a configuration, a traffic mix, a per-layer metric and a
kernel-name file are picked up from new files alone (bench/layout.py),
and a whole run of the harness goes through at a tiny size on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import layout, rehearse, run  # noqa: E402

NEW = "phi3-mini-twin.chat-short"


@pytest.fixture
def tree(tmp_path, monkeypatch):
    # keep the persistent compile cache out of the checkout in tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    rehearse.tiny_tree(tmp_path)
    b = tmp_path / "bench"
    shutil.copy(b / "configs" / "phi3-mini.json",
                b / "configs" / "phi3-mini-twin.json")
    (b / "traffic" / "chat-short.json").write_text(json.dumps(dict(
        json.loads((b / "traffic" / "sharegpt-turns.json").read_text()),
        clients=3, size_seed=99)))
    shutil.copy(b / "workloads" / "phi3-mini.chat-mixed.json",
                b / "workloads" / f"{NEW}.json")
    (b / "metrics" / "client.sent.py").write_text(
        '"""Requests the clients sent."""\n\n\n'
        "def read(run):\n    return len(run.reqs)\n")
    (b / "metrics" / "never.py").write_text(
        '"""Finds nothing to read."""\n\n\n'
        "def read(run):\n    return None\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][-1], name="phi3-mini-twin",
                                file="bench/configs/phi3-mini-twin.json"))
    spec["workloads"].append({"name": NEW, "config": "phi3-mini-twin",
                              "traffic": "chat-short", "chips": 1,
                              "why": "a cell added as files"})
    spec["end_to_end"].append({"name": "client.sent", "unit": "requests",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": [NEW]})
    spec["end_to_end"].append({"name": "never", "unit": "ms",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_new_cell_config_mix_and_metric_are_found_by_name(tree):
    cell = layout.Benchmark(tree).cell(NEW)
    assert cell.config["name"] == "phi3-mini-twin"
    assert cell.mix["clients"] == 3
    out = run.run_cell(tree, NEW, 2**31 + 5, 0.5, False,
                       require_chip=False, t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    # itl_p95_ms names its cell, and this is not one
    assert set(m) == {"out_tok_per_s", "setup_s", "client.sent"}
    assert m["client.sent"]["unit"] == "requests"
    assert m["client.sent"]["value"] >= 3
    assert "never" not in m  # a reader that finds nothing is left out
    assert list(out)[-1] == "check"
    assert out["check"]["max_logit_gap"]["limit"] > 0


def test_a_traced_run_reports_the_per_layer_metrics(tree):
    out = run.run_cell(tree, "phi3-mini.chat-mixed", 2**31 + 6, 0.5, True,
                       require_chip=False, t_start=time.perf_counter())
    # no device in a CPU trace: the device readers find nothing to read
    assert {"engine.prefill_share", "engine.decode_rows",
            "step.decode_ms"} <= set(out["metrics"])
    assert "linear_roofline" not in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def test_a_cell_file_with_unknown_keys_is_refused(tree):
    path = tree / "bench" / "workloads" / f"{NEW}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), seeds=3)))
    with pytest.raises(ValueError, match="exactly"):
        layout.Benchmark(tree).cell(NEW)


def test_no_chip_means_no_result(tree):
    with pytest.raises(run.Refused, match="needs a TPU"):
        run.run_cell(tree, NEW, 1, 0.5, False)


def test_no_program_means_no_result(tree):
    (tree / "src").unlink()
    with pytest.raises(run.Refused, match="no program"):
        run.run_cell(tree, NEW, 1, 0.5, False, require_chip=False)
