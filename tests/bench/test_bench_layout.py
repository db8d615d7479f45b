"""A cell, a configuration, a traffic mix, a per-layer metric and a
kernel-name file are picked up from new files alone (bench/layout.py),
and a whole run of the harness goes through at a tiny size on the CPU:
also for a configuration that asks for windows or experts in
``program.model``, and for a cell on four chips."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import layout, rehearse, run  # noqa: E402
from variants import VARIANTS, variant  # noqa: E402

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


def add_cell(tree, name, config, chips=1):
    """A cell of ``config`` on the chat-short mix, as new files alone."""
    b = tree / "bench"
    (b / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    shutil.copy(b / "workloads" / "phi3-mini.chat-mixed.json",
                b / "workloads" / f"{name}.json")
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][-1], name=config["name"],
                                file=f"bench/configs/{config['name']}"
                                     ".json"))
    spec["workloads"].append({"name": name, "config": config["name"],
                              "traffic": "chat-short", "chips": chips,
                              "why": "a cell added as files"})
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("kind", sorted(VARIANTS))
def test_a_configuration_with_windows_or_experts_runs_as_files(tree, kind):
    base = json.loads((tree / "bench" / "configs" / "phi3-mini.json")
                      .read_text())
    name = f"phi3-mini-{kind}"
    add_cell(tree, f"{name}.chat-short", dict(variant(base, kind),
                                              name=name))
    out = run.run_cell(tree, f"{name}.chat-short", 2**31 + 7, 0.5, False,
                       require_chip=False, t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["out_tok_per_s"]["value"] > 0


FOUR_CHIPS = """
import json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src"]
import repro.serving
from bench import run

meshes = []
Engine = repro.serving.Engine


class Held(Engine):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        meshes.append(dict(self.mesh.shape) if self.mesh else None)


repro.serving.Engine = Held
out = run.run_cell(Path(sys.argv[1]), "phi3-mini-tp4.chat-short",
                   2**31 + 8, 0.5, False, require_chip=False,
                   t_start=time.perf_counter())
print(json.dumps({"meshes": meshes, **out}))
"""


def test_a_four_chip_cell_builds_a_model_mesh(tree):
    base = json.loads((tree / "bench" / "configs" / "phi3-mini.json")
                      .read_text())
    add_cell(tree, "phi3-mini-tp4.chat-short",
             dict(base, name="phi3-mini-tp4"), chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    got = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(tree),
                          str(ROOT)], env=env, capture_output=True,
                         text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    out = json.loads(got.stdout.strip().splitlines()[-1])
    assert out["meshes"] == [{"model": 4}]
    assert out["device"]["count"] == 4
    assert out["correct"] is True and out["failed"] == 0
    assert "[bench] mesh {'model': 4}" in got.stderr
