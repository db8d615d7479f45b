"""The reduction from a profiler trace to device busy time, idle share,
linear-kernel time and the breakdown (bench/trace.py)."""

from __future__ import annotations

import re
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import layout  # noqa: E402
from bench.trace import TraceView, op_name  # noqa: E402

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def recorded():
    """A window of 100 ms on the host clock; the device runs a scanned
    step (a while op holding two kernels and an add) twice, with host
    spans around each step and the client's work between them."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 10, 100),
        ev("engine.step", 10, 45), ev("client", 55, 5),
        ev("engine.step", 60, 50)])])
    ops = [ev("%while.3 = (s32[], bf16[8,1,6144]) while(...)", 12, 40),
           ev("%msgemm_pallas.43 = f32[8,6144]{1,0} custom-call(...)", 12, 20),
           ev("%msgemm_pallas.42 = f32[8,24576]{1,0} custom-call(...)", 32, 15),
           ev("%add.1 = bf16[8,6144]{1,0} add(%msgemm_pallas.43)", 47, 5),
           # the second step runs past the window's end
           ev("%while.3 = (s32[], bf16[8,1,6144]) while(...)", 70, 50),
           ev("%msgemm_pallas.43 = f32[8,6144]{1,0} custom-call(...)", 70, 50),
           # before the window: not counted
           ev("%msgemm_pallas.43 = f32[8,6144]{1,0} custom-call(...)", 0, 5)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_raw_step(1)", 0, 200)]),
        NS(name="XLA Ops", events=ops)])
    other = NS(name="#Chip0 Misc", lines=[])
    return [other, device, host]


@pytest.fixture
def view():
    pats = [re.compile(r"^%msgemm_pallas(\.\d+)? = ")]
    return TraceView.from_planes(recorded(), pats)


def test_busy_is_the_union_of_op_intervals_in_the_window(view):
    # [12, 52) and [70, 110): 40 + 40 ms of a 100 ms window
    assert view.window_s == pytest.approx(0.100)
    assert view.busy_s == pytest.approx(0.080)
    assert 100 * (1 - view.busy_s / view.window_s) == pytest.approx(20.0)


def test_kernel_time_sums_matched_ops_clipped_to_the_window(view):
    # 20 + 15 + 40 (clipped at 110): the consumer of a kernel's output
    # names it in its operands and does not count
    assert view.kernel_s == pytest.approx(0.075)


def test_breakdown_names_ops_and_idle_gaps(view):
    b = view.breakdown()
    names = dict(b["device_ops"])
    assert names["%msgemm_pallas.43 = f32[8,6144]"] == pytest.approx(0.060)
    assert not any(k.startswith("%while") for k in names)
    gaps = b["idle_gaps"]
    assert [g[0] for g in gaps] == ["engine.step", "engine.step"]
    assert [g[1] for g in gaps] == pytest.approx([0.018, 0.002])


def test_a_trace_without_the_window_span_is_an_error():
    planes = [p for p in recorded() if not p.name.startswith("/host")]
    with pytest.raises(ValueError, match="bench.window"):
        TraceView.from_planes(planes, [])


def test_op_name_keeps_name_and_shape():
    assert op_name("%msgemm_pallas.37 = f32[16,49152]{1,0:T(8,128)S(1)} "
                   "custom-call(s32[171,12,49152]{2,1,0} %r)") == \
        "%msgemm_pallas.37 = f32[16,49152]"


def test_kernel_files_are_collected_by_name(tmp_path):
    (tmp_path / "bench" / "kernels").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text("{}")
    for name, pat in (("a", "^%a = "), ("b", "^%b(\\\\.\\\\d+)? = ")):
        (tmp_path / "bench" / "kernels" / f"{name}.json").write_text(
            f'{{"backend": "{name}", "patterns": ["{pat}"]}}')
    pats = layout.Benchmark(tmp_path).kernel_patterns()
    assert [p.pattern for p in pats] == ["^%a = ", "^%b(\\.\\d+)? = "]
    assert pats[1].search("%b.4 = f32[8]")


def test_repo_kernel_pattern_matches_the_chip_op_names():
    pats = layout.Benchmark(ROOT).kernel_patterns()
    chip_name = ("%msgemm_pallas.43 = f32[16,6144]{1,0:T(8,128)S(1)} "
                 "custom-call(s32[683,12,6144]{2,1,0:T(8,128)} %reshape.645)")
    consumer = "%fusion.2 = bf16[16,6144]{1,0} fusion(%msgemm_pallas.43)"
    assert any(p.search(chip_name) for p in pats)
    assert not any(p.search(consumer) for p in pats)


def test_garbage_collections_are_timed_inside_the_block():
    import gc

    from bench.loop import GcPauses

    with GcPauses() as pauses:
        gc.collect()
    gc.collect()
    assert gc.callbacks.count(pauses) == 0
    assert [g for _, _, g in pauses.pauses] == [2]
    (a, b, _), = pauses.pauses
    assert pauses.summary(a, b + 1).startswith("1 collections (1 full)")
    assert pauses.summary(b + 1, b + 2).startswith("0 collections")
