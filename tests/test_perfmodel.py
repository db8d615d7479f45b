"""Perf-model tests: calibration fit/round-trip, model-guided autotune
pruning (winner parity with the full sweep under a deterministic clock),
the measured-vs-predicted regression sentinel, and the interpret-tagged
timing rows the calibration partitions on."""

import json

import pytest

from repro import dispatch, obs
from repro.core.spec import QuantSpec
from repro.dispatch import autotune as at
from repro.obs import perfmodel as pm

MS2 = QuantSpec(mode="msgemm", d=2, scale_block=12, storage="packed_idx")

# ground-truth constants for the synthetic clock: every "measured" time
# is exactly the model evaluated at these, so fits recover them and the
# model's ranking provably matches the timing ranking
SYNTH = {"launch_s": 1e-4, "step_s": 1e-5, "produce_s_per_flop": 2e-9,
         "consume_s_per_op": 1e-9, "hbm_s_per_byte": 5e-10}
SYNTH_CAL = pm.Calibration(device="cpu", interpret=True,
                           constants={"*": SYNTH},
                           fit={"n_samples": 99}, created_unix=1.0)


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Fresh plan cache + no ambient calibration for every test."""
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "calib.json"))
    dispatch.set_cache_path(None)
    obs.registry().reset()
    yield
    dispatch.set_cache_path(None)


def _synthetic_sample(backend, m, k, b, *, d=2, tm=None, tj=None, tb=None,
                      chunk=1, acc=True, scale=1.0, device="cpu",
                      interpret=True):
    feats = pm.features(backend, "msgemm", d, 12, m, k, b, tm=tm, tj=tj,
                        tb=tb, consume_chunk=chunk, acc_in_vmem=acc)
    t = sum(SYNTH[n] * feats[n] for n in pm.CONSTANT_NAMES) * scale
    return pm.Sample(backend=backend, mode="msgemm", d=d, scale_block=12,
                     m=m, k=k, b=b, measured_s=t, device=device,
                     interpret=interpret, tm=tm, tj=tj, tb=tb,
                     consume_chunk=chunk, acc_in_vmem=acc,
                     source=f"synth:m{m}k{k}b{b}")


def _synthetic_grid():
    out = []
    for backend in ("msgemm_pallas", "msgemm_jnp"):
        for (m, k, b) in [(16, 24, 8), (64, 24, 8), (16, 48, 8),
                          (128, 96, 16), (256, 24, 64)]:
            for chunk in (1, 2):
                out.append(_synthetic_sample(backend, m, k, b, chunk=chunk))
    return out


def _patch_synthetic_clock(monkeypatch):
    """Replace autotune's wall-clock candidate timer with the exact
    SYNTH model — deterministic, so winner comparisons can't flake."""
    calls = []

    def fake_time(be, spec, p, params, x, k, reps):
        b = x.shape[0]
        m = params["scales"].shape[0]
        d = dispatch.plan_d(spec, m, k)
        feats = pm.features(be.name, spec.mode, d, spec.scale_block,
                            m, k, b, tm=p.tm, tj=p.tj, tb=p.tb,
                            consume_chunk=p.consume_chunk,
                            acc_in_vmem=p.acc_in_vmem)
        calls.append(p)
        return sum(SYNTH[n] * feats[n] for n in pm.CONSTANT_NAMES)

    monkeypatch.setattr(at, "_time_plan", fake_time)
    return calls


# ------------------------------------------------------------- features
def test_features_amortization_visible_to_model():
    """The model must price the legacy grid's per-m-tile re-produce —
    that asymmetry is what lets it rank acc_in_vmem correctly."""
    new = pm.features("msgemm_pallas", "msgemm", 3, 12, 2048, 768, 8,
                      tm=256, tj=128, tb=8, acc_in_vmem=True)
    legacy = pm.features("msgemm_pallas", "msgemm", 3, 12, 2048, 768, 8,
                         tm=256, tj=128, tb=8, acc_in_vmem=False)
    assert legacy["produce_s_per_flop"] == pytest.approx(
        8 * new["produce_s_per_flop"])  # nm = 2048/256
    assert legacy["hbm_s_per_byte"] > new["hbm_s_per_byte"]
    assert new["step_s"] == legacy["step_s"]


def test_predict_uncalibrated_falls_back():
    plan = dispatch.ExecPlan(backend="msgemm_pallas")
    c = pm.predict(plan, MS2, 64, 24, 8)
    assert c.t_total_s > 0 and not c.calibrated
    c2 = pm.predict(plan, MS2, 64, 24, 8, calib=SYNTH_CAL)
    assert c2.calibrated and c2.t_total_s > 0


# ---------------------------------------------------------- calibration
def test_calibration_fit_recovers_synthetic_constants():
    cal = pm.fit(_synthetic_grid(), device="cpu", interpret=True)
    assert cal.fit["n_samples"] == len(_synthetic_grid())
    # exact linear data -> near-exact fit
    assert cal.fit["max_abs_rel_err"] < 1e-6
    for s in _synthetic_grid()[:4]:
        assert pm.predict_sample(s, cal).t_total_s == pytest.approx(
            s.measured_s, rel=1e-6)


def test_calibration_roundtrip_identical_predictions(tmp_path):
    cal = pm.fit(_synthetic_grid(), device="cpu", interpret=True)
    path = tmp_path / "c.json"
    cal.save(path)
    assert pm.validate_calibration_file(path) == []
    loaded = pm.load_calibration(path, device="cpu", interpret=True)
    assert loaded is not None
    for s in _synthetic_grid():
        assert (pm.predict_sample(s, loaded).t_total_s
                == pm.predict_sample(s, cal).t_total_s)  # bitwise


def test_calibration_partition_and_staleness(tmp_path):
    cal = pm.fit(_synthetic_grid(), device="cpu", interpret=True)
    path = tmp_path / "c.json"
    cal.save(path)
    # wrong partition -> stale -> None
    assert pm.load_calibration(path, device="tpu", interpret=True) is None
    assert pm.load_calibration(path, device="cpu", interpret=False) is None
    assert pm.load_calibration(path, device="cpu", interpret=True)
    # corrupt / wrong version -> None + validator errors
    doc = json.loads(path.read_text())
    doc["version"] = 99
    path.write_text(json.dumps(doc))
    assert pm.load_calibration(path, device="cpu", interpret=True) is None
    assert pm.validate_calibration_file(path)
    path.write_text("{not json")
    assert pm.load_calibration(path, device="cpu", interpret=True) is None


def test_fit_requires_samples_in_partition():
    wrong = [_synthetic_sample("msgemm_jnp", 16, 24, 8, interpret=False)
             for _ in range(5)]
    with pytest.raises(ValueError, match="needs >= 3 samples"):
        pm.fit(wrong, device="cpu", interpret=True)


# ----------------------------------------------- model-guided autotune
def test_model_guided_matches_full_search_winner(monkeypatch, tmp_path):
    """On a shape grid, the model-guided sweep (<= MODEL_TOP_K measured)
    picks the same winner as the full sweep, and the full winner is
    always inside the model's predicted top-k — under a deterministic
    synthetic clock equal to the calibration's own ground truth."""
    device = at.registry.device_kind()
    cal = pm.Calibration(device=device, interpret=True,
                         constants={"*": SYNTH},
                         fit={"n_samples": 99}, created_unix=1.0)
    cal.save(tmp_path / "calib.json")
    calls = _patch_synthetic_clock(monkeypatch)
    # shapes chosen so the candidate grid is strictly larger than
    # MODEL_TOP_K (tiny shapes collapse to <= 3 candidates and the
    # model-guided path correctly degenerates to the full sweep)
    grid = [(256, 24, 64), (128, 48, 16), (64, 48, 8)]
    for m, k, b in grid:
        calls.clear()
        dispatch.set_cache_path(tmp_path / "full.json")
        full = at.autotune(MS2, m, k, b, "msgemm_pallas", interpret=True,
                           search="full")
        n_full = len(calls)
        calls.clear()
        dispatch.set_cache_path(tmp_path / "model.json")
        guided = at.autotune(MS2, m, k, b, "msgemm_pallas",
                             interpret=True, search="model")
        assert len(calls) <= at.MODEL_TOP_K < n_full
        assert guided == full
        # full winner sits inside the model's predicted top-k
        d = dispatch.plan_d(MS2, m, k)
        cands = at.candidate_plans(MS2, d, m, k, b, "msgemm_pallas",
                                   True)
        base = dispatch.heuristic_plan(
            MS2, d, m, k, b, "msgemm_pallas",
            dispatch.ExecPolicy(interpret=True))
        kept = at._model_prune(cands, MS2, d, m, k, b, "msgemm_pallas",
                               base, cal)
        assert dataclasses_replace_nosrc(full) in {
            dataclasses_replace_nosrc(p) for p in kept}
    snap = obs.registry().snapshot()
    pruned = [c for c in snap["counters"]
              if c["name"] == "dispatch_autotune_model_pruned_total"]
    assert pruned and pruned[0]["value"] > 0


def dataclasses_replace_nosrc(p):
    import dataclasses

    return dataclasses.replace(p, interpret=None, source="x")


def test_full_search_bypasses_model(monkeypatch, tmp_path):
    device = at.registry.device_kind()
    pm.Calibration(device=device, interpret=True, constants={"*": SYNTH},
                   fit={"n_samples": 9},
                   created_unix=1.0).save(tmp_path / "calib.json")
    calls = _patch_synthetic_clock(monkeypatch)
    at.autotune(MS2, 256, 24, 64, "msgemm_pallas", interpret=True,
                search="full")
    assert len(calls) > at.MODEL_TOP_K
    snap = obs.registry().snapshot()
    assert not [c for c in snap["counters"]
                if c["name"] == "dispatch_autotune_model_pruned_total"]


def test_model_search_falls_back_without_calibration(monkeypatch,
                                                     tmp_path):
    # REPRO_CALIBRATION points at a missing file -> full sweep + counter
    calls = _patch_synthetic_clock(monkeypatch)
    at.autotune(MS2, 256, 24, 64, "msgemm_pallas", interpret=True,
                search="model")
    assert len(calls) > at.MODEL_TOP_K
    snap = obs.registry().snapshot()
    fb = [c for c in snap["counters"]
          if c["name"] == "dispatch_autotune_model_fallback_total"]
    assert fb and fb[0]["value"] == 1


def test_timings_rows_carry_partition_tags(monkeypatch):
    _patch_synthetic_clock(monkeypatch)
    at.autotune(MS2, 16, 24, 8, "msgemm_jnp", interpret=True,
                search="full")
    key = next(iter(at.cache()._timings))
    rows = at.cache().timings(key)
    assert rows
    for r in rows:
        assert r["interpret"] is True
        assert r["device"] == at.registry.device_kind()


def test_samples_from_plan_cache_skips_untagged(monkeypatch, tmp_path):
    _patch_synthetic_clock(monkeypatch)
    at.autotune(MS2, 16, 24, 8, "msgemm_jnp", interpret=True,
                search="full")
    path = at.cache().path
    doc = json.loads(path.read_text())
    key = next(iter(doc["timings"]))
    legacy_row = dict(doc["timings"][key][0])
    legacy_row.pop("interpret")
    legacy_row.pop("device")
    doc["timings"][key].append(legacy_row)  # a pre-tag row
    doc.pop("crc", None)  # hand-edited: drop the stamp, legacy-style load
    path.write_text(json.dumps(doc))
    samples, untagged = pm.samples_from_plan_cache(path)
    assert untagged == 1
    assert len(samples) == len(doc["timings"][key]) - 1
    assert all(s.interpret for s in samples)


# ------------------------------------------------------------- sentinel
def test_sentinel_passes_clean_and_flags_injected_regression():
    cal = pm.fit(_synthetic_grid(), device="cpu", interpret=True)
    clean = pm.check_regressions(_synthetic_grid(), cal)
    assert clean["ok"] and clean["n_outliers"] == 0
    assert clean["n_samples"] == len(_synthetic_grid())

    slowed = _synthetic_grid()
    bad = _synthetic_sample("msgemm_pallas", 16, 24, 8,
                            scale=10 * pm.DEFAULT_TOLERANCE)
    slowed.append(bad)
    report = pm.check_regressions(slowed, cal)
    assert not report["ok"] and report["n_outliers"] == 1
    # ranked: the regression is row 0
    assert report["rows"][0]["outlier"]
    assert report["rows"][0]["source"] == bad.source
    text = pm.render_report(report)
    assert "REGRESSION" in text and "OUTLIER" in text


def test_sentinel_skips_other_partition_and_fast_rows_pass():
    cal = pm.fit(_synthetic_grid(), device="cpu", interpret=True)
    mixed = [_synthetic_sample("msgemm_jnp", 16, 24, 8, interpret=False),
             _synthetic_sample("msgemm_jnp", 16, 24, 8, scale=0.01)]
    report = pm.check_regressions(mixed, cal)
    assert report["ok"]
    assert report["n_skipped_other_partition"] == 1
    assert report["n_fast"] == 1  # faster than predicted never fails


# ------------------------------------------------------------------ CLI
def test_obs_cli_calibrate_and_check_regressions(monkeypatch, tmp_path,
                                                 capsys):
    from repro.obs.__main__ import main as obs_main

    _patch_synthetic_clock(monkeypatch)
    for m, k, b in [(16, 24, 8), (64, 24, 8), (32, 48, 16)]:
        at.autotune(MS2, m, k, b, "msgemm_jnp", interpret=True,
                    search="full")
    cache_path = str(at.cache().path)
    calib = str(tmp_path / "cli_calib.json")
    assert obs_main(["--calibrate", "--plan-cache", cache_path,
                     "--calibration", calib]) == 0
    assert obs_main(["--validate-calibration", calib]) == 0
    report = str(tmp_path / "report.md")
    assert obs_main(["--check-regressions", "--plan-cache", cache_path,
                     "--calibration", calib, "--report-out",
                     report]) == 0
    assert "verdict: OK" in open(report).read()
    # inject a slowdown -> exit 1
    doc = json.loads(open(cache_path).read())
    key = next(iter(doc["timings"]))
    doc["timings"][key][0]["s"] *= 100 * pm.DEFAULT_TOLERANCE
    doc.pop("crc", None)  # hand-edited: drop the stamp, legacy-style load
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(doc))
    capsys.readouterr()
    assert obs_main(["--check-regressions", "--plan-cache", str(slow),
                     "--calibration", calib]) == 1
    assert "OUTLIER" in capsys.readouterr().out
