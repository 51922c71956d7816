"""ISSUE 16 sensor plane: workload fingerprints sampled at drain
points, drift detection with bounded detect lag and zero false
positives on stable streams, the per-stage cost model fit from the
checked-in bench corpus, and the obs drift/trend/costmodel CLIs."""

import json
import os

import numpy as np
import pytest

from scotty_tpu.obs import (
    COSTMODEL_RESIDUAL_PCT,
    RESIDUAL_BOUND_PCT,
    WORKLOAD_AUDITS,
    WORKLOAD_DRIFT_EVENTS,
    CostModel,
    DriftDetector,
    HealthPolicy,
    Observability,
    WorkloadFingerprint,
    WorkloadMonitor,
    feature_gauge,
)
from scotty_tpu.obs import costmodel as cm
from scotty_tpu.obs.device import LATE_AGE_EDGES_MS, late_bucket_names
from scotty_tpu.obs.diff import _cells
from scotty_tpu.obs.drift import (
    DEFAULT_DRIFT_THRESHOLDS,
    compare_features,
    load_fingerprint,
)
from scotty_tpu.obs.report import main as obs_main
from scotty_tpu.obs.trend import build_trend
from scotty_tpu.obs.workload import _late_age_p50
from scotty_tpu.resilience.clock import ManualClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "bench_results")


# ---------------------------------------------------------------------------
# monitor plumbing
# ---------------------------------------------------------------------------


def _mk_monitor(**kw):
    obs = Observability()
    clock = ManualClock()
    mon = obs.attach_workload(
        WorkloadMonitor(clock=clock, audit_interval_s=1.0, **kw))
    return obs, clock, mon


def _second(obs, clock, mon, n_in=1000, n_late=0, key_loads=None,
            late_buckets=None):
    """Simulate one second of stream telemetry then hit the drain point."""
    obs.counter("ingest_tuples").inc(n_in)
    if n_late:
        obs.counter("late_tuples").inc(n_late)
    for name, c in (late_buckets or {}).items():
        obs.counter(name).inc(c)
    if key_loads is not None:
        mon.observe_key_loads(key_loads)
    clock.advance(1.0)
    obs.flight_sync()


def test_monitor_arms_then_audits_per_window():
    obs, clock, mon = _mk_monitor()
    _second(obs, clock, mon, n_in=500)          # arms the first window
    assert mon.audits == 0
    _second(obs, clock, mon, n_in=1000)
    assert mon.audits == 1
    feats = mon.features()
    assert feats["arrival_rate_per_s"] == pytest.approx(1000.0)
    assert feats["late_share"] == 0.0
    # features double as workload_<feature> gauges + the audit counter
    reg = obs.registry
    assert reg.gauges[feature_gauge("arrival_rate_per_s")].value \
        == pytest.approx(1000.0)
    assert reg.counters[WORKLOAD_AUDITS].value == 1.0


def test_sub_interval_samples_are_cheap_no_audit():
    obs, clock, mon = _mk_monitor()
    _second(obs, clock, mon)                    # arm
    obs.counter("ingest_tuples").inc(100)
    clock.advance(0.25)                         # inside the audit window
    obs.flight_sync()
    assert mon.audits == 0                      # clock read only, no fold


def test_flight_sync_samples_without_flight_recorder():
    # the workload sample must run even with NO flight ring attached —
    # flight_sync is the drain-point hook, not a flight-only path
    obs, clock, mon = _mk_monitor()
    assert obs.flight is None
    _second(obs, clock, mon)
    _second(obs, clock, mon)
    assert mon.audits == 1


def test_fingerprint_in_export_and_roundtrip():
    obs, clock, mon = _mk_monitor()
    _second(obs, clock, mon)
    _second(obs, clock, mon, n_in=2000)
    out = obs.export()
    fp = out["fingerprint"]
    assert fp["schema"] == "scotty_tpu.workload/1"
    assert fp["audits"] == 1
    assert fp["features"]["arrival_rate_per_s"] == pytest.approx(2000.0)
    rt = WorkloadFingerprint.from_dict(json.loads(json.dumps(fp)))
    assert rt.features == pytest.approx(fp["features"])
    # flat-gauge fallback reconstruction (exports without the section)
    flat = {feature_gauge("arrival_rate_per_s"): 2000.0,
            feature_gauge("late_share"): 0.25, WORKLOAD_AUDITS: 7}
    fp2 = WorkloadFingerprint.from_flat_metrics(flat)
    assert fp2.features == {"arrival_rate_per_s": 2000.0,
                            "late_share": 0.25}
    assert fp2.audits == 7


def test_late_age_p50_walks_the_strata():
    names = late_bucket_names()
    # all mass in the first bucket -> its upper edge
    assert _late_age_p50({names[0]: 10.0}) == float(LATE_AGE_EDGES_MS[0])
    # median lands in the second bucket
    assert _late_age_p50({names[0]: 2.0, names[1]: 8.0}) \
        == float(LATE_AGE_EDGES_MS[1])
    # all mass overflow -> the conservative 2x last edge
    assert _late_age_p50({names[-1]: 5.0}) \
        == float(2 * LATE_AGE_EDGES_MS[-1])
    assert _late_age_p50({}) == 0.0


def test_monitor_folds_late_age_from_device_strata():
    obs, clock, mon = _mk_monitor()
    names = late_bucket_names()
    _second(obs, clock, mon)
    obs.counter("device_ingest_tuples").inc(1000)
    obs.counter("device_late_tuples").inc(100)
    _second(obs, clock, mon, n_in=0,
            late_buckets={names[2]: 60, names[0]: 40})
    feats = mon.features()
    assert feats["late_share"] == pytest.approx(0.1)
    assert feats["late_age_p50_ms"] == float(LATE_AGE_EDGES_MS[2])


def test_key_skew_features_from_load_histogram():
    obs, clock, mon = _mk_monitor(top_k=8)
    _second(obs, clock, mon, key_loads=np.ones(64))
    _second(obs, clock, mon, key_loads=np.ones(64))
    feats = mon.features()
    assert feats["key_top_share"] == pytest.approx(8 / 64)
    assert feats["key_entropy"] == pytest.approx(1.0)
    skew = np.ones(64)
    skew[0] = 64 * 4                           # one key owns ~80%
    _second(obs, clock, mon, key_loads=skew)
    feats = mon.features()
    assert feats["key_top_share"] > 0.8
    assert feats["key_entropy"] < 0.5


# ---------------------------------------------------------------------------
# drift detection: injections + bounded detect lag, zero false positives
# ---------------------------------------------------------------------------


def _with_detector(**det_kw):
    obs, clock, mon = _mk_monitor()
    det = DriftDetector(**det_kw)
    mon.attach_detector(det)
    return obs, clock, mon, det


def test_rate_shift_detected_within_bounded_window():
    obs, clock, mon, det = _with_detector()
    for _ in range(6):                          # arm + baseline + stable
        _second(obs, clock, mon, n_in=1000)
    assert det.events == 0
    shift_audit = mon.audits + 1
    for _ in range(4):
        _second(obs, clock, mon, n_in=8000)
    fired = {f["feature"]: f["audit"] for f in det.fired}
    assert "arrival_rate_per_s" in fired
    # confirm=2 hysteresis: detected within <= 4 audit windows of onset
    assert fired["arrival_rate_per_s"] - shift_audit + 1 <= 4
    assert obs.registry.counters[WORKLOAD_DRIFT_EVENTS].value \
        == float(det.events)


def test_lateness_storm_detected():
    obs, clock, mon, det = _with_detector()
    for _ in range(6):
        _second(obs, clock, mon, n_in=1000)
    assert det.events == 0
    storm_audit = mon.audits + 1
    for _ in range(4):
        _second(obs, clock, mon, n_in=1000, n_late=300)
    fired = {f["feature"]: f["audit"] for f in det.fired}
    assert "late_share" in fired
    assert fired["late_share"] - storm_audit + 1 <= 4


def test_key_skew_flip_detected():
    obs, clock, mon, det = _with_detector()
    uniform = np.ones(64)
    skew = np.ones(64)
    skew[0] = 64 * 4
    for _ in range(6):
        _second(obs, clock, mon, key_loads=uniform)
    assert det.events == 0
    flip_audit = mon.audits + 1
    for _ in range(4):
        _second(obs, clock, mon, key_loads=skew)
    fired = {f["feature"]: f["audit"] for f in det.fired}
    assert "key_top_share" in fired and "key_entropy" in fired
    assert fired["key_top_share"] - flip_audit + 1 <= 4


def test_stable_stream_fires_zero_false_positives():
    obs, clock, mon, det = _with_detector()
    rng = np.random.default_rng(7)
    for _ in range(60):                         # long stable arm, jittered
        n = int(1000 * (1.0 + rng.uniform(-0.05, 0.05)))
        _second(obs, clock, mon, n_in=n, key_loads=np.ones(64))
    assert det.events == 0
    assert WORKLOAD_DRIFT_EVENTS not in obs.registry.counters


def test_drift_latch_fires_once_then_rearms():
    det = DriftDetector(reference={"late_share": 0.0}, confirm=2)
    audits = [0.0] * 4 + [0.4] * 6 + [0.0] * 4 + [0.4] * 3
    fired = []
    for v in audits:
        fired += det.observe({"late_share": v})
    # one event per sustained excursion, re-armed by the in-band gap
    assert fired == ["late_share", "late_share"]
    assert det.events == 2


def test_explicit_reference_judges_immediately():
    ref = WorkloadFingerprint(features={"arrival_rate_per_s": 1000.0})
    det = DriftDetector(reference=ref, confirm=1)
    assert det.observe({"arrival_rate_per_s": 1050.0}) == []
    assert det.observe({"arrival_rate_per_s": 9000.0}) \
        == ["arrival_rate_per_s"]


def test_compare_features_judges_shared_set_only():
    findings = compare_features(
        {"late_share": 0.0, "fill_ratio": 0.9},
        {"late_share": 0.3, "key_entropy": 0.2})
    assert [f["feature"] for f in findings] == ["late_share"]
    assert findings[0]["drifted"]
    for feature in DEFAULT_DRIFT_THRESHOLDS:
        assert set(DEFAULT_DRIFT_THRESHOLDS[feature]) \
            <= {"rel_tol", "abs_tol"}


def test_healthz_drift_check_probes_new_events():
    obs, clock, mon, det = _with_detector()
    policy = HealthPolicy()
    # no drift counter yet -> the check must not appear (runs without a
    # detector keep their exact verdict shape)
    assert "workload_drift" not in policy.verdict(obs)["checks"]
    for _ in range(6):
        _second(obs, clock, mon, n_in=1000)
    for _ in range(4):
        _second(obs, clock, mon, n_in=9000)
    v = policy.verdict(obs)
    chk = v["checks"]["workload_drift"]
    assert chk["new_since_last_probe"] >= 1 and not chk["ok"]
    assert not v["healthy"]
    # next probe with no NEW events: healthy again (edge-triggered)
    v2 = policy.verdict(obs)
    assert v2["checks"]["workload_drift"]["ok"]


def test_keyed_connector_counts_late_tuples():
    from scotty_tpu.connectors.base import (AscendingWatermarks,
                                            KeyedScottyWindowOperator)
    from scotty_tpu.core.aggregates import SumAggregation
    from scotty_tpu.core.windows import TumblingWindow, WindowMeasure

    obs = Observability()
    op = KeyedScottyWindowOperator(
        windows=[TumblingWindow(WindowMeasure.Time, 100)],
        aggregations=[SumAggregation()], allowed_lateness=500,
        watermark_policy=AscendingWatermarks(), obs=obs)
    for ts in (10, 200, 400, 900):
        op.process_element("k", 1.0, ts)
    assert obs.registry.counters.get("late_tuples") is None or \
        obs.registry.counters["late_tuples"].value == 0.0
    op.process_element("k", 1.0, 450)          # below wm, within lateness
    assert obs.registry.counters["late_tuples"].value == 1.0


# ---------------------------------------------------------------------------
# cost model: synthetic round-trips + the checked-in corpus
# ---------------------------------------------------------------------------


def _flat(rate_mtps, **targets):
    flat = {"tuples_per_sec": rate_mtps * 1e6}
    for target, ms in targets.items():
        flat[f"{target}_mean"] = ms
        flat[f"{target}_count"] = 5
    return flat


def test_costmodel_fit_recovers_affine_law():
    cells = [_flat(r, sync_ms=2.0 + 3.0 * r) for r in (1.0, 2.0, 4.0)]
    model = cm.fit(cells)
    law = model.laws["sync_ms"]
    assert law["fit_residual_pct"] < 0.5
    assert model.predict(8.0)["sync_ms"] == pytest.approx(26.0, rel=1e-6)


def test_costmodel_fit_recovers_reciprocal_law():
    # tuples-per-interval physics: interval_step_ms * rate ~ constant
    cells = [_flat(r, interval_step_ms=1.0 + 240.0 / r)
             for r in (10.0, 20.0, 40.0, 60.0)]
    model = cm.fit(cells)
    law = model.laws["interval_step_ms"]
    assert law["per_inv_mtuple_s"] == pytest.approx(240.0, rel=1e-3)
    assert law["fit_residual_pct"] < 0.5
    # held-out rate round-trips through the reciprocal term
    assert model.predict(30.0)["interval_step_ms"] \
        == pytest.approx(9.0, rel=1e-3)


def test_costmodel_single_rate_degrades_to_intercept():
    cells = [_flat(2.0, sync_ms=7.0), _flat(2.0, sync_ms=9.0)]
    law = cm.fit(cells).laws["sync_ms"]
    assert law["per_mtuple_s"] == 0.0
    assert law["intercept"] == pytest.approx(8.0)


def test_costmodel_live_residual_and_drift_feature():
    model = CostModel(laws={"interval_step_ms": {
        "intercept": 0.0, "per_mtuple_s": 0.0,
        "per_inv_mtuple_s": 2000.0, "n_cells": 4,
        "fit_residual_pct": 0.0}})
    feats = {"arrival_rate_per_s": 50e6}       # 50 Mt/s -> 40 ms predicted
    assert model.predict_interval_ms(feats) == pytest.approx(40.0)
    assert model.residual_pct(feats, 40.0) == pytest.approx(0.0)
    assert model.residual_pct(feats, 60.0) == pytest.approx(50.0)
    assert model.residual_pct(feats, None) is None
    # riding the monitor: residual lands in the gauge + the feature set
    obs, clock, mon = _mk_monitor()
    mon.attach_costmodel(model)
    det = DriftDetector(reference={"arrival_rate_per_s": 50e6,
                                   "costmodel_residual_pct": 0.0},
                        confirm=1)
    mon.attach_detector(det)
    _second(obs, clock, mon)
    obs.counter("ingest_tuples").inc(50_000_000)
    obs.histogram("interval_step_ms").observe(80.0)  # 2x the prediction
    clock.advance(1.0)
    obs.flight_sync()
    assert obs.registry.gauges[COSTMODEL_RESIDUAL_PCT].value \
        == pytest.approx(100.0)
    assert any(f["feature"] == "costmodel_residual_pct"
               for f in det.fired)


def test_costmodel_corpus_leave_one_out_within_bound():
    """The sliding-count family (4 cells, one window shape, 4 rates) is
    the corpus regime the reciprocal law models: each held-out cell's
    interval_step_ms must predict within the stated residual bound."""
    flats = list(_cells(os.path.join(
        RESULTS, "result_sliding-count.json")).values())
    usable = [f for f in flats
              if cm._cell_rate_mtps(f)
              and "interval_step_ms" in cm._cell_observations(f)]
    assert len(usable) >= 4
    for i, held in enumerate(usable):
        model = cm.fit(usable[:i] + usable[i + 1:])
        rate = cm._cell_rate_mtps(held)
        observed = cm._cell_observations(held)["interval_step_ms"]
        predicted = model.predict(rate)["interval_step_ms"]
        residual = 100.0 * abs(predicted - observed) / observed
        assert residual <= RESIDUAL_BOUND_PCT, \
            f"cell {i}: {residual:.1f}% > {RESIDUAL_BOUND_PCT}%"


def test_costmodel_drain_ownership_matches_pr13_attribution():
    """The PR 13 stage-stamped lineage put drain_fetch at 67-71 ms of
    the ~70.8 ms first-emit anchor; the fitted decomposition must
    reproduce that ownership from the checked-in headline cell."""
    path = os.path.join(RESULTS, "result_latency-headline.json")
    (flat,) = _cells(path).values()
    drain_p99 = flat["latency_stage_drain_ms_p99"]
    fe_p99 = flat["latency_first_emit_ms_p99"]
    assert 66.0 <= drain_p99 <= 72.0
    assert drain_p99 >= 0.90 * fe_p99          # drain owns the anchor
    model = cm.fit_paths([path])
    rate = cm._cell_rate_mtps(flat)
    grouped = model.grouped(rate)
    assert grouped["drain_fetch"] == \
        pytest.approx(flat["latency_stage_drain_ms_mean"], rel=1e-6)
    # drain_fetch dominates every other PROCESSING group (generator_lift
    # carries the eligibility stage — event-time slack waiting for the
    # watermark, not work on the 70.8 ms first-emit critical path)
    others = sum(ms for g, ms in grouped.items()
                 if g not in ("drain_fetch", "generator_lift"))
    assert grouped["drain_fetch"] > others


# ---------------------------------------------------------------------------
# CLIs: obs drift / trend / costmodel exit codes
# ---------------------------------------------------------------------------


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)
    return str(path)


def test_obs_drift_cli_exit_codes(tmp_path):
    base = _write(tmp_path / "base.json",
                  {"schema": "scotty_tpu.workload/1", "audits": 5,
                   "features": {"arrival_rate_per_s": 1000.0,
                                "late_share": 0.0}})
    same = _write(tmp_path / "same.json",
                  {"schema": "scotty_tpu.workload/1", "audits": 5,
                   "features": {"arrival_rate_per_s": 1040.0,
                                "late_share": 0.0}})
    moved = _write(tmp_path / "moved.json",
                   {"schema": "scotty_tpu.workload/1", "audits": 5,
                    "features": {"arrival_rate_per_s": 9000.0,
                                 "late_share": 0.4}})
    bare = _write(tmp_path / "bare.json", {"not": "a fingerprint"})
    assert obs_main(["drift", base, same]) == 0
    assert obs_main(["drift", base, moved]) == 1
    assert obs_main(["drift", base, bare]) == 2


def test_load_fingerprint_from_recorded_cell():
    fp = load_fingerprint(os.path.join(
        RESULTS, "result_workload-drift.json"))
    assert fp is not None
    assert fp.features["arrival_rate_per_s"] > 0
    assert fp.audits > 0


def test_obs_trend_reconstructs_rounds_and_exit_codes(tmp_path):
    # five round records in the driver's shape, each faster than the last
    paths = [_write(tmp_path / f"BENCH_r{n:02d}.json",
                    {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
                     "parsed": {"metric": "tuples_per_sec",
                                "value": 1e9 * n,
                                "p99_window_emit_ms": 200.0 - 10 * n,
                                "rtt_floor_ms": 50.0}})
             for n in range(1, 6)]
    trend = build_trend(paths=paths, results_dir=RESULTS)
    assert [r["round"] for r in trend["rounds"]] \
        == sorted(r["round"] for r in trend["rounds"])
    assert len(trend["rounds"]) >= 5
    assert trend["transitions"], "no judged transitions"
    # the checked-in trajectory is clean under the obs-diff thresholds
    assert all(t["status"] == "ok" for t in trend["transitions"])
    assert obs_main(["trend", *paths, "--results", RESULTS]) == 0
    # a synthetic regressed round must flag + exit 1
    r1 = _write(tmp_path / "BENCH_r90.json",
                {"n": 90, "parsed": {"metric": "tuples_per_sec",
                                     "value": 1_000_000.0,
                                     "p99_window_emit_ms": 10.0}})
    r2 = _write(tmp_path / "BENCH_r91.json",
                {"n": 91, "parsed": {"metric": "tuples_per_sec",
                                     "value": 400_000.0,
                                     "p99_window_emit_ms": 40.0}})
    assert obs_main(["trend", r1, r2]) == 1
    # no parseable rounds
    junk = _write(tmp_path / "BENCH_r99.json", {"no": "parsed"})
    assert obs_main(["trend", junk]) == 2


def test_obs_costmodel_cli_fit_predict_exit_codes(tmp_path):
    corpus = os.path.join(RESULTS, "result_sliding-count.json")
    model_path = str(tmp_path / "model.json")
    assert obs_main(["costmodel", "fit", corpus, "-o", model_path]) == 0
    model = CostModel.load(model_path)
    assert model.schema == cm.COSTMODEL_SCHEMA
    assert "interval_step_ms" in model.laws
    # predicting the fit corpus stays within the stated bound
    assert obs_main(["costmodel", "predict", model_path, corpus]) == 0
    # a cell far outside the fitted regime blows the headline residual
    blown = _write(tmp_path / "blown.json",
                   [{"name": "x", "windows": "w", "engine": "e",
                     "aggregation": "sum", "tuples_per_sec": 50e6,
                     "metrics": {"metrics": {
                         "interval_step_ms_mean": 4000.0,
                         "interval_step_ms_count": 5}}}])
    assert obs_main(["costmodel", "predict", model_path, blown]) == 1
    # no usable cells on either side -> 2
    empty = _write(tmp_path / "empty.json", [])
    assert obs_main(["costmodel", "fit", empty]) == 2
    assert obs_main(["costmodel", "predict", model_path, empty]) == 2


def test_workload_drift_cell_detects_all_phases(monkeypatch):
    """The bench cell end-to-end at a miniature rate: 3 transitions
    detected, stable arm clean, extras present on the result (the
    aligned-pipeline overhead arm is stubbed — its compile cost belongs
    to the recorded cell, not the unit suite)."""
    from scotty_tpu.bench import runner
    from scotty_tpu.bench.harness import BenchmarkConfig

    monkeypatch.setattr(runner, "measure_workload_overhead",
                        lambda **kw: 0.0)
    cfg = BenchmarkConfig(name="wd-mini", throughput=256,
                          watermark_period_ms=1000, max_lateness=4000,
                          n_keys=16, seed=3)
    res = runner.run_cell(cfg, "Tumbling(1000)", "sum", "WorkloadDrift")
    assert res.drift_all_detected is True
    assert res.drift_false_positives == 0
    assert set(res.drift_detect_lags) \
        == {"rate_x8", "late_storm", "key_skew"}
    assert all(0 < lag <= 4 for lag in res.drift_detect_lags.values())
    assert res.metrics["fingerprint"]["features"]
    assert res.n_tuples > 0 and res.n_windows_emitted > 0


def test_recorded_drift_cell_acceptance_artifact():
    """The checked-in workload-drift cell must carry the acceptance
    evidence: all 3 phase transitions detected within the bounded
    window, zero stable-arm false positives, sensor-plane A/B within
    the 2% overhead band."""
    path = os.path.join(RESULTS, "result_workload-drift.json")
    with open(path) as f:
        (cell,) = json.load(f)
    assert cell["drift_all_detected"] is True
    assert cell["drift_false_positives"] == 0
    lags = cell["drift_detect_lags"]
    assert set(lags) == {"rate_x8", "late_storm", "key_skew"}
    assert all(0 < lag <= 4 for lag in lags.values())
    assert cell["workload_overhead_pct_median"] <= 2.0
    assert cell["metrics"]["fingerprint"]["features"]
