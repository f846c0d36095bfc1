"""Attribution + trend-gate tests (ISSUE 13): the checked-in synthetic
trace fixture pinned to its generator, scope-tree reconstruction and the
busy/idle split over crafted timelines, the flops join (both roofline
branches, unknown-device degradation), the ≥90% coverage floor, fusion
ranking, driver-wrapper unwrapping (parsed / tail / truncated-tail /
garbage), the regression gate over the committed series and over injected
tmp series, the thinning + delta-annotation helpers fid_trend rides, the
run_meta provenance stamp, and the GRAFT-A004 host-only contract for both
new modules."""

import gzip
import json
import os
import re

import pytest

from ddim_cold_tpu.analysis import ast_checks
from ddim_cold_tpu.obs import attrib, trend
from ddim_cold_tpu.utils import flops as flops_util
from ddim_cold_tpu.utils.record import run_metadata

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "attrib_trace.json")


# ---------------------------------------------------------------------------
# fixture + loading
# ---------------------------------------------------------------------------

def test_fixture_pinned_to_generator():
    """The checked-in trace IS synthetic_demo_trace() — fixture drift (edit
    one without the other) is a hard failure, so --demo, the CPU bench
    fallback, and these tests always attribute the same timeline."""
    with open(FIXTURE) as f:
        on_disk = json.load(f)
    assert on_disk == attrib.synthetic_demo_trace()


def test_load_trace_dict_passthrough_and_validation():
    t = attrib.synthetic_demo_trace()
    assert attrib.load_trace(t) is t
    with pytest.raises(attrib.AttribError):
        attrib.load_trace({"no_events": []})


def test_load_trace_file_and_gz(tmp_path):
    t = attrib.synthetic_demo_trace()
    plain = tmp_path / "t.trace.json"
    plain.write_text(json.dumps(t))
    assert attrib.load_trace(str(plain)) == t
    gz = tmp_path / "t.trace.json.gz"
    with gzip.open(gz, "wt") as f:
        json.dump(t, f)
    assert attrib.load_trace(str(gz)) == t
    bad = tmp_path / "bad.json"
    bad.write_text("not json at all {")
    with pytest.raises(attrib.AttribError):
        attrib.load_trace(str(bad))


def test_load_trace_profiler_dir_layout(tmp_path):
    """The jax.profiler on-disk shape: plugins/profile/<run>/<host>.trace
    .json.gz, newest run wins, per-host dumps merge."""
    old = tmp_path / "plugins" / "profile" / "2026_01_01"
    new = tmp_path / "plugins" / "profile" / "2026_02_02"
    for d in (old, new):
        d.mkdir(parents=True)
    with gzip.open(old / "h.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [{"ph": "M", "name": "stale"}]}, f)
    t = attrib.synthetic_demo_trace()
    half = len(t["traceEvents"]) // 2
    with gzip.open(new / "a.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": t["traceEvents"][:half]}, f)
    with gzip.open(new / "b.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": t["traceEvents"][half:]}, f)
    merged = attrib.load_trace(str(tmp_path))
    assert len(merged["traceEvents"]) == len(t["traceEvents"])
    assert not any(e.get("name") == "stale" for e in merged["traceEvents"])
    with pytest.raises(attrib.AttribError):
        attrib.load_trace(str(tmp_path / "plugins"))  # no dumps below here


# ---------------------------------------------------------------------------
# scope matching + interval arithmetic
# ---------------------------------------------------------------------------

def test_scope_chain_orders_by_text_position():
    ev = {"name": "fusion.3", "args": {"long_name":
          "jit(f)/sampler/model/flash_attention/fwd/flash_fwd"}}
    assert attrib.scope_chain(ev) == ("sampler/model", "flash_attention/fwd")
    # bare op: the scope path is the event name itself
    assert attrib.scope_chain(
        {"name": "jit(f)/sampler/cached_step/select_n"}) == (
        "sampler/cached_step",)
    assert attrib.scope_chain({"name": "copy.1"}) == ()


def test_merged_busy_overlap_union():
    # [0,100] ∪ [50,150] ∪ [200,250] → 200µs busy over two merged spans
    busy, merged = attrib._merged_busy([(0, 100), (50, 150), (200, 250)])
    assert busy == pytest.approx(200e-6)
    assert merged == [[0, 150], [200, 250]]
    assert attrib._merged_busy([]) == (0.0, [])


def _crafted(events):
    meta = [{"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}}]
    return {"traceEvents": meta + events}


def test_busy_idle_split_arithmetic():
    t = _crafted([
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "jit(f)/sampler/model/dot"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 50, "dur": 100,
         "name": "jit(f)/sampler/model/dot2"},  # overlaps: no double count
        {"ph": "X", "pid": 1, "tid": 1, "ts": 200, "dur": 50,
         "name": "copy.1"},  # busy but unattributed
    ])
    rep = attrib.attribute(t)
    assert rep["device_lanes"] == 1
    assert rep["window_s"] == pytest.approx(250e-6)
    assert rep["device_busy_s"] == pytest.approx(200e-6)
    assert rep["idle_s"] == pytest.approx(50e-6)
    assert rep["busy_fraction"] == pytest.approx(0.8)
    assert rep["coverage"] == pytest.approx(150e-6 / 200e-6)
    node = rep["scopes"]["sampler/model"]
    assert node["events"] == 2
    assert node["self_s"] == pytest.approx(200e-6)  # per-event durations sum


def test_lane_selection_ignores_hosts_and_module_lanes():
    """The demo trace carries a /host:CPU shadow lane with identical
    timings; a second device lane with no scope names (the XLA Modules
    plane) must lose to the op lane rather than double busy time."""
    t = attrib.synthetic_demo_trace()
    t["traceEvents"].append({"ph": "M", "pid": 1, "tid": 7,
                             "name": "thread_name",
                             "args": {"name": "XLA Modules"}})
    t["traceEvents"] += [{"ph": "X", "pid": 1, "tid": 7, "ts": 1000,
                          "dur": 4000, "name": "jit(ddim_sample)"}]
    rep = attrib.attribute(t)
    assert rep["device_lanes"] == 1
    base = attrib.attribute(attrib.synthetic_demo_trace())
    assert rep["device_busy_s"] == base["device_busy_s"]


def test_scope_tree_reconstruction():
    rep = attrib.demo_report()
    assert rep["tree"] == {"sampler/model":
                           ["dequant_matmul/pallas", "flash_attention/fwd"]}
    model = rep["scopes"]["sampler/model"]
    # inclusive total covers the nested flash + dequant events too
    assert model["total_s"] > model["self_s"]
    flash = rep["scopes"]["flash_attention/fwd"]
    assert flash["total_s"] == pytest.approx(flash["self_s"])


# ---------------------------------------------------------------------------
# flops join + coverage + fusion
# ---------------------------------------------------------------------------

def test_flops_join_both_roofline_branches():
    rep = attrib.demo_report()
    ridge = flops_util.ridge_flops_per_byte(attrib.DEMO_DEVICE_KIND)
    assert rep["ridge_flops_per_byte"] == pytest.approx(ridge, abs=0.1)
    flash = rep["scopes"]["flash_attention/fwd"]
    assert flash["flops_per_byte"] >= ridge
    assert flash["roofline"] == "compute-bound"
    model = rep["scopes"]["sampler/model"]
    assert model["flops_per_byte"] < ridge
    assert model["roofline"] == "hbm-bound"
    # demo MFU lands in the measured sampler range (PERF.md ~0.03–0.09)
    assert 0.03 <= model["mfu"] <= 0.09
    assert model["achieved_tflops"] == pytest.approx(
        model["flops"] / model["total_s"] / 1e12, rel=1e-3)
    # zero-flop comms scope: defined, not a divide-by-zero
    a2a = rep["scopes"]["sp/all_to_all_gather"]
    assert a2a["mfu"] == 0.0 and a2a["roofline"] == "hbm-bound"


def test_unknown_device_degrades_to_time_only():
    rep = attrib.attribute(attrib.synthetic_demo_trace(),
                           scope_costs=attrib.demo_scope_costs())
    assert rep["peak_bf16_tflops"] is None
    assert rep["ridge_flops_per_byte"] is None
    model = rep["scopes"]["sampler/model"]
    assert model["mfu"] is None and model["roofline"] is None
    assert model["achieved_tflops"] is not None  # flops need no peak


def test_coverage_meets_floor_and_drops_without_scopes():
    rep = attrib.demo_report()
    assert rep["coverage"] >= attrib.COVERAGE_FLOOR
    stripped = attrib.synthetic_demo_trace()
    for ev in stripped["traceEvents"]:
        ev.pop("args", None) if ev.get("ph") == "X" else None
    bare = attrib.attribute(stripped)
    assert (bare["coverage"] or 0.0) < attrib.COVERAGE_FLOOR
    assert bare["device_busy_s"] == rep["device_busy_s"]  # busy is scope-free


def test_fusion_candidates_ranked_and_gap_gated():
    rep = attrib.demo_report()
    cands = rep["fusion_candidates"]
    assert cands, "demo timeline has 5µs launch gaps — candidates expected"
    gaps = [c["total_gap_us"] for c in cands]
    assert gaps == sorted(gaps, reverse=True)
    top = cands[0]
    assert top["count"] == attrib._DEMO_STEPS
    assert top["mean_gap_us"] == pytest.approx(attrib._DEMO_GAP_US)
    # combined busy counts BOTH ops of the pair
    assert top["combined_busy_us"] > top["total_gap_us"]
    # a ceiling under the demo's launch gap empties the list
    assert attrib.demo_report(gap_us=1.0)["fusion_candidates"] == []


def test_ranked_scopes_slowest_first():
    rep = attrib.demo_report()
    ranked = attrib.ranked_scopes(rep)
    selfs = [node["self_s"] for _, node in ranked]
    assert selfs == sorted(selfs, reverse=True)
    assert ranked[0][0] == "sampler/model"


def test_registered_scopes_pinned_to_tree_call_sites():
    """Every registry entry is a literal profiling.scope(...) call in the
    tree — renaming a planted scope without updating the registry (or vice
    versa) breaks attribution silently otherwise."""
    pat = re.compile(r'profiling\.scope\("([^"]+)"\)')
    planted = set()
    for sub in ("ops", "parallel"):
        root = os.path.join(REPO, "ddim_cold_tpu", sub)
        for dirpath, _, names in os.walk(root):
            for n in names:
                if n.endswith(".py"):
                    with open(os.path.join(dirpath, n)) as f:
                        planted |= set(pat.findall(f.read()))
    assert set(attrib.REGISTERED_SCOPES) == planted


def test_vit_scope_costs_shape():
    costs = flops_util.vit_scope_costs(flash=True, quant=True)
    assert {"sampler/model", "flash_attention/fwd",
            "dequant_matmul/pallas"} <= set(costs)
    for c in costs.values():
        assert c["flops"] >= 0 and c["bytes"] > 0
    # nested scopes cost no more than the inclusive model forward
    assert costs["flash_attention/fwd"]["flops"] <= \
        costs["sampler/model"]["flops"]
    assert flops_util.vit_scope_costs().keys() == {"sampler/model"}


# ---------------------------------------------------------------------------
# trend: wrapper unwrapping + series loading
# ---------------------------------------------------------------------------

def test_unwrap_wrapper_variants():
    rec = {"value": 1.0, "chip": "TPU v5 lite"}
    assert trend.unwrap({"cmd": "x", "rc": 0, "tail": "noise",
                         "parsed": rec}) == (rec, None)
    tail = "log line\n" + json.dumps(rec) + "\n"
    got, note = trend.unwrap({"cmd": "x", "rc": 0, "tail": tail})
    assert got == rec and note is None
    got, note = trend.unwrap({"cmd": "x", "rc": 0,
                              "tail": 'truncated..."mfu": 0.05}'})
    assert got is None and "truncated" in note
    assert trend.unwrap(rec) == (rec, None)  # non-wrapper passthrough


def test_load_record_error_paths(tmp_path):
    garbage = tmp_path / "BENCH_r01.json"
    garbage.write_text("definitely { not json")
    with pytest.raises(trend.TrendError):
        trend.load_record(str(garbage))
    with pytest.raises(trend.TrendError):
        trend.load_record(str(tmp_path / "absent.json"))
    jsonl = tmp_path / "BENCH_r02.json"
    jsonl.write_text('junk\n{"value": 1}\n{"value": 2}\n')
    assert trend.load_record(str(jsonl)) == ({"value": 2}, None)


def _bench(tmp_path, rnd, value, ts=None, chip="TPU v5 lite", wrap=True):
    rec = {"value": value, "mfu": round(value / 80000, 4), "chip": chip}
    if ts is not None:
        rec["run_meta"] = {"timestamp": ts}
    obj = {"cmd": "bench", "rc": 0, "tail": json.dumps(rec) + "\n",
           "parsed": rec} if wrap else rec
    p = tmp_path / f"BENCH_r{rnd:02d}.json"
    p.write_text(json.dumps(obj))
    return str(p)


def test_series_orders_by_run_meta_timestamp(tmp_path):
    # filenames say r01 < r02, stamps say the opposite — stamps win
    _bench(tmp_path, 1, 4000, ts=200.0)
    _bench(tmp_path, 2, 3000, ts=100.0)
    pts = trend.load_series(str(tmp_path / "BENCH_r*.json"))
    assert [pt.record["value"] for pt in pts] == [3000, 4000]
    # an unstamped point anywhere → the whole series falls back to rounds
    _bench(tmp_path, 3, 5000)
    pts = trend.load_series(str(tmp_path / "BENCH_r*.json"))
    assert [pt.round for pt in pts] == [1, 2, 3]


def test_truncated_wrapper_is_skipped_point_not_crash(tmp_path):
    _bench(tmp_path, 1, 4000)
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"cmd": "bench", "rc": 124, "tail": '"value": 3980}'}))
    pts = trend.load_series(str(tmp_path / "BENCH_r*.json"))
    assert pts[1].record is None and "truncated" in pts[1].note
    res = trend.check(pts, "value", "higher")
    assert res["points"] == 1  # the skipped point never enters the series
    assert res["status"] == "first_run"


# ---------------------------------------------------------------------------
# trend: noise bands + the gate
# ---------------------------------------------------------------------------

def test_noise_band_maths():
    assert trend.noise_band([]) == trend.REL_FLOOR
    assert trend.noise_band([100.0]) == trend.REL_FLOOR
    # deltas 0.2 and ~0.1667 → median 0.1833, band = 3× that
    band = trend.noise_band([100.0, 120.0, 100.0])
    assert band == pytest.approx(3.0 * 0.5 * (0.2 + 20 / 120), rel=1e-6)
    # tight series floors out
    assert trend.noise_band([100.0, 101.0, 100.5]) == trend.REL_FLOOR


def _pts(tmp_path):
    return trend.load_series(str(tmp_path / "BENCH_r*.json"))


def test_gate_first_run_missing_and_in_band(tmp_path):
    _bench(tmp_path, 1, 4000)
    assert trend.check(_pts(tmp_path), "value")["status"] == "first_run"
    _bench(tmp_path, 2, 3900)  # −2.5%: inside the 10% floor
    res = trend.check(_pts(tmp_path), "value")
    assert res["status"] == "ok"
    assert res["delta_rel"] == pytest.approx(-0.025)
    missing = trend.check(_pts(tmp_path), "submetrics.absent.value")
    assert missing["status"] == "missing"
    # higher-is-better: a +40% jump is not a regression
    _bench(tmp_path, 3, 5600)
    assert trend.check(_pts(tmp_path), "value")["status"] == "ok"


def test_gate_flags_injected_regression(tmp_path):
    _bench(tmp_path, 1, 4000)
    _bench(tmp_path, 2, 4100)
    _bench(tmp_path, 3, 2000)  # −51% vs median 4050: beyond any band
    res = trend.check(_pts(tmp_path), "value")
    assert res["status"] == "regression"
    report = trend.gate(str(tmp_path))
    assert report["exit_code"] == 1
    assert report["statuses"]["regression"] >= 1
    assert trend.main(["--root", str(tmp_path)]) == 1


def test_gate_ignores_cpu_fallback_records(tmp_path):
    _bench(tmp_path, 1, 4000)
    _bench(tmp_path, 2, 100, chip="cpu (fallback)")  # r02-style outage
    res = trend.check(_pts(tmp_path), "value")
    assert res["status"] == "first_run"  # CPU point filtered, one remains


def test_multichip_checks_rc_and_ok(tmp_path):
    p = tmp_path / "MULTICHIP_r01.json"
    p.write_text(json.dumps({"n_devices": 4, "rc": 0, "ok": True,
                             "tail": ""}))
    report = trend.gate(str(tmp_path))
    assert report["exit_code"] == 0
    p.write_text(json.dumps({"n_devices": 4, "rc": 1, "ok": False,
                             "tail": ""}))
    report = trend.gate(str(tmp_path))
    assert report["exit_code"] == 1


def test_gate_green_on_committed_series():
    """The acceptance bar: the repo's own series passes. The BENCH_r* records
    of the earlier rounds are gone (taken on a device path that no longer
    exists), so the gate must stand on an empty BENCH series — no points is
    not a regression — next to the MULTICHIP one."""
    report = trend.gate(REPO)
    assert report["exit_code"] == 0
    assert report["bench_points"] == 0
    assert report["multichip_points"] >= 1
    assert "regression" not in report["statuses"]
    assert trend.main(["--root", REPO]) == 0


# ---------------------------------------------------------------------------
# series shaping + provenance
# ---------------------------------------------------------------------------

def test_thin_keeps_first_and_last():
    seq = list(range(25))
    out = trend.thin(seq, 10)
    assert len(out) == 10 and out[0] == 0 and out[-1] == 24
    assert out == sorted(out)
    assert trend.thin(seq, 100) == seq
    assert trend.thin(seq, 1) == [0]
    assert trend.thin([], 5) == []


def test_annotate_deltas_lower_is_better():
    rows = [{"ckpt": "random", "fid": 400.0},
            {"ckpt": "epoch_1", "fid": 120.0},
            {"ckpt": "best", "fid": 118.0},
            {"ckpt": "drift", "fid": 250.0}]
    out = trend.annotate_deltas(rows, "fid", lower_is_better=True)
    assert "delta_rel" not in out[0]  # first point has no predecessor
    assert out[1]["in_band"]  # improvement is always in band
    assert out[2]["in_band"]
    assert not out[3]["in_band"]  # +112% FID: out of band, flagged
    assert rows[1].keys() == {"ckpt", "fid"}  # input rows untouched


def test_run_metadata_stamp(monkeypatch):
    monkeypatch.setenv("DDIM_COLD_RUN_TS", "1754400000")
    monkeypatch.setenv("DDIM_COLD_ROUND", "6")
    meta = run_metadata(chip="TPU v5 lite")
    assert meta["timestamp"] == 1754400000.0
    assert meta["round"] == 6
    assert meta["device_kind"] == "TPU v5 lite"
    assert meta["jax"]  # installed in every supported environment
    monkeypatch.delenv("DDIM_COLD_RUN_TS")
    monkeypatch.delenv("DDIM_COLD_ROUND")
    monkeypatch.delenv("SOURCE_DATE_EPOCH", raising=False)
    meta = run_metadata()
    assert meta["timestamp"] is None  # never the wall clock
    assert meta["round"] is None


# ---------------------------------------------------------------------------
# host-only contract (GRAFT-A004) + emit-site lint (GRAFT-A005)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rel", ("ddim_cold_tpu/obs/attrib.py",
                                 "ddim_cold_tpu/obs/trend.py"))
def test_new_modules_registered_host_only_and_clean(rel):
    assert rel in ast_checks.HOST_ONLY_MODULES
    with open(os.path.join(REPO, rel)) as f:
        src = f.read()
    findings = ast_checks.lint_source(src, rel, host_only=True)
    assert [f for f in findings if f.rule == "GRAFT-A004"] == []


def test_attrib_metrics_registered():
    from ddim_cold_tpu.obs import metrics
    names = {m[0] for m in metrics.METRICS}
    assert {"attrib.traces", "attrib.coverage_pct", "attrib.device_busy_s",
            "trend.points", "trend.checks"} <= names
