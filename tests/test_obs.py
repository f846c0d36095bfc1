"""Observability subsystem tests (ISSUE 11): span propagation through a
2-replica chaos run (hedged attempts share one trace; retired replicas close
their lifetime spans), the tracing-disabled zero-overhead/bitwise contract,
device step telemetry against the adaptive gate's schedule, the metrics
registry as the single source behind the legacy ``stats`` surfaces, the
GRAFT-A005 emit-site lint, and the health/timeout diagnostics satellites."""

import json

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu import serve
from ddim_cold_tpu.analysis import ast_checks
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.obs import device as obs_device
from ddim_cold_tpu.obs import metrics, spans
from ddim_cold_tpu.ops import sampling, schedule
from ddim_cold_tpu.serve.router import Router
from ddim_cold_tpu.utils import faults, profiling
from ddim_cold_tpu.utils.faults import FaultSpec

TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)
K = 500  # 4 reverse steps — same geometry as test_serve.py / test_fleet.py
CFG = serve.SamplerConfig(k=K)


@pytest.fixture(autouse=True)
def clean_tracing():
    """Tracing is process-global: every test starts disabled with an empty
    recorder and must leave it that way."""
    spans.disable()
    spans.clear()
    yield
    assert not spans.enabled(), "test leaked an enabled tracing state"
    spans.disable()
    spans.clear()


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


def _router(model_and_params, **kwargs):
    model, params = model_and_params
    factory = serve.local_factory(model, params, buckets=(4, 8))
    kwargs.setdefault("configs", [CFG])
    kwargs.setdefault("warm_kwargs", dict(persistent_cache=False))
    kwargs.setdefault("drain_timeout_s", 10.0)
    return Router(factory, **kwargs)


def _direct(model, params, seed, n):
    return np.asarray(sampling.ddim_sample(
        model, params, jax.random.PRNGKey(seed), k=K, n=n))


def _by_name(all_spans, name):
    return [s for s in all_spans if s.name == name]


# ------------------------------------------------- trace propagation (fleet)


def test_chaos_run_spans_share_trace_and_close(model_and_params, tmp_path):
    """The tentpole acceptance run: a hedged request's attempts all carry
    ONE trace_id, every span of the completed request closes, a retired
    replica's lifetime span closes, and both exports round-trip — with zero
    compiles after warmup."""
    model, params = model_and_params
    with spans.tracing():
        router = _router(model_and_params, replicas=2, quarantine_limit=2,
                         max_hedges=2)
        # phase A — deterministic hedge: one assembly kill on r0 (the idle
        # fleet's first placement) re-places the request on r1
        spec = FaultSpec("serve.assemble", "transient", rate=1.0,
                         match="replica:r0|", max_fires=1)
        with faults.inject(spec) as plan:
            t = router.submit(seed=151, n=3, config=CFG)
            got = t.result(timeout=60)
        np.testing.assert_array_equal(got, _direct(model, params, 151, 3))
        assert len(plan.realized) == 1 and router.stats["hedges"] == 1

        roots = _by_name(spans.spans(), "router.request")
        assert len(roots) == 1
        root = roots[0]
        trace = root.trace_id
        attempts = _by_name(spans.spans(), "router.attempt")
        assert len(attempts) == 2  # original + hedge
        assert {a.trace_id for a in attempts} == {trace}
        assert {a.parent_id for a in attempts} == {root.span_id}
        # both attempts hit distinct replicas and both ended with an outcome
        assert {a.attrs["replica"] for a in attempts} == {"r0", "r1"}
        assert all(a.ended and "outcome" in a.attrs for a in attempts)
        # the engine leg parents under its attempt, stages under the engine
        engine_spans = [s for s in _by_name(spans.spans(), "engine.request")
                        if s.trace_id == trace]
        assert engine_spans and all(s.ended for s in engine_spans)
        att_ids = {a.span_id for a in attempts}
        assert all(s.parent_id in att_ids for s in engine_spans)
        done = [s for s in engine_spans if "latency_s" in s.attrs]
        assert len(done) == 1  # exactly one attempt delivered
        stage_names = {s.name for s in spans.spans()
                       if s.trace_id == trace
                       and s.parent_id in {e.span_id for e in engine_spans}}
        assert {"plan", "assemble", "dispatch", "fetch"} <= stage_names
        assert root.ended and root.attrs["hedges"] == 1

        # phase B — permanent dispatch kill on r0: quarantine, retire,
        # replace; the retired replica's lifetime span must close
        kill = FaultSpec("serve.dispatch", "permanent", rate=1.0,
                         match="replica:r0|")
        with faults.inject(kill):
            for seed in (152, 153):  # quarantine_limit=2 needs two victims
                t2 = router.submit(seed=seed, n=1, config=CFG)
                assert t2.exception(timeout=60) is not None
            deadline = time.time() + 30
            while time.time() < deadline:
                h = router.health()
                if h["retired_replicas"] >= 1 and h["active_replicas"] == 2:
                    break
                time.sleep(0.05)
        lifetimes = _by_name(spans.spans(), "replica.lifetime")
        r0 = [s for s in lifetimes if s.attrs.get("replica") == "r0"]
        assert len(r0) == 1 and r0[0].ended and r0[0].attrs["retired"]
        # the failed requests' traces closed with the error recorded
        failed_roots = [s for s in _by_name(spans.spans(), "router.request")
                        if "error" in s.attrs]
        assert len(failed_roots) == 2 and all(s.ended for s in failed_roots)

        h = router.drain(timeout=10)
        assert h["compiles_after_warmup"] == 0
        # drain closes the survivors' lifetime spans too (retired=False)
        assert all(s.ended
                   for s in _by_name(spans.spans(), "replica.lifetime"))

        # exports round-trip: chrome JSON loads, jsonl parses line-per-span
        chrome_path = tmp_path / "trace.json"
        doc = spans.export_chrome(str(chrome_path))
        loaded = json.loads(chrome_path.read_text())
        assert loaded == json.loads(json.dumps(doc))
        assert loaded["traceEvents"]
        for ev in loaded["traceEvents"]:
            assert ev["ph"] == "X" and ev["ts"] >= 0 and ev["dur"] >= 0
        jsonl_path = tmp_path / "trace.jsonl"
        rows = spans.export_jsonl(str(jsonl_path))
        lines = [json.loads(ln) for ln in
                 jsonl_path.read_text().splitlines()]
        assert lines == json.loads(json.dumps(rows))
        assert len(lines) == len(spans.spans())
    spans.clear()


def test_tracing_disabled_records_nothing_and_is_bitwise(model_and_params):
    """Disabled tracing is the default and must be absolutely inert: no
    spans recorded, NULL handles everywhere, and outputs bitwise-identical
    to a traced run of the same seeds (tracing never perturbs numerics)."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [CFG], persistent_cache=False)
    compiles = eng.stats["compiles"]

    n_spans = len(spans.spans())
    t = eng.submit(seed=171, n=2, config=CFG)
    eng.run()
    plain = t.result(timeout=60)
    assert len(spans.spans()) == n_spans  # not one span recorded
    assert t.span is None and t.telemetry is None

    with spans.tracing():
        t2 = eng.submit(seed=171, n=2, config=CFG)
        eng.run()
        traced = t2.result(timeout=60)
        assert t2.span is not None and t2.span.ended
    assert len(spans.spans()) > n_spans
    np.testing.assert_array_equal(plain, traced)
    np.testing.assert_array_equal(plain, _direct(model, params, 171, 2))
    assert eng.stats["compiles"] == compiles  # both runs: zero new programs
    spans.clear()


def test_begin_returns_null_when_disabled():
    s = spans.begin("anything", rid=1)
    assert s is spans.NULL and not s
    s.set(a=1).child("x").end()  # all no-ops
    spans.record(s, "stage", 0.0, 1.0)
    assert spans.spans() == []


# --------------------------------------------------------- device telemetry


def test_telemetry_static_mode_matches_schedule(model_and_params):
    model, params = model_and_params
    out, tel = sampling.ddim_sample(
        model, params, jax.random.PRNGKey(5), k=K, n=2, cache_interval=2,
        telemetry=True)
    branch = np.asarray(tel.branch)
    want = obs_device.static_schedule(4, 2, "delta")
    np.testing.assert_array_equal(branch, want)
    np.testing.assert_array_equal(np.asarray(tel.drift), np.zeros(4))
    # telemetry never changes the images
    plain = sampling.ddim_sample(
        model, params, jax.random.PRNGKey(5), k=K, n=2, cache_interval=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


def test_telemetry_adaptive_gate_limits(model_and_params):
    """τ=0 promotes every step to refresh (the ``>=`` gate); τ=∞ collapses
    to the static adaptive schedule; the summary's promoted count is the
    difference against the static plan."""
    model, params = model_and_params

    def run(tau):
        _, tel = sampling.ddim_sample(
            model, params, jax.random.PRNGKey(6), k=K, n=2, cache_interval=2,
            cache_mode="adaptive", cache_threshold=tau, telemetry=True)
        return np.asarray(tel.branch), np.asarray(tel.drift)

    always, drift0 = run(0.0)
    np.testing.assert_array_equal(
        always, np.full(4, schedule.CACHE_REFRESH, np.int32))
    never, drift_inf = run(1e30)
    np.testing.assert_array_equal(
        never, obs_device.static_schedule(4, 2, "adaptive"))
    # the gate computed real drifts on reuse steps in both runs
    assert np.all(np.isfinite(drift0)) and np.all(drift_inf >= 0.0)

    summary = obs_device.summarize(
        obs_device.StepTelemetry(branch=always, drift=drift0),
        cache_interval=2, cache_mode="adaptive", cache_threshold=0.0)
    assert summary["steps"] == 4
    assert summary["refreshes"] == 4 and summary["reuses"] == 0
    assert summary["promoted_refreshes"] == (
        4 - summary["planned_refreshes"]) > 0
    assert summary["refresh_ratio"] == 1.0
    assert len(summary["branch"]) == len(summary["drift"]) == 4


def test_telemetry_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="telemetry"):
        sampling.ddim_sample(model, params, jax.random.PRNGKey(0), k=K, n=2,
                             telemetry=True)  # uncached
    with pytest.raises(ValueError, match="last-only"):
        sampling.ddim_sample(model, params, jax.random.PRNGKey(0), k=K, n=2,
                             cache_interval=2, telemetry=True,
                             return_sequence=True)
    with pytest.raises(ValueError, match="telemetry"):
        serve.SamplerConfig(k=K, telemetry=True)  # uncached config
    with pytest.raises(ValueError, match="telemetry"):
        serve.SamplerConfig(k=K, cache_interval=2, preview_every=2,
                            telemetry=True)


def test_served_telemetry_attaches_to_ticket(model_and_params):
    """The engine fetches the step aux with the batch, decodes it once and
    attaches it to every ticket before delivery — with zero serve-time
    compiles (the telemetry program is its own warmed executable)."""
    model, params = model_and_params
    cfg = serve.SamplerConfig(k=K, cache_interval=2, cache_mode="adaptive",
                              cache_threshold=0.05, telemetry=True)
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [cfg], persistent_cache=False)
    compiles = eng.stats["compiles"]
    t = eng.submit(seed=181, n=2, config=cfg)
    eng.run()
    assert t.result(timeout=60).shape == (2, 16, 16, 3)
    tel = t.telemetry
    assert tel is not None and tel["steps"] == 4
    assert tel["cache_mode"] == "adaptive" and tel["cache_threshold"] == 0.05
    assert tel["refreshes"] + tel["reuses"] == 4
    assert tel["refreshes"] >= tel["planned_refreshes"]
    assert eng.stats["compiles"] == compiles
    assert eng.metrics.value("engine.cache_refresh_steps") == tel["refreshes"]
    assert eng.metrics.value("engine.cache_reuse_steps") == tel["reuses"]


# --------------------------------------------------------- metrics registry


def test_engine_stats_is_a_registry_view(model_and_params):
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [CFG], persistent_cache=False)
    for seed in (191, 192):
        eng.submit(seed=seed, n=2, config=CFG)
    eng.run()
    s = eng.stats
    m = eng.metrics
    assert s["compiles"] == m.value("engine.compiles") > 0
    assert s["dispatches"] == m.value("engine.dispatches") > 0
    assert s["rows"] == m.value("engine.rows") == 4
    assert s["latencies_s"] == m.samples("engine.latency_s")
    assert len(s["latencies_s"]) == 2
    # unquantized path: gauge never set, stats renders it as legacy None
    assert s["param_bytes"] is m.raw("engine.param_bytes") is None
    snap = m.snapshot()
    assert snap["engine.rows"] == 4
    # the registry-level snapshot carries this engine's scope verbatim
    assert metrics.snapshot()[m.sid] == snap
    with pytest.raises(ValueError, match="unregistered"):
        m.inc("engine.not_a_metric")
    with pytest.raises(ValueError, match="gauge"):
        m.inc("engine.param_bytes")  # kind mismatch: gauge emitted as counter


def test_router_stats_is_a_registry_view(model_and_params):
    router = _router(model_and_params, replicas=1)
    t = router.submit(seed=195, n=1, config=CFG)
    t.result(timeout=60)
    s = router.stats
    m = router.metrics
    assert s["submitted"] == m.value("router.submitted") == 1
    assert s["completed"] == m.value("router.completed") == 1
    assert s["placements"] == m.value("router.placements") >= 1
    assert s["replicas_spawned"] == m.value("router.replicas_spawned") == 1
    assert s["rejected_by_tenant"] == m.by_key("router.rejected_by_tenant")
    h = router.drain(timeout=10)
    assert h["compiles_after_warmup"] == 0
    # fleet lifecycle transitions landed keyed by state (new→ready→…→closed)
    fleet_keys = {}
    for sid, series in metrics.snapshot().items():
        if sid.startswith("fleet#"):
            for key, n in series.get(
                    "fleet.replica_transitions/by_key", {}).items():
                fleet_keys[key] = fleet_keys.get(key, 0) + n
    assert fleet_keys.get("new", 0) >= 1 and fleet_keys.get("closed", 0) >= 1


def test_faults_injected_metric():
    before = sum(
        series.get("faults.injected/by_key", {}).get("data.next", 0)
        for sid, series in metrics.snapshot().items()
        if sid.startswith("faults#"))
    with faults.inject(FaultSpec("data.next", "latency", rate=1.0,
                                 latency_s=0.0)):
        faults.fire("data.next", tag="t")
    after = sum(
        series.get("faults.injected/by_key", {}).get("data.next", 0)
        for sid, series in metrics.snapshot().items()
        if sid.startswith("faults#"))
    assert after == before + 1


# ------------------------------------------------------------- A005 lint


NAMES = ("engine.compiles", "engine.failed_batches")


def _lint(src, **kw):
    kw.setdefault("metric_names", NAMES)
    return ast_checks.lint_source(src, "f.py", **kw)


def test_a005_dynamic_name_flagged():
    fs = _lint("m.inc(name)\n")
    assert [f.rule for f in fs] == ["GRAFT-A005"]
    assert fs[0].subject == "metric:<dynamic>"


def test_a005_unregistered_name_flagged():
    fs = _lint('m.inc("engine.nope")\n')
    assert [f.subject for f in fs] == ["metric:engine.nope"]


def test_a005_duplicate_site_flagged_and_keys_disambiguate():
    dup = 'm.inc("engine.compiles")\nother.inc("engine.compiles")\n'
    fs = _lint(dup)
    assert len(fs) == 1 and "duplicate" in fs[0].message
    keyed = ('m.inc("engine.failed_batches", key="dispatch")\n'
             'm.inc("engine.failed_batches", key="plan")\n')
    assert _lint(keyed) == []
    # a dynamic key subdivides ONE site — never part of the uniqueness map
    dyn = 'm.inc("engine.compiles", key=state)\n' * 2
    assert _lint(dyn) == []
    # gauge/observe emits share the uniqueness map with inc
    mixed = ('m.gauge("engine.compiles", 1)\n'
             'm.observe("engine.compiles", 2)\n')
    fs = _lint(mixed)
    assert len(fs) == 1 and "duplicate" in fs[0].message


def test_a005_live_tree_is_clean_and_covered():
    """The real tree lints clean against the live registry — and actually
    contains emit sites (the rule is exercised, not vacuous)."""
    from ddim_cold_tpu.analysis import cli

    root = cli.repo_root()
    assert ast_checks.lint_tree(root) == []
    import os

    n_emits = 0
    for rel in ("ddim_cold_tpu/serve/engine.py",
                "ddim_cold_tpu/serve/router.py",
                "ddim_cold_tpu/serve/fleet.py",
                "ddim_cold_tpu/utils/faults.py"):
        with open(os.path.join(root, rel)) as f:
            import ast as ast_mod

            n_emits += len(ast_checks._metric_calls(ast_mod.parse(f.read())))
    assert n_emits >= 20


# ----------------------------------------------- satellites: profiling etc.


def test_latency_summary_has_p99_and_count():
    s = profiling.latency_summary([0.01 * i for i in range(1, 101)])
    assert s["count"] == s["n"] == 100
    assert s["p50_s"] <= s["p95_s"] <= s["p99_s"] <= s["max_s"]
    assert s["p99_s"] == pytest.approx(np.percentile(
        [0.01 * i for i in range(1, 101)], 99))
    empty = profiling.latency_summary([])
    assert empty["count"] == 0 and empty["p99_s"] == 0.0


def test_health_last_stage_and_timeout_message(model_and_params):
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [CFG], persistent_cache=False)
    t = eng.submit(seed=201, n=1, config=CFG)
    eng.run()
    t.result(timeout=60)
    h = eng.health()
    assert isinstance(h["last_stage"], str) and h["last_stage"]
    assert h["stalled_for_s"] >= 0.0
    # a timed-out waiter sees the stage diagnostics in its message
    t2 = eng.submit(seed=202, n=1, config=CFG)  # never run
    with pytest.raises(TimeoutError, match="last seen at stage"):
        t2.result(timeout=0.01)
    eng.drain(timeout=5)
