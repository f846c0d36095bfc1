"""Serving engine tests: bucket planning edge cases, engine-vs-direct
BITWISE equality (the ISSUE-2 contract: same request rng, padding rows
discarded), and the zero-compiles-after-warmup guard.

Bitwise works because every sampler row is computed independently of its
batchmates; the engine draws each request's init at the request's own n
(the draw the direct call makes) and only ever slices it. The mesh path is
allclose, not bitwise — a sharded reduction orders differently (same
tolerance as the sampler's own mesh tests)."""

import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import promise
import pytest

from ddim_cold_tpu import serve
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops import sampling
from ddim_cold_tpu.serve.batching import Request, cover_rows, plan_batches, select_bucket
from ddim_cold_tpu.utils import faults

TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)
K = 500  # 4 reverse steps — cheap enough to AOT-compile several programs


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def warmed(model_and_params):
    """One engine + warmed plain-DDIM programs at two buckets, shared by the
    bitwise/packing/stats tests (AOT compiles are the expensive part)."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4, 8))
    cfg = serve.SamplerConfig(k=K)
    report = serve.warmup(eng, [cfg], persistent_cache=False)
    assert report["new_compiles"] == 2  # one program per bucket
    return eng, cfg


def _direct(model, params, seed, n, **kw):
    return np.asarray(sampling.ddim_sample(
        model, params, jax.random.PRNGKey(seed), k=K, n=n, **kw))


def _assert_bitwise(got, model, params, seed, n, buckets=(4, 8)):
    """The bitwise half of the engine's promise (``tests/promise.py``): each
    row is the direct sampler's at the batch size of a bucket that may have
    served it. At the request's own n the last bit may differ."""
    assert got.shape[0] == n
    promise.assert_sample_served("same_bucket", got, model, params, seed, K,
                                 buckets)


# --------------------------------------------------------------- planning


def test_select_bucket():
    assert select_bucket(1, (8, 32, 128)) == 8
    assert select_bucket(8, (8, 32, 128)) == 8
    assert select_bucket(9, (8, 32, 128)) == 32
    assert select_bucket(129, (8, 32, 128)) is None


def test_cover_rows():
    assert cover_rows(5, (4, 8)) == [8]            # 1 batch beats [4, 4]
    assert cover_rows(5, (4, 32, 128)) == [4, 4]   # pad 3 beats [32]'s 27
    assert cover_rows(11, (4, 8)) == [8, 4]
    assert cover_rows(8, (8,)) == [8]
    assert cover_rows(260, (8, 32, 128)) == [128, 128, 8]
    assert cover_rows(1, (8, 32)) == [8]
    with pytest.raises(ValueError):
        cover_rows(3, ())
    with pytest.raises(ValueError):
        cover_rows(3, (0, 4))


def test_plan_batches_empty_queue():
    assert plan_batches([], (8, 32)) == []


def test_plan_batches_packing_offsets_and_split():
    """A request above the largest bucket splits; offsets tile each batch
    contiguously and only the last batch of a group carries padding."""
    cfg = serve.SamplerConfig(k=K)
    reqs = [Request(config=cfg, n=11), Request(config=cfg, n=3)]
    plans = plan_batches(reqs, (4, 8))  # 14 rows → [8, 8] (pad 2)
    assert [p.bucket for p in plans] == [8, 8]
    assert [p.rows for p in plans] == [8, 6]
    assert plans[0].padded_rows == 0 and plans[1].padded_rows == 2
    # request 0's rows 0..8 ride batch 0; rows 8..11 open batch 1, then
    # request 1's rows 0..3 follow at offset 3
    assert plans[0].entries == ((reqs[0], 0, 8, 0),)
    assert plans[1].entries == ((reqs[0], 8, 11, 0), (reqs[1], 0, 3, 3))
    # every batch is tiled contiguously from offset 0
    for plan in plans:
        offset = 0
        for _, lo, hi, off in plan.entries:
            assert off == offset
            offset += hi - lo
        assert offset == plan.rows


def test_plan_batches_mixed_configs_never_share():
    a = serve.SamplerConfig(k=K)
    b = serve.SamplerConfig(k=K, cache_interval=2)
    c = serve.SamplerConfig(sampler="cold")
    reqs = [Request(config=a, n=2), Request(config=b, n=2),
            Request(config=a, n=2), Request(config=c, n=2)]
    plans = plan_batches(reqs, (4, 8))
    assert len(plans) == 3  # a-group coalesced; b and c alone
    for plan in plans:
        assert {e[0].config for e in plan.entries} == {plan.config}
    a_plan = next(p for p in plans if p.config == a)
    assert a_plan.rows == 4 and a_plan.bucket == 4  # coalesced, zero pad


# ----------------------------------------------------------------- engine


def test_engine_bitwise_at_two_buckets(model_and_params, warmed):
    """The acceptance contract, at both compiled buckets in one drain: mixed
    request sizes coalesce into a bucket-8 and a bucket-4 batch, and every
    request comes back bitwise equal to its direct ddim_sample."""
    model, params = model_and_params
    eng, cfg = warmed
    compiles = eng.stats["compiles"]
    tickets = {seed: eng.submit(seed=seed, n=n, config=cfg)
               for seed, n in [(21, 5), (22, 4), (23, 3)]}  # 12 rows → [8, 4]
    report = eng.run()
    assert report["batches"] == 2 and report["rows"] == 12
    assert report["padded_rows"] == 0
    assert eng.stats["compiles"] == compiles  # warmed: zero new programs
    for seed, n in [(21, 5), (22, 4), (23, 3)]:
        got = tickets[seed].result(timeout=5)
        assert got.shape == (n, 16, 16, 3)
        _assert_bitwise(got, model, params, seed, n)


def test_engine_bitwise_padded_single_request(model_and_params, warmed):
    """A lone n=3 request pads to bucket 4; padding rows are discarded and
    the real rows keep their bits."""
    model, params = model_and_params
    eng, cfg = warmed
    t = eng.submit(seed=31, n=3, config=cfg)
    report = eng.run()
    assert report["batches"] == 1 and report["padded_rows"] == 1
    _assert_bitwise(t.result(timeout=5), model, params, 31, 3)


def test_engine_bitwise_split_request(model_and_params, warmed):
    """n=11 exceeds the largest bucket (8): the request splits across two
    batches and reassembles bitwise."""
    model, params = model_and_params
    eng, cfg = warmed
    t = eng.submit(seed=41, n=11, config=cfg)
    report = eng.run()
    assert report["batches"] == 2  # [8, 4]
    _assert_bitwise(t.result(timeout=5), model, params, 41, 11)


def test_engine_bitwise_cached_and_cold(model_and_params):
    """Cached-sampler and cold-sampler configs serve bitwise too (their
    scans return the recycled cache; rows must be untouched by that)."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    cached = serve.SamplerConfig(k=K, cache_interval=2)
    cold = serve.SamplerConfig(sampler="cold", levels=4)
    serve.warmup(eng, [cached, cold], persistent_cache=False)
    compiles = eng.stats["compiles"]
    tc = eng.submit(seed=51, n=3, config=cached)
    tk = eng.submit(seed=52, n=2, config=cold)
    # second cached request: exercises cache-buffer recycling across batches
    tc2 = eng.submit(seed=53, n=2, config=cached)
    eng.run()
    assert eng.stats["compiles"] == compiles
    np.testing.assert_array_equal(
        tc.result(timeout=5),
        _direct(model, params, 51, 3, cache_interval=2))
    np.testing.assert_array_equal(
        tc2.result(timeout=5),
        _direct(model, params, 53, 2, cache_interval=2))
    np.testing.assert_array_equal(
        tk.result(timeout=5),
        np.asarray(sampling.cold_sample(model, params, jax.random.PRNGKey(52),
                                        n=2, levels=4)))


def test_engine_guided_requests_bitwise(model_and_params, warmed):
    """Guided serving (x_init + t_start — the sample_from path): the host
    array uploads through the prefetch thread and returns bitwise equal to
    the direct call."""
    model, params = model_and_params
    eng, _ = warmed
    cfg = serve.SamplerConfig(k=K, t_start=999)
    enc = np.asarray(jax.random.normal(jax.random.PRNGKey(61), (2, 16, 16, 3)))
    t = eng.submit(x_init=enc, config=cfg)  # new config: compiles lazily
    eng.run()
    want = np.asarray(sampling.sample_from(model, params, jnp.asarray(enc),
                                           t_start=999, k=K))
    np.testing.assert_array_equal(t.result(timeout=5), want)


def test_zero_compiles_after_warmup_mixed_sizes(model_and_params, warmed):
    """The compile-count guard: after warmup, a stream of requests at many
    distinct sizes — across several drains — triggers ZERO program builds
    (dispatch only ever calls the warmup's AOT executables, which cannot
    retrace). Complement: an unwarmed engine does compile, so the counter
    is live, not trivially zero."""
    model, params = model_and_params
    eng, cfg = warmed
    compiles = eng.stats["compiles"]
    for batch_sizes in ([1, 2], [3, 5, 7], [11], [4, 8, 6]):
        tickets = [eng.submit(seed=70 + n, n=n, config=cfg)
                   for n in batch_sizes]
        eng.run()
        for t in tickets:
            assert t.done
    assert eng.stats["compiles"] == compiles

    fresh = serve.Engine(model, params, buckets=(4,))
    t = fresh.submit(seed=1, n=2, config=cfg)
    fresh.run()
    assert fresh.stats["compiles"] > 0  # lazy compile happened and was counted
    assert t.done


def test_engine_stats_and_latency(model_and_params, warmed):
    eng, cfg = warmed
    n_before = len(eng.stats["latencies_s"])
    t = eng.submit(seed=81, n=2, config=cfg)
    assert eng.queue_depth() == 1
    report = eng.run()
    assert eng.queue_depth() == 0
    assert t.latency_s is not None and t.latency_s > 0
    assert len(eng.stats["latencies_s"]) == n_before + 1
    lat = report["latency"]
    assert lat["n"] == 1 and lat["p95_s"] >= lat["p50_s"] > 0
    assert report["img_per_sec"] > 0
    assert eng.stats["max_queue_depth"] >= 1


def test_engine_validation_and_ticket_timeout(model_and_params):
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    with pytest.raises(ValueError, match="seed= or rng="):
        eng.submit(n=2)
    with pytest.raises(ValueError, match="not both"):
        eng.submit(seed=0, n=2, config=serve.SamplerConfig(), k=10)
    with pytest.raises(ValueError, match="DDIM path"):
        eng.submit(x_init=np.zeros((1, 16, 16, 3)), sampler="cold")
    with pytest.raises(ValueError, match="n must be"):
        eng.submit(seed=0, n=0)
    with pytest.raises(ValueError, match="sampler must be"):
        serve.SamplerConfig(sampler="euler")
    with pytest.raises(ValueError, match="cache_mode"):
        serve.SamplerConfig(cache_mode="none")
    with pytest.raises(ValueError, match="buckets"):
        serve.Engine(model, params, buckets=())
    ticket = eng.submit(seed=0, n=2)
    # never ran — must not hang forever, and the timeout carries the engine
    # health snapshot (an ops page beats "did Engine.run() run?")
    with pytest.raises(TimeoutError, match="queue_depth"):
        ticket.result(timeout=0.01)
    with pytest.raises(TimeoutError, match="engine health"):
        ticket.exception(timeout=0.01)
    # a BARE ticket (no engine attached) keeps the did-run hint
    with pytest.raises(TimeoutError, match="no engine attached"):
        serve.Ticket(1).result(timeout=0.01)


def test_engine_mesh_sharded(model_and_params):
    """Sharded serving: buckets must divide the data axis, and the sharded
    drain reproduces the single-device result within the sampler's own
    SPMD tolerance (bitwise is a per-backend contract, not cross-mesh)."""
    from ddim_cold_tpu.parallel.mesh import make_mesh

    model, params = model_and_params
    mesh = make_mesh({"data": 8})
    with pytest.raises(ValueError, match="divide"):
        serve.Engine(model, params, mesh=mesh, buckets=(4, 8))
    eng = serve.Engine(model, params, mesh=mesh, buckets=(8,))
    cfg = serve.SamplerConfig(k=K)
    serve.warmup(eng, [cfg], persistent_cache=False)
    compiles = eng.stats["compiles"]
    t = eng.submit(seed=91, n=8, config=cfg)
    eng.run()
    assert eng.stats["compiles"] == compiles
    np.testing.assert_allclose(t.result(timeout=5),
                               _direct(model, params, 91, 8),
                               rtol=2e-5, atol=2e-6)


def test_check_compile_cache_script():
    """The scripts/ CI check passes (or capability-skips) on the running
    jax — rc 0 either way; rc 1 means the persistent cache wiring broke."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "check_compile_cache.py")],
        capture_output=True, text=True, timeout=300, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert ("PASS" in proc.stdout) or ("SKIP" in proc.stdout), proc.stdout


# ------------------------------------------------------------------ chaos
#
# Failure isolation under deterministic fault injection (utils/faults.py).
# The liveness contract every case pins: NO ticket ever blocks forever —
# each resolves to its rows or to a typed exception — and the engine keeps
# serving after the chaos scope closes, with ZERO new compiles (recovery
# re-packs at the warmed buckets).


def _all_resolved(tickets, timeout=30):
    """Every ticket resolves (rows or error) within timeout — the no-hung-
    ticket guarantee. Returns the failures."""
    errs = []
    for t in tickets:
        exc = t.exception(timeout=timeout)  # raises TimeoutError on a hang
        if exc is not None:
            errs.append(exc)
    return errs


def test_chaos_transient_dispatch_kill(model_and_params, warmed):
    """Kill a seeded ≥20% of dispatches with the retryable fault class: the
    backoff-retry path absorbs every hit, ALL tickets complete, and every
    one is bitwise-equal to the direct sampler."""
    model, params = model_and_params
    eng, cfg = warmed
    compiles = eng.stats["compiles"]
    retries0 = eng.stats["retries"]
    reqs = [(s, n) for s, n in zip(range(200, 210), [3, 5, 2, 8, 1, 4, 6, 2, 7, 3])]
    spec = faults.FaultSpec("serve.dispatch", "transient", rate=0.35, seed=11)
    with faults.inject(spec) as plan:
        tickets = {s: eng.submit(seed=s, n=n, config=cfg) for s, n in reqs}
        report = eng.run()
        injected = len(plan.realized)
    dispatch_calls = report["batches"] + injected  # every fire = one attempt
    assert injected >= 0.2 * dispatch_calls, (injected, dispatch_calls)
    assert _all_resolved(list(tickets.values())) == []
    assert eng.stats["retries"] - retries0 == injected
    for s, n in reqs:
        _assert_bitwise(tickets[s].result(timeout=5), model, params, s, n)
    assert eng.stats["compiles"] == compiles  # recovery never compiles


def test_chaos_every_serve_site(model_and_params, warmed):
    """Faults at EVERY serve.* pipeline site at once (assemble raises,
    dispatch raises transient, fetch raises): each batch fails only itself,
    non-quarantined survivors are bitwise, nothing hangs, and the engine
    serves a clean follow-up drain."""
    model, params = model_and_params
    eng, cfg = warmed
    compiles = eng.stats["compiles"]
    reqs = [(s, n) for s, n in zip(range(300, 312),
                                   [2, 3, 1, 4, 2, 5, 3, 2, 1, 6, 2, 3])]
    with faults.inject(
            faults.FaultSpec("serve.assemble", "permanent", rate=0.25, seed=2),
            faults.FaultSpec("serve.dispatch", "transient", rate=0.3, seed=3),
            faults.FaultSpec("serve.fetch", "permanent", rate=0.25, seed=4),
    ) as plan:
        tickets = {s: eng.submit(seed=s, n=n, config=cfg) for s, n in reqs}
        eng.run()
        assert len(plan.realized) > 0
        assert set(plan.by_site()) <= {"serve.assemble", "serve.dispatch",
                                       "serve.fetch"}
    errs = _all_resolved(list(tickets.values()))
    for e in errs:  # typed failures only, each carrying the injected cause
        assert isinstance(e, serve.RequestFailedError)
        assert isinstance(e.__cause__, faults.FaultError)
    for s, n in reqs:  # survivors keep their bits
        if not tickets[s].failed:
            _assert_bitwise(tickets[s].result(timeout=5), model, params, s, n)
    assert eng.stats["compiles"] == compiles
    # chaos scope closed: the engine serves clean
    t = eng.submit(seed=399, n=3, config=cfg)
    eng.run()
    _assert_bitwise(t.result(timeout=5), model, params, 399, 3)
    assert eng.stats["compiles"] == compiles


def test_chaos_bisection_quarantines_poisoned_request(model_and_params,
                                                      warmed):
    """A request that deterministically fails ANY batch containing it is
    bisected out: only IT fails (RequestQuarantinedError, injected fault as
    cause), every innocent batchmate completes bitwise, and recovery stays
    on the warmed programs."""
    model, params = model_and_params
    eng, cfg = warmed
    compiles = eng.stats["compiles"]
    quarantined0 = eng.stats["quarantined"]
    tickets = {}
    poison_rid = eng._next_rid + 2  # the third of the five submits below
    with faults.inject(faults.FaultSpec("serve.dispatch", "permanent",
                                        match=f"req:{poison_rid}|")):
        for i, (s, n) in enumerate(zip(range(410, 415), [2, 1, 2, 1, 2])):
            tickets[s] = eng.submit(seed=s, n=n, config=cfg)
        eng.run()
    errs = _all_resolved(list(tickets.values()))
    assert len(errs) == 1 and isinstance(errs[0], serve.RequestQuarantinedError)
    assert isinstance(errs[0].__cause__, faults.PermanentFault)
    assert eng.stats["quarantined"] - quarantined0 == 1
    assert poison_rid in eng.quarantined
    for s, n in zip(range(410, 415), [2, 1, 2, 1, 2]):
        if not tickets[s].failed:
            _assert_bitwise(tickets[s].result(timeout=5), model, params, s, n)
    assert sum(1 for s in range(410, 415) if tickets[s].failed) == 1
    assert eng.stats["compiles"] == compiles  # bisection repacks, no compile


def test_chaos_fetch_corrupt_is_detectable(model_and_params, warmed):
    """A corrupt injection at the fetch site lands exactly one NaN in the
    delivered buffer (detectability: a checksum/validation layer upstream
    would catch it) and records which element in the plan."""
    model, params = model_and_params
    eng, cfg = warmed
    with faults.inject(faults.FaultSpec("serve.fetch", "corrupt", seed=5,
                                        max_fires=1)) as plan:
        t = eng.submit(seed=420, n=4, config=cfg)
        eng.run()
        out = t.result(timeout=5)
    assert int(np.isnan(out).sum()) <= 1  # ≤: the flip may land in padding
    assert plan.realized[0]["detail"]["index"] >= 0
    clean = _direct(model, params, 420, 4)
    mism = out != clean
    assert mism.sum() <= 1  # exactly the flipped element differs


def test_deadline_enforced_at_plan_and_dispatch(model_and_params, warmed):
    """deadline_s=0 expires in the queue (plan-time gate); a live deadline
    that lapses during a slow assembly expires at the dispatch gate and the
    all-expired batch skips the device entirely."""
    model, params = model_and_params
    eng, cfg = warmed
    t0 = eng.submit(seed=430, n=2, config=cfg, deadline_s=0.0)
    time.sleep(0.01)
    eng.run()
    assert isinstance(t0.exception(timeout=5), serve.DeadlineExceeded)
    skipped0 = eng.stats["skipped_batches"]
    t1 = eng.submit(seed=431, n=4, config=cfg, deadline_s=0.1)
    with faults.inject(faults.FaultSpec("serve.assemble", "latency",
                                        latency_s=0.3, max_fires=1)):
        eng.run()
    assert isinstance(t1.exception(timeout=5), serve.DeadlineExceeded)
    assert eng.stats["skipped_batches"] == skipped0 + 1
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(seed=0, n=1, config=cfg, deadline_s=-1)


def test_bounded_queue_rejects_on_overload(model_and_params):
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,), max_queue=2)
    cfg = serve.SamplerConfig(k=K)
    a = eng.submit(seed=0, n=1, config=cfg)
    b = eng.submit(seed=1, n=1, config=cfg)
    with pytest.raises(serve.QueueFullError, match="max_queue=2"):
        eng.submit(seed=2, n=1, config=cfg)
    assert eng.stats["rejected"] == 1
    assert eng.health()["queue_depth"] == 2
    # drain (without ever running): queued tickets fail deterministically
    health = eng.drain(timeout=1)
    assert health["closed"] and health["queue_depth"] == 0
    for t in (a, b):
        assert isinstance(t.exception(timeout=5), serve.EngineClosedError)
    with pytest.raises(serve.EngineClosedError):
        eng.submit(seed=3, n=1, config=cfg)
    with pytest.raises(ValueError, match="max_queue"):
        serve.Engine(model, params, buckets=(4,), max_queue=0)


def test_stall_watchdog_fails_tickets_not_process(model_and_params):
    """A wedged dispatch (injected 1.2s silence against a 0.3s stall budget)
    trips the SOFT watchdog: in-flight tickets fail with EngineStalledError,
    run() returns (stalled flagged) — the process survives, nothing hangs."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,), stall_s=0.3)
    cfg = serve.SamplerConfig(k=K)
    serve.warmup(eng, [cfg], persistent_cache=False)
    t = eng.submit(seed=440, n=4, config=cfg)
    with faults.inject(faults.FaultSpec("serve.dispatch", "latency",
                                        latency_s=1.2, max_fires=1)):
        report = eng.run()
    assert report["stalled"]
    assert isinstance(t.exception(timeout=5), serve.EngineStalledError)
    assert eng.stats["stalls"] == 1
    assert eng.health()["stalled"]
    # the engine recovers on the next drain (fresh watchdog per run)
    t2 = eng.submit(seed=441, n=2, config=cfg)
    report2 = eng.run()
    assert not report2["stalled"]
    _assert_bitwise(t2.result(timeout=5), model, params, 441, 2, buckets=(4,))


def test_warmup_tolerate_errors(model_and_params):
    """Degraded startup: a failing compile is recorded, the rest of the
    programs warm, and strict mode still raises."""
    model, params = model_and_params
    cfg = serve.SamplerConfig(k=K)
    eng = serve.Engine(model, params, buckets=(4, 8))
    with faults.inject(faults.FaultSpec("serve.compile", "permanent",
                                        max_fires=1)):
        with pytest.raises(faults.PermanentFault):
            serve.warmup(eng, [cfg], persistent_cache=False)
        report = serve.warmup(eng, [cfg], persistent_cache=False,
                              tolerate_errors=True)
    assert len(report["errors"]) == 0  # max_fires spent on the strict call
    eng2 = serve.Engine(model, params, buckets=(4, 8))
    with faults.inject(faults.FaultSpec("serve.compile", "permanent",
                                        max_fires=1)):
        report = serve.warmup(eng2, [cfg], persistent_cache=False,
                              tolerate_errors=True)
    assert len(report["errors"]) == 1
    assert report["new_compiles"] == 1  # the other program warmed anyway


def test_disarmed_serving_is_bitwise_and_compile_free(model_and_params,
                                                      warmed):
    """The zero-overhead-disarmed contract: after any amount of chaos, a
    disarmed drain is byte-identical to the direct sampler and triggers no
    compiles — the fault hooks cost a flag check on the fast path."""
    model, params = model_and_params
    eng, cfg = warmed
    assert not faults.active()
    compiles = eng.stats["compiles"]
    t = eng.submit(seed=450, n=6, config=cfg)
    eng.run()
    _assert_bitwise(t.result(timeout=5), model, params, 450, 6)
    assert eng.stats["compiles"] == compiles


# ----------------------------------------------------- fleet satellites
#
# Engine-level pieces the replica router (serve/router.py) builds on: the
# drain(timeout) idle-report fix, the health() snapshot fields supervision
# reads, and replica-id threading through failure messages and fault tags.


def test_drain_timeout_skips_sweep_when_not_idle(model_and_params):
    """drain(timeout) against a mid-flight run reports idle=False and does
    NOT sweep the queue — the old code dropped the wait's return and failed
    queued requests while their batches were still on the device. Liveness
    still holds: the run itself fails what it finds queued after close."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    cfg = serve.SamplerConfig(k=K)
    serve.warmup(eng, [cfg], persistent_cache=False)
    a = eng.submit(seed=460, n=2, config=cfg)
    with faults.inject(faults.FaultSpec("serve.dispatch", "latency",
                                        latency_s=0.5, max_fires=1)):
        worker = threading.Thread(target=eng.run, daemon=True)
        worker.start()
        deadline = time.time() + 5
        while (eng.queue_depth() > 0 or not eng.health()["running"]) \
                and time.time() < deadline:
            time.sleep(0.005)  # wait until the run owns request a
        b = eng.submit(seed=461, n=1, config=cfg)  # queued behind the run
        report = eng.drain(timeout=0.05)
        assert report["idle"] is False
        assert not a.done and not b.done  # sweep skipped, nothing raced
        worker.join(timeout=10)
    # the run flushed a (bitwise) and failed b typed on seeing closed
    _assert_bitwise(a.result(timeout=5), model, params, 460, 2, buckets=(4,))
    assert isinstance(b.exception(timeout=5), serve.EngineClosedError)
    assert eng.drain(timeout=5)["idle"] is True  # settled now


def test_health_has_supervision_fields(model_and_params):
    """health() carries what fleet supervision needs without touching the
    engine: replica identity, max_queue (admission headroom), uptime_s, and
    last_progress_s (wedge detection from a snapshot alone)."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,), max_queue=5,
                       replica_id="rX")
    h = eng.health()
    assert h["replica"] == "rX" and h["max_queue"] == 5
    assert h["uptime_s"] >= 0 and h["last_progress_s"] >= 0
    time.sleep(0.05)
    cfg = serve.SamplerConfig(k=K)
    t = eng.submit(seed=470, n=1, config=cfg)
    eng.run()
    assert t.result(timeout=30) is not None
    h2 = eng.health()
    assert h2["uptime_s"] > h["uptime_s"]
    # the run just made progress: its age is far below the engine's
    assert h2["last_progress_s"] < h2["uptime_s"]
    assert h2["last_progress_s"] < 0.05 + h2["uptime_s"] - h["uptime_s"]


def test_replica_id_in_failure_messages_and_fault_tags(model_and_params):
    """A replica-scoped engine names itself in every failure message (so a
    fleet-level error is attributable) and prefixes its fault tags with
    replica:<id>| (so chaos schedules can target one replica)."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,), replica_id="r9")
    cfg = serve.SamplerConfig(k=K)
    serve.warmup(eng, [cfg], persistent_cache=False)
    with faults.inject(faults.FaultSpec("serve.dispatch", "permanent",
                                        match="replica:r9|")) as plan:
        t = eng.submit(seed=480, n=1, config=cfg)
        eng.run()
        exc = t.exception(timeout=5)
    assert isinstance(exc, serve.RequestQuarantinedError)
    assert "replica 'r9'" in str(exc)
    assert plan.realized and all(
        r["tag"].startswith("replica:r9|") for r in plan.realized)
    # drain-path message carries the id too
    t2 = eng.submit(seed=481, n=1, config=cfg)
    eng.drain(timeout=1)
    assert "replica 'r9'" in str(t2.exception(timeout=5))


# ------------------------------------------------------ sequence parallelism


SP2 = serve.SamplerConfig(k=K, sp_mode="ulysses", sp_degree=2)


def test_sp_config_validation():
    """The sp fields are validated at CONSTRUCTION (satellite of the sp
    tentpole): mode domain, degree floor, the none⟺degree-1 identity in
    both directions, and the sp × batch-coupled-adaptive rejection — each
    error names the knob to change and is the typed
    parallel.SeqParallelConfigError (a ValueError, so untyped callers
    still catch it)."""
    from ddim_cold_tpu.parallel import SeqParallelConfigError
    with pytest.raises(SeqParallelConfigError, match="sp_mode"):
        serve.SamplerConfig(k=K, sp_mode="megatron")
    with pytest.raises(SeqParallelConfigError, match="sp_degree"):
        serve.SamplerConfig(k=K, sp_degree=0)
    with pytest.raises(SeqParallelConfigError, match="sp_mode='ulysses'"):
        serve.SamplerConfig(k=K, sp_degree=2)  # a degree needs a strategy
    with pytest.raises(SeqParallelConfigError, match="sp_degree >= 2"):
        serve.SamplerConfig(k=K, sp_mode="ulysses")  # a strategy, a degree
    with pytest.raises(SeqParallelConfigError, match="adaptive"):
        serve.SamplerConfig(k=K, sp_mode="ring", sp_degree=2,
                            cache_interval=2, cache_mode="adaptive",
                            cache_threshold=0.05)


def test_sp_degenerate_degree1_is_default_config():
    """sp_degree=1 IS the existing program: the config carries no sp state
    (sp_mode='none' is the only legal degree-1 spelling), so it hashes and
    compares equal to the pre-sp default — bitwise-vs-existing is identity
    at the registry key, not a float comparison."""
    assert serve.SamplerConfig(k=K, sp_mode="none", sp_degree=1) == \
        serve.SamplerConfig(k=K)
    assert hash(serve.SamplerConfig(k=K, sp_mode="none", sp_degree=1)) == \
        hash(serve.SamplerConfig(k=K))


@pytest.mark.skipif(jax.device_count() % 2 != 0,
                    reason="sp_degree=2 needs an even device count")
def test_sp_serving_allclose_both_buckets(model_and_params):
    """sp_degree=2 serves at BOTH warmed buckets with zero compiles after
    warmup; rows are allclose to direct sampling — the mesh tolerance (a
    sharded reduction orders differently), not the bitwise contract."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4, 8))
    wu = serve.warmup(eng, [SP2], persistent_cache=False)
    assert wu["new_compiles"] == 2  # one sp program per bucket
    compiles = eng.stats["compiles"]
    tickets = {seed: eng.submit(seed=seed, n=n, config=SP2)
               for seed, n in [(61, 8), (62, 4)]}
    report = eng.run()
    assert report["batches"] == 2
    assert eng.stats["compiles"] == compiles  # zero compiles after warmup
    for seed, n in [(61, 8), (62, 4)]:
        got = tickets[seed].result(timeout=5)
        assert got.shape == (n, 16, 16, 3)
        np.testing.assert_allclose(got, _direct(model, params, seed, n),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.skipif(jax.device_count() % 8 != 0,
                    reason="sp_degree=8 needs a multiple of 8 devices")
def test_sp_ring_fallback_serves(model_and_params):
    """sp_degree=8 with 4 heads cannot run Ulysses (4 % 8 != 0): the engine
    resolves the model through models.sp_clone — the ONE resolver shared
    with the analysis sweep — and serves the config as ring, transparently
    to the caller, at the same float tolerance."""
    model, params = model_and_params
    cfg = serve.SamplerConfig(k=K, sp_mode="ulysses", sp_degree=8)
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [cfg], persistent_cache=False)
    assert eng._model_for(cfg).sp_mode == "ring"
    compiles = eng.stats["compiles"]
    t = eng.submit(seed=71, n=4, config=cfg)
    eng.run()
    assert eng.stats["compiles"] == compiles
    np.testing.assert_allclose(t.result(timeout=5),
                               _direct(model, params, 71, 4),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.skipif(jax.device_count() != 8,
                    reason="pins the 8-device data-axis arithmetic")
def test_sp_bucket_must_divide_data_axis(model_and_params):
    """bucket 2 cannot tile sp_degree=2's data axis (8 devices → data=4):
    the engine refuses at ensure_program with an actionable error instead
    of letting a mis-tiled batch reach placement."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(2, 4))
    with pytest.raises(ValueError, match="data axis"):
        eng.ensure_program(SP2, 2)


@pytest.mark.skipif(jax.device_count() % 2 != 0,
                    reason="sp_degree=2 needs an even device count")
def test_sp_cached_config_prewarms_spare_pool(model_and_params):
    """A cached sp config warms its program AND a spare step-cache carry
    keyed by (bucket, (kind, sp_mode, sp_degree)) — a carry placed on one
    mesh can never be donated to a program compiled for another — and the
    drain itself is allclose with zero compiles."""
    model, params = model_and_params
    cfg = serve.SamplerConfig(k=K, cache_interval=2, cache_mode="full",
                              sp_mode="ulysses", sp_degree=2)
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [cfg], persistent_cache=False)
    assert (4, ("pair", "ulysses", 2)) in eng._spare_caches
    compiles = eng.stats["compiles"]
    t = eng.submit(seed=81, n=4, config=cfg)
    eng.run()
    assert eng.stats["compiles"] == compiles
    np.testing.assert_allclose(
        t.result(timeout=5),
        _direct(model, params, 81, 4, cache_interval=2, cache_mode="full"),
        rtol=2e-5, atol=2e-5)
