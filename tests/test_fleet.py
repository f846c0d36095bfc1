"""Fleet router tests (serve/router.py + serve/fleet.py): placement
bitwise-vs-direct, hedged re-placement, quarantine terminality, the
chaos-contract acceptance test (ISSUE 6: permanent dispatch kill on one
replica + ≥20% transients elsewhere → survivors bitwise, failures typed and
replica-named, killed replica drained AND replaced, zero compiles after
warmup across every replica including the replacement), tenant QoS
fair-share admission, and stub-backed supervision/lifecycle units.

The in-process replicas serve from worker threads, so WHICH replica a
request lands on is timing-dependent — assertions here are placement-
agnostic (bitwise for survivors, typed-and-named for failures, fleet-level
counters) rather than schedule-exact."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import promise
import pytest

from ddim_cold_tpu import serve
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops import sampling
from ddim_cold_tpu.serve import fleet
from ddim_cold_tpu.serve.router import Router
from ddim_cold_tpu.utils import faults
from ddim_cold_tpu.utils.faults import FaultSpec

TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)
K = 500  # 4 reverse steps, same geometry as test_serve.py

pytestmark = pytest.mark.usefixtures("no_leaked_faults")


@pytest.fixture()
def no_leaked_faults():
    assert not faults.active(), "a previous test leaked an armed fault scope"
    yield
    assert not faults.active(), "this test leaked an armed fault scope"


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


CFG = serve.SamplerConfig(k=K)


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


def _assert_served(how, got, model, params, seed, n):
    """``got`` against direct sampling as ``tests/promise.py`` states it.
    Which replica, which batchmates and which of the two buckets served a
    row is a matter of timing here, so ``same_bucket`` admits either."""
    assert got.shape[0] == n
    promise.assert_sample_served(how, got, model, params, seed, K, (4, 8))


# ------------------------------------------------------------ clean routing


@pytest.mark.parametrize("how", promise.HOWS)
def test_router_bitwise_and_zero_compiles(model_and_params, how):
    """The inherited engine contract at fleet scope: mixed-size requests
    spread over two replicas all come back as direct sampling gives them
    (bitwise at a bucket's batch size, to the tolerance at their own n),
    with zero program builds after warmup anywhere."""
    model, params = model_and_params
    router = _router(model_and_params, replicas=2)
    sizes = [(41, 5), (42, 4), (43, 3), (44, 1)]
    tickets = {s: router.submit(seed=s, n=n, config=CFG) for s, n in sizes}
    for s, n in sizes:
        got = tickets[s].result(timeout=60)
        assert got.shape == (n, 16, 16, 3)
        _assert_served(how, got, model, params, s, n)
    h = router.drain(timeout=10)
    assert h["compiles_after_warmup"] == 0
    assert h["completed"] == len(sizes) and h["failed"] == 0
    assert h["active_replicas"] == 2 and h["retired_replicas"] == 0
    # every placement named a real replica and warmup compiled per replica
    for rid, rh in h["replicas"].items():
        assert rh["replica"] == rid
        assert rh["compiles_after_warmup"] == 0


def test_router_guided_request_bitwise(model_and_params):
    """x_init requests (the sample_from path) route like fresh ones — the
    router passes the host array through untouched."""
    model, params = model_and_params
    router = _router(model_and_params, replicas=2,
                     configs=[serve.SamplerConfig(k=K, t_start=1000)])
    x0 = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (3, 16, 16, 3)))
    t = router.submit(x_init=x0, config=serve.SamplerConfig(k=K, t_start=1000))
    got = t.result(timeout=60)
    want = np.asarray(sampling.sample_from(
        model, params, jnp.asarray(x0, jnp.float32), t_start=1000, k=K))
    np.testing.assert_array_equal(got, want)
    assert router.drain(timeout=10)["compiles_after_warmup"] == 0


def test_router_validation():
    with pytest.raises(ValueError, match="replicas"):
        Router(lambda rid: None, replicas=0, auto_start=False)
    with pytest.raises(ValueError, match="max_pending"):
        Router(lambda rid: None, replicas=1, max_pending=0, auto_start=False)


# ------------------------------------------------------- hedging and chaos


def test_hedged_replacement_is_bitwise(model_and_params):
    """A retryable failure (assembly-stage transient — the engine does NOT
    retry assembly internally) hedges the request once to another replica;
    the hedge re-issues the same rng, so the result is bitwise."""
    model, params = model_and_params
    router = _router(model_and_params, replicas=2)
    # the first placement of an idle fleet is deterministic (least loaded,
    # id tiebreak → r0); kill exactly one assembly there
    spec = FaultSpec("serve.assemble", "transient", rate=1.0,
                     match="replica:r0|", max_fires=1)
    with faults.inject(spec) as plan:
        t = router.submit(seed=51, n=3, config=CFG)
        got = t.result(timeout=60)
    _assert_served("same_bucket", got, model, params, 51, 3)
    assert len(plan.realized) == 1
    assert router.stats["hedges"] == 1
    h = router.drain(timeout=10)
    assert h["compiles_after_warmup"] == 0  # hedge reused warmed programs


def test_quarantined_request_is_never_hedged(model_and_params):
    """RequestQuarantinedError is terminal: bisection proved the request
    itself is the poison, so the router fails it through — with the
    replica-naming message — instead of poisoning the next replica."""
    router = _router(model_and_params, replicas=2)
    spec = FaultSpec("serve.dispatch", "permanent", rate=1.0,
                     match="replica:r0|")
    with faults.inject(spec):
        t = router.submit(seed=52, n=2, config=CFG)
        exc = t.exception(timeout=60)
        assert isinstance(exc, serve.RequestQuarantinedError)
        assert "replica 'r0'" in str(exc)
        assert router.stats["hedges"] == 0
        # let supervision retire the poisoned replica inside the fault
        # scope (its engine keeps the armed spec realistic); the request
        # counter guard (quarantine_limit=2) needs a second victim
        t2 = router.submit(seed=53, n=1, config=CFG)
        t2.exception(timeout=60)
        deadline = time.time() + 20
        while time.time() < deadline:
            h = router.health()
            if h["retired_replicas"] >= 1 and h["active_replicas"] >= 2:
                break
            time.sleep(0.05)
    h = router.drain(timeout=10)
    assert h["retired_replicas"] >= 1
    assert h["replicas_spawned"] >= 3  # 2 initial + the replacement


@pytest.mark.parametrize("how", promise.HOWS)
def test_fleet_chaos_contract(model_and_params, how):
    """ISSUE 6 acceptance: seeded schedule kills r0's dispatch outright
    (permanent) and injects 20–25% transients at assembly and placement.
    Every surviving ticket is what direct sampling gives (``how``), every
    failed ticket carries a typed cause naming its replica, r0 is drained
    and replaced, and compiles-after-warmup is 0 across ALL replicas —
    replacement included."""
    model, params = model_and_params
    router = _router(model_and_params, replicas=2, quarantine_limit=2,
                     max_hedges=2)
    schedule = (
        FaultSpec("serve.dispatch", "permanent", rate=1.0,
                  match="replica:r0|"),
        FaultSpec("serve.assemble", "transient", rate=0.25, seed=11),
        # scoped to r1 so place-transients never steer requests away from
        # r0 — the kill must actually be hit for the lifecycle to run
        FaultSpec("router.place", "transient", rate=0.2, seed=12,
                  match="replica:r1|"),
    )
    sizes = [(61, 3), (62, 2), (63, 4), (64, 1), (65, 2), (66, 3), (67, 1)]
    with faults.inject(*schedule) as plan:
        tickets = {s: router.submit(seed=s, n=n, config=CFG)
                   for s, n in sizes}
        outcomes = {s: tickets[s].exception(timeout=120) for s, _ in sizes}
        # which replica a request lands on is a matter of timing: where the
        # schedule sent r0 fewer requests than its quarantine limit, probes
        # make up the count (an idle fleet places on r0 first: least loaded,
        # id tiebreak; past the limit they land on r1 and succeed)
        for probe in range(4):
            if router.health()["retired_replicas"] >= 1:
                break
            router.submit(seed=70 + probe, n=1,
                          config=CFG).exception(timeout=120)
        # wait for supervision to finish the lifecycle: r0 retired and the
        # fleet back at target size
        deadline = time.time() + 30
        while time.time() < deadline:
            h = router.health()
            if h["retired_replicas"] >= 1 and h["active_replicas"] == 2:
                break
            time.sleep(0.05)
    assert len(plan.realized) >= 3 and "serve.dispatch" in plan.by_site()
    survivors = failures = 0
    for s, n in sizes:
        exc = outcomes[s]
        if exc is None:
            survivors += 1
            _assert_served(how, tickets[s].result(0), model, params, s, n)
        else:
            failures += 1
            # typed, and the message names the replica it died on
            assert isinstance(exc, serve.ServeError)
            assert "replica 'r" in str(exc)
    assert survivors >= 1  # the fleet kept serving through the kill
    h = router.drain(timeout=10)
    # the killed replica was drained (closed) and the fleet healed
    retired = [rh for rh in h["replicas"].values()
               if rh.get("state") == fleet.CLOSED and rh["replica"] == "r0"]
    assert h["retired_replicas"] >= 1 and retired, \
        f"r0 was not retired: {h['replicas'].keys()}"
    assert h["replicas_spawned"] >= 3
    assert h["active_replicas"] == 2
    # the headline: zero compiles after warmup, replacement included
    assert h["compiles_after_warmup"] == 0
    for rid, rh in h["replicas"].items():
        assert rh.get("compiles_after_warmup", 0) == 0, rid


def test_router_place_permanent_fault_fails_typed(model_and_params):
    """A permanent fault in the placement path itself (router.place) fails
    the request with a typed error naming the target replica."""
    router = _router(model_and_params, replicas=1)
    with faults.inject(FaultSpec("router.place", "permanent", rate=1.0)):
        t = router.submit(seed=54, n=1, config=CFG)
        exc = t.exception(timeout=30)
    assert isinstance(exc, serve.RequestFailedError)
    assert isinstance(exc.__cause__, faults.PermanentFault)
    assert "replica 'r0'" in str(exc)
    router.drain(timeout=5)


def test_replica_spawn_fault_is_fatal_at_cold_start(model_and_params):
    """replica.spawn chaos at construction surfaces immediately — a fleet
    that cannot build its initial replicas must not pretend to exist."""
    with faults.inject(FaultSpec("replica.spawn", "permanent", rate=1.0)):
        with pytest.raises(faults.PermanentFault):
            _router(model_and_params, replicas=1)


# -------------------------------------------------------------- tenant QoS


def test_qos_flooding_tenant_only_exhausts_its_share(model_and_params):
    """ISSUE 6 QoS acceptance at 4:1 weights over max_pending=10: the
    flooder caps at 8 (its excess gets QueueFullError), the light tenant
    keeps its 2 and completes within its deadline. auto_start=False makes
    admission deterministic: nothing resolves until start()."""
    router = _router(model_and_params, replicas=2,
                     tenants={"heavy": 4, "light": 1}, max_pending=10,
                     auto_start=False)
    heavy, rejected = [], 0
    for i in range(14):
        try:
            heavy.append(router.submit(seed=100 + i, n=1, config=CFG,
                                       tenant="heavy"))
        except serve.QueueFullError as exc:
            rejected += 1
            assert "'heavy'" in str(exc) and "fair share" in str(exc)
    assert len(heavy) == 8 and rejected == 6  # 10 * 4 // 5
    light = [router.submit(seed=200 + i, n=1, config=CFG, tenant="light",
                           priority=1, deadline_s=60.0) for i in range(2)]
    router.start()
    for t in light:
        assert t.result(timeout=60).shape == (1, 16, 16, 3)
        assert t.latency_s < 60.0  # completed within its deadline
    for t in heavy:
        assert t.result(timeout=60) is not None
    h = router.drain(timeout=10)
    assert h["rejected_by_tenant"] == {"heavy": 6}
    assert h["completed"] == 10
    assert h["compiles_after_warmup"] == 0


def test_qos_share_frees_up_as_tickets_resolve(model_and_params):
    """The cap is on admitted-UNRESOLVED requests: once the flood drains,
    the same tenant can submit again (backpressure, not a ban)."""
    router = _router(model_and_params, replicas=1,
                     tenants={"a": 1, "b": 1}, max_pending=4)
    first = [router.submit(seed=300 + i, n=1, config=CFG, tenant="a")
             for i in range(2)]
    for t in first:
        t.result(timeout=60)
    # share released — two more admit cleanly
    again = [router.submit(seed=310 + i, n=1, config=CFG, tenant="a")
             for i in range(2)]
    for t in again:
        t.result(timeout=60)
    assert router.drain(timeout=10)["rejected"] == 0


# -------------------------------------------------- shutdown and stub units


def test_router_drain_rejects_and_fails_queued(model_and_params):
    """After drain: new submissions raise EngineClosedError and anything
    still queued failed with it (typed, never stranded)."""
    router = _router(model_and_params, replicas=1, auto_start=False)
    t = router.submit(seed=70, n=1, config=CFG)
    h = router.drain(timeout=0.2)  # control loop never ran: t still queued
    assert h["closed"]
    assert isinstance(t.exception(timeout=5), serve.EngineClosedError)
    with pytest.raises(serve.EngineClosedError):
        router.submit(seed=71, n=1, config=CFG)


class StubReplica(fleet.ReplicaHandle):
    """Health-programmable replica for supervision units (no jax, no
    engine — exactly the ReplicaHandle surface the router sees)."""

    def __init__(self, rid):
        self.replica_id = rid
        self.state = fleet.NEW
        self.drained = False
        self.h = {"stalled": False, "closed": False, "quarantined": 0,
                  "queue_depth": 0, "open_tickets": 0,
                  "last_progress_s": 0.0, "compiles_after_warmup": 0}

    def warm(self, configs, buckets=None, **kwargs):
        self.state = fleet.READY
        return {"new_compiles": 0}

    def start(self):
        pass

    def health(self):
        return dict(self.h, state=self.state, replica=self.replica_id)

    def drain(self, timeout=None):
        self.drained = True
        self.state = fleet.CLOSED
        return self.health()

    def close(self):
        self.state = fleet.CLOSED


def test_supervision_retires_and_replaces_stalled_replica():
    """A replica whose snapshot turns stalled is drained and replaced —
    pure control-plane logic, provable without an engine."""
    reps = {}

    def factory(rid):
        reps[rid] = StubReplica(rid)
        return reps[rid]

    router = Router(factory, replicas=2, configs=(), tick_s=0.01)
    reps["r0"].h["stalled"] = True
    deadline = time.time() + 10
    while time.time() < deadline:
        h = router.health()
        if h["retired_replicas"] == 1 and h["active_replicas"] == 2:
            break
        time.sleep(0.02)
    assert reps["r0"].drained and reps["r0"].state == fleet.CLOSED
    assert "r2" in reps  # the replacement
    h = router.drain(timeout=2)
    assert h["replicas_spawned"] == 3 and h["replicas_retired"] == 1


def test_supervision_counts_spawn_failures_and_retries():
    """A failing factory leaves a deficit and a counter — the fleet keeps
    retrying on its tick instead of crashing the control loop."""
    calls = {"n": 0}

    def factory(rid):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("no capacity")
        return StubReplica(rid)

    router = Router(factory, replicas=2, configs=(), tick_s=0.01)
    # retire r0 → replacement spawn fails → deficit persists, counter grows
    router._replicas["r0"].h["quarantined"] = 99
    deadline = time.time() + 10
    while time.time() < deadline:
        if router.stats["spawn_failures"] >= 2:
            break
        time.sleep(0.02)
    assert router.stats["spawn_failures"] >= 2
    assert router.health()["active_replicas"] == 1
    router.drain(timeout=2)


def test_wedge_detection_from_snapshot():
    """wedge_after_s retires a replica whose last_progress_s age exceeds
    the budget while it holds open tickets — the snapshot-only stall
    detection the engine's health() satellite exists for."""
    reps = {}

    def factory(rid):
        reps[rid] = StubReplica(rid)
        return reps[rid]

    router = Router(factory, replicas=1, configs=(), tick_s=0.01,
                    wedge_after_s=0.5)
    reps["r0"].h.update(open_tickets=3, last_progress_s=9.0)
    deadline = time.time() + 10
    while time.time() < deadline:
        if router.stats["replicas_retired"] >= 1:
            break
        time.sleep(0.02)
    assert reps["r0"].drained
    router.drain(timeout=2)


def test_sp_ticket_failover_reuses_warmed_programs(model_and_params):
    """Sequence-parallel placement contract (the sp tentpole at fleet
    scope): every replica warms the SAME config set, sp included, so an sp
    ticket hedged off a faulted replica lands on a peer whose (data, seq)
    program is already compiled — allclose to direct (the mesh tolerance)
    with zero compiles after warmup anywhere."""
    model, params = model_and_params
    sp_cfg = serve.SamplerConfig(k=K, sp_mode="ulysses", sp_degree=2)
    router = _router(model_and_params, replicas=2, configs=[CFG, sp_cfg])
    spec = FaultSpec("serve.assemble", "transient", rate=1.0,
                     match="replica:r0|", max_fires=1)
    with faults.inject(spec) as plan:
        t = router.submit(seed=91, n=4, config=sp_cfg)
        got = t.result(timeout=60)
    np.testing.assert_allclose(
        got, _direct(model, params, 91, 4), rtol=2e-5, atol=2e-5)
    assert len(plan.realized) == 1
    assert router.stats["hedges"] == 1
    h = router.drain(timeout=10)
    assert h["compiles_after_warmup"] == 0
