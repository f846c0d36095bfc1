"""Open loop through the program's ``serve.Router``: requests are sent when
they are due, whatever the system is doing, and each is timed from the moment
it was DUE.

Traffic file: {"driver": "serve_open", "arrivals": {...},
"images_per_request": {...} (see ``benchmark/traffic.py``), "buckets": [...],
"sampler": {"sampler": "ddim", "k": 20}, "drain_s": wait for stragglers after
the window, "check_requests": finished requests compared with the reference}.

``attempted`` is the requests due in the window; ``failed`` those that
raised, were rejected, or were not done ``drain_s`` after it closed (they
count with a latency of window + drain). ``serve_img_per_s`` counts the
images of requests finished inside the window.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import stats, traffic, weights
from benchmark.drivers import common
from benchmark.harness import Compared, log


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from
    ``jax.monitoring`` (listeners cannot be removed, so one per process)."""

    _instance = None

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += seconds

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def setup(run) -> dict:
    from ddim_cold_tpu import serve

    model = common.build_model(run.config)
    params = weights.make(run.config, run.seed)
    buckets = tuple(int(b) for b in run.traffic["buckets"])
    cfg = serve.SamplerConfig(**run.traffic["sampler"])
    replicas = []
    base = serve.local_factory(model, params, buckets=buckets)

    def factory(replica_id: str):
        rep = base(replica_id)
        replicas.append(rep)
        return rep

    with run.spans.span("router_warm"):
        router = serve.Router(factory, replicas=1, configs=[cfg])
    engine = replicas[0].engine
    state = {"model": model, "params": params, "router": router, "cfg": cfg,
             "engine": engine, "clock": CompileClock.get()}
    # every program once, then every request size of the mix once: the
    # engine draws each request's noise at the request's own size
    sizes = sorted({int(k) for k in run.traffic["images_per_request"]})
    with run.spans.span("warmup"):
        for i, n in enumerate(list(buckets) + sizes):
            router.submit(seed=i, n=n, config=cfg).result(timeout=900)
        # then the cell's own traffic for a while, on another seed: the
        # engine assembles each batch with eager device ops, which JAX
        # compiles once for every new combination of request sizes; this
        # fills that cache with the common ones before the window opens
        warm_s = float(run.traffic.get("warmup_traffic_s", 0))
        if warm_s > 0:
            _, tickets, _, _ = _offer(run, state, warm_s, run.seed + 1)
            for _, t in tickets:
                t.exception(timeout=900)
    if engine.stats["failed_tickets"]:
        raise RuntimeError("warm-up requests failed")
    return state


def _offer(run, state, seconds: float, seed: int):
    """Send the schedule's requests when they are due. Returns the window's
    opening time, (request, ticket) pairs, lateness and refusals."""
    router, cfg = state["router"], state["cfg"]
    reqs = traffic.schedule(run.traffic, seconds, seed)
    tickets, lateness, refused = [], [], 0
    t0 = time.perf_counter()
    for r in reqs:
        delay = t0 + r.due_s - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        lateness.append(time.perf_counter() - (t0 + r.due_s))
        try:
            tickets.append((r, router.submit(seed=r.seed, n=r.images,
                                             config=cfg)))
        except Exception as e:  # noqa: BLE001 - a refusal is a failure
            refused += 1
            log(f"request {r.index} refused: {e!r}")
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    return t0, tickets, lateness, refused


def window(run, state, seconds: float) -> dict:
    engine = state["engine"]
    drain_s = float(run.traffic["drain_s"])
    s0, c0 = engine.stats, state["clock"].seconds
    with run.spans.span("generate"):
        t0, tickets, lateness, refused = _offer(run, state, seconds, run.seed)
    t1 = t0 + seconds
    with run.spans.span("drain"):
        deadline = t1 + drain_s
        for _, t in tickets:
            try:
                t.exception(timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                break  # the rest are judged as they stand
    s1 = engine.stats
    latencies, finished, images_in_window, failed = [], [], 0, refused
    for r, t in tickets:
        ok = t.done and not t.failed
        if ok:
            latencies.append(t.done_time - (t0 + r.due_s))
            finished.append((r, t))
            if t.done_time <= t1:
                images_in_window += r.images
        else:
            failed += 1
            latencies.append(seconds + drain_s)
    latencies += [seconds + drain_s] * refused
    attempted = len(tickets) + refused
    log(f"{attempted} requests due, {len(finished)} finished, {failed} failed; "
        f"{images_in_window} images inside the window; generator late p95 "
        f"{stats.percentile(lateness, 95) * 1e3:.3f} ms; latency ms "
        + " ".join(f"p{q} {stats.percentile(latencies, q) * 1e3:.1f}"
                   for q in (50, 75, 90, 95, 99))
        + f" mean {sum(latencies) / len(latencies) * 1e3:.1f}")
    return {
        "attempted": attempted, "failed": failed, "window_s": seconds,
        "t0": t0, "t1": t1,
        "e2e": {"serve_img_per_s": images_in_window / seconds},
        "latency_s": latencies,
        "counters": {
            "rows": s1["rows"] - s0["rows"],
            "padded_rows": s1["padded_rows"] - s0["padded_rows"],
            "compiles": s1["compiles"] - s0["compiles"],
            "dispatches": s1["dispatches"] - s0["dispatches"],
            "max_queue_depth": s1["max_queue_depth"],
            "jax_compile_s": state["clock"].seconds - c0},
        "lateness_s": lateness, "finished": finished,
    }


def close(run, state) -> None:
    state["router"].close()


def check(run, state, result) -> list:
    """A seeded sample of the finished requests, the largest among them,
    each against the reference's trajectory from the request's own noise."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import ddim

    finished = result["finished"]
    if not finished:
        return [Compared("requests_finished", 1.0, 0.0)]
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 0xC4EC]))
    order = rng.permutation(len(finished))
    largest = max(range(len(finished)), key=lambda i: finished[i][0].images)
    picks = [largest] + [int(i) for i in order if i != largest]
    picks = picks[: int(run.traffic["check_requests"])]
    h, w = state["model"].img_size
    inits = [jax.random.normal(jax.random.PRNGKey(finished[i][0].seed),
                               (finished[i][0].images, h, w, 3), jnp.float32)
             for i in picks]
    x_init = jnp.concatenate(inits)
    want = np.asarray(ddim.sample(
        state["params"], x_init, k=state["cfg"].k,
        total_steps=run.config["total_steps"],
        arch=weights.arch_of(run.config)))
    got = [np.asarray(finished[i][1].result(timeout=0)) for i in picks]
    bad_shape = any(g.shape != (finished[i][0].images, h, w, 3)
                    or not np.isfinite(g).all() for g, i in zip(got, picks))
    if bad_shape:
        return [Compared("tickets_finite_and_shaped", 1.0, 0.0)]
    edges = np.cumsum([0] + [len(g) for g in got])
    result["reference"] = (x_init, want, edges)
    return [Compared("tickets_finite_and_shaped", 0.0, 0.0)] + _compare(
        run, np.concatenate(got), want, edges)


def _compare(run, got, want, edges) -> list:
    worst = max(common.rms(got[a:b], want[a:b])
                for a, b in zip(edges[:-1], edges[1:]))
    limits = run.cell.limits
    return [
        Compared("serve_rms_vs_reference", common.rms(got, want),
                 limits["serve_rms_vs_reference"]),
        Compared("serve_worst_request_rms", worst,
                 limits["serve_worst_request_rms"]),
    ]


def control(run, state, result) -> list:
    """The reference one precision below the configuration's, put in the
    program's place on the requests ``check`` compared. Must fail a limit."""
    from benchmark.reference import ddim, lowprec

    x_init, want, edges = result["reference"]
    below = lowprec.BY_NAME[lowprec.BELOW[run.config["precision"]]]
    got = np.asarray(ddim.sample(
        state["params"], x_init, k=state["cfg"].k,
        total_steps=run.config["total_steps"],
        arch=weights.arch_of(run.config), ops=below))
    return _compare(run, got, want, edges)
