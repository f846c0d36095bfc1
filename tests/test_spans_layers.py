"""The program's one span recorder (ISSUE 24): one clock
(``time.perf_counter_ns``), layer spans always on in a bounded ring, mirrored
into a live profiler session as ``ddim/<name>``, and the spans and counters
at the loader's, the samplers', the engine's and JAX's compile boundaries."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu import serve
from ddim_cold_tpu.data import loader as data_loader
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.obs import metrics, spans
from ddim_cold_tpu.ops import sampling, schedule
from ddim_cold_tpu.utils import profiling

TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)


@pytest.fixture(autouse=True)
def empty_record():
    spans.disable()
    spans.clear()
    yield
    assert not spans.enabled(), "test leaked an enabled tracing state"
    spans.clear()


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


def _named(name):
    return [s for s in spans.layer_spans() if s.name == name]


def _total(counter):
    return sum(series.get(counter, 0)
               for series in metrics.snapshot().values())


# ------------------------------------------------------------ the recorder


def test_span_times_are_perf_counter_ns_readings():
    before = time.perf_counter_ns()
    with spans.layer("outer", batch=3) as outer:
        with spans.layer("inner") as inner:
            pass
    with spans.tracing():
        ticket = spans.begin("engine.request")
        ticket.end()
    after = time.perf_counter_ns()
    for s in (outer, inner, ticket):
        assert isinstance(s.t0, int) and isinstance(s.t1, int)
        assert before <= s.t0 <= s.t1 <= after
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    # what the guide asks of a span: name, start, end, cause, unit, attributes
    assert inner.parent_id == outer.span_id and outer.parent_id is None
    assert inner.trace_id == outer.trace_id and outer.attrs == {"batch": 3}
    # layer spans are always recorded, apart from the opt-in ticket traces
    assert spans.layer_spans() == [outer, inner]
    assert spans.spans() == [ticket]


def test_layer_span_parent_is_per_thread_and_trace_can_cross_threads():
    seen = {}

    def worker(trace_id):
        seen["root"] = spans.current()
        with spans.layer("work", trace_id=trace_id) as s:
            seen["span"] = s

    with spans.layer("main") as main:
        t = threading.Thread(target=worker, args=(main.trace_id,))
        t.start()
        t.join()
        assert spans.current() is main
    assert spans.current() is None
    # another thread's open span is no parent, but the unit of work is shared
    assert seen["root"] is None and seen["span"].parent_id is None
    assert seen["span"].trace_id == main.trace_id


def test_ring_drops_the_oldest_and_stays_its_length(monkeypatch):
    assert spans.RING_LEN == 65536
    assert spans.recorder()._ring.maxlen == spans.RING_LEN
    monkeypatch.setattr(spans, "RING_LEN", 8)
    rec = spans.Recorder()
    made = [rec.layer("s", i=i) for i in range(20)]
    for s in made:
        s.end()
    kept = rec.layer_spans()
    assert len(kept) == 8 and kept == made[-8:]
    rec.event("jax/x", time.perf_counter_ns(), 1000)
    assert len(rec.layer_spans()) == 8


def test_closed_span_lets_go_of_its_stack_and_its_annotation():
    """A closed span stays in the ring for a long time: it must not keep a
    dead thread's open-span stack or the profiler's annotation alive."""
    exited = []

    class Annotation:
        def __exit__(self, *exc):
            exited.append(self)

    rec = spans.Recorder()
    rec.set_sink(lambda name: Annotation())
    s = rec.layer("held")
    assert s._open == [s] and isinstance(s._mirror, Annotation)
    s.end()
    s.end()  # idempotent: the annotation is closed once
    assert s._open is None and s._mirror is None and len(exited) == 1
    assert rec.current() is None and rec.layer_spans() == [s]


def test_recorder_under_many_threads_loses_no_span():
    """More threads than cores, a short switch interval: every span lands in
    the ring once, nests under its own thread's spans only, and ids are
    unique."""
    import sys

    rec = spans.Recorder()
    n_threads, n_each = 32, 200
    errors = []

    def worker(k):
        try:
            for i in range(n_each):
                with rec.layer("outer", k=k) as outer:
                    with rec.layer("inner", k=k) as inner:
                        rec.event("jax/x", time.perf_counter_ns(), 10, k=k)
                    assert inner.parent_id == outer.span_id
                    assert rec.current() is outer
            assert rec.current() is None
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    got = rec.layer_spans()
    assert len(got) == 3 * n_threads * n_each
    assert len({s.span_id for s in got}) == len(got)
    by_id = {s.span_id: s for s in got}
    for s in got:
        assert s.ended
        if s.parent_id is not None:
            assert by_id[s.parent_id].attrs["k"] == s.attrs["k"]


def test_event_is_a_closed_child_of_the_open_span():
    with spans.layer("outer") as outer:
        t1 = time.perf_counter_ns()
        e = spans.event("jax/backend_compile_duration", t1, 2_000_000,
                        event="/jax/core/compile/backend_compile_duration")
    assert (e.t0, e.t1) == (t1 - 2_000_000, t1) and e.ended
    assert e.parent_id == outer.span_id and e.trace_id == outer.trace_id
    orphan = spans.event("jax/x", time.perf_counter_ns(), 0)
    assert orphan.parent_id is None and orphan.trace_id != outer.trace_id


# ------------------------------------------------- the profiler's timeline


def _host_events(trace_dir, prefix):
    """{name: [(start_ns, end_ns)]} of the host planes' ``prefix`` events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return found


def test_spans_are_mirrored_on_the_profilers_clock(tmp_path):
    """With a session live each span is also a ``ddim/<name>`` event on the
    host plane of the ``.xplane.pb``; the gap between two of them there is
    the gap between the two in-memory spans: one clock."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.layer("mirror/a") as a:
            time.sleep(0.01)
        time.sleep(0.03)
        with spans.layer("mirror/b") as b:
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    with spans.layer("mirror/after"):  # the session is over: memory only
        pass
    found = _host_events(str(tmp_path), "ddim/")
    assert set(found) == {"ddim/mirror/a", "ddim/mirror/b"}
    (a0, a1), = found["ddim/mirror/a"]
    (b0, b1), = found["ddim/mirror/b"]
    assert abs((b0 - a0) - (b.t0 - a.t0)) < 1_000_000  # within 1 ms
    assert abs((a1 - a0) - (a.t1 - a.t0)) < 1_000_000
    assert abs((b1 - b0) - (b.t1 - b.t0)) < 1_000_000


def test_no_session_no_profiler_call(monkeypatch):
    """Off the profiler a span costs one ``is_enabled()`` read and no
    annotation: nothing of ``jax.profiler`` is constructed."""
    calls = {"is_enabled": 0, "made": 0}

    class Annotation:
        def __init__(self, name):
            calls["made"] += 1

        @staticmethod
        def is_enabled():
            calls["is_enabled"] += 1
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with spans.layer("quiet") as s:
        pass
    assert s.ended and s._mirror is None
    assert calls == {"is_enabled": 1, "made": 0}


def test_listener_and_sink_are_installed_once():
    import importlib

    from jax._src import monitoring

    def ours():
        return [fn for fn in monitoring.get_event_duration_listeners()
                if getattr(fn, "__name__", "") == "_on_duration"]

    assert len(ours()) == 1
    importlib.reload(profiling)
    assert len(ours()) == 1
    assert spans.recorder()._sink is not None


# ----------------------------------------------------------------- loader


def _drain(fn, consumer_sleep, n=6, **kwargs):
    out = []
    for item in data_loader._background_map(range(n), fn, 2, **kwargs):
        time.sleep(consumer_sleep)
        out.append(item)
    assert out == [fn(i) for i in range(n)]
    return _named("data/place/work"), _named("data/place/get_wait")


def test_slow_producer_shows_as_get_wait_on_the_consumer():
    def slow(i):
        time.sleep(0.02)
        return i * i

    work, get_wait = _drain(slow, 0.0, stage="place", waits=True, epoch=7)
    assert len(work) == 6
    assert len(get_wait) >= 6  # every batch, and the end of the stream
    assert sum(s.t1 - s.t0 for s in get_wait) > 0.08e9
    assert [s.attrs["batch"] for s in work] == list(range(6))
    assert [s.attrs["batch"] for s in get_wait[:6]] == list(range(6))
    assert all(s.attrs["epoch"] == 7 for s in work + get_wait)
    # the consumer's waits and the producer thread's work: one pipeline id,
    # on two threads
    assert len({s.trace_id for s in work + get_wait}) == 1
    assert all(s.parent_id is None for s in work + get_wait)


def test_slow_consumer_waits_for_the_first_item_only():
    work, get_wait = _drain(lambda i: i * i, 0.02, stage="place", waits=True)
    assert len(work) == 6
    # the pipeline fills while the consumer sleeps
    assert all(s.attrs["batch"] == 0 for s in get_wait)
    assert len({s.trace_id for s in work}) == 1


def test_pipeline_records_only_what_it_is_asked_for():
    """The decode stage records its work and no wait; a pipeline with no
    stage (the engine's batch assembly) records nothing of the data layer."""
    def slow(i):
        time.sleep(0.005)
        return i * i

    _drain(slow, 0.0, stage="decode", epoch=1)
    assert len(_named("data/decode/work")) == 6
    assert not _named("data/decode/get_wait")
    spans.clear()
    _drain(slow, 0.0)
    list(data_loader.device_prefetch(range(3), slow, stage=None))
    assert spans.layer_spans() == []
    list(data_loader.device_prefetch(range(3), slow))
    assert {s.name for s in spans.layer_spans()} == {
        "data/place/work", "data/place/get_wait"}


class _Rows:
    """Eight 4x4 'images' behind the loader's raw-batch contract."""

    def __len__(self):
        return 8

    def get_raw_batch(self, idxs, num_threads=1, pool=None):
        return (np.zeros((len(idxs), 4, 4, 3), np.uint8),
                np.asarray(idxs, np.int32))


@pytest.mark.parametrize("num_threads", [1, 4])
def test_sharded_loader_records_its_batches_with_their_epoch(num_threads):
    loader = data_loader.ShardedLoader(_Rows(), 2, shuffle=False, raw=True,
                                       num_threads=num_threads)
    loader.set_epoch(3)
    assert len(list(loader)) == 4
    work = _named("data/decode/work")
    assert [s.attrs["batch"] for s in work] == [0, 1, 2, 3]
    assert all(s.attrs["epoch"] == 3 for s in work)
    assert len({s.trace_id for s in work}) == 1  # one pipeline, one id
    assert {s.name for s in spans.layer_spans()} == {"data/decode/work"}
    # the placing stage takes the loader's epoch onto its own spans
    list(data_loader.device_prefetch(loader, lambda b: b))
    placed = _named("data/place/work")
    assert len(placed) == 4 and all(s.attrs["epoch"] == 3 for s in placed)
    assert len(_named("data/decode/work")) == 8


# ---------------------------------------------------------------- samplers


@pytest.mark.parametrize("sampler,kwargs,steps", [
    ("ddim_sample", dict(k=500), len(range(2000 - 1, 0, -500))),
    ("ddim_sample", dict(k=300, t_start=1000), len(range(1000, 0, -300))),
    ("cold_sample", dict(levels=3), 3),
    ("ddim_sample_fewstep", dict(steps=2), 2),
])
def test_sampler_call_has_both_children_and_counts_its_steps(
        model_and_params, sampler, kwargs, steps):
    model, params = model_and_params
    if "t_start" in kwargs:  # the guided path, as sample_from calls it
        out = sampling.sample_from(
            model, params, jnp.zeros((3, 16, 16, 3)), kwargs["t_start"],
            k=kwargs["k"])
    else:
        out = getattr(sampling, sampler)(model, params, jax.random.PRNGKey(1),
                                         n=3, **kwargs)
    assert out.shape == (3, 16, 16, 3)
    (call,) = _named("sampler/call")
    children = [s for s in spans.layer_spans()
                if s.parent_id == call.span_id
                and not s.name.startswith("jax/")]
    assert [s.name for s in children] == ["sampler/init", "sampler/dispatch"]
    assert all(call.t0 <= s.t0 <= s.t1 <= call.t1 for s in children)
    assert call.attrs["n"] == 3 and call.attrs["scan_steps"] == steps
    assert {"k", "steps"} & set(call.attrs)
    assert len({s.trace_id for s in [call] + children}) == 1
    if sampler == "ddim_sample":  # the count the benchmark's driver computes
        assert steps == len(schedule.ddim_time_sequence(
            2000, kwargs["k"], kwargs.get("t_start")))


# ------------------------------------------------------------ JAX compiles


def test_compile_leaves_events_under_the_open_span_once():
    """A new jitted function inside an open span leaves ``jax/*`` events
    whose parent is that span, counts as a compile on this thread, and a
    second call of it leaves nothing."""
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 0.125)
    x = jnp.ones((5, 7))  # made first: its own eager ops compile here
    metrics.reset()
    spans.clear()
    before = profiling.compile_count()
    with spans.layer("outer") as outer:
        f(x).block_until_ready()
    events = [s for s in spans.layer_spans() if s.name.startswith("jax/")]
    assert events and all(s.parent_id == outer.span_id for s in events)
    assert all(s.ended and s.t1 <= outer.t1 for s in events)
    names = {s.name for s in events}
    assert {"jax/jaxpr_trace_duration", "jax/jaxpr_to_mlir_module_duration",
            "jax/backend_compile_duration"} <= names
    backend = [s for s in events if s.name == "jax/backend_compile_duration"]
    assert backend[0].attrs["event"] == (
        "/jax/core/compile/backend_compile_duration")
    assert profiling.compile_count() - before == len(backend) == 1
    assert _total("runtime.compiles") == 1
    n = len(spans.layer_spans())
    with spans.layer("again"):
        f(x).block_until_ready()
    assert len(spans.layer_spans()) == n + 1  # the span itself, no event
    assert profiling.compile_count() - before == 1


def test_compile_count_is_per_thread():
    counts = {}

    def other():
        counts["start"] = profiling.compile_count()

    jax.jit(lambda x: x * 1.5 - 2.0)(jnp.ones(3)).block_until_ready()
    assert profiling.compile_count() >= 1
    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert counts["start"] == 0


# ------------------------------------------------------------------ engine


def test_engine_counts_assembly_compiles_and_queue_wait(model_and_params):
    """Batches whose tuples of part shapes differ make the eager assembly
    compile; a tuple seen before does not. Every planned ticket leaves its
    submit-to-plan wait."""
    model, params = model_and_params
    cfg = serve.SamplerConfig(k=500)
    eng = serve.Engine(model, params, buckets=(8,))
    serve.warmup(eng, [cfg], persistent_cache=False)

    def drain(sizes, seed):
        tickets = [eng.submit(seed=seed + i, n=n, config=cfg)
                   for i, n in enumerate(sizes)]
        time.sleep(0.01)
        eng.run()
        return [t.result(timeout=60) for t in tickets]

    drain((3, 2), 300)                       # parts (3, 2) and 3 pad rows
    first = eng.stats["assemble_compiles"]
    assert first >= 1
    drain((3, 2), 310)                       # the same tuple: nothing new
    assert eng.stats["assemble_compiles"] == first
    drain((1, 4, 2), 320)                    # a tuple not seen before
    assert eng.stats["assemble_compiles"] > first
    waits = eng.stats["queue_waits_s"]
    assert len(waits) == 7 and all(w >= 0.01 for w in waits)
    h = eng.health()
    assert h["assemble_compiles"] == eng.stats["assemble_compiles"]
    assert 0.01 <= h["queue_wait_p50_s"] <= h["queue_wait_p95_s"]
    assert eng.stats["compiles"] == 1        # the sampler program: warm-up's


def test_engine_stages_are_live_layer_spans_and_ticket_copies(
        model_and_params):
    model, params = model_and_params
    cfg = serve.SamplerConfig(k=500)
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [cfg], persistent_cache=False)
    spans.clear()
    # ticket traces off: the batch's stages are recorded all the same
    t = eng.submit(seed=400, n=2, config=cfg)
    eng.run()
    plain = t.result(timeout=60)
    live = {s.name for s in spans.layer_spans()}
    assert {"engine/plan", "engine/assemble", "engine/dispatch",
            "engine/fetch"} <= live
    assert spans.spans() == [] and t.span is None
    (assemble,) = _named("engine/assemble")
    assert assemble.attrs == {"bucket": 4}
    # assembly runs in the prefetch pipeline, which is not the data layer's
    assert not any(name.startswith("data/") for name in live)
    # on: each ticket gets closed copies over the same measured windows,
    # and its queue wait as a span
    spans.clear()
    with spans.tracing():
        t2 = eng.submit(seed=400, n=2, config=cfg)
        eng.run()
        traced = t2.result(timeout=60)
        copies = {s.name: s for s in spans.spans()
                  if s.parent_id == t2.span.span_id}
    np.testing.assert_array_equal(plain, traced)
    assert {"queue_wait", "plan", "assemble", "dispatch",
            "fetch"} <= set(copies)
    for name in ("plan", "assemble", "dispatch", "fetch"):
        (stage,) = _named("engine/" + name)
        assert (copies[name].t0, copies[name].t1) == (stage.t0, stage.t1)
    wait = copies["queue_wait"]
    assert wait.t0 == int(t2.submit_time * 1e9) and wait.t1 == copies["plan"].t0
