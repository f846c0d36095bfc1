"""The readers of set-up's stages over a hand-made record: each gives its
hand-computed value, nesting counts once, a span that straddles the end of
set-up is clipped, a stage with no span reads 0.0, a program without a record
(or with a ring that dropped events) reads ``None``, and the four parts make
the stretch."""

import json
import os
import sys
import time
import types

import pytest

from benchmark import manifest as mf
from benchmark import result_line
from benchmark.layer_metrics import setup_record

from ddim_cold_tpu.obs import metrics, spans

MS = 1_000_000
S = 1_000_000_000
#: the process starts at 10 s on the spans' clock, set-up ends at 50 s
#: (``first_steps`` closes), a profiler starts, the window is 60 s to 70 s
START, END, T0, T1 = 10.0, 50.0, 60.0, 70.0

SETUP = ["setup_cache_load_s", "setup_trace_lower_s", "setup_data_s",
         "setup_place_state_s", "setup_start_s", "setup_first_run_wait_s",
         "setup_unattributed_s"]
SAMPLER_CELLS = ["flower200_sample_k20", "jamba2_3b_sample512_k20",
                 "laguna_s21_sample1024_k20", "glm52_sample1536_k50"]
PENDING = os.path.join(mf.HERE, "layer_metrics", "pending_sampler_cells.json")


def view(records=(("dataset", 24.9, 28.1), ("first_steps", 40.0, END),
                  ("next_batch", 40.0, 41.2), ("dispatch", 41.2, 44.0),
                  ("dispatch", 61.0, 61.1))):
    return types.SimpleNamespace(
        result={"t0": T0, "t1": T1}, window_s=T1 - T0,
        spans=types.SimpleNamespace(records=list(records)))


def at(name, start_s, dur_ms, **attrs):
    """A closed span of the program's record from ``start_s`` on."""
    return spans.event(name, int(start_s * S) + int(dur_ms * MS),
                       int(dur_ms * MS), **attrs)


def jax_event(name, start_s, dur_ms, **attrs):
    if name == "backend_compile_duration":
        metrics.scope("runtime").inc("runtime.compiles")
    return at("jax/" + name, start_s, dur_ms, **attrs)


@pytest.fixture(autouse=True)
def empty_record(monkeypatch):
    spans.clear()
    metrics.reset()
    monkeypatch.setattr(sys.modules["__main__"], "_T0", START, raising=False)
    yield
    spans.clear()
    metrics.reset()


def training_setup():
    """A warm training cell's set-up, by hand."""
    jax_event("jaxpr_trace_duration", 20.0, 1000.0)       # weights: first
    jax_event("jaxpr_trace_duration", 20.2, 300.0)        # nested: once
    jax_event("jaxpr_to_mlir_module_duration", 21.0, 500.0)
    jax_event("backend_compile_duration", 22.0, 2000.0)
    jax_event("cache_retrieval_time_sec", 22.5, 500.0)    # inside the compile
    jax_event("compile_time_saved_sec", 23.0, 0.0, saved_s=40.0)
    at("data/dataset/open", 25.0, 2000.0, images=8, cached=True)
    at("data/native/load", 25.5, 1000.0, built=True)
    at("data/native/build", 25.6, 800.0, ok=True)
    at("data/decode/work", 27.0, 1000.0, batch=0, epoch=0)
    at("parallel/place_state", 30.0, 500.0, bytes=4096, leaves=3, devices=4)
    # the first steps: the step's trace, lowering and cache load, a wait on
    # the loader inside the trace, then the host waits for the device
    jax_event("jaxpr_trace_duration", 40.0, 2000.0, fun="step_body")
    at("data/place/get_wait", 41.0, 200.0, batch=0, epoch=0)
    jax_event("jaxpr_to_mlir_module_duration", 42.0, 500.0, fun="step_body")
    jax_event("backend_compile_duration", 42.5, 1500.0, fun="step_body")
    jax_event("cache_retrieval_time_sec", 43.0, 800.0)
    at("data/decode/work", 49.5, 1500.0, batch=4, epoch=0)  # straddles END
    # after set-up: the profiler's start, then the window
    jax_event("backend_compile_duration", 55.0, 1000.0)
    at("data/decode/work", 61.0, 20.0, batch=9, epoch=0)


def read(name, v=None):
    return mf.load_reader(name).read(v or view())


def test_each_stage_reads_its_hand_computed_seconds():
    training_setup()
    assert read("setup_start_s") == pytest.approx(10.0)
    assert read("setup_cache_load_s") == pytest.approx(0.5 + 0.8)
    # [20, 21.5] and [40, 42.5]: the nested trace counts once
    assert read("setup_trace_lower_s") == pytest.approx(1.5 + 2.5)
    # [25, 28] with load and build inside it, the wait, and the straddling
    # decode clipped at the end of set-up
    assert read("setup_data_s") == pytest.approx(3.0 + 0.2 + 0.5)
    assert read("setup_place_state_s") == pytest.approx(0.5)
    # first_steps is [40, 50]; the program covers [40, 44] and [49.5, 50]
    assert read("setup_first_run_wait_s") == pytest.approx(5.5)
    # the gaps 21.5-22, 24-25, 28-30 and 30.5-40
    assert read("setup_unattributed_s") == pytest.approx(13.0)
    # the accepted reader counts whole events that ended before the window
    assert read("setup_compile_s") == pytest.approx(1.5 + 2.0 + 4.0 + 1.0)


def test_the_four_parts_make_the_stretch_to_the_millisecond():
    training_setup()
    setup = setup_record.of(view())
    assert setup.stretch_s == pytest.approx(END - START)
    parts = (read("setup_start_s") + setup.covered_s
             + read("setup_first_run_wait_s") + read("setup_unattributed_s"))
    assert abs(parts - setup.stretch_s) < 1e-3
    assert setup.covered_s == pytest.approx(1.5 + 2.0 + 3.0 + 0.5 + 4.0 + 0.5)


def test_a_sampler_cells_warm_up_call_is_the_first_run_wait():
    """``sampler/call`` ends when the scan's call returns: the device's 17 s
    lie under no program span, and the set-up ends with ``warmup``."""
    jax_event("backend_compile_duration", 20.0, 1000.0)   # the weights'
    call = at("sampler/call", 30.0, 3000.0, n=4, k=20, scan_steps=100)
    at("sampler/init", 30.0, 10.0)
    jax_event("backend_compile_duration", 30.5, 2000.0, fun="run_scan")
    at("sampler/dispatch", 32.9, 100.0)
    v = view([("warmup", 30.0, 50.0), ("ddim_sample", 60.0, 70.0)])
    assert call.t1 == 33 * S
    assert read("setup_first_run_wait_s", v) == pytest.approx(17.0)
    assert read("setup_start_s", v) == pytest.approx(10.0)
    assert read("setup_unattributed_s", v) == pytest.approx(9.0)   # 21-30
    assert read("setup_data_s", v) == 0.0


@pytest.mark.parametrize("name", SETUP)
def test_a_stage_without_a_span_of_its_name_took_no_time(name):
    """A recorder and no span of the name is 0.0 s, never ``None``: what the
    parent, which has no ``parallel/place_state``, reads."""
    jax_event("backend_compile_duration", START, 1000.0)  # from the start on
    value = read(name, view([("first_steps", END, END)]))
    # nothing but the one event: what it leaves of the stretch is unattributed
    assert value == (pytest.approx(END - START - 1.0)
                     if name == "setup_unattributed_s" else 0.0)


@pytest.mark.parametrize("name", SETUP)
def test_no_record_or_a_wrapped_ring_reads_none(name, monkeypatch):
    training_setup()
    assert read(name) is not None
    metrics.scope("runtime").inc("runtime.compiles")  # one the ring dropped
    assert read(name) is None
    # a program from before the recorder: no record at all, and no raise
    monkeypatch.delattr(spans, "layer_spans")
    assert read(name) is None


def test_set_up_ends_at_the_window_where_no_span_closes_it():
    jax_event("backend_compile_duration", 20.0, 1000.0)
    setup = setup_record.of(view([("dataset", 24.0, 28.0)]))
    assert (setup.lo, setup.hi) == (int(START * S), int(T0 * S))
    assert setup.first_run_wait_s == 0.0


def test_without_run_pys_clock_the_stretch_starts_with_the_process(
        monkeypatch):
    """``_T0`` of the main module is ``run.py``'s; any other main module gets
    the OS's start time of the process on the spans' clock."""
    monkeypatch.delattr(sys.modules["__main__"], "_T0")
    now = time.perf_counter_ns()
    lo = setup_record.start_ns()
    assert lo is not None and now - 3600 * S < lo < now


@pytest.mark.parametrize("name,counter,key", [
    ("block_tokenwise_kernel_share", "kernels.block_tokenwise", "kernel"),
    ("flash_bwd_fused_share", "kernels.flash_bwd_schedule", "fused"),
])
def test_kernel_share_is_the_counters_key_over_all_traces(name, counter, key):
    reader = mf.load_reader(name)
    assert reader.read(None) is None  # no trace of it in the process
    scope = metrics.scope("kernels")
    other = "xla" if key == "kernel" else "resident"
    for k in (key, key, key, other):
        scope.inc(counter, key=k)
    assert reader.read(None) == pytest.approx(75.0)


@pytest.mark.parametrize("cell,names", [
    ("vit_tiny64_train_loader", {
        "loader_restart_wait_share": "%", "loader_decode_ms_per_batch": "ms",
        "loader_place_ms_per_batch": "ms", "compiles_in_window": "count",
        "setup_compile_s": "s", **dict.fromkeys(SETUP, "s")}),
    ("flower200_train_dp4", {
        "loader_restart_wait_share": "%", "loader_decode_ms_per_batch": "ms",
        "loader_place_ms_per_batch": "ms", "compiles_in_window": "count",
        "setup_compile_s": "s", **dict.fromkeys(SETUP, "s"),
        "flash_bwd_fused_share": "%"}),
])
def test_the_manifest_promises_each_training_cell_its_readings(cell, names):
    manifest = mf.load_manifest()
    got = result_line.expected_metrics(manifest, cell, traced=True)
    assert names.items() <= got.items()
    for name in names:
        assert callable(mf.load_reader(name).read)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["moves"] == "setup_s"
               for n in ["setup_compile_s", *SETUP])


@pytest.mark.parametrize("cell", SAMPLER_CELLS)
def test_pending_entries_give_each_sampler_cell_its_set_up_readings(cell):
    """What waits for a ``benchmark`` PR (three tests of this directory pin
    the sampler cells' metric sets): merged as the file says, every sampler
    cell gets the six set-up readings it can have, beside the ones it has,
    and every name has a reader."""
    manifest = mf.load_manifest()
    before = result_line.expected_metrics(manifest, cell, traced=True)
    with open(PENDING) as f:
        pending = json.load(f)
    for m in manifest["per_layer"]:
        m["workloads"] = m["workloads"] + pending["append_workloads"].get(
            m["name"], [])
    manifest["per_layer"] += pending["per_layer"]
    names = {"sampler_host_ms_per_call": "ms", "setup_compile_s": "s",
             **dict.fromkeys(set(SETUP) - {"setup_data_s",
                                           "setup_place_state_s"}, "s")}
    if cell == "flower200_sample_k20":
        names["block_tokenwise_kernel_share"] = "%"
    after = result_line.expected_metrics(manifest, cell, traced=True)
    assert after == {**before, **names}
    for name in names:
        assert callable(mf.load_reader(name).read)


@pytest.mark.parametrize("cell", ["toy_sample", "toy_train_dp4"])
def test_a_toy_cells_set_up_is_put_down_whole(toy_run, monkeypatch, cell):
    """Through the ``execute`` a real run uses: the stretch is ``setup_s``,
    the four parts make it, and a training cell's placement and data spans
    are in it."""
    from benchmark.harness import View
    from benchmark.run import execute

    t0 = time.perf_counter()
    monkeypatch.setattr(sys.modules["__main__"], "_T0", t0)
    run = toy_run(cell, seed=2**31 + 35)
    result, _, setup_s, *_ = execute(run, t0=t0)
    v = View(run, result, None)
    setup = setup_record.of(v)
    assert setup_s - 0.05 < setup.stretch_s <= setup_s
    got = {name: read(name, v) for name in SETUP}
    assert all(value >= 0.0 for value in got.values()), got
    parts = (got["setup_start_s"] + setup.covered_s
             + got["setup_first_run_wait_s"] + got["setup_unattributed_s"])
    assert abs(parts - setup.stretch_s) < 1e-3
    assert got["setup_trace_lower_s"] > 0.0
    assert got["setup_trace_lower_s"] <= read("setup_compile_s", v)
    training = cell == "toy_train_dp4"
    assert (got["setup_place_state_s"] > 0.0) == training
    assert (got["setup_data_s"] > 0.0) == training
    (placed,) = [s for s in spans.layer_spans()
                 if s.name == "parallel/place_state"] or [None]
    assert (placed is not None) == training
    if training:
        assert placed.attrs["devices"] == 4
        assert mf.load_reader("flash_bwd_fused_share").read(v) is not None
