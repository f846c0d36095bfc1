"""The readers of the program's own record over a hand-made record: each
gives the hand-computed value, clips at the window's ``t0``/``t1``, and
returns ``None`` only when its spans are absent."""

import json
import os
import types

import pytest

from benchmark import manifest as mf
from benchmark import result_line
from benchmark.layer_metrics import program_record as rec

from ddim_cold_tpu.obs import metrics, spans

MS = 1_000_000
S = 1_000_000_000
#: the window: 100 s to 110 s on the spans' clock
T0, T1 = 100.0, 110.0

PENDING = os.path.join(mf.HERE, "layer_metrics", "pending.json")


def view(t0=T0, t1=T1):
    return types.SimpleNamespace(result={"t0": t0, "t1": t1},
                                 window_s=t1 - t0)


def at(name, start_s, dur_ms, **attrs):
    """A closed span of the program's record from ``start_s`` on."""
    return spans.event(name, int(start_s * S) + int(dur_ms * MS),
                       int(dur_ms * MS), **attrs)


@pytest.fixture(autouse=True)
def empty_record():
    spans.clear()
    metrics.reset()
    yield
    spans.clear()
    metrics.reset()


def read(name):
    return mf.load_reader(name).read(view())


def test_restart_wait_share_takes_an_epochs_first_two_batches_and_clips():
    at("data/place/work", 101.0, 5.0)
    at("data/place/get_wait", 99.5, 1000.0, batch=0, epoch=3)  # half before t0
    at("data/place/get_wait", 104.0, 250.0, batch=1, epoch=3)
    at("data/place/get_wait", 106.0, 700.0, batch=7, epoch=3)  # mid-epoch
    at("data/place/get_wait", 109.9, 400.0, batch=0, epoch=4)  # 100 ms in
    at("data/decode/work", 105.0, 3000.0, batch=0, epoch=4)    # another span
    assert read("loader_restart_wait_share") == pytest.approx(
        100.0 * (0.5 + 0.25 + 0.1) / 10.0)


def test_restart_wait_share_is_zero_when_the_loop_never_waited():
    at("data/place/work", 101.0, 5.0)
    assert read("loader_restart_wait_share") == 0.0


def test_decode_and_place_means_take_the_windows_batches_only():
    at("data/decode/work", 90.0, 500.0)       # set-up's
    at("data/decode/work", 101.0, 10.0)
    at("data/decode/work", 102.0, 30.0)
    at("data/decode/work", 111.0, 70.0)       # after the window
    at("data/place/work", 101.0, 4.0)
    at("data/place/work", 103.0, 8.0)
    at("data/place/work", 50.0, 100.0)
    assert read("loader_decode_ms_per_batch") == pytest.approx(20.0)
    assert read("loader_place_ms_per_batch") == pytest.approx(6.0)


def test_sampler_host_time_is_the_calls_two_children():
    with spans.layer("sampler/call", n=2) as warm:   # outside the window
        pass
    warm.t0, warm.t1 = 10 * S, 20 * S
    for start in (101, 105):
        with spans.layer("sampler/call", n=2) as call:
            with spans.layer("sampler/init") as a, \
                    spans.layer("sampler/other"):
                pass
            with spans.layer("sampler/dispatch") as b:
                pass
        call.t0, call.t1 = start * S, (start + 3) * S
        a.t0, a.t1 = start * S, start * S + 2 * MS
        b.t0, b.t1 = start * S + 2 * MS, start * S + 9 * MS
    # init 2 ms + dispatch 7 ms a call; the third span is not counted
    assert read("sampler_host_ms_per_call") == pytest.approx(9.0)


def _compile(start_s, dur_ms, name="backend_compile_duration"):
    scope = metrics.scope("runtime")
    if name == "backend_compile_duration":
        scope.inc("runtime.compiles")
    return at("jax/" + name, start_s, dur_ms,
              event="/jax/core/compile/" + name)


def test_setup_compile_is_the_union_before_the_window():
    _compile(10.0, 1000.0, "jaxpr_trace_duration")
    _compile(10.2, 300.0, "jaxpr_trace_duration")     # nested: counts once
    _compile(11.0, 2000.0)
    at("jax/cache_retrieval_time_sec", 11.5, 500.0)   # inside the compile
    at("jax/compile_time_saved_sec", 12.0, 0.0, saved_s=40.0)
    _compile(104.0, 700.0)                            # in the window
    assert read("setup_compile_s") == pytest.approx(3.0)
    assert read("compiles_in_window") == 1.0


def test_no_compile_in_the_window_reads_zero_not_none():
    _compile(10.0, 1000.0)
    assert read("compiles_in_window") == 0.0


def test_a_record_that_lost_compiles_is_not_read():
    _compile(10.0, 1000.0)
    metrics.scope("runtime").inc("runtime.compiles")  # one the ring dropped
    assert read("setup_compile_s") is None
    assert read("compiles_in_window") is None


@pytest.mark.parametrize("name", [
    "loader_restart_wait_share", "loader_decode_ms_per_batch",
    "loader_place_ms_per_batch", "sampler_host_ms_per_call",
    "setup_compile_s", "compiles_in_window"])
def test_absent_spans_read_none(name, monkeypatch):
    at("bench/other", 101.0, 5.0)
    assert read(name) is None
    # a program from before the recorder: no record at all, and no raise
    monkeypatch.delattr(spans, "layer_spans")
    assert read(name) is None


def test_union_counts_overlap_once():
    a = at("x", 1.0, 1000.0)
    b = at("x", 1.5, 1000.0)
    c = at("x", 5.0, 250.0)
    assert rec.union_s([c, a, b]) == pytest.approx(1.75)


@pytest.mark.parametrize("cell,names", [
    ("flower200_sample_k20", {"sampler_host_ms_per_call": "ms",
                              "setup_compile_s": "s"}),
    ("vit_tiny64_train_loader", {"loader_restart_wait_share": "%",
                                 "loader_decode_ms_per_batch": "ms",
                                 "loader_place_ms_per_batch": "ms",
                                 "setup_compile_s": "s",
                                 "compiles_in_window": "count"}),
    ("flower200_train_dp4", {"loader_restart_wait_share": "%",
                             "loader_decode_ms_per_batch": "ms",
                             "loader_place_ms_per_batch": "ms",
                             "setup_compile_s": "s",
                             "compiles_in_window": "count"}),
])
def test_pending_entries_give_each_cell_its_metrics(cell, names):
    """With the pending entries appended the manifest promises each cell
    the new names with their units, beside the ones it has, and every
    promised name has a reader."""
    manifest = mf.load_manifest()
    before = result_line.expected_metrics(manifest, cell, traced=True)
    with open(PENDING) as f:
        manifest["per_layer"] = manifest["per_layer"] + json.load(f)["per_layer"]
    after = result_line.expected_metrics(manifest, cell, traced=True)
    assert after == {**before, **names}
    for name in names:
        assert callable(mf.load_reader(name).read)
