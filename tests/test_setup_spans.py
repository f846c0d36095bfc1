"""The spans of set-up's own work (ISSUE 35): the training state's placement
on the mesh, a dataset's opening, the native decoder's load and, when it
runs, its build — each on the one recorder, each mirrored into a profiler
session only while one is live."""

import os

import jax
import jax.numpy as jnp
import optax
import pytest

from ddim_cold_tpu.data import (ColdDownSampleDataset, DiffusionDataset,
                                native)
from ddim_cold_tpu.obs import spans
from ddim_cold_tpu.parallel import make_mesh, shard_train_state
from ddim_cold_tpu.train.step import EmaTrainState

from test_spans_layers import _host_events, _named


@pytest.fixture(autouse=True)
def empty_record():
    spans.clear()
    yield
    spans.clear()


def _train_state(ema: bool):
    params = {"w": jnp.ones((8, 4), jnp.float32),
              "b": jnp.zeros((4,), jnp.bfloat16)}
    return EmaTrainState.create(
        apply_fn=lambda *a: None, params=params, tx=optax.adam(1e-3),
        ema_params=jax.tree.map(jnp.copy, params) if ema else None)


@pytest.mark.parametrize("chips,ema", [(1, False), (4, False), (4, True)])
def test_placing_the_train_state_is_one_span_with_its_bytes(chips, ema):
    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    state = shard_train_state(_train_state(ema), mesh, None)
    (span,) = _named("parallel/place_state")
    assert span.ended and span.parent_id is None
    placed = jax.tree.leaves((state.params, state.opt_state,
                              state.ema_params))
    # w and b, Adam's mu and nu of each and its count; the shadow when kept
    assert span.attrs == {"devices": chips, "leaves": len(placed),
                          "bytes": sum(x.nbytes for x in placed)}
    assert span.attrs["leaves"] == (9 if ema else 7)
    assert span.attrs["bytes"] == (4 if ema else 3) * (8 * 4 * 4 + 4 * 2) + 4
    assert all(len(x.sharding.device_set) == chips for x in placed)
    # the placement's own device_puts compile nothing under another name
    assert {s.name.split("/")[0] for s in spans.layer_spans()} <= {
        "parallel", "jax"}


@pytest.mark.parametrize("cls,cached", [(ColdDownSampleDataset, True),
                                        (DiffusionDataset, False)])
def test_opening_a_dataset_is_one_span_an_object(synthetic_image_dir, cls,
                                                 cached):
    data = cls(synthetic_image_dir, (64, 64), cache_images=cached)
    (span,) = _named("data/dataset/open")
    assert span.ended
    assert len(data.imgList) == 10
    assert span.attrs == {"images": 10, "cached": cached}
    # the header probe is what first asks for the native decoder: its load,
    # if this process had not loaded it yet, lies inside the open
    for load in _named("data/native/load"):
        assert load.parent_id == span.span_id
    cls(synthetic_image_dir, (64, 64), use_native=False)
    assert len(_named("data/dataset/open")) == 2


def _fresh_library(monkeypatch, so_path):
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_failed", False)
    monkeypatch.delenv("DDIM_COLD_NO_NATIVE", raising=False)
    monkeypatch.setattr(native, "_SO_PATH", str(so_path))


def test_the_decoders_build_is_a_child_span_only_when_it_runs(
        monkeypatch, tmp_path):
    if not native.available():
        pytest.skip("native library unavailable")
    # a process that finds the library built: one load, no build
    _fresh_library(monkeypatch, native._SO_PATH)
    with spans.layer("data/decode/work") as first_decode:
        assert native.available()
    (load,) = _named("data/native/load")
    assert load.attrs == {"built": False}
    assert load.parent_id == first_decode.span_id
    assert not _named("data/native/build")
    # once loaded, asking again records nothing
    assert native.available() and len(_named("data/native/load")) == 1
    # a checkout's first use: the g++ run is a span inside the load
    spans.clear()
    _fresh_library(monkeypatch, tmp_path / "libddim_data.so")
    assert native.available()
    (load,) = _named("data/native/load")
    (build,) = _named("data/native/build")
    assert load.attrs == {"built": True} and build.attrs == {"ok": True}
    assert build.parent_id == load.span_id
    assert load.t0 <= build.t0 <= build.t1 <= load.t1
    assert os.path.isfile(tmp_path / "libddim_data.so")


def test_a_build_that_cannot_run_leaves_no_span(monkeypatch, tmp_path):
    """No source, no g++ run: ``_build`` says so before it opens a span, and
    the switched-off library records nothing at all."""
    _fresh_library(monkeypatch, tmp_path / "libddim_data.so")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    assert not native.available()
    (load,) = _named("data/native/load")
    assert load.attrs == {"built": False} and not _named("data/native/build")
    spans.clear()
    _fresh_library(monkeypatch, tmp_path / "libddim_data.so")
    monkeypatch.setenv("DDIM_COLD_NO_NATIVE", "1")
    assert not native.available() and spans.layer_spans() == []


def test_set_up_spans_are_mirrored_only_while_a_session_is_live(
        tmp_path, synthetic_image_dir):
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    ColdDownSampleDataset(synthetic_image_dir, (64, 64))   # no session: memory
    jax.profiler.start_trace(str(tmp_path))
    try:
        ColdDownSampleDataset(synthetic_image_dir, (64, 64))
        shard_train_state(_train_state(False), mesh, None)
    finally:
        jax.profiler.stop_trace()
    shard_train_state(_train_state(False), mesh, None)     # the session is over
    assert len(_named("data/dataset/open")) == 2
    assert len(_named("parallel/place_state")) == 2
    found = _host_events(str(tmp_path), "ddim/")
    assert {name: len(found.get(name, [])) for name in (
        "ddim/data/dataset/open", "ddim/parallel/place_state")} == {
        "ddim/data/dataset/open": 1, "ddim/parallel/place_state": 1}
    (a0, a1), = found["ddim/parallel/place_state"]
    live = _named("parallel/place_state")[0]
    assert abs((a1 - a0) - (live.t1 - live.t0)) < 1_000_000  # within 1 ms
