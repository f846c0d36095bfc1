"""The readers of device time by layer (``layer_metrics/scope_record.py``)
over a hand-made trace and a hand-made scope map: each gives its
hand-computed value, a ``while`` does not count its body twice, two devices
are averaged, an instruction the map lacks is ``unmapped``; every reader gives
0.0 (never ``None``) on a program without ``obs.scopes`` and with an empty map;
and the manifest promises the new names where this PR says it does."""

import json
import os
import sys
import types

import pytest

from benchmark import manifest as mf
from benchmark import result_line, trace_reduce
from benchmark.layer_metrics import scope_record

TRAIN = ["train_scope_attributed_share", "train_attention_time_share",
         "train_mlp_time_share", "train_backward_time_share",
         "train_optimizer_time_share"]
TRUNK = ["trunk_scope_attributed_share", "trunk_attention_time_share",
         "trunk_mixer_time_share", "trunk_experts_time_share",
         "trunk_route_time_share", "trunk_mlp_time_share"]
PENDING = os.path.join(mf.HERE, "layer_metrics", "pending_scope_cells.json")
EXPERTS = {"laguna_s21_sample1024_k20", "glm52_sample1536_k50",
           "pangu_ultra_sample1536_k50", "nemotron3_super_sample2048_k50",
           "kimi_linear_sample2048_k50", "smallthinker_21b_sample2032_k50"}
MIXER = {"jamba2_3b_sample512_k20", "nemotron3_super_sample2048_k50",
         "kimi_linear_sample2048_k50"}
NO_MLP = {"nemotron3_super_sample2048_k50", "smallthinker_21b_sample2032_k50"}
SAMPLER_CELLS = sorted(EXPERTS | MIXER | {"flower200_sample_k20"})


def op(name, start_us, end_us, opcode="fusion"):
    """One ``XLA Ops`` event, its ends in microseconds."""
    return (start_us * 1e3, end_us * 1e3,
            f"%{name} = f32[8]{{0:T(128)}} {opcode}(f32[8] %x)")


def entry(layer, direction="fwd", mixed=False):
    return {"scope": None, "layer": layer, "direction": direction,
            "mixed": mixed}


#: device 0: a 1,000 µs ``while`` whose body is three instructions (700 µs:
#: 300 µs of the loop are its own), then the optimizer's fusion and one the
#: map does not know; device 1: attention alone
DEVICES = {
    0: {"ops": [op("while.9", 0, 1000, "while"),
                op("fusion.1", 100, 400),            # attention, fwd
                op("fwd_masked.2", 400, 600, "custom-call"),  # attention, fwd
                op("fusion.3", 700, 900),            # mlp, bwd, mixed
                op("fusion.4", 1000, 1500),          # optimizer
                op("copy.5", 1500, 1600, "copy")],   # not in the map
        "async": []},
    1: {"ops": [op("fusion.1", 0, 800)], "async": []},
}
MAP = {"while.9": entry("outside"), "fusion.1": entry("attention"),
       "fwd_masked.2": entry("attention"),
       "fusion.3": entry("mlp", "bwd", True),
       "fusion.4": entry("optimizer")}


def view(devices=DEVICES, window_us=2000):
    trace = trace_reduce.Reduced(window_us * 1e-6, devices, [],
                                 (0, window_us * 1e3))
    return types.SimpleNamespace(trace=trace)


@pytest.fixture
def mapped(monkeypatch):
    monkeypatch.setattr(scope_record, "_map", dict(MAP))
    monkeypatch.setattr(scope_record, "_printed", False)


def test_the_split_by_hand(mapped, capsys):
    v = view()
    # busy: 1,600 µs on device 0, 800 on device 1 -> 1,200 µs a device
    assert v.trace.busy_s == pytest.approx(1200e-6)
    got = scope_record.by_layer(v)
    assert got == pytest.approx({
        "attention": (300 + 200 + 800) / 2 * 1e-6, "mlp": 100e-6,
        "optimizer": 250e-6, "outside": 150e-6, "unmapped": 50e-6})
    assert sum(got.values()) == pytest.approx(v.trace.busy_s)
    split = scope_record.split(v)
    assert (split.bwd_s, split.mixed_s) == (pytest.approx(100e-6),) * 2
    read = {name: mf.load_reader(name).read(v) for name in TRAIN + TRUNK}
    assert read == pytest.approx({
        "train_scope_attributed_share": 100 * 1000 / 1200,
        "train_attention_time_share": 100 * 650 / 1200,
        "train_mlp_time_share": 100 * 100 / 1200,
        "train_backward_time_share": 100 * 100 / 1200,
        "train_optimizer_time_share": 100 * 250 / 1200,
        "trunk_scope_attributed_share": 100 * 1000 / 1200,
        "trunk_attention_time_share": 100 * 650 / 1200,
        "trunk_mixer_time_share": 0.0, "trunk_experts_time_share": 0.0,
        "trunk_route_time_share": 0.0,
        "trunk_mlp_time_share": 100 * 100 / 1200})
    # the table, once a process: layers by time, short names inside
    err = capsys.readouterr().err
    assert err.count("layer x instruction") == 1
    assert "attention" in err and "fusion 0.000550, fwd_masked 0.000100" in err


def test_a_full_name_is_the_instructions_own():
    text = ("%convert_reduce_fusion.12 = (f32[]{:T(128)}, bf16[256,512]"
            "{1,0:T(8,128)(2,1)}) fusion(bf16[256,512] %x.1)")
    assert scope_record.full_name(text) == "convert_reduce_fusion.12"
    assert trace_reduce.short_name(text) == "convert_reduce_fusion"


def test_a_recorded_trace_splits_into_what_it_was_busy(monkeypatch):
    """The chip's own event names (``fixtures/trace_1chip.xplane.pb``)."""
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "trace_1chip.xplane.pb")
    v = types.SimpleNamespace(trace=trace_reduce.reduce_file(path, 1))
    monkeypatch.setattr(scope_record, "_printed", True)
    monkeypatch.setattr(scope_record, "_map",
                        {"convert_reduce_fusion": entry("mlp")})
    got = scope_record.by_layer(v)
    assert set(got) == {"mlp", "unmapped"}  # the copies are nobody's
    assert sum(got.values()) == pytest.approx(v.trace.busy_s)
    assert mf.load_reader("train_mlp_time_share").read(v) > 99.0


@pytest.mark.parametrize("name", TRAIN + TRUNK)
def test_a_reader_gives_zero_where_there_is_no_map(name, monkeypatch, capsys):
    """``run.py`` cannot leave a metric out (a ``None`` refuses the whole
    line): a program without ``obs.scopes`` (the parent, on which the driver
    runs these files), an empty map and no trace all read 0.0."""
    read = mf.load_reader(name).read
    monkeypatch.setattr(scope_record, "_map", {})
    assert read(view()) == 0.0
    monkeypatch.setattr(scope_record, "_map", dict(MAP))
    assert read(types.SimpleNamespace(trace=None)) == 0.0
    # the program lacks the module: the import fails, the reason is said once
    monkeypatch.setattr(scope_record, "_map", None)
    import ddim_cold_tpu.obs

    monkeypatch.setitem(sys.modules, "ddim_cold_tpu.obs.scopes", None)
    monkeypatch.delattr(ddim_cold_tpu.obs, "scopes", raising=False)
    assert read(view()) == 0.0 and read(view()) == 0.0
    assert capsys.readouterr().err.count("has no obs.scopes") == 1


def test_a_map_that_fails_to_build_reads_zero(monkeypatch, capsys):
    from ddim_cold_tpu.obs import scopes

    def broken():
        raise RuntimeError("no such executable")

    monkeypatch.setattr(scopes, "scope_map", broken)
    monkeypatch.setattr(scope_record, "_map", None)
    assert mf.load_reader("train_scope_attributed_share").read(view()) == 0.0
    assert "failed to build (RuntimeError: no such executable)" in (
        capsys.readouterr().err)


def test_the_map_is_the_programs_and_is_built_once(monkeypatch):
    """Through the program's own ``note`` and ``scope_map``: a jitted toy
    under ``trunk/mlp``, its instructions' names as the trace would print
    them."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.obs import scopes

    @jax.jit
    def toy(x, w):
        with jax.named_scope("trunk/mlp"):
            return jnp.tanh(x @ w)

    scopes.clear()
    monkeypatch.setattr(scope_record, "_map", None)
    monkeypatch.setattr(scope_record, "_printed", True)
    x = jnp.ones((8, 8))
    scopes.note("toy", toy, (x, x), {})
    toy(x, x).block_until_ready()
    got = scope_record.scope_map()
    names = [n for n, e in got.items() if e["layer"] == "mlp"]
    assert names
    devices = {0: {"ops": [op(names[0], 0, 100)], "async": []}}
    assert mf.load_reader("trunk_mlp_time_share").read(
        view(devices, 100)) == pytest.approx(100.0)
    calls = []
    monkeypatch.setattr(scopes, "scope_map", lambda: calls.append(1) or {})
    assert scope_record.scope_map() is got and not calls
    scopes.clear()


@pytest.mark.parametrize("cell", ["vit_tiny64_train_loader",
                                  "flower200_train_dp4"])
def test_the_manifest_promises_each_training_cell_the_five_readings(cell):
    manifest = mf.load_manifest()
    got = result_line.expected_metrics(manifest, cell, traced=True)
    assert dict.fromkeys(TRAIN, "%").items() <= got.items()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in TRAIN:
        assert callable(mf.load_reader(name).read)
        assert by_name[name]["moves"] == "train_img_per_s"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["workloads"] == ["vit_tiny64_train_loader",
                                              "flower200_train_dp4"]
    assert [m["name"] for m in manifest["per_layer"][-5:]] == TRAIN
    # no sampler cell's line changes
    for other in SAMPLER_CELLS:
        assert not set(TRAIN + TRUNK) & set(
            result_line.expected_metrics(manifest, other, traced=True))


@pytest.mark.parametrize("cell", SAMPLER_CELLS)
def test_pending_entries_give_each_sampler_cell_its_layers(cell):
    """What waits for a ``benchmark`` PR (seven tests of this directory pin
    the sampler cells' metric sets): merged as the file says, every sampler
    cell gets the layers its stack has, beside the readings it has, and
    every name has a reader."""
    manifest = mf.load_manifest()
    before = result_line.expected_metrics(manifest, cell, traced=True)
    with open(PENDING) as f:
        pending = json.load(f)
    assert set(pending) == {"why_pending", "per_layer"}
    manifest["per_layer"] += pending["per_layer"]
    names = {"trunk_scope_attributed_share", "trunk_attention_time_share"}
    if cell in MIXER:
        names.add("trunk_mixer_time_share")
    if cell in EXPERTS:
        names |= {"trunk_experts_time_share", "trunk_route_time_share"}
    if cell not in NO_MLP:
        names.add("trunk_mlp_time_share")
    after = result_line.expected_metrics(manifest, cell, traced=True)
    assert after == {**before, **dict.fromkeys(names, "%")}
    for name in names:
        assert callable(mf.load_reader(name).read)
    for m in pending["per_layer"]:
        assert m["moves"] == "sample_img_per_s" and m["unit"] == "%"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
