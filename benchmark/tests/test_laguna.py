"""The sparse-expert configuration's benchmark files at a toy size
(``fixtures_laguna/``: hidden 64, 5 layers, 16 router outputs of which 8 are
held, 16 x 16 px): the driver end to end through the same ``execute`` a real
run uses, the control, the weights against the program's own tree, the cost
functions at the published sizes, and the five readers on a hand-made
trace."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import costs, costs_laguna, manifest as mf, result_line
from benchmark import weights_laguna
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_laguna")
CELL = "toy_sample_laguna"
REAL = "laguna_s21_sample1024_k20"
PEAKS = mf.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(mf.HERE, "configs",
                           "laguna_s21_ep2_px1024.json")) as f:
        return json.load(f)


def toy_run(seed=3, seconds=0.5, traced=False):
    import jax

    cell = mf.Cell(mf.load_manifest(FIXTURES), CELL,
                   here=os.path.join(FIXTURES, "benchmark"))
    return Run(cell, seed, seconds, traced, jax.devices()[:1], PEAKS)


def test_driver_end_to_end_and_the_control_is_not_correct():
    run = toy_run(seed=2**31 + 11)
    result, compared, setup_s, peak, _, state = execute(
        run, t0=time.perf_counter())
    assert [c.name for c in compared] == ["images_finite_in_unit_range",
                                          "sample_rms_vs_reference"]
    assert all(c.ok for c in compared), [str(c) for c in compared]
    manifest = mf.load_manifest(FIXTURES)
    expected = result_line.expected_metrics(manifest, CELL, False)
    line = result_line.build(
        correct=True, attempted=result["attempted"], failed=result["failed"],
        values=dict(result["e2e"], setup_s=setup_s), units=expected,
        device={"platform": "cpu", "kind": "cpu", "count": 1,
                "memory_peak_bytes": max(peak, 1)})
    result_line.validate(line, expected, traced=False, chips=1)
    assert result["counters"]["scan_steps"] == result["attempted"] * 5
    control = mf.load_driver(run.cell.driver).control(run, state, result)
    assert not all(c.ok for c in control), [str(c) for c in control]


def test_broken_sampler_is_not_correct(monkeypatch):
    from ddim_cold_tpu.ops import sampling

    real = sampling.ddim_sample

    def swapped(*args, **kwargs):
        out = np.array(real(*args, **kwargs))
        out[[0, 1]] = out[[1, 0]]
        return out

    monkeypatch.setattr(sampling, "ddim_sample", swapped)
    _, compared, *_ = execute(toy_run(), t0=time.perf_counter())
    assert not all(c.ok for c in compared)


def test_weights_are_the_tree_the_model_declares():
    """Names, shapes and dtypes of ``model.init`` — at both storage types."""
    import jax
    import jax.numpy as jnp

    driver = mf.load_driver("sample_closed_moe")
    toy = toy_run().config
    for precision in ("bfloat16", "float32"):
        config = dict(toy, precision=precision)
        model = driver.build_model(config)
        x, t = jnp.zeros((2, 16, 16, 3)), jnp.zeros((2,), jnp.int32)
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        params = weights_laguna.make(config, 7)
        assert spec(params) == spec(declared)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(
        weights_laguna.make(toy, 7)))
    # another seed, other weights; the same seed, the same
    a, b, c = (weights_laguna.make(toy, s)["layers_1"]["mlp"]["router"]
               for s in (7, 7, 8))
    assert (a == b).all() and not (a == c).all()


def test_the_configuration_file_carries_the_catalog_rows_keys(published):
    """Every key the source's config.json has, under the same name and with
    the same value, but for what ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
    assert published["source"] == row["source_url"]
    assert sorted(published["published_keys"]) == sorted(row["config"])
    differs = sorted(k for k, v in row["config"].items() if published[k] != v)
    assert differs == sorted(published["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert published["source_values"] == {
        k: row["config"][k] for k in published["reduced"]}
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == "laguna_s21_ep2_px1024")
    assert entry["reduced"] == published["reduced"]
    assert entry["source"] == published["source"]
    # the share: 128 of 256 held from expert 0, the router at its width
    trunk = weights_laguna.trunk_of(published)
    assert (trunk["num_experts"], trunk["num_experts_routed"],
            trunk["experts_held_from"]) == (128, 256, 0)
    assert len(trunk["layer_types"]) == 48  # as published; layers 0-4 are read


def test_costs_at_the_published_sizes(published):
    """ISSUE 30's arithmetic: 5.73 TF an image a forward on this chip; a
    window layer's scores are a quarter of a full layer's; one grouped
    product over the ~81,940 rows routed here is 0.52 TF against 1.5 GB and
    compute-bound."""
    assert costs.tokens(published) == 4097
    flops = costs_laguna.forward_flops(published)
    assert 5.70e12 < flops < 5.80e12
    assert costs_laguna.held_share(published) == 0.5
    assert costs_laguna.window_of(published, 72) == 512
    assert costs_laguna.window_of(published, 48) is None
    assert costs_laguna.seen(4097, None) == 4097 * 4098 // 2
    assert costs_laguna.seen(4097, 512) == 512 * 513 // 2 + 3585 * 512
    assert costs_laguna.seen(17, 8) == 36 + 9 * 8
    full = costs_laguna.flash_masked_fwd_cost(published, 4, 48)
    window = costs_laguna.flash_masked_fwd_cost(published, 4, 72)
    assert full["flops"] == 4 * 4 * 48 * 128 * 4097 * 4098 // 2
    assert window["bytes"] == 2 * 4 * 4097 * 128 * 2 * (72 + 8)
    assert 0.23 < window["flops"] / 72 / (full["flops"] / 48) < 0.24
    assert costs.roofline_seconds(full, PEAKS)[1] == "compute"
    assert costs.roofline_seconds(window, PEAKS)[1] == "compute"
    gmm = costs_laguna.moe_gmm_cost(published, 81940, 3072, 1024)
    assert gmm["flops"] == 2 * 81940 * 3072 * 1024
    assert gmm["bytes"] == (81940 * 4096 + 128 * 3072 * 1024) * 2
    seconds, bound = costs.roofline_seconds(gmm, PEAKS)
    assert bound == "compute" and seconds == pytest.approx(2.617e-3, rel=1e-3)


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


def test_roofline_and_time_share_readers_on_a_hand_made_trace(published):
    tail = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
    gate = "%moe_gmm.12 = bf16[163968,1024]{1,0:T(8,128)(2,1)}" + tail
    down = "%moe_gmm.14 = bf16[163968,3072]{1,0:T(8,128)(2,1)}" + tail
    window = "%fwd_masked.6 = bf16[4,4097,9216]{2,1,0:T(8,128)(2,1)}" + tail
    full = "%fwd_masked = bf16[4,4097,6144]{2,1,0:T(8,128)(2,1)}" + tail
    unmasked = "%fwd.3 = bf16[4,4097,6144]{2,1,0:T(8,128)(2,1)}" + tail
    other = "%fusion.3 = bf16[4,4097,3072]{2,1,0} fusion(%x), kind=kOutput"
    ms = 1_000_000
    ops = [(0, 5 * ms, gate), (5 * ms, 10 * ms, down), (10 * ms, 12 * ms, window),
           (12 * ms, 20 * ms, full), (20 * ms, 30 * ms, other),
           (30 * ms, 40 * ms, unmasked)]
    view = _view(published, ops, busy_s=40e-3)
    peak = PEAKS["bf16_flops_per_s"]
    rows = 4 * 4097 * 10 * 0.5  # the held half of the call's assignments
    gmm_least = 2 * (2 * rows * 3072 * 1024) / peak
    assert mf.load_reader("moe_gmm_roofline").read(view) == pytest.approx(
        100 * gmm_least / 10e-3, rel=1e-6)
    assert mf.load_reader("moe_gmm_time_share").read(view) == pytest.approx(25.0)
    attn_least = (costs_laguna.flash_masked_fwd_cost(published, 4, 72)["flops"]
                  + costs_laguna.flash_masked_fwd_cost(published, 4, 48)["flops"]
                  ) / peak
    assert mf.load_reader("flash_masked_fwd_roofline").read(view) == (
        pytest.approx(100 * attn_least / 10e-3, rel=1e-6))
    assert mf.load_reader("flash_masked_fwd_time_share").read(view) == (
        pytest.approx(25.0))
    # the unmasked kernel's reader does not see the masked launches
    from benchmark.layer_metrics import flash_fwd_roofline
    assert sum(bool(flash_fwd_roofline.NAME.match(text))
               for *_, text in ops) == 1
    # nothing to read: no trace, or a trace without the kernels
    for reader in ("moe_gmm_roofline", "moe_gmm_time_share",
                   "flash_masked_fwd_roofline", "flash_masked_fwd_time_share"):
        assert mf.load_reader(reader).read(_view(published, [], 1.0)) is None
        assert mf.load_reader(reader).read(types.SimpleNamespace(
            trace=None, config=published, peaks=PEAKS)) is None


def test_kernel_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("moe_gmm_kernel_share")
    assert reader.read(None) is None  # no trace of the product in the process
    scope = metrics.scope("kernels")
    for key in ("kernel", "kernel", "kernel", "xla"):
        scope.inc("kernels.moe_gmm_schedule", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, REAL)
    assert (cell.chips, cell.driver) == (1, "sample_closed_moe")
    assert (cell.traffic["n"], cell.traffic["k"]) == (4, 20)
    assert set(result_line.expected_metrics(manifest, cell.name, False)) == {
        "sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, cell.name, True)) == {
        "sampler_step_ms", "moe_gmm_roofline", "moe_gmm_time_share",
        "moe_gmm_kernel_share", "flash_masked_fwd_roofline",
        "flash_masked_fwd_time_share"}
    # the old cells' lines do not change
    assert set(result_line.expected_metrics(
        manifest, "flower200_sample_k20", True)) == {
        "sampler_step_ms", "flash_fwd_roofline"}
    assert set(result_line.expected_metrics(
        manifest, "jamba2_3b_sample512_k20", True)) == {
        "sampler_step_ms", "ssm_scan_roofline", "ssm_scan_time_share",
        "ssm_scan_kernel_share"}
    limits = json.load(open(os.path.join(mf.HERE, "workloads", REAL + ".json")))
    sound = limits["limits_from"]["sample_rms_vs_reference"]["program"]
    control = limits["limits_from"]["sample_rms_vs_reference"][
        "control_float8_e4m3"]
    assert len(sound) >= 6 and len(control) >= 3
    assert max(sound) < cell.limits["sample_rms_vs_reference"] < min(control)
