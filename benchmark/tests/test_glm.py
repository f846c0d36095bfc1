"""The latent-attention, sparse-selection configuration's benchmark files at
a toy size (``fixtures_glm/``: hidden 64, published layers 2-6 of an 8-long
pattern, 4 heads of 24 + 8, 4 index heads of 16 choosing 8 of 17 tokens, 16
router outputs of which 8 are held, 16 x 16 px): the driver end to end
through the same ``execute`` a real run uses, the control, the weights against
the program's own tree, the configuration against the catalog's row, the cost
functions at the published sizes, and the readers on a hand-made trace."""

import json
import os
import time
import types

import pytest

from benchmark import costs, costs_glm, manifest as mf, result_line
from benchmark import weights_glm
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_glm")
CELL = "toy_sample_glm"
REAL = "glm52_sample1536_k50"
PEAKS = mf.peaks_for("TPU v5 lite")
NEW_METRICS = ("flash_selected_fwd_roofline", "flash_selected_fwd_time_share",
               "dsa_index_roofline", "dsa_select_roofline",
               "dsa_index_time_share", "dsa_select_kernel_share")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(mf.HERE, "configs", "glm52_ep16_px1536.json")) as f:
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


def test_weights_are_the_tree_the_model_declares():
    """Names, shapes and dtypes of ``model.init`` — at both storage types; a
    ``shared`` layer has no indexer leaves."""
    import jax
    import jax.numpy as jnp

    driver = mf.load_driver("sample_closed_glm")
    toy = toy_run().config
    for precision in ("bfloat16", "float32"):
        config = dict(toy, precision=precision)
        model = driver.build_model(config)
        x, t = jnp.zeros((2, 16, 16, 3)), jnp.zeros((2,), jnp.int32)
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        params = weights_glm.make(config, 7)
        assert spec(params) == spec(declared)
    assert ["indexer" in params[f"layers_{i}"]["self_attn"]
            for i in range(5)] == [True, False, False, False, True]
    a, b, c = (weights_glm.make(toy, s)["layers_1"]["mlp"]["router"]
               for s in (7, 7, 8))
    assert (a == b).all() and not (a == c).all()


def test_the_configuration_file_carries_the_catalog_rows_keys(published):
    """Every key the source's config.json has, under the same name and with
    the same value, but for what ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert published["source"] == row["source_url"]
    assert sorted(published["published_keys"]) == sorted(row["config"])
    differs = sorted(k for k, v in row["config"].items() if published[k] != v)
    assert differs == sorted(published["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert published["source_values"] == {
        k: row["config"][k] for k in published["reduced"]}
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == "glm52_ep16_px1536")
    assert entry["reduced"] == published["reduced"]
    assert entry["source"] == published["source"]
    # the share and the slice: 16 of 256 held from expert 0, layers 2-6
    trunk = weights_glm.trunk_of(published)
    assert (trunk["n_routed_experts"], trunk["n_experts_routed"],
            trunk["experts_held_from"], trunk["layers_from"]) == (16, 256, 0, 2)
    assert costs_glm.layer_kinds(published) == [
        ("full", "dense"), ("shared", "sparse"), ("shared", "sparse"),
        ("shared", "sparse"), ("full", "sparse")]
    assert len(trunk["indexer_types"]) == 78  # as published


def test_parameters_counted_from_the_specs_are_the_issues(published):
    """3,644 M parameters, 7.29 GB in bfloat16, as ISSUE 33 counted them."""
    import math

    trunk = weights_glm.trunk_of(published)
    count = lambda specs: sum(math.prod(shape) for shape, *_ in specs.values())
    layers = [count(weights_glm.layer_specs(trunk, i)) for i in range(5)]
    assert [round(n / 1e6, 1) for n in layers] == [
        400.9, 808.3, 808.3, 808.3, 817.7]
    total = sum(layers) + count(weights_glm.outer_specs(published))
    assert 7.28e9 < 2 * sum(layers) < 7.30e9 and total - sum(layers) < 80e6


def test_costs_at_the_published_sizes(published):
    """ISSUE 33's arithmetic: 16.78 M selected of 42.48 M causal pairs a head;
    1.10 TF of attention inside the selection and 0.35 TF of index scores a
    layer; about 30 TF a forward by the selection, 39 TF if every causal pair
    is multiplied."""
    n = costs.tokens(published)
    assert n == 9217
    assert costs_glm.causal_pairs(n) == 9217 * 9218 // 2
    assert costs_glm.selected_pairs(n, 2048) == 2048 * 2049 // 2 + 7169 * 2048
    assert costs_glm.selected_pairs(17, 8) == 36 + 9 * 8
    assert costs_glm.selected_pairs(5, 8) == 15
    assert costs_glm.held_share(published) == 1 / 16
    attn = costs_glm.flash_selected_fwd_cost(published, 1)
    assert attn["flops"] == 2 * 64 * 512 * costs_glm.selected_pairs(n, 2048)
    assert 1.09e12 < attn["flops"] < 1.11e12
    assert costs.roofline_seconds(attn, PEAKS)[1] == "compute"
    index = costs_glm.dsa_index_cost(published, 1)
    assert index["flops"] == 2 * 32 * 128 * costs_glm.causal_pairs(n)
    assert costs.roofline_seconds(index, PEAKS)[1] == "compute"
    select = costs_glm.dsa_select_cost(published, 2)
    assert select == {"flops": 0.0,
                      "bytes": 2 * 5 * costs_glm.causal_pairs(n)}
    assert costs.roofline_seconds(select, PEAKS)[1] == "memory"
    assert 30.0e12 < costs_glm.forward_flops(published) < 30.8e12
    assert 38.4e12 < costs_glm.forward_flops(published, True) < 39.2e12


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


def test_roofline_and_time_share_readers_on_a_hand_made_trace(published):
    tail = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
    attn = "%fwd_selected.3 = bf16[1,9217,16384]{2,1,0:T(8,128)(2,1)}" + tail
    index = "%dsa_index.1 = f32[1,9728,9728]{2,1,0:T(8,128)}" + tail
    select = "%dsa_select = s8[1,9728,9728]{2,1,0:T(8,128)(4,1)}" + tail
    masked = "%fwd_masked.6 = bf16[1,9217,16384]{2,1,0:T(8,128)(2,1)}" + tail
    other = "%fusion.3 = bf16[1,9217,6144]{2,1,0} fusion(%x), kind=kOutput"
    ms = 1_000_000
    ops = [(0, 40 * ms, attn), (40 * ms, 80 * ms, attn),
           (80 * ms, 84 * ms, index), (84 * ms, 90 * ms, select),
           (90 * ms, 95 * ms, masked), (95 * ms, 100 * ms, other)]
    view = _view(published, ops, busy_s=100e-3)
    peak, hbm = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    read = lambda name: mf.load_reader(name).read(view)
    attn_least = costs_glm.flash_selected_fwd_cost(published, 1)["flops"] / peak
    assert read("flash_selected_fwd_roofline") == pytest.approx(
        100 * 2 * attn_least / 80e-3, rel=1e-6)
    assert read("flash_selected_fwd_time_share") == pytest.approx(80.0)
    assert read("dsa_index_roofline") == pytest.approx(
        100 * costs_glm.dsa_index_cost(published, 1)["flops"] / peak / 4e-3,
        rel=1e-6)
    assert read("dsa_select_roofline") == pytest.approx(
        100 * costs_glm.dsa_select_cost(published, 1)["bytes"] / hbm / 6e-3,
        rel=1e-6)
    assert read("dsa_index_time_share") == pytest.approx(10.0)
    # a masked-dense kernel at its own 100 % reads selected / causal here
    whole = 2 * 64 * 512 * costs_glm.causal_pairs(9217) / peak
    dense = _view(published, [(0, int(whole * 1e9), attn)], busy_s=whole)
    assert mf.load_reader("flash_selected_fwd_roofline").read(dense) == (
        pytest.approx(39.5, abs=0.05))
    # the masked kernel's reader does not see the selected launches
    from benchmark.layer_metrics import flash_masked_fwd_roofline
    assert sum(bool(flash_masked_fwd_roofline.NAME.match(text))
               for *_, text in ops) == 1
    # nothing to read (the parent's program): no trace, or none of the kernels
    for reader in NEW_METRICS[:5]:
        assert mf.load_reader(reader).read(
            _view(published, [(0, ms, masked)], 1.0)) is None
        assert mf.load_reader(reader).read(types.SimpleNamespace(
            trace=None, config=published, peaks=PEAKS)) is None


def test_kernel_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("dsa_select_kernel_share")
    assert reader.read(None) is None  # no trace of the selection in the process
    scope = metrics.scope("kernels")
    for key in ("kernel", "kernel", "kernel", "xla"):
        scope.inc("kernels.dsa_select_schedule", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, REAL)
    assert (cell.chips, cell.driver) == (1, "sample_closed_glm")
    assert (cell.traffic["n"], cell.traffic["k"],
            cell.traffic["check_rows"]) == (1, 50, 1)
    assert set(result_line.expected_metrics(manifest, cell.name, False)) == {
        "sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, cell.name, True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        *NEW_METRICS}
    # the old cells' lines do not change
    assert set(result_line.expected_metrics(
        manifest, "laguna_s21_sample1024_k20", True)) == {
        "sampler_step_ms", "moe_gmm_roofline", "moe_gmm_time_share",
        "moe_gmm_kernel_share", "flash_masked_fwd_roofline",
        "flash_masked_fwd_time_share"}
    limits = json.load(open(os.path.join(mf.HERE, "workloads", REAL + ".json")))
    sound = limits["limits_from"]["sample_rms_vs_reference"]["program"]
    control = limits["limits_from"]["sample_rms_vs_reference"][
        "control_float8_e4m3"]
    assert len(sound) >= 8 and len(control) >= 3
    assert max(sound) < cell.limits["sample_rms_vs_reference"] < min(control)
