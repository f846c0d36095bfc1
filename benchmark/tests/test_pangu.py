"""The dense-causal latent-attention, sandwich-normed configuration's
benchmark files at a toy size (``fixtures_pangu/``: hidden 64, published
layers 2-4 with 3 leading dense layers, 2 heads of 128 + 64 / 128, 16 router
outputs of which 8 are held, 16 x 16 px): the driver end to end through the
same ``execute`` a real run uses, the control, the weights against the
program's own tree, the configuration against the catalog's row, the cost
functions at the published sizes, and the readers on a hand-made trace."""

import json
import math
import os
import time
import types

import pytest

from benchmark import costs, costs_glm, costs_pangu, manifest as mf, result_line
from benchmark import weights_pangu
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_pangu")
CELL = "toy_sample_pangu"
REAL = "pangu_ultra_sample1536_k50"
CONFIG = "pangu_ultra_ep32_px1536"
PEAKS = mf.peaks_for("TPU v5 lite")
NEW_METRICS = ("flash_latent_fwd_roofline", "flash_latent_fwd_time_share",
               "flash_latent_fwd_kernel_share")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(mf.HERE, "configs", CONFIG + ".json")) as f:
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
    """Names, shapes and dtypes of ``model.init`` — at both storage types;
    four norms a layer, no selection bias, a dense MLP in the leading layer."""
    import jax
    import jax.numpy as jnp

    driver = mf.load_driver("sample_closed_pangu")
    toy = toy_run().config
    for precision in ("bfloat16", "float32"):
        config = dict(toy, precision=precision)
        model = driver.build_model(config)
        x, t = jnp.zeros((2, 16, 16, 3)), jnp.zeros((2,), jnp.int32)
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        params = weights_pangu.make(config, 7)
        assert spec(params) == spec(declared)
    assert ["router" in params[f"layers_{i}"]["mlp"] for i in range(3)] == [
        False, True, True]
    assert "e_score_correction_bias" not in params["layers_1"]["mlp"]
    assert sum(k.endswith("layernorm") for k in params["layers_1"]) == 4
    a, b, c = (weights_pangu.make(toy, s)["layers_1"]["mlp"]["router"]
               for s in (7, 7, 8))
    assert (a == b).all() and not (a == c).all()


def test_the_configuration_file_carries_the_catalog_rows_keys(published):
    """Every key the source's config.json has, under the same name and with
    the same value, but for what ``reduced`` lists; every width as
    published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    assert published["source"] == row["source_url"]
    assert sorted(published["published_keys"]) == sorted(row["config"])
    differs = sorted(k for k, v in row["config"].items() if published[k] != v)
    assert differs == sorted(published["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert published["source_values"] == {
        k: row["config"][k] for k in published["reduced"]} == {
        "num_hidden_layers": 61, "n_routed_experts": 256,
        "vocab_size": 153600, "num_nextn_predict_layers": 1}
    assert [published[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok")
            ] == [7680, 128, 128, 64, 128, 1536, 512, 18432, 2048, 8]
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == published["reduced"]
    assert entry["source"] == published["source"]
    # the share and the slice: 8 of 256 held from expert 0, layers 2-6
    trunk = weights_pangu.trunk_of(published)
    assert (trunk["n_routed_experts"], trunk["n_experts_routed"],
            trunk["experts_held_from"], trunk["layers_from"]) == (8, 256, 0, 2)
    assert costs_pangu.layer_kinds(published) == ["dense"] + ["sparse"] * 4
    for key in ("router", "sandwich_norm", "weight_column_order", "rotary",
                "inner_norms", "attention_form"):
        assert key in published["assumed"], key
    assert "32 chips" in published["deployment"]


def test_parameters_counted_from_the_specs_are_the_issues(published):
    """ISSUE 39's cut, recounted: attention 196.58 M a layer, the dense layer
    621.3 M, an expert layer 623.2 M with 8 held, 3,114 M in five layers,
    6.43 GB in bfloat16 with the input and output stage."""
    trunk = weights_pangu.trunk_of(published)
    count = lambda specs: sum(math.prod(shape) for shape, *_ in specs.values())
    specs = weights_pangu.layer_specs(trunk, 1)
    attn = sum(math.prod(shape) for path, (shape, *_) in specs.items()
               if path[0] == "self_attn" and path[-1] == "kernel")
    assert round(attn / 1e6, 2) == 196.58
    assert math.prod(specs["mlp", "gate_proj"][0]) * 3 / 8 == 47_185_920
    layers = [count(weights_pangu.layer_specs(trunk, i)) for i in range(5)]
    assert [round(n / 1e6, 1) for n in layers] == [621.3] + [623.2] * 4
    total = sum(layers) + count(weights_pangu.outer_specs(published))
    assert round(sum(layers) / 1e6) == 3114
    assert 6.42e9 < 2 * total < 6.44e9
    # the program's two up-projections hold a head's parts apart
    assert specs["self_attn", "q_b_proj", "kernel"][0] == (1536, 128 * 192)
    assert specs["self_attn", "kv_b_proj", "kernel"][0] == (512, 128 * 256)


def test_costs_at_the_published_sizes(published):
    """ISSUE 39's arithmetic: 42.48 M causal pairs a head; 3.48 TF a launch
    of the latent forward, compute-bound; about 48 TF a forward, 17.4 of them
    attention."""
    n = costs.tokens(published)
    assert n == 9217 and costs_glm.causal_pairs(n) == 42_481_153
    assert costs_pangu.held_share(published) == 1 / 32
    attn = costs_pangu.flash_latent_fwd_cost(published, 1)
    assert attn["flops"] == 2 * 128 * (128 + 64 + 128) * 42_481_153
    assert 3.47e12 < attn["flops"] < 3.49e12
    # q (192), k_nope, v and the context (128 each) a head, and k_r once
    assert attn["bytes"] == 2 * 9217 * (128 * (192 + 3 * 128) + 64)
    assert costs.roofline_seconds(attn, PEAKS)[1] == "compute"
    assert costs_pangu.flash_latent_fwd_cost(published, 3)["flops"] == (
        3 * attn["flops"])
    whole = costs_pangu.forward_flops(published)
    assert 47.5e12 < whole < 48.5e12
    assert 30.0e12 < whole - 5 * attn["flops"] < 31.0e12  # the GEMMs


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


def test_roofline_and_time_share_readers_on_a_hand_made_trace(published):
    tail = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
    latent = "%fwd_latent.3 = bf16[1,9217,16384]{2,1,0:T(8,128)(2,1)}" + tail
    selected = "%fwd_selected.3 = bf16[1,9217,16384]{2,1,0:T(8,128)(2,1)}" + tail
    masked = "%fwd_masked.6 = bf16[1,9217,16384]{2,1,0:T(8,128)(2,1)}" + tail
    other = "%fusion.3 = bf16[1,9217,7680]{2,1,0} fusion(%x), kind=kOutput"
    ms = 1_000_000
    ops = [(0, 40 * ms, latent), (40 * ms, 80 * ms, latent),
           (80 * ms, 85 * ms, selected), (85 * ms, 90 * ms, masked),
           (90 * ms, 100 * ms, other)]
    view = _view(published, ops, busy_s=100e-3)
    peak = PEAKS["bf16_flops_per_s"]
    read = lambda name: mf.load_reader(name).read(view)
    least = costs_pangu.flash_latent_fwd_cost(published, 1)["flops"] / peak
    assert read("flash_latent_fwd_roofline") == pytest.approx(
        100 * 2 * least / 80e-3, rel=1e-6)
    assert read("flash_latent_fwd_time_share") == pytest.approx(80.0)
    # a launch at the MXU's peak on what it multiplies (256 + 128 dims a
    # pair: the rotated 64 cost a whole 128-deep pass) reads 83 %, under 100
    padded = 2 * 128 * (256 + 128) * 42_481_153 / peak
    dense = _view(published, [(0, int(padded * 1e9), latent)], busy_s=padded)
    assert mf.load_reader("flash_latent_fwd_roofline").read(dense) == (
        pytest.approx(100 * 320 / 384, abs=0.01))
    # the other forwards' readers do not see the latent launches, nor it theirs
    from benchmark.layer_metrics import (flash_fwd_roofline,
                                         flash_latent_fwd_roofline,
                                         flash_masked_fwd_roofline,
                                         flash_selected_fwd_roofline)
    seen = lambda reader: sum(bool(reader.NAME.match(text))
                              for *_, text in ops)
    assert (seen(flash_latent_fwd_roofline), seen(flash_selected_fwd_roofline),
            seen(flash_masked_fwd_roofline), seen(flash_fwd_roofline)) == (
        2, 1, 1, 0)
    # nothing to read (the parent's program): no trace, or none of the kernel
    for reader in NEW_METRICS[:2]:
        assert mf.load_reader(reader).read(
            _view(published, [(0, ms, masked)], 1.0)) is None
        assert mf.load_reader(reader).read(types.SimpleNamespace(
            trace=None, config=published, peaks=PEAKS)) is None


def test_kernel_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("flash_latent_fwd_kernel_share")
    assert reader.read(None) is None  # no trace of the attention in the process
    scope = metrics.scope("kernels")
    for key in ("kernel", "kernel", "kernel", "xla"):
        scope.inc("kernels.flash_latent_schedule", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, REAL)
    assert (cell.chips, cell.driver, cell.config_name) == (
        1, "sample_closed_pangu", CONFIG)
    assert cell.traffic == {"driver": "sample_closed_pangu", "n": 1, "k": 50,
                            "check_rows": 1, "trace_window_s": 1}
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
    assert set(result_line.expected_metrics(
        manifest, "glm52_sample1536_k50", True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_selected_fwd_roofline", "flash_selected_fwd_time_share",
        "dsa_index_roofline", "dsa_select_roofline", "dsa_index_time_share",
        "dsa_select_kernel_share"}
    assert set(result_line.expected_metrics(
        manifest, "flower200_sample_k20", True)) == {
        "sampler_step_ms", "flash_fwd_roofline"}
    limits = json.load(open(os.path.join(mf.HERE, "workloads", REAL + ".json")))
    sound = limits["limits_from"]["sample_rms_vs_reference"]["program"]
    control = limits["limits_from"]["sample_rms_vs_reference"][
        "control_float8_e4m3"]
    assert len(sound) >= 8 and len(control) >= 3
    assert max(sound) < cell.limits["sample_rms_vs_reference"] < min(control)
