"""The delta-attention / position-free latent-attention / sigmoid-routed
configuration's benchmark files at a toy size (``fixtures_kimi/``: hidden 64,
layers delta, delta, latent, delta by the two lists, the first with the dense
MLP, 4 delta heads of a 16 x 16 state, 2 latent heads of 128 + 64 / 128, 16
router outputs top-3 of which 8 are held, 32 x 64 px = 129 tokens, one chunk
of the scan and one token): the driver end to end through the same
``execute`` a real run uses, the control, the weights against the program's
own tree, the configuration against the catalog's row, the cost functions at
the published sizes, and the readers on a hand-made trace."""

import json
import math
import os
import time
import types

import pytest

from benchmark import costs, costs_glm, costs_kimi, costs_pangu, manifest as mf
from benchmark import result_line, weights_kimi
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_kimi")
CELL = "toy_sample_kimi"
REAL = "kimi_linear_sample2048_k50"
CONFIG = "kimi_linear_ep2_px2048"
CATALOG_NAME = "Kimi-Linear-48B-A3B-Instruct"
PEAKS = mf.peaks_for("TPU v5 lite")
NEW_METRICS = ("kda_chunk_roofline", "kda_chunk_time_share",
               "kda_chunk_kernel_share")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


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
    the mixer's leaves by the two lists, the MLP's by
    ``first_k_dense_replace``."""
    import jax
    import jax.numpy as jnp

    driver = mf.load_driver("sample_closed_kimi")
    toy = toy_run().config
    for precision in ("bfloat16", "float32"):
        config = dict(toy, precision=precision)
        model = driver.build_model(config)
        x, t = jnp.zeros((2, 32, 64, 3)), jnp.zeros((2,), jnp.int32)
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        params = weights_kimi.make(config, 7)
        assert spec(params) == spec(declared)
    assert all(sorted(params[f"layers_{i}"]) == [
        "input_layernorm", "mlp", "post_attention_layernorm", "self_attn"]
        for i in range(4))
    kinds = [("A_log" in m["self_attn"], "kv_b_proj" in m["self_attn"],
              "router" in m["mlp"])
             for m in (params[f"layers_{i}"] for i in range(4))]
    assert kinds == [(True, False, False), (True, False, True),
                     (False, True, True), (True, False, True)]
    experts = params["layers_1"]["mlp"]
    assert experts["gate_proj"].shape == experts["up_proj"].shape == (8, 64, 24)
    assert experts["router"].shape == (64, 16)
    assert experts["e_score_correction_bias"].shape == (16,)
    # the delta mixer's own leaves: a rate a head in [1, 16], a step a key
    # channel in [1e-3, 1e-1], no convolution bias, no query latent
    mixer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         params["layers_0"]["self_attn"])
    assert mixer["A_log"].shape == (4,) and mixer["dt_bias"].shape == (64,)
    assert (mixer["A_log"] >= 0).all() and (mixer["A_log"] <= math.log(16)).all()
    step = jax.nn.softplus(mixer["dt_bias"])
    assert (step > 0.9e-3).all() and (step < 0.11).all()
    assert sorted(mixer["q_conv1d"]) == ["conv1d_kernel"]
    assert "q_a_proj" not in params["layers_2"]["self_attn"]
    a, b, c = (weights_kimi.make(toy, s)["layers_1"]["mlp"]["router"]
               for s in (7, 7, 8))
    assert (a == b).all() and not (a == c).all()


def test_the_configuration_file_carries_the_catalog_rows_keys(published):
    """Every key the source's config.json has, under the same name and with
    the same value, but for what ``reduced`` lists; every width as published,
    the nested ``linear_attn_config`` whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CATALOG_NAME)
    assert published["source"] == row["source_url"]
    assert sorted(published["published_keys"]) == sorted(row["config"])
    differs = sorted(k for k, v in row["config"].items() if published[k] != v)
    assert differs == sorted(published["reduced"]) == sorted(REDUCED)
    assert published["reduced"] == REDUCED
    assert published["source_values"] == {
        k: row["config"][k] for k in REDUCED}
    lists = published["linear_attn_config"]
    assert lists == row["config"]["linear_attn_config"]
    assert sorted(lists["kda_layers"] + lists["full_attn_layers"]) == list(
        range(1, 28))
    assert (len(lists["kda_layers"]), len(lists["full_attn_layers"])) == (20, 7)
    assert [published[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "q_lora_rank", "num_experts_per_token",
        "num_shared_experts", "routed_scaling_factor")] == [
        2304, 9216, 1024, 32, 128, 64, 128, 512, None, 8, 1, 2.446]
    assert (lists["num_heads"], lists["head_dim"],
            lists["short_conv_kernel_size"]) == (32, 128, 4)
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == published["reduced"]
    assert entry["source"] == published["source"]
    # the share: 128 of 256 held from expert 0; the slice: layers 0-4
    trunk = weights_kimi.trunk_of(published)
    assert (trunk["num_experts"], trunk["num_experts_routed"],
            trunk["experts_held_from"], trunk["layers_from"]) == (
        128, 256, 0, 0)
    assert costs_kimi.layer_kinds(published) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    for key in ("kda_projections", "kda_conv_bias", "kda_l2", "kda_decay",
                "kda_beta", "kda_output", "kda_seeds", "kda_chunks", "mla",
                "weight_column_order", "router", "router_precision",
                "position_table", "weights_dtype"):
        assert key in published["assumed"], key
    for key in ("rope_theta", "rope_scaling", "head_dim 72",
                "num_key_value_heads", "model_max_length",
                "tie_word_embeddings"):
        assert key in published["unused_keys"], key
    for key in REDUCED:
        assert published[key + "_why"]
    assert "2 chips" in published["deployment"]
    assert "6 pipeline stages" in published["deployment"]


def test_parameters_counted_from_the_specs_are_the_issues(published):
    """ISSUE 45's cut, recounted: a delta mixer 39.51 M, the latent mixer
    29.11 M, an expert layer 7.67 M beside its experts of 7.078 M each, the
    dense MLP 63.70 M, 3,905 M in five layers, 7.81 GB in bfloat16."""
    trunk = weights_kimi.trunk_of(published)
    count = lambda specs, under: sum(
        math.prod(shape) for path, (shape, *_) in specs.items()
        if path[0] == under)
    layers = [weights_kimi.layer_specs(trunk, i) for i in range(5)]
    assert round(count(layers[0], "self_attn") / 1e6, 2) == 39.51
    assert round(count(layers[0], "mlp") / 1e6, 2) == 63.70
    assert round(count(layers[3], "self_attn") / 1e6, 2) == 29.11
    assert layers[3]["self_attn", "q_proj", "kernel"][0] == (2304, 32 * 192)
    assert layers[1]["self_attn", "f_b_proj", "kernel"][0] == (128, 4096)
    expert = 3 * 2304 * 1024
    assert math.prod(layers[1]["mlp", "up_proj"][0]) == 128 * 2304 * 1024
    assert round((count(layers[1], "mlp") - 128 * expert) / 1e6, 2) == 7.67
    total = sum(weights_kimi.parameters(trunk, i) for i in range(5))
    assert round(total / 1e6) == 3905
    assert 7.80e9 < 2 * total < 7.82e9
    whole = total + sum(math.prod(shape) for shape, *_ in
                        weights_kimi.outer_specs(published).values())
    assert 7.89e9 < 2 * whole < 7.92e9  # with the input and output stage


def test_costs_at_the_published_sizes(published):
    """ISSUE 45's arithmetic at the launch's own chunk of 128: 5.8 MF of scan
    a token and layer, 94 GF and 807 MB a launch, memory-bound (0.99 against
    0.48 ms); 2.75 TF of causal pairs in the one latent layer; 16 TF a
    forward, a third of it the delta mixers."""
    from ddim_cold_tpu.ops import kda

    assert costs_kimi.SCAN_CHUNK == kda.CHUNK == 128
    n = costs.tokens(published)
    assert n == 16385 == 128 * 128 + 1
    a_token = costs_kimi.scan_flops_a_token(published)
    assert a_token == 32 * (4 * 128 * 128 + 6 * 128 * 128 + 128 * 128)
    scan = costs_kimi.kda_cost(published, 1)
    assert scan["flops"] == 16385 * a_token
    assert scan["bytes"] == 16385 * (4 * 4096 * 2 + 4096 * 4 + 32 * 4)
    least, bound = costs.roofline_seconds(scan, PEAKS)
    assert bound == "memory" and 0.98e-3 < least < 0.99e-3
    assert 0.47e-3 < scan["flops"] / PEAKS["bf16_flops_per_s"] < 0.49e-3
    gated = costs_kimi.kda_cost(published, 2, gated=True)
    assert gated["flops"] == 2 * scan["flops"]
    assert gated["bytes"] == 2 * (scan["bytes"] + 16385 * 2 * 4096)
    assert costs_kimi.held_share(published) == 0.5
    whole = costs_kimi.forward_flops(published)
    assert 15.9e12 < whole < 16.2e12
    latent = costs_pangu.flash_latent_fwd_cost(published, 1)
    assert latent["flops"] == 2 * 32 * 320 * costs_glm.causal_pairs(n)
    assert 2.7e12 < latent["flops"] < 2.8e12
    mixers = 4 * n * (2 * (4 * 2304 * 4096 + 4 * 2304 * 128 + 2304 * 32)
                      + a_token)
    assert 0.33 < mixers / whole < 0.36


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


def test_roofline_and_time_share_readers_on_a_hand_made_trace(published):
    target = 'custom_call_target="tpu_custom_call"'
    scan = ("%kda_chunk.4 = bf16[1,16385,4096]{2,1,0:T(8,128)(2,1)} "
            "custom-call(%fusion.96, %fusion.97, %multiply_convert_fusion.9, "
            "%fusion.116, %copy-done.34), " + target)
    gated = scan.replace("%copy-done.34)", "%copy-done.34, %fusion.120)")
    up = ("%moe_gmm.8 = bf16[131200,1024]{1,0:T(8,128)(2,1)} "
          "custom-call(%a, %b), " + target)
    latent = ("%fwd_latent.1 = bf16[1,16385,4096]{2,1,0:T(8,128)(2,1)} "
              "custom-call(%a, %b, %c, %d, %e), " + target)
    other = "%fusion.3 = bf16[1,16385,4096]{2,1,0} fusion(%x), kind=kOutput"
    ms = 1_000_000
    ops = [(0, 2 * ms, scan), (2 * ms, 4 * ms, scan), (4 * ms, 8 * ms, up),
           (8 * ms, 28 * ms, latent), (28 * ms, 40 * ms, other)]
    view = _view(published, ops, busy_s=40e-3)
    read = lambda name: mf.load_reader(name).read(view)
    least = costs.roofline_seconds(costs_kimi.kda_cost(published, 1), PEAKS)[0]
    assert read("kda_chunk_roofline") == pytest.approx(
        100 * 2 * least / 4e-3, rel=1e-6)
    assert read("kda_chunk_time_share") == pytest.approx(10.0)
    # a launch that applies the gate is told by its sixth operand, and
    # credited for reading it
    with_gate = costs.roofline_seconds(
        costs_kimi.kda_cost(published, 1, gated=True), PEAKS)[0]
    assert mf.load_reader("kda_chunk_roofline").read(
        _view(published, [(0, 2 * ms, gated)], 2e-3)) == pytest.approx(
        100 * with_gate / 2e-3, rel=1e-6)
    # the accepted readers read true on this cell unedited: the latent
    # launch at 32 heads of 128 + 64 / 128, the experts' launches by name
    want = costs_pangu.flash_latent_fwd_cost(published, 1)["flops"] / PEAKS[
        "bf16_flops_per_s"]
    assert read("flash_latent_fwd_roofline") == pytest.approx(
        100 * want / 20e-3, rel=1e-6)
    assert read("flash_latent_fwd_time_share") == pytest.approx(50.0)
    assert read("moe_gmm_time_share") == pytest.approx(10.0)
    # at the memory system's peak on what a launch needs: 100 %
    at_peak = _view(published, [(0, int(least * 1e9), scan)], least)
    assert mf.load_reader("kda_chunk_roofline").read(at_peak) == (
        pytest.approx(100.0, abs=0.01))
    # nothing to read (the parent's program, another configuration)
    for reader in NEW_METRICS[:2]:
        assert mf.load_reader(reader).read(
            _view(published, [(0, ms, latent)], 1.0)) is None
        assert mf.load_reader(reader).read(types.SimpleNamespace(
            trace=None, config=published, peaks=PEAKS)) is None


def test_kernel_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("kda_chunk_kernel_share")
    assert reader.read(None) is None  # no trace of the scan in the process
    scope = metrics.scope("kernels")
    for key in ("kernel", "kernel", "kernel", "xla"):
        scope.inc("kernels.kda_schedule", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, REAL)
    assert (cell.chips, cell.driver, cell.config_name) == (
        1, "sample_closed_kimi", CONFIG)
    assert cell.traffic == {"driver": "sample_closed_kimi", "n": 1,
                            "k": 50, "check_rows": 1, "trace_window_s": 1}
    assert set(result_line.expected_metrics(manifest, cell.name, False)) == {
        "sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, cell.name, True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_latent_fwd_roofline", "flash_latent_fwd_time_share",
        "flash_latent_fwd_kernel_share", *NEW_METRICS}
    # the old cells' lines do not change
    assert set(result_line.expected_metrics(
        manifest, "pangu_ultra_sample1536_k50", True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_latent_fwd_roofline", "flash_latent_fwd_time_share",
        "flash_latent_fwd_kernel_share"}
    assert set(result_line.expected_metrics(
        manifest, "nemotron3_super_sample2048_k50", True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_masked_fwd_time_share", "ssd_chunk_roofline",
        "ssd_chunk_time_share", "ssd_chunk_kernel_share",
        "moe_gmm_latent_roofline"}
    assert (len(manifest["configs"]), len(manifest["workloads"])) == (8, 9)
    limits = json.load(open(os.path.join(mf.HERE, "workloads", REAL + ".json")))
    sound = limits["limits_from"]["sample_rms_vs_reference"]["program"]
    control = limits["limits_from"]["sample_rms_vs_reference"][
        "control_float8_e4m3"]
    assert len(sound) >= 8 and len(control) >= 3
    assert max(sound) < cell.limits["sample_rms_vs_reference"] < min(control)
