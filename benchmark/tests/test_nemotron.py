"""The Mamba-2 / attention / latent-expert configuration's benchmark files at
a toy size (``fixtures_nemotron/``: hidden 64, pattern ``ME*E``, 4 heads of 8
x 16 state in 2 groups scanned over chunks of 8, 4 query heads on 2 K/V heads,
16 router outputs top-3 of which 8 are held, experts 32 -> 24 -> 32 in a
latent, 16 x 16 px = 17 tokens, two chunks and one token): the driver end to
end through the same ``execute`` a real run uses, the control, the weights
against the program's own tree, the configuration against the catalog's row,
the cost functions at the published sizes, and the readers on a hand-made
trace."""

import json
import math
import os
import time
import types

import pytest

from benchmark import costs, costs_glm, costs_nemotron, manifest as mf
from benchmark import result_line, weights_nemotron
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_nemotron")
CELL = "toy_sample_nemotron"
REAL = "nemotron3_super_sample2048_k50"
CONFIG = "nemotron3_super_ep4_px2048"
CATALOG_NAME = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
PEAKS = mf.peaks_for("TPU v5 lite")
NEW_METRICS = ("ssd_chunk_roofline", "ssd_chunk_time_share",
               "ssd_chunk_kernel_share", "moe_gmm_latent_roofline")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]


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
    one norm and one mixer a layer, the mixer's leaves by the pattern's
    letter."""
    import jax
    import jax.numpy as jnp

    driver = mf.load_driver("sample_closed_nemotron")
    toy = toy_run().config
    for precision in ("bfloat16", "float32"):
        config = dict(toy, precision=precision)
        model = driver.build_model(config)
        x, t = jnp.zeros((2, 16, 16, 3)), jnp.zeros((2,), jnp.int32)
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        params = weights_nemotron.make(config, 7)
        assert spec(params) == spec(declared)
    assert all(sorted(params[f"layers_{i}"]) == ["mixer", "norm"]
               for i in range(4))
    kinds = [("A_log" in m, "q_proj" in m, "router" in m)
             for m in (params[f"layers_{i}"]["mixer"] for i in range(4))]
    assert kinds == [(True, False, False), (False, False, True),
                     (False, True, False), (False, False, True)]
    experts = params["layers_1"]["mixer"]
    assert "gate_proj" not in experts  # ungated
    assert "gate_proj" not in experts["shared_expert"]
    assert experts["up_proj"].shape == (8, 32, 24)  # in the latent
    assert experts["e_score_correction_bias"].shape == (16,)
    # what follows a squared ReLU is centred over its input (float32 here)
    assert float(jnp.abs(experts["down_proj"].mean(1)).max()) < 1e-8
    assert float(jnp.abs(
        experts["shared_expert"]["down_proj"]["kernel"].mean(0)).max()) < 1e-8
    assert float(jnp.abs(experts["up_proj"].mean(1)).max()) > 1e-4
    # Mamba-2's own leaves: a negative rate a head in [1, 16], Delta in range
    mixer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         params["layers_0"]["mixer"])
    assert (mixer["A_log"] >= 0).all() and (mixer["A_log"] <= math.log(16)).all()
    delta = jax.nn.softplus(mixer["dt_bias"])
    assert (delta > 0.9e-3).all() and (delta < 0.11).all()
    a, b, c = (weights_nemotron.make(toy, s)["layers_1"]["mixer"]["router"]
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
        row = next(r for r in map(json.loads, f) if r["name"] == CATALOG_NAME)
    assert published["source"] == row["source_url"]
    assert sorted(published["published_keys"]) == sorted(row["config"])
    differs = sorted(k for k, v in row["config"].items() if published[k] != v)
    assert differs == sorted(published["reduced"]) == sorted(REDUCED)
    assert published["reduced"] == REDUCED
    assert published["source_values"] == {
        k: row["config"][k] for k in REDUCED}
    pattern = published["source_values"]["hybrid_override_pattern"]
    assert len(pattern) == 88 == published["source_values"]["num_hidden_layers"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        40, 40, 8)
    # the slice is letters 27-37 of the published pattern: one whole period
    first = published["layers_from"]
    assert pattern[first:first + 11] == published["hybrid_override_pattern"] == (
        "MEMEMEMEM*E")
    assert pattern[:first] == "MEMEMEM*E" * 3
    assert [published[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "chunk_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "moe_latent_size", "moe_shared_expert_intermediate_size",
        "num_experts_per_tok", "routed_scaling_factor")] == [
        4096, 128, 64, 128, 8, 4, 128, 32, 2, 128, 2688, 1024, 5376, 22, 5]
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == published["reduced"]
    assert entry["source"] == published["source"]
    # the share: 128 of 512 held from expert 0
    trunk = weights_nemotron.trunk_of(published)
    assert (trunk["n_routed_experts"], trunk["n_experts_routed"],
            trunk["experts_held_from"], trunk["layers_from"]) == (128, 512, 0, 27)
    assert costs_nemotron.layer_kinds(published) == list("MEMEMEMEM*E")
    for key in ("no_rotary", "in_proj_columns", "delta", "gated_norm",
                "latent", "router", "router_precision", "experts",
                "position_table", "weights_dtype"):
        assert key in published["assumed"], key
    for key in ("rope_theta", "partial_rotary_factor",
                "mtp_hybrid_override_pattern"):
        assert key in published["unused_keys"], key
    for key in REDUCED:
        assert published[key + "_why"]
    assert "4 chips" in published["deployment"]


def test_parameters_counted_from_the_specs_are_the_issues(published):
    """ISSUE 41's cut, recounted: a Mamba-2 layer 109.64 M, the attention
    layer 35.66 M, an expert layer 54.53 M beside its experts of 5.505 M
    each, 4,380 M in eleven layers, 8.76 GB in bfloat16."""
    trunk = weights_nemotron.trunk_of(published)
    count = lambda specs: sum(math.prod(shape) for shape, *_ in specs.values())
    layers = [weights_nemotron.layer_specs(trunk, i) for i in range(11)]
    assert round(count(layers[0]) / 1e6, 2) == 109.64
    assert layers[0]["mixer", "in_proj", "kernel"][0] == (4096, 18560)
    assert layers[0]["mixer", "conv1d_kernel"][0] == (4, 10240)
    assert round(count(layers[9]) / 1e6, 2) == 35.66
    expert = math.prod(layers[1]["mixer", "up_proj"][0]) * 2 // 128
    assert expert == 2 * 1024 * 2688 == 5_505_024
    assert round((count(layers[1]) - 128 * expert) / 1e6, 2) == 54.53
    total = sum(map(count, layers))
    assert round(total / 1e6) == 4380
    assert 8.75e9 < 2 * total < 8.77e9
    whole = total + count(weights_nemotron.outer_specs(published))
    assert 8.9e9 < 2 * whole < 9.1e9  # with the input and output stage


def test_costs_at_the_published_sizes(published):
    """ISSUE 41's arithmetic: 6.55 MF of scan a token and layer, 107 GF and
    612 MB a launch, memory-bound (0.75 against 0.55 ms); 2.2 TF of attention
    in the one layer; about 36 TF a forward, half of it the mixers."""
    n = costs.tokens(published)
    assert n == 16385 == 128 * 128 + 1
    a_token = costs_nemotron.scan_flops_a_token(published)
    assert a_token == 128 * (2 * 128 * 64 + 4 * 128 * 64) + 8 * 2 * 128 * 128
    assert a_token == 6_553_600
    scan = costs_nemotron.ssd_cost(published, 1)
    assert scan["flops"] == 16385 * 6_553_600
    assert scan["bytes"] == 16385 * (2 * (2 * 8192 + 2 * 1024) + 4 * 128)
    least, bound = costs.roofline_seconds(scan, PEAKS)
    assert bound == "memory" and 0.74e-3 < least < 0.76e-3
    assert 0.54e-3 < scan["flops"] / PEAKS["bf16_flops_per_s"] < 0.55e-3
    gated = costs_nemotron.ssd_cost(published, 2, gated=True)
    assert gated["flops"] == 2 * scan["flops"]
    assert gated["bytes"] == 2 * (scan["bytes"] + 16385 * 2 * 8192)
    assert costs_nemotron.held_share(published) == 0.25
    # one product a launch, a quarter of the 16,385 x 22 assignments
    rows = 16385 * 22 / 4
    up = costs_nemotron.moe_gmm_cost(published, rows, 1024, 2688)
    assert up["flops"] == 2 * rows * 1024 * 2688
    assert up["bytes"] == 2 * (rows * (1024 + 2688) + 128 * 1024 * 2688)
    assert costs.roofline_seconds(up, PEAKS)[1] == "compute"
    whole = costs_nemotron.forward_flops(published)
    assert 35.5e12 < whole < 36.5e12
    attention = 2 * 32 * 2 * 128 * costs_glm.causal_pairs(n)
    assert 2.1e12 < attention < 2.3e12
    mixers = 5 * n * (2 * (4096 * 18560 + 8192 * 4096) + a_token)
    assert 0.50 < mixers / whole < 0.53


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


def test_roofline_and_time_share_readers_on_a_hand_made_trace(published):
    target = 'custom_call_target="tpu_custom_call"'
    scan = ("%ssd_chunk.1 = bf16[1,16385,8192]{2,1,0:T(8,128)(2,1)} "
            "custom-call(%a_0_.1, %a_3_.1, %a_4_.1, %copy.17, %bitcast.17, "
            "/*index=5*/%reshape.11), " + target)
    gated = scan.replace("%reshape.11)", "%reshape.11, %a_9_.1)")
    up = ("%moe_gmm.7 = bf16[360576,2688]{1,0:T(8,128)(2,1)} "
          "custom-call(%a, %b), " + target)
    down = up.replace("[360576,2688]", "[360576,1024]")
    masked = ("%fwd_masked.6 = bf16[1,16385,4096]{2,1,0:T(8,128)(2,1)} "
              "custom-call(%a, %b, %c), " + target)
    other = "%fusion.3 = bf16[1,16385,4096]{2,1,0} fusion(%x), kind=kOutput"
    ms = 1_000_000
    ops = [(0, 2 * ms, scan), (2 * ms, 4 * ms, scan), (4 * ms, 8 * ms, up),
           (8 * ms, 12 * ms, down), (12 * ms, 15 * ms, masked),
           (15 * ms, 20 * ms, other)]
    view = _view(published, ops, busy_s=20e-3)
    read = lambda name: mf.load_reader(name).read(view)
    least = costs.roofline_seconds(
        costs_nemotron.ssd_cost(published, 1), PEAKS)[0]
    assert read("ssd_chunk_roofline") == pytest.approx(
        100 * 2 * least / 4e-3, rel=1e-6)
    assert read("ssd_chunk_time_share") == pytest.approx(20.0)
    # a launch that applies the gate is told by its seventh operand, and
    # credited for reading z
    with_gate = costs.roofline_seconds(
        costs_nemotron.ssd_cost(published, 1, gated=True), PEAKS)[0]
    assert mf.load_reader("ssd_chunk_roofline").read(
        _view(published, [(0, 2 * ms, gated)], 2e-3)) == pytest.approx(
        100 * with_gate / 2e-3, rel=1e-6)
    # the held quarter of the 360,470 assignments, one product a launch
    rows = 360470 / 4
    peak = PEAKS["bf16_flops_per_s"]
    want = 2 * (2 * rows * 1024 * 2688 / peak)
    assert read("moe_gmm_latent_roofline") == pytest.approx(
        100 * want / 8e-3, rel=1e-6)
    assert read("moe_gmm_time_share") == pytest.approx(40.0)
    assert read("flash_masked_fwd_time_share") == pytest.approx(15.0)
    # both products at the MXU's peak on what the held quarter needs: 100 %
    at_peak = _view(published, [(0, int(want / 2 * 1e9), up)], want / 2)
    assert mf.load_reader("moe_gmm_latent_roofline").read(at_peak) == (
        pytest.approx(100.0, abs=0.01))
    # nothing to read (the parent's program, another configuration)
    for reader in NEW_METRICS[:2] + NEW_METRICS[3:]:
        assert mf.load_reader(reader).read(
            _view(published, [(0, ms, masked)], 1.0)) is None
        assert mf.load_reader(reader).read(types.SimpleNamespace(
            trace=None, config=published, peaks=PEAKS)) is None
    laguna = json.load(open(os.path.join(
        mf.HERE, "configs", "laguna_s21_ep2_px1024.json")))
    assert mf.load_reader("moe_gmm_latent_roofline").read(
        _view(laguna, ops, 20e-3)) is None


def test_kernel_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("ssd_chunk_kernel_share")
    assert reader.read(None) is None  # no trace of the scan in the process
    scope = metrics.scope("kernels")
    for key in ("kernel", "kernel", "kernel", "xla"):
        scope.inc("kernels.ssd_schedule", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, REAL)
    assert (cell.chips, cell.driver, cell.config_name) == (
        1, "sample_closed_nemotron", CONFIG)
    assert cell.traffic == {"driver": "sample_closed_nemotron", "n": 1,
                            "k": 50, "check_rows": 1, "trace_window_s": 1}
    assert set(result_line.expected_metrics(manifest, cell.name, False)) == {
        "sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, cell.name, True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_masked_fwd_time_share", *NEW_METRICS}
    # the old cells' lines do not change
    assert set(result_line.expected_metrics(
        manifest, "pangu_ultra_sample1536_k50", True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_latent_fwd_roofline", "flash_latent_fwd_time_share",
        "flash_latent_fwd_kernel_share"}
    assert set(result_line.expected_metrics(
        manifest, "jamba2_3b_sample512_k20", True)) == {
        "sampler_step_ms", "ssm_scan_roofline", "ssm_scan_time_share",
        "ssm_scan_kernel_share"}
    limits = json.load(open(os.path.join(mf.HERE, "workloads", REAL + ".json")))
    sound = limits["limits_from"]["sample_rms_vs_reference"]["program"]
    control = limits["limits_from"]["sample_rms_vs_reference"][
        "control_float8_e4m3"]
    assert len(sound) >= 8 and len(control) >= 3
    assert max(sound) < cell.limits["sample_rms_vs_reference"] < min(control)
