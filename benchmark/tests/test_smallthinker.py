"""The router-before-attention / ReLU-gated-experts / rotary-window and
position-free-full configuration's benchmark files at a toy size
(``fixtures_smallthinker/``: hidden 64, layers full, window, window, window,
full by the two lists, 14 query heads on 2 K/V heads of 16, 8 router outputs
top-3 all held, experts of width 32, window 8, 12 x 52 px = 40 tokens): the
driver end to end through the same ``execute`` a real run uses, the control,
the weights against the program's own tree, the configuration against the
catalog's row, the cost functions at the published sizes, the three new
readers on a hand-made trace, and the cell in the manifest."""

import json
import math
import os
import time
import types

import pytest

from benchmark import costs, costs_laguna, costs_smallthinker, manifest as mf
from benchmark import result_line, weights_smallthinker
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_smallthinker")
CELL = "toy_sample_smallthinker"
REAL = "smallthinker_21b_sample2032_k50"
CONFIG = "smallthinker_21b_l8_px2032"
CATALOG_NAME = "SmallThinker-21BA3B-Instruct"
PEAKS = mf.peaks_for("TPU v5 lite")
NEW_METRICS = ("moe_gmm_reglu_roofline", "flash_masked_mixed_roofline",
               "moe_route_layer_input_share")
REDUCED = ["num_hidden_layers", "vocab_size"]


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
    every layer the same leaves, no shared expert, the router as wide as the
    stream it reads."""
    import jax
    import jax.numpy as jnp

    driver = mf.load_driver("sample_closed_smallthinker")
    toy = toy_run().config
    for precision in ("bfloat16", "float32"):
        config = dict(toy, precision=precision)
        model = driver.build_model(config)
        x, t = jnp.zeros((2, 12, 52, 3)), jnp.zeros((2,), jnp.int32)
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        params = weights_smallthinker.make(config, 7)
        assert spec(params) == spec(declared)
    assert all(sorted(params[f"layers_{i}"]) == [
        "input_layernorm", "mlp", "post_attention_layernorm", "self_attn"]
        for i in range(5))
    experts = params["layers_1"]["mlp"]
    assert sorted(experts) == ["down_proj", "gate_proj", "router", "up_proj"]
    assert experts["gate_proj"].shape == experts["up_proj"].shape == (8, 64, 32)
    assert experts["router"].shape == (64, 8)
    assert sorted(params["layers_0"]["self_attn"]) == [
        "k_proj", "o_proj", "q_proj", "v_proj"]
    a, b, c = (weights_smallthinker.make(toy, s)["layers_1"]["mlp"]["router"]
               for s in (7, 7, 8))
    assert (a == b).all() and not (a == c).all()


def test_the_configuration_file_carries_the_catalog_rows_keys(published):
    """Every key the source's config.json has, under the same name and with
    the same value, but for what ``reduced`` lists: depth and vocabulary
    only, every width and every expert as published."""
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
    assert published["source_values"] == {k: row["config"][k] for k in REDUCED}
    assert [published[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_num_primary_experts",
        "moe_num_active_primary_experts", "moe_ffn_hidden_size",
        "sliding_window_size", "rope_theta", "num_hidden_layers",
        "vocab_size")] == [2560, 28, 4, 128, 64, 6, 768, 4096, 1500000, 8, 0]
    assert published["rope_layout"] == published["sliding_window_layout"] == (
        [0, 1, 1, 1] * 13)
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == published["reduced"]
    assert entry["source"] == published["source"]
    # the share: all 64 of 64 held from expert 0
    trunk = weights_smallthinker.trunk_of(published)
    assert (trunk["moe_num_primary_experts"],
            trunk["moe_num_primary_experts_routed"],
            trunk["experts_held_from"], trunk["model_type"]) == (
        64, 64, 0, "smallthinker")
    assert costs_smallthinker.held_share(published) == 1.0
    assert [costs_smallthinker.window_of(published, i) for i in range(8)] == [
        None, 4096, 4096, 4096] * 2
    for key in ("model_type", "router_input", "router", "activation",
                "secondary_experts", "shared_expert", "qk_norm",
                "attention_bias", "rotary", "masks", "img_size",
                "position_table", "total_steps", "weights_dtype"):
        assert key in published["assumed"], key
    for key in ("max_position_embeddings", "tie_word_embeddings",
                "vocab_size", "model_name"):
        assert key in published["unused_keys"], key
    for key in REDUCED:
        assert published[key + "_why"]
    assert "all 64 experts" in published["deployment"]
    assert "pipeline stages of 8" in published["deployment"]
    # 127 x 127 patches and the class token stay inside the published
    # positions; one more patch a side would not
    assert costs.tokens(published) == 16130 <= published[
        "max_position_embeddings"] < 128 * 128 + 1


def test_parameters_counted_from_the_specs_are_the_issues(published):
    """ISSUE 48's cut, recounted: a layer 20.97 M of attention + 0.164 M of
    router + 64 experts of 5.898 M = 398.6 M, eight layers 3.19 B, 6.38 GB in
    bfloat16, 37 % of the chip."""
    trunk = weights_smallthinker.trunk_of(published)
    specs = weights_smallthinker.layer_specs(trunk)
    count = lambda under: sum(math.prod(shape) for path, (shape, *_)
                              in specs.items() if path[0] == under)
    assert round(count("self_attn") / 1e6, 2) == 20.97
    assert math.prod(specs["mlp", "router"][0]) == 2560 * 64 == 163840
    expert = 3 * 2560 * 768
    assert round(expert / 1e6, 3) == 5.898
    assert count("mlp") == 163840 + 64 * expert
    a_layer = weights_smallthinker.parameters(trunk)
    assert round(a_layer / 1e6, 1) == 398.6
    assert round(8 * a_layer / 1e9, 2) == 3.19
    assert 6.37e9 < 2 * 8 * a_layer < 6.39e9
    assert 0.37 < 2 * 8 * a_layer / PEAKS["hbm_bytes"] < 0.38


def test_costs_at_the_published_sizes(published):
    """ISSUE 48's arithmetic: 57.68 M pairs a head in a window layer against
    130.10 M in a full one; 23.3 TF a forward of the eight layers, of which
    the experts 39.2 %, attention inside its masks 37.3 % (window layers
    21.3, full 16.0), the projections 23.2 %, the router 0.2 %; 1,512 rows an
    expert."""
    n = costs.tokens(published)
    assert n == 16130 == 127 * 127 + 1
    window, full = costs_laguna.seen(n, 4096), costs_laguna.seen(n, None)
    assert (round(window / 1e6, 2), round(full / 1e6, 2)) == (57.68, 130.10)
    assert full == n * (n + 1) // 2
    assert 0.44 < window / full < 0.45
    parts = costs_smallthinker.forward_parts(published)
    assert costs_smallthinker.forward_flops(published) == sum(parts.values())
    # the issue counts the eight layers; the patch projection in and the
    # head out (``stage``, 0.13 TF) are this system's own
    layers = sum(parts.values()) - parts["stage"]
    assert 23.25e12 < layers < 23.35e12
    share = lambda *names: round(100 * sum(parts[k] for k in names) / layers, 1)
    assert share("experts") == 39.2
    assert share("attn_window", "attn_full") == 37.3
    assert (share("attn_window"), share("attn_full")) == (21.3, 16.0)
    assert share("projections") == 23.2
    assert share("router") == 0.2
    assert 9.12e12 < parts["experts"] < 9.14e12
    assert round(n * 6 / 64) == 1512
    assert 930e12 < 40 * layers < 935e12  # a call of 40 forwards
    # a launch: pairs inside the mask at the true token count, q and the
    # context once, k and v once a K/V head
    for windowed, pairs in ((True, window), (False, full)):
        launch = costs_smallthinker.flash_masked_fwd_cost(published, 2, windowed)
        assert launch["flops"] == 2 * 4 * 28 * 128 * pairs
        assert launch["bytes"] == 2 * 2 * n * 128 * 2 * (28 + 4)
        assert costs.roofline_seconds(launch, PEAKS)[1] == "compute"
    # the experts' two launches: the first credited both its products
    rows = n * 6
    gate_up = costs_smallthinker.moe_gmm_cost(published, rows, 2560, 768, 2)
    down = costs_smallthinker.moe_gmm_cost(published, rows, 768, 2560)
    assert gate_up["flops"] == 2 * down["flops"] == 4 * rows * 2560 * 768
    assert 8 * (gate_up["flops"] + down["flops"]) == parts["experts"]
    assert down["bytes"] == (rows * (768 + 2560) + 64 * 768 * 2560) * 2
    assert gate_up["bytes"] == (rows * (768 + 2560) + 2 * 64 * 768 * 2560) * 2
    assert costs.roofline_seconds(gate_up, PEAKS)[1] == "compute"


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


TARGET = ('custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{bf16[1,16130,3584]{2,1,0}}, frontend_attributes={kernel_metadata={}}')
#: the launches' texts as the chip's trace writes them (my chip run, PR 48):
#: every operand with its type and tiled layout. A window layer's launch,
#: which turns q where it holds it: q, k, v and the cos and sin tables; a full
#: layer's: q, k, v
_QKV = ("bf16[1,16130,3584]{2,1,0:T(8,128)(2,1)} %convolution_bitcast_fusion.6, "
        "bf16[1,16130,512]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.11, "
        "bf16[1,16130,512]{2,1,0:T(8,128)(2,1)} %fusion.93")
WINDOW_LAUNCH = ("%fwd_masked.3 = bf16[1,16130,3584]{2,1,0:T(8,128)(2,1)} "
                 "custom-call(" + _QKV + ", f32[16384,128]{1,0:T(8,128)S(1)} "
                 "%pad_maximum_fusion.1, f32[16384,128]{1,0:T(8,128)S(1)} "
                 "%pad_maximum_fusion), " + TARGET)
FULL_LAUNCH = ("%fwd_masked.1 = bf16[1,16130,3584]{2,1,0:T(8,128)(2,1)} "
               "custom-call(" + _QKV + "), " + TARGET)
_WORK = ("s32[821]{0:T(1024)S(1)} %while.4, s32[821]{0:T(1024)S(1)} "
         "%get-tuple-element.7, s32[821]{0:T(1024)S(1)} %get-tuple-element.8, "
         "s32[66]{0:T(128)S(1)} %pad_add_fusion.2, s32[1]{0:T(128)} "
         "%dynamic_slice.3, ")
GATE_UP_LAUNCH = ("%moe_gmm.2 = bf16[96896,768]{1,0:T(8,128)(2,1)} "
                  "custom-call(" + _WORK + "bf16[96896,2560]{1,0:T(8,128)(2,1)} "
                  "%fusion.71, bf16[64,2560,768]{2,1,0:T(8,128)(2,1)} "
                  "%get-tuple-element.21, bf16[64,2560,768]{2,1,0:T(8,128)(2,1)}"
                  " %get-tuple-element.22), " + TARGET)
DOWN_LAUNCH = ("%moe_gmm.3 = bf16[96896,2560]{1,0:T(8,128)(2,1)} "
               "custom-call(" + _WORK + "bf16[96896,768]{1,0:T(8,128)(2,1)} "
               "%moe_gmm.2, bf16[64,768,2560]{2,1,0:T(8,128)(2,1)} "
               "%get-tuple-element.23), " + TARGET)
MS = 1_000_000


def test_the_masked_reader_tells_a_window_launch_from_a_full_one(published):
    """Both results are ``[1, 16130, 28 x 128]``; five operands are a window
    layer's launch, credited the pairs inside the window, three a full
    layer's, credited every causal pair."""
    read = lambda ops, busy: mf.load_reader(
        "flash_masked_mixed_roofline").read(_view(published, ops, busy))
    least = {w: costs.roofline_seconds(
        costs_smallthinker.flash_masked_fwd_cost(published, 1, w), PEAKS)[0]
        for w in (True, False)}
    assert least[False] / least[True] == pytest.approx(130.10 / 57.68, rel=1e-3)
    assert read([(0, 10 * MS, WINDOW_LAUNCH)], 10e-3) == pytest.approx(
        100 * least[True] / 10e-3, rel=1e-6)
    assert read([(0, 10 * MS, FULL_LAUNCH)], 10e-3) == pytest.approx(
        100 * least[False] / 10e-3, rel=1e-6)
    # a forward's eight launches, other ops between them
    other = "%fusion.3 = bf16[1,16130,2560]{2,1,0} fusion(%x), kind=kOutput"
    ops, at = [], 0
    for text, ms in [(FULL_LAUNCH, 20), (other, 5)] + [(WINDOW_LAUNCH, 10)] * 3:
        ops.append((at * MS, (at + ms) * MS, text))
        at += ms
    assert read(ops, at * 1e-3) == pytest.approx(
        100 * (least[False] + 3 * least[True]) / 50e-3, rel=1e-6)
    # at the MXU's peak on the pairs a launch needs: 100 %
    assert read([(0, int(least[True] * 1e9), WINDOW_LAUNCH)], least[True]) == (
        pytest.approx(100.0, abs=0.01))
    # the compiler's own text names its operands bare: told apart the same
    bare = lambda n: ("%fwd_masked = bf16[1,16130,3584]{2,1,0} custom-call("
                      + ", ".join(f"%p.{i}" for i in range(n))
                      + '), custom_call_target="tpu_custom_call"')
    assert read([(0, 10 * MS, bare(5))], 10e-3) == pytest.approx(
        100 * least[True] / 10e-3, rel=1e-6)
    assert read([(0, 10 * MS, bare(3))], 10e-3) == pytest.approx(
        100 * least[False] / 10e-3, rel=1e-6)
    # nothing to read: no launch, no trace, another configuration
    assert read([(0, MS, other)], 1.0) is None
    reader = mf.load_reader("flash_masked_mixed_roofline")
    assert reader.read(types.SimpleNamespace(
        trace=None, config=published, peaks=PEAKS)) is None
    assert reader.read(_view({"head_dim": 128}, [(0, MS, FULL_LAUNCH)],
                             1.0)) is None
    apart = dict(published, rope_layout=[1] * 52)
    assert reader.read(_view(apart, [(0, MS, FULL_LAUNCH)], 1.0)) is None


def test_the_reglu_reader_credits_the_gate_up_launch_both_products(published):
    """An equal shape (buffer rows, K and N swapped): the gate-up launch is
    credited twice the down launch's products; the buffer's tile padding
    (96,896 rows for 96,780 assignments) is not credited."""
    read = lambda ops, busy: mf.load_reader("moe_gmm_reglu_roofline").read(
        _view(published, ops, busy))
    rows = 16130 * 6
    cost = lambda *a: costs.roofline_seconds(
        costs_smallthinker.moe_gmm_cost(published, rows, *a), PEAKS)[0]
    gate_up, down = cost(2560, 768, 2), cost(768, 2560, 1)
    assert gate_up == pytest.approx(2 * down, rel=1e-9)  # compute-bound both
    assert read([(0, 10 * MS, GATE_UP_LAUNCH)], 10e-3) == pytest.approx(
        100 * gate_up / 10e-3, rel=1e-6)
    assert read([(0, 10 * MS, DOWN_LAUNCH)], 10e-3) == pytest.approx(
        100 * down / 10e-3, rel=1e-6)
    assert read([(0, 10 * MS, GATE_UP_LAUNCH), (10 * MS, 16 * MS, DOWN_LAUNCH)],
                16e-3) == pytest.approx(100 * 3 * down / 16e-3, rel=1e-6)
    # the accepted reader beside it credits the first launch one product
    # (PERF.md section 7) and stays as it is: it reads another configuration
    assert read([(0, int(gate_up * 1e9), GATE_UP_LAUNCH)], gate_up) == (
        pytest.approx(100.0, abs=0.01))
    # the accepted time shares read this cell's launches unedited
    ops = [(0, 10 * MS, GATE_UP_LAUNCH), (10 * MS, 20 * MS, WINDOW_LAUNCH),
           (20 * MS, 40 * MS, FULL_LAUNCH)]
    view = _view(published, ops, 40e-3)
    assert mf.load_reader("moe_gmm_time_share").read(view) == (
        pytest.approx(25.0))
    assert mf.load_reader("flash_masked_fwd_time_share").read(view) == (
        pytest.approx(75.0))
    # nothing to read: no launch, no trace, another configuration
    assert read([(0, MS, FULL_LAUNCH)], 1.0) is None
    reader = mf.load_reader("moe_gmm_reglu_roofline")
    assert reader.read(types.SimpleNamespace(
        trace=None, config=published, peaks=PEAKS)) is None
    assert reader.read(_view({"hidden_size": 64}, [(0, MS, DOWN_LAUNCH)],
                             1.0)) is None


def test_route_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("moe_route_layer_input_share")
    assert reader.read(None) is None  # no expert layer traced in the process
    scope = metrics.scope("kernels")
    for key in ("layer_input", "layer_input", "layer_input", "expert_input"):
        scope.inc("kernels.moe_route_source", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, REAL)
    assert (cell.chips, cell.driver, cell.config_name) == (
        1, "sample_closed_smallthinker", CONFIG)
    assert cell.traffic == {"driver": "sample_closed_smallthinker", "n": 1,
                            "k": 50, "check_rows": 1, "trace_window_s": 1}
    assert set(result_line.expected_metrics(manifest, cell.name, False)) == {
        "sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, cell.name, True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_masked_fwd_time_share", *NEW_METRICS}
    for name in NEW_METRICS:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert (metric["workloads"], metric["moves"], metric["layer"]) == (
            [REAL], "sample_img_per_s", "kernels")
        mf.load_reader(name)  # the reader is found by the metric's name
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == REDUCED
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    # (no count of the manifest's entries: the next configuration's PR appends
    # after these)
    limits = json.load(open(os.path.join(mf.HERE, "workloads", REAL + ".json")))
    sound = limits["limits_from"]["sample_rms_vs_reference"]["program"]
    control = limits["limits_from"]["sample_rms_vs_reference"][
        "control_float8_e4m3"]
    assert len(sound) >= 10 and len(control) >= 3
    assert max(sound) < cell.limits["sample_rms_vs_reference"] < min(control)
    assert cell.limits["sample_rms_vs_reference"] == pytest.approx(
        math.sqrt(max(sound) * min(control)), rel=0.02)


def test_the_cells_before_this_one_keep_their_lines():
    """What ``test_kimi.py``'s case of the manifest says of the accepted
    cells, without its count of the manifest's entries at PR 45 (8
    configurations, 9 cells), which tier-1 leaves out of that file's run
    (``tests/test_benchmark_selftests.LEFT_OUT``): this cell's entries change
    no accepted cell's line."""
    manifest = mf.load_manifest()
    kimi = mf.Cell(manifest, "kimi_linear_sample2048_k50")
    assert (kimi.chips, kimi.driver, kimi.config_name) == (
        1, "sample_closed_kimi", "kimi_linear_ep2_px2048")
    assert kimi.traffic == {"driver": "sample_closed_kimi", "n": 1,
                            "k": 50, "check_rows": 1, "trace_window_s": 1}
    assert set(result_line.expected_metrics(
        manifest, kimi.name, False)) == {"sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, kimi.name, True)) == {
        "sampler_step_ms", "moe_gmm_time_share", "moe_gmm_kernel_share",
        "flash_latent_fwd_roofline", "flash_latent_fwd_time_share",
        "flash_latent_fwd_kernel_share", "kda_chunk_roofline",
        "kda_chunk_time_share", "kda_chunk_kernel_share"}
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
    limits = json.load(open(os.path.join(
        mf.HERE, "workloads", kimi.name + ".json")))
    of = limits["limits_from"]["sample_rms_vs_reference"]
    assert len(of["program"]) >= 8 and len(of["control_float8_e4m3"]) >= 3
    assert max(of["program"]) < kimi.limits["sample_rms_vs_reference"] < min(
        of["control_float8_e4m3"])
