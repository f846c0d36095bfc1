"""The hybrid state-space configuration's benchmark files at a toy size
(``fixtures_hybrid/``: width 64, 4 layers, 16 x 16 px): the driver end to end
through the same ``execute`` a real run uses, the control, the cost
functions at the published sizes, and the three ``ssm_scan_*`` readers on a
hand-made trace."""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import costs, costs_hybrid, manifest as mf, result_line
from benchmark.harness import Run
from benchmark.run import execute

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures_hybrid")
CELL = "toy_sample_hybrid"
PEAKS = mf.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(mf.HERE, "configs", "jamba2_3b_px512.json")) as f:
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


def test_the_configuration_file_carries_the_catalog_rows_keys(published):
    """Every key the source's config.json has, under the same name and with
    the same value, but for what ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    assert published["source"] == row["source_url"]
    assert sorted(published["published_keys"]) == sorted(row["config"])
    differs = [k for k, v in row["config"].items() if published[k] != v]
    assert differs == published["reduced"] == ["vocab_size"]


def test_costs_at_the_published_sizes(published):
    """ISSUE 26's arithmetic: 2.862 B trunk parameters x 2 x 1,025 tokens =
    5.87 TF an image plus 0.03 TF of attention and embeds; one scan launch
    at n = 4 moves 168 MB, 0.205 ms at 819 GB/s, and is memory-bound."""
    assert costs.tokens(published) == 1025
    assert costs_hybrid.mamba_layers(published) == 26
    flops = costs_hybrid.hybrid_forward_flops(published)
    assert 5.87e12 < flops < 5.92e12
    cost = costs_hybrid.ssm_scan_cost(published, 4)
    assert cost["bytes"] == 4 * 1025 * (4 * 5120 + 32) * 2
    assert cost["flops"] == 9 * 4 * 1025 * 5120 * 16
    seconds, bound = costs.roofline_seconds(cost, PEAKS)
    assert bound == "memory" and seconds == pytest.approx(0.2054e-3, rel=1e-3)


def _view(config, ops, busy_s):
    trace = types.SimpleNamespace(
        devices={0: {"ops": ops, "async": []}}, busy_s=busy_s, n_devices=1)
    return types.SimpleNamespace(trace=trace, config=config, peaks=PEAKS)


def test_roofline_and_time_share_readers_on_a_hand_made_trace(published):
    launch = ("%ssm_scan.7 = bf16[4,1025,5120]{2,1,0:T(8,128)(2,1)} "
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    other = ("%fusion.3 = bf16[4,1025,2560]{2,1,0} fusion(%x), kind=kOutput")
    ms = 1_000_000
    ops = [(0, 1 * ms, launch), (2 * ms, 6 * ms, other),
           (6 * ms, 7 * ms, launch)]
    view = _view(published, ops, busy_s=6e-3)
    roofline = mf.load_reader("ssm_scan_roofline").read(view)
    assert roofline == pytest.approx(100 * 2 * 0.2054e-3 / 2e-3, rel=1e-3)
    assert mf.load_reader("ssm_scan_time_share").read(view) == pytest.approx(
        100 * 2 / 6)
    # nothing to read: no trace, or a trace without the kernel
    for reader in ("ssm_scan_roofline", "ssm_scan_time_share"):
        assert mf.load_reader(reader).read(_view(published, [], 1.0)) is None
        assert mf.load_reader(reader).read(types.SimpleNamespace(
            trace=None, config=published, peaks=PEAKS)) is None


def test_kernel_share_reads_the_programs_counter():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    reader = mf.load_reader("ssm_scan_kernel_share")
    assert reader.read(None) is None  # no trace of the scan in the process
    scope = metrics.scope("kernels")
    for key in ("kernel", "kernel", "kernel", "xla"):
        scope.inc("kernels.ssm_scan_schedule", key=key)
    assert reader.read(None) == pytest.approx(75.0)
    metrics.reset()


def test_the_new_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.Cell(manifest, "jamba2_3b_sample512_k20")
    assert (cell.chips, cell.driver) == (1, "sample_closed_hybrid")
    assert set(result_line.expected_metrics(manifest, cell.name, False)) == {
        "sample_img_per_s", "setup_s"}
    assert set(result_line.expected_metrics(manifest, cell.name, True)) == {
        "sampler_step_ms", "ssm_scan_roofline", "ssm_scan_time_share",
        "ssm_scan_kernel_share"}
    # the old cells' lines do not change
    assert set(result_line.expected_metrics(
        manifest, "flower200_sample_k20", True)) == {
        "sampler_step_ms", "flash_fwd_roofline"}
