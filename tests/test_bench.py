"""bench.py smoke: the driver-facing record must always parse and carry the
headline keys (a bench regression silently loses the round's BENCH record)."""

import json

import numpy as np
import pytest


def test_bench_smoke_record(capsys):
    import bench

    bench.main(["--smoke", "--cpu", "--steps", "3", "--batch", "4",
                "--skip-sampler"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "train_throughput_vit_tiny64_b32"
    assert np.isfinite(rec["value"]) and rec["value"] > 0
    assert rec["unit"] == "img/s"
    assert np.isfinite(rec["vs_baseline"])
    assert rec["chip"] == "cpu"
    assert "submetrics" in rec and isinstance(rec["submetrics"], dict)
    assert np.isfinite(rec["ms_per_step"]) and rec["ms_per_step"] > 0


def test_bench_serving_smoke_record(capsys):
    """The --serving leg must record the serving submetrics the driver
    compares round over round — sustained img/s, one-shot baseline, latency
    percentiles, and a zero compiles-after-warmup count (the engine's whole
    point). Same --batch/--steps as the plain smoke test so the in-process
    jit caches keep the train half nearly free."""
    import bench

    bench.main(["--smoke", "--cpu", "--steps", "3", "--batch", "4",
                "--skip-sampler", "--no-ksweep", "--serving"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    srv = rec["submetrics"]["serving"]
    assert srv["compiles_after_warmup"] == 0
    assert srv["warmup"]["new_compiles"] >= 1
    assert np.isfinite(srv["img_per_sec"]) and srv["img_per_sec"] > 0
    assert np.isfinite(srv["oneshot_img_per_sec"]) and srv["oneshot_img_per_sec"] > 0
    # vs_oneshot is recorded for the driver's >= 0.9 acceptance gate; CPU CI
    # timing is too noisy to assert the ratio itself here
    assert np.isfinite(srv["vs_oneshot"]) and srv["vs_oneshot"] > 0
    assert srv["p95_latency_s"] >= srv["p50_latency_s"] > 0
    assert srv["rows"] > 0 and srv["batches"] > 0
    assert srv["padded_rows"] == 0  # smoke sizes are built to tile exactly
    assert srv["max_queue_depth"] >= 1


def test_bench_faults_smoke_record(capsys):
    """The --faults robustness leg: a disarmed drain (zero
    compiles-after-warmup, the zero-overhead guarantee) then the fixed
    seeded chaos schedule — the record must carry degraded-mode throughput
    and the recovery counters the driver compares round over round."""
    import bench

    bench.main(["--smoke", "--cpu", "--steps", "3", "--batch", "4",
                "--skip-sampler", "--no-ksweep", "--faults"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    fl = rec["submetrics"]["faults"]
    assert fl["compiles_after_warmup"] == 0  # clean AND chaos drains
    assert fl["warmup_new_compiles"] >= 1
    assert np.isfinite(fl["clean_img_per_sec"]) and fl["clean_img_per_sec"] > 0
    assert np.isfinite(fl["chaos_img_per_sec"]) and fl["chaos_img_per_sec"] > 0
    assert fl["degraded_ratio"] > 0
    # the fixed schedule always quarantines its one poisoned request, and
    # the permanent fault fired at least once to cause it
    assert fl["quarantined"] == 1 and fl["failed_tickets"] == 1
    assert fl["injected"] >= 1 and fl["by_site"]
    assert fl["rows"] > 0


def test_bench_quant_smoke_record(capsys):
    """The --quant 64px leg must record both dequant-matmul modes with
    paired drift + the param-byte saving, and stamp quant_rev next to
    kernel_rev (stale-record protection keys off both)."""
    import bench
    from ddim_cold_tpu.ops.quant import QUANT_REV

    bench.main(["--smoke", "--cpu", "--steps", "3", "--batch", "4",
                "--skip-sampler", "--no-ksweep", "--quant"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    sub = rec["submetrics"]
    assert sub["quant_rev"] == QUANT_REV and "kernel_rev" in sub
    q = sub["sampler_64px_w8a16"]
    assert q["param_bytes_quant"] < q["param_bytes"]
    assert q["float_img_per_sec"] > 0
    for mode in ("xla", "pallas"):
        leg = q["modes"][mode]
        assert np.isfinite(leg["img_per_sec"]) and leg["img_per_sec"] > 0
        assert np.isfinite(leg["speedup_vs_float"])
        # bf16 model: quant noise rides under the bf16 activation noise
        assert leg["max_abs_pixel_delta"] < 0.1


def test_bench_stall_watchdog_emits_partial_record():
    """A device call that never returns mid-run (it blocks forever, no
    exception) must still produce a parseable record: the watchdog emits the
    partial JSON and exits (nonzero, so callers never log the partial run
    as success) instead of hanging until an outer kill."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.update(DDIM_COLD_BENCH_STALL_S="2", DDIM_COLD_BENCH_TEST_HANG_S="3600",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--cpu", "--steps", "2",
         "--batch", "2", "--skip-sampler"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-2000:])
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "aborted" in rec["submetrics"], rec
    # the stall hit before the headline ran; the record says so honestly
    assert rec["value"] is None
    assert rec["metric"] == "train_throughput_vit_tiny64_b32"


def test_bench_raising_phase_exits_nonzero(capsys, monkeypatch):
    """A phase that raises ends the run: the exception leaves main() (a
    non-zero exit for the script), after the partial record — headline
    included — went out with the failing phase named in it."""
    import bench
    from ddim_cold_tpu.analysis import memory_checks

    def boom():
        raise RuntimeError("refused by the compiler")

    monkeypatch.setattr(memory_checks, "budget_report", boom)
    with pytest.raises(RuntimeError, match="refused by the compiler"):
        bench.main(["--smoke", "--cpu", "--steps", "2", "--batch", "2",
                    "--skip-sampler"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "refused by the compiler" in rec["submetrics"]["memory_budget_error"]
    assert "fatal_error" in rec["submetrics"]
    assert rec["value"] is not None  # the headline finished before the phase


def test_bench_e2e_section_runs_on_cpu():
    """The e2e section (H2D probe + grouped dispatch loop) must run end to
    end — it is only exercised on hardware otherwise, and a shape bug there
    would burn the round's chip window."""
    import argparse

    import jax
    import jax.numpy as jnp

    import bench
    from ddim_cold_tpu.models import MODEL_CONFIGS, DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state

    model = DiffusionViT(dtype=jnp.bfloat16, **MODEL_CONFIGS["vit_tiny"])
    r = np.random.RandomState(0)
    batch = (jnp.asarray(r.randn(4, 64, 64, 3), jnp.float32),
             jnp.asarray(r.randn(4, 64, 64, 3), jnp.float32),
             jnp.asarray(r.randint(1, 7, size=(4,)), jnp.int32))
    state = create_train_state(model, jax.random.PRNGKey(0), lr=2e-4,
                               total_steps=100, sample_batch=batch)
    args = argparse.Namespace(smoke=True, batch=4)
    out = bench._bench_e2e(args, model, state, lambda m: None)
    assert out["h2d_bandwidth_mib_s"] > 0
    for label in ("cold", "warm"):
        row = out[f"e2e_train_throughput_{label}"]
        assert np.isfinite(row["value"]) and row["value"] > 0
        assert row["steps_per_dispatch"] == 1  # cpu backend: nothing to amortize

    # the grouped loop (the accelerator default, spd=8 on chip) must also
    # run before its first hardware execution — forced via the env override.
    # Fresh state: the first call's train steps DONATED the old one's buffers.
    import os

    state2 = create_train_state(model, jax.random.PRNGKey(0), lr=2e-4,
                                total_steps=100, sample_batch=batch)
    os.environ["DDIM_COLD_E2E_SPD"] = "2"
    try:
        out2 = bench._bench_e2e(args, model, state2, lambda m: None)
    finally:
        del os.environ["DDIM_COLD_E2E_SPD"]
    for label in ("cold", "warm"):
        row = out2[f"e2e_train_throughput_{label}"]
        assert np.isfinite(row["value"]) and row["value"] > 0
        assert row["steps_per_dispatch"] == 2


def test_bench_fatal_error_still_emits_partial_record():
    """An exception escaping the try body (here: a headline failure forced by
    an invalid batch) must emit the partial record with a fatal_error note
    and exit nonzero — never crash recordless."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--cpu", "--steps", "2",
         "--batch", "-1", "--skip-sampler"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode != 0
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "fatal_error" in rec["submetrics"], rec
    assert rec["metric"] == "train_throughput_vit_tiny64_b32"


def test_bench_fleet_smoke_record(capsys):
    """The --fleet leg: a 2-replica router serves the stream clean, then
    under the seeded chaos schedule that kills r0 and sprays transients —
    the record must show the fleet surviving (throughput, not outage), the
    replica replacement, and ZERO compiles after warmup including the
    replacement's service life."""
    import bench

    bench.main(["--smoke", "--cpu", "--steps", "3", "--batch", "4",
                "--skip-sampler", "--no-ksweep", "--fleet"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    fl = rec["submetrics"]["fleet"]
    assert fl["compiles_after_warmup"] == 0  # replacement included
    assert np.isfinite(fl["clean_img_per_sec"]) and fl["clean_img_per_sec"] > 0
    assert np.isfinite(fl["chaos_img_per_sec"]) and fl["chaos_img_per_sec"] > 0
    assert fl["survivors"] >= 1  # the kill degraded, never silenced, serving
    assert fl["survivors"] + fl["failed_tickets"] == len(fl["stream_sizes"])
    # r0's permanent kill fired, and the lifecycle ran: retire + respawn
    assert fl["injected"] >= 1 and "serve.dispatch" in fl["by_site"]
    assert fl["replicas_retired"] >= 1
    assert fl["replicas_spawned"] >= 3  # 2 initial + the replacement
