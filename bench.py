#!/usr/bin/env python
"""Benchmark: training + sampling throughput vs the reference's record.

Headline: images/sec for the vit_tiny 64px cold-diffusion training step at the
reference's effective batch 32 (train.log steady state: 4.56 s / 100 steps ≈
702 img/s on one RTX 3090 — BASELINE.md). Alongside it, machine-readable
sub-metrics the acceptance criteria name (VERDICT round 1 items 2/4/5):

* ``sampler_throughput_200px_k20`` — the north-star path (200px DDIM k=20
  img/s/chip, BASELINE.json), flash kernel on and off;
* DDIM k-sweep on vit_tiny (the `ViT.py:226` ⌈1999/k⌉ cost model);
* MFU + chip name + peak bf16 TFLOP/s (utils/flops.py) so ``vs_baseline``
  can be normalized across hardware, plus a batch-scaling table;
* end-to-end steps/s with the real data path (ShardedLoader + the C++
  decode pipeline feeding from a disk folder), cold and warm epoch.

Prints ONE JSON line on stdout:
    {"metric": ..., "value": ..., "unit": "img/s", "vs_baseline": ...,
     "chip": ..., "mfu": ..., "submetrics": {...}}

``--smoke`` shrinks every measurement for CPU sanity runs. ``--skip-northstar``
/ ``--skip-e2e`` / ``--skip-scaling`` drop the slower sections.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

BASELINE_IMG_PER_SEC = 702.0  # train.log steady state, 1×3090 (BASELINE.md)

# the north-star kernel block configs live next to the kernel they tune
# (ops/flash_attention.py) so the graftcheck kernels layer proves the exact
# geometry this bench dispatches; re-exported here because the CPU tile-rule
# guard (tests/test_flash_attention.py) imports them from bench
from ddim_cold_tpu.ops.flash_attention import (  # noqa: E402
    FLASH_BLOCK_SWEEP, NS_FLASH_BLOCKS,
)

#: e2e's generated temp dataset, registered so a watchdog abort (os._exit
#: skips every finally) can still remove it instead of leaking 4096 images
#: into /tmp
_E2E_TMP = {"path": None}


def main(argv=None):
    """``argv=None`` → sys.argv; tests pass a list."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny quick run (CI/CPU)")
    ap.add_argument("--steps", type=int, default=None,
                    help="timed steps (default 100, or 10 under --smoke; an "
                         "explicit value always wins)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--skip-northstar", action="store_true")
    ap.add_argument("--skip-e2e", action="store_true")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the batch-scaling sweep AND the scan_blocks "
                         "depth-layout comparison")
    ap.add_argument("--skip-sampler", action="store_true",
                    help="skip the 64px sampler section (CI smoke)")
    ap.add_argument("--ksweep", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="sweep sampler stride k (BASELINE.json's k-sweep "
                         "config). Default: on, except under --smoke; pass "
                         "--ksweep/--no-ksweep to force either way")
    ap.add_argument("--profile-northstar", action="store_true",
                    help="capture a jax.profiler trace of ONE tuned-blocks "
                         "flash sampling run into results/profile_northstar/ "
                         "(best-effort; the evidence for the NEXT kernel "
                         "optimization round — says where the remaining "
                         "sampler time goes once the GEMMs are bf16)")
    ap.add_argument("--flash-block-sweep", action="store_true",
                    help="in the north-star section, additionally time the "
                         "flash kernel under alternative (block_q, block_kv) "
                         "choices — kernel tuning for the 200px config; a "
                         "few extra compiles of chip time")
    ap.add_argument("--serving", action="store_true",
                    help="run the serving-engine leg (ddim_cold_tpu/serve): "
                         "bucketed continuous batching over a mixed request "
                         "stream after AOT warmup — records sustained img/s, "
                         "p50/p95 request latency, queue depth and "
                         "compiles-after-warmup; composes with --smoke for "
                         "a CPU-budget run")
    ap.add_argument("--fewstep", action="store_true",
                    help="run the few-step distilled-sampling leg "
                         "(SamplerConfig(steps=k), ops/sampling."
                         "ddim_sample_fewstep): k ∈ {1, 2, 4} served "
                         "through ONE warmed engine — per-k sustained "
                         "img/s and single-request latency against the "
                         "stride-k baseline on the same host, plus the "
                         "warmup-dedup record (a student config aliases "
                         "the teacher's executable instead of compiling). "
                         "RAISES if anything compiles after warmup or if "
                         "the k=1 single-request latency is not strictly "
                         "below the baseline's; composes with --smoke for "
                         "the CPU CI gate")
    ap.add_argument("--faults", action="store_true",
                    help="run the robustness leg (utils/faults.py + the "
                         "fault-tolerant engine): a disarmed drain (must "
                         "match the plain serving numbers — the "
                         "zero-overhead-disarmed guarantee) then the same "
                         "stream under a FIXED seeded fault schedule, "
                         "recording degraded-mode throughput, recovery "
                         "counters (retries/quarantined/failed) and "
                         "compiles-after-warmup (recovery never compiles); "
                         "composes with --smoke for a CPU-budget run")
    ap.add_argument("--fleet", action="store_true",
                    help="run the fleet-router leg (serve/router.py): the "
                         "same request stream twice through a 2-replica "
                         "Router — once clean, once under a seeded chaos "
                         "schedule that permanently kills one replica's "
                         "dispatch and injects transients elsewhere — "
                         "recording clean vs chaos img/s, hedge/failover "
                         "counts, the replica replacement, and "
                         "compiles-after-warmup (MUST be 0, replacement "
                         "included); composes with --smoke for a CPU-budget "
                         "run")
    ap.add_argument("--fleet-proc", action="store_true",
                    help="run the out-of-process fleet leg (serve/remote.py): "
                         "a Router over TWO subprocess replicas (each its own "
                         "OS process speaking the socket RPC) serving a mixed "
                         "stream while a seeded chaos schedule SIGKILLs r0 "
                         "mid-drain and sprays rpc latency; records "
                         "spawn-warmup wall warm vs cold (the persistent "
                         "compile cache), kill-to-recovered latency, and the "
                         "autoscaler converging back to target; raises on any "
                         "compile after warmup or a non-bitwise survivor; "
                         "composes with --smoke for a CPU-budget run")
    ap.add_argument("--edit", action="store_true",
                    help="run the guided-editing workloads leg "
                         "(ddim_cold_tpu/workloads): all four tasks "
                         "(inpaint, superres, draft, interp) served through "
                         "one engine after a single warmup — per-task "
                         "sustained img/s, then a preview-enabled drain "
                         "recording latency-to-first-frame for the streamed "
                         "x̂0 previews; raises if any task or the preview "
                         "variant compiles after warmup; composes with "
                         "--smoke for a CPU-budget run")
    ap.add_argument("--quant", action="store_true",
                    help="run the w8a16 quantized-inference legs "
                         "(ops/quant.py): 64px sampler in both dequant-matmul "
                         "modes with paired pixel drift + param-byte savings, "
                         "a quantized serving drain when --serving is also "
                         "set, and the 200px "
                         "sampler_throughput_200px_k20_flash_w8a16 leg when "
                         "the north-star section runs; composes with --smoke "
                         "for a CPU-budget run")
    ap.add_argument("--cache-adaptive", action="store_true",
                    help="run the adaptive step-cache leg (ops/step_cache.py "
                         "error-gated 'adaptive' + top-k 'token' modes): "
                         "one-shot sampler ratios vs fixed interval=2 and "
                         "uncached, a threshold→0 bitwise-vs-exact guard, "
                         "then an engine drain over all three cache configs "
                         "after one warmup — RAISES if anything compiles "
                         "after warmup; composes with --smoke for the "
                         "CPU CI gate")
    ap.add_argument("--parallel", action="store_true",
                    help="run the sequence-parallel serving leg (parallel/ "
                         "ulysses + the (data, seq) mesh programs): warms "
                         "one engine at sp_degree ∈ {1, 2, all-local} and "
                         "records single-request latency and img/s per "
                         "degree — the batch-vs-sequence crossover evidence "
                         "for PERF.md. RAISES if anything compiles after "
                         "warmup or if the degenerate sp_degree=1 program "
                         "is not bitwise the direct sampler (on CPU those "
                         "structural contracts ARE the leg; the >1.3× "
                         "latency gate only arms on real chips); composes "
                         "with --smoke for the CPU CI gate")
    ap.add_argument("--obs", action="store_true",
                    help="run the observability leg (ddim_cold_tpu/obs): the "
                         "same mixed serving stream with tracing OFF then ON "
                         "— records the measured tracing overhead (PERF.md "
                         "target < 2%%), verifies the traced drain produces "
                         "complete span trees and bitwise-identical images, "
                         "round-trips the Chrome/JSONL exports, drains one "
                         "step-telemetry request, and captures a span-keyed "
                         "profiler trace. RAISES if tracing records nothing, "
                         "a span tree is incomplete, or anything compiles "
                         "after warmup; composes with --smoke for CI")
    ap.add_argument("--attrib", action="store_true",
                    help="run the attribution leg (ddim_cold_tpu/obs/"
                         "attrib.py): capture a profiler trace of a warmed "
                         "serving drain, attribute ≥90%% of device-busy "
                         "time to the planted named scopes, join with "
                         "utils/flops.py flop/byte estimates → per-scope "
                         "MFU + roofline class + ranked fusion candidates, "
                         "then run the obs/trend.py gate over the committed "
                         "BENCH_r* series. RAISES if coverage misses the "
                         "floor, anything compiles after warmup, or the "
                         "captured drain is not bitwise the uncaptured one "
                         "(attribution must be off-switchable); on CPU the "
                         "capture has no device lanes, so coverage is "
                         "asserted over the checked-in synthetic fixture — "
                         "loudly labeled; composes with --smoke for CI")
    ap.add_argument("--fusion", action="store_true",
                    help="run the fused-trunk leg: ONE engine drains the "
                         "same seeds through the unfused w8a16 sampler "
                         "(quant='pallas') and the fused megakernel one "
                         "(fused=True — dequant-qkv + flash + proj in one "
                         "Pallas program, fused bias/GELU Mlp), then "
                         "compares per-step latency and MFU. RAISES if "
                         "either drain compiles after warmup or if the "
                         "fused images diverge (bitwise at f32, allclose "
                         "at bf16); on CPU the kernels run in interpret "
                         "mode so timing is structural and MFU is None — "
                         "the parity/compile contracts ARE the leg; "
                         "composes with --smoke for CI")
    ap.add_argument("--xla-blockwise", action="store_true",
                    help="also time the pure-XLA blockwise attention leg in "
                         "the north-star section (retired from the default "
                         "set — measured behind both dense and flash)")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend (the same request as "
                         "JAX_PLATFORMS=cpu). Without it the run uses the "
                         "backend JAX finds and fails if that one does not "
                         "come up — it never moves to the CPU by itself")
    args = ap.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from ddim_cold_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # repeat compiles become disk reads; first-ever
    # compiles are unaffected
    import jax.numpy as jnp
    import numpy as np

    from ddim_cold_tpu.models import MODEL_CONFIGS, DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step
    from ddim_cold_tpu.utils import flops as flops_util

    if args.smoke:
        # a smoke run is the train-step sanity check only — the north-star /
        # e2e / scaling sections are real-hardware measurements (the 200px
        # Pallas leg alone is minutes-to-hours under CPU interpret mode)
        args.skip_northstar = args.skip_e2e = args.skip_scaling = True
    if args.steps is None:
        args.steps = 10 if args.smoke else 100  # an explicit --steps wins
    if args.ksweep is None:  # default: full runs sweep, smoke doesn't —
        args.ksweep = not args.smoke  # an explicit flag wins either way

    from ddim_cold_tpu.ops.flash_attention import KERNEL_REV
    from ddim_cold_tpu.ops.quant import QUANT_REV
    from ddim_cold_tpu.utils.record import run_metadata
    from ddim_cold_tpu.utils.watchdog import StallWatchdog

    # both revision stamps ride every record
    sub = {"kernel_rev": KERNEL_REV, "quant_rev": QUANT_REV}
    # The record is assembled INCREMENTALLY and the watchdog below can emit it
    # mid-run: a device call that never returns raises nothing to catch, and
    # a bench that hangs until an outer kill records nothing. Emitting the
    # partial record and exiting non-zero is strictly better.
    record = {
        "metric": "train_throughput_vit_tiny64_b32",
        "value": None,
        "unit": "img/s",
        "vs_baseline": None,
        "baseline": {"value": BASELINE_IMG_PER_SEC, "unit": "img/s",
                     "hardware": "RTX 3090 (train.log, torch AMP)"},
        "chip": None,
        "n_devices": 1,
        "peak_bf16_tflops": None,
        "ms_per_step": None,
        "mfu": None,
        "submetrics": sub,
        # provenance stamp (git sha, device kind once known, jax versions,
        # externally-supplied timestamp) — obs/trend.py orders the committed
        # series off it instead of inferring from filenames
        "run_meta": run_metadata(),
    }
    # Armed unless the CONFIGURED platform is cpu (read from jax.config, not a
    # backend query, so the watchdog runs before this process's first
    # jax.devices()): healthy CPU runs of the heavy sections exceed any sane
    # deadline. An explicit env value always wins (tests arm it on cpu; 0
    # disables anywhere). 1800s default: generous against legitimately slow
    # markless windows (a big compile, one e2e epoch). env_stall is re-read
    # below: an EXPLICIT env value also suppresses the auto-detected-cpu
    # disarm after backend init.
    from ddim_cold_tpu.utils.platform import watchdog_stall_s

    env_stall = os.environ.get("DDIM_COLD_BENCH_STALL_S") or None
    stall_s = watchdog_stall_s("DDIM_COLD_BENCH_STALL_S", 1800.0)

    def _emit_partial(label, idle):
        """Watchdog abort hook: the record (metadata + whatever sections
        finished) goes out before the nonzero exit, then the e2e temp
        dataset is removed (pure fs work _exit would otherwise skip)."""
        for _ in range(3):  # retry a transient emit race, but NEVER loop
            # forever: a process that can't emit (harness closed stdout)
            # must still exit rather than sit holding the chip
            try:
                # snapshot: the main thread may mutate sub mid-serialization
                snap = dict(record, submetrics=dict(
                    sub,
                    aborted=f"no progress for {idle:.0f}s after "
                            f"{label!r} — stalled mid-run; "
                            "partial record emitted (raise "
                            "DDIM_COLD_BENCH_STALL_S to wait longer)"))
                print(json.dumps(snap))
                sys.stdout.flush()
                break
            except Exception:  # noqa: BLE001
                time.sleep(0.2)
        if _E2E_TMP["path"]:
            shutil.rmtree(_E2E_TMP["path"], ignore_errors=True)
        # StallWatchdog then os._exit(3)s: the record is out (or
        # unemittable), callers must not log the partial run as success

    wd = StallWatchdog(stall_s, on_abort=_emit_partial, name="bench").start()

    def mark(label, budget_s=None):
        """Liveness beacon. ``budget_s`` stretches the watchdog deadline for
        the window AFTER this mark — known-long silent operations (a first
        XLA/Mosaic compile of the 200px model can legitimately exceed the
        default stall budget) must not be killed as stalled."""
        wd.mark(label, budget_s)
    # everything below runs under the armed watchdog: the finally guarantees
    # it dies with main() even on an exception, so an in-process caller that
    # catches the exception is never os._exit'd by an orphaned watchdog
    # later (pytest)
    try:
        hang_s = float(os.environ.get("DDIM_COLD_BENCH_TEST_HANG_S", "0"))
        if hang_s:  # test hook: a stalled device call = blocked, no marks
            time.sleep(hang_s)
        # first in-process backend touch; the armed watchdog bounds it
        chip = jax.devices()[0].device_kind
        # a CPU run (asked for with --cpu / JAX_PLATFORMS=cpu) has no peak and
        # records no MFU; an accelerator this repo has no peaks for is an
        # error, not a record with mfu=None
        peak = (None if jax.default_backend() == "cpu"
                else flops_util.require_peak_tflops(chip))
        record.update(chip=chip, peak_bf16_tflops=peak)
        record["run_meta"]["device_kind"] = chip
        mark("backend up")
        if env_stall is None and jax.default_backend() == "cpu":
            # platform was auto-DETECTED as cpu (nothing configured, no env
            # override): same reasoning as the configured-cpu default above —
            # heavy sections legitimately run for hours on cpu. Disarm
            # before they start.
            wd.done()
        if jax.default_backend() == "cpu":
            try:  # CPU numbers are only honest on an uncontended box — record it
                load1 = os.getloadavg()[0]
                if load1 > 0.8 * (os.cpu_count() or 1):
                    sub["cpu_contention"] = (
                        f"1-min loadavg {load1:.2f} on {os.cpu_count()} core(s) — "
                        "another process shares the CPU; timings are pessimistic")
            except OSError:
                pass

        def log(msg):
            mark(str(msg)[:100])  # every log line is a liveness beacon
            print(f"[bench] {msg}", file=sys.stderr)

        # ------------------------------------------------------------------ train
        model = DiffusionViT(dtype=jnp.bfloat16, **MODEL_CONFIGS["vit_tiny"])
        rng = np.random.RandomState(0)
        B = args.batch
        def synth_batch(b):
            return (
                jnp.asarray(rng.randn(b, 64, 64, 3), jnp.float32),
                jnp.asarray(rng.randn(b, 64, 64, 3), jnp.float32),
                jnp.asarray(rng.randint(1, 7, size=(b,)), jnp.int32),
            )
        batch = synth_batch(B)
        state = create_train_state(model, jax.random.PRNGKey(0), lr=2e-4,
                                   total_steps=51200, sample_batch=batch)
        train_step = make_train_step(model)

        def time_train(st, bt, steps, step=None):
            """Compile, settle, then time `steps` steps as TWO windows and keep
            the faster — a transient host stall inside one window then costs
            half the steps, not the whole measurement. Every window ends in a
            float() of the device result, so it times the work, not the
            dispatch."""
            step = step or train_step
            mark(f"train-step compile b{bt[0].shape[0]}",  # pre-compile beacon:
                 budget_s=2 * stall_s)  # compiles are silent AND can be long
            ema = jnp.float32(5.0)  # the compile itself emits no progress
            t0 = time.time()
            st, _, ema = step(st, bt, jax.random.PRNGKey(1), ema)
            float(ema)
            compile_s = time.time() - t0
            for _ in range(3):
                st, _, ema = step(st, bt, jax.random.PRNGKey(1), ema)
            float(ema)
            per = max(1, steps // 2)
            best = float("inf")
            for _ in range(2):
                t0 = time.time()
                for _ in range(per):
                    st, _, ema = step(st, bt, jax.random.PRNGKey(1), ema)
                float(ema)
                best = min(best, (time.time() - t0) / per)
            return st, best, compile_s

        def emit_snapshot():
            """Print the record as it stands (consumers take the LAST
            parseable line, so intermediate snapshots are strictly additive).
            An externally-killed healthy run (a timeout shorter than the full
            bench) then still leaves everything measured so far on stdout;
            the stall watchdog only covers stalls, not kills."""
            print(json.dumps(record))
            sys.stdout.flush()

        state, spi, compile_s = time_train(state, batch, args.steps)
        img_per_sec = B / spi
        step_flops = flops_util.train_step_flops(
            B, mlp_ratio=1.0, **MODEL_CONFIGS["vit_tiny"])
        train_mfu = flops_util.mfu(step_flops, spi, chip)
        record.update(
            value=round(img_per_sec, 1),
            vs_baseline=round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
            ms_per_step=round(1000 * spi, 3),
            mfu=None if train_mfu is None else round(train_mfu, 4))
        emit_snapshot()  # the headline survives even an early external kill
        log(f"platform={jax.default_backend()} chip={chip!r} "
            f"peak_bf16={peak} TFLOP/s compile={compile_s:.1f}s "
            f"{args.steps} steps @ b{B}: {1000*spi:.2f} ms/step "
            f"({img_per_sec:.0f} img/s, mfu={train_mfu if train_mfu is None else round(train_mfu, 4)})")

        def section(name, fn):
            """Run one phase after the headline. A phase that raises ends the
            run: its error is noted in the record, the handler at the bottom
            of main() prints the partial record, and the exception goes on up
            — the exit code is non-zero. Nothing is retried and nothing is
            skipped over: on the chip a failing phase is the finding."""
            try:
                fn()
            except Exception as e:
                log(f"{name} section failed: {type(e).__name__}: {e}")
                sub[name + "_error"] = f"{type(e).__name__}: {e}"
                raise
            emit_snapshot()  # each finished section lands on stdout

        # ---------------------------------------------------- static memory budget
        def run_memory_budget():
            # abstract-trace-only (graftcheck's kernels+memory layers over the
            # 200px registry): peak live HBM per sampler program and per-kernel
            # VMEM land in the BENCH record so obs/trend.py bands residency
            # regressions without costing a hardware window
            from ddim_cold_tpu.analysis import memory_checks

            mark("memory budget")
            report = memory_checks.budget_report()
            sub["memory"] = report
            log(f"memory budget: peak {report['peak_hbm_gb']} GiB HBM, "
                f"max kernel VMEM {report['max_kernel_vmem_mb']} MiB "
                f"({report['device_kind']})")
            if report["findings"]:
                raise RuntimeError(
                    f"{len(report['findings'])} static budget finding(s): "
                    + "; ".join(report["findings"])[:500])

        section("memory_budget", run_memory_budget)

        # --------------------------------------------------------- batch scaling
        scaling_rows = {}  # per-batch memo: a section retry redoes only the tail

        def run_scaling():
            # through b1024 (VERDICT r3 item 4: find where the MFU curve
            # flattens — 7.2M params + Adam state is HBM-trivial, activations
            # at b1024/65 tokens are ~1.3 GB in bf16, well inside a v5e)
            for b in (64, 128, 256, 512, 1024):
                if b in scaling_rows:
                    continue
                bt = synth_batch(b)
                st = create_train_state(model, jax.random.PRNGKey(0), lr=2e-4,
                                        total_steps=51200, sample_batch=bt)
                st, sp, _ = time_train(st, bt, max(10, args.steps // 2))
                fl = flops_util.train_step_flops(b, mlp_ratio=1.0,
                                                 **MODEL_CONFIGS["vit_tiny"])
                m = flops_util.mfu(fl, sp, chip)
                scaling_rows[b] = {"batch": b, "ms_per_step": round(1000 * sp, 3),
                                   "img_per_sec": round(b / sp, 1),
                                   "mfu": None if m is None else round(m, 4)}
                log(f"scaling b{b}: {1000*sp:.2f} ms/step ({b/sp:.0f} img/s, "
                    f"mfu={scaling_rows[b]['mfu']})")
                # write-through per row: measured rows survive in the record
                # even if a later batch OOMs on both attempts
                sub["batch_scaling"] = [
                    scaling_rows[x] for x in sorted(scaling_rows)]

        if not args.skip_scaling:
            section("batch_scaling", run_scaling)

        # ------------------------------------------- depth-layout rows (big batch)
        def run_layout_row(name, **model_kwargs):
            # measured basis for the PERF.md compile-vs-step decision, taken
            # at the LARGEST batch the scaling sweep completed (VERDICT r3
            # item 4: the interesting regime is where MFU flattens, not b32):
            # scan_blocks = depth under nn.scan (stacked params, one compiled
            # block body); remat = jax.checkpoint each block (recompute
            # activations in backward — the HBM-for-FLOPs trade)
            big = max(scaling_rows) if scaling_rows else B
            bt = batch if big == B else synth_batch(big)
            lm = DiffusionViT(dtype=jnp.bfloat16, **model_kwargs,
                              **MODEL_CONFIGS["vit_tiny"])
            st = create_train_state(lm, jax.random.PRNGKey(0), lr=2e-4,
                                    total_steps=51200, sample_batch=bt)
            _, sp, comp = time_train(st, bt, max(10, args.steps // 2),
                                     step=make_train_step(lm))
            fl = flops_util.train_step_flops(big, mlp_ratio=1.0,
                                             **MODEL_CONFIGS["vit_tiny"])
            m = flops_util.mfu(fl, sp, chip)
            plain = scaling_rows.get(big)
            plain_ms = plain["ms_per_step"] if plain else round(1000 * spi, 3)
            sub[name] = {
                "batch": big,
                "ms_per_step": round(1000 * sp, 3),
                "img_per_sec": round(big / sp, 1),
                "mfu": None if m is None else round(m, 4),
                "compile_s": round(comp, 1),
                "plain_ms_per_step": plain_ms,
                "plain_compile_s": round(compile_s, 1)}
            log(f"{name} b{big}: {1000*sp:.2f} ms/step (compile {comp:.1f}s) "
                f"vs plain {plain_ms} ms/step")

        if not args.skip_scaling:  # --skip-scaling drops the depth-layout rows
            section("scan_blocks",
                    lambda: run_layout_row("scan_blocks", scan_blocks=True))
            section("remat", lambda: run_layout_row("remat", remat=True))

        # ------------------------------------------------------------- samplers
        def time_ddim(smodel, sparams, k, n, label, cache_interval=1,
                      cache_mode="delta", cache_threshold=None,
                      cache_tokens=None):
            """Compile+sync one sampling run, then time TWO and keep the faster
            (one transient host stall must not poison the record) — syncing via
            a real host transfer (see time_train). Memoized per
            (model, k, n, cache config)."""
            from ddim_cold_tpu.ops import sampling

            # flax modules hash/compare by field values: same-config models
            # share a memo row across sections, and a GC'd model's reused id()
            # can never alias a different config onto a stale timing
            key = (smodel, k, n, cache_interval, cache_mode,
                   cache_threshold, cache_tokens)
            ck = dict(cache_interval=cache_interval, cache_mode=cache_mode,
                      cache_threshold=cache_threshold,
                      cache_tokens=cache_tokens)
            if key not in timed:
                # the 200px flash kernel's first Mosaic compile is the
                # longest silent window in the whole bench — give it slack
                mark(f"sampler compile {label} k={k} n={n}", budget_s=2 * stall_s)
                img = sampling.ddim_sample(smodel, sparams, jax.random.PRNGKey(2),
                                           k=k, n=n, **ck)
                np.asarray(img)
                best = float("inf")
                for seed in (3, 4):
                    mark(f"sampler timing {label} k={k} n={n}")
                    t0 = time.time()
                    img = sampling.ddim_sample(smodel, sparams,
                                               jax.random.PRNGKey(seed), k=k,
                                               n=n, **ck)
                    np.asarray(img)
                    best = min(best, time.time() - t0)
                timed[key] = best
            sdt = timed[key]
            log(f"{label} DDIM k={k:3d} N={n}: {sdt:6.2f}s → {n/sdt:8.2f} img/s/chip")
            return sdt

        timed = {}
        n_sample = 8 if args.smoke else 64

        def run_sampler64():
            k20 = time_ddim(model, state.params, 20, n_sample, "vit_tiny 64px")
            sub["sampler_throughput_64px_k20"] = {
                "value": round(n_sample / k20, 2), "unit": "img/s/chip"}

        if not args.skip_sampler:
            section("sampler_64px", run_sampler64)

        def run_ksweep():
            from ddim_cold_tpu.ops import sampling

            sweep = {}
            cached = {}
            for k in (5, 20, 50) if args.smoke else (1, 5, 20, 50):
                sweep[str(k)] = round(
                    n_sample / time_ddim(model, state.params, k, n_sample, "k-sweep"), 2)
                if k == 1:
                    # k=1 is ~2000 steps — a cached rerun would double the
                    # sweep's longest leg for a row nobody tunes against
                    continue
                # throughput/quality trade-off per stride (ops/step_cache.py):
                # interval=2 "full" reuse, paired same-rng pixel delta
                sdt = time_ddim(model, state.params, k, n_sample,
                                "k-sweep cached", cache_interval=2,
                                cache_mode="full")
                a = sampling.ddim_sample(model, state.params,
                                         jax.random.PRNGKey(5), k=k, n=n_sample)
                b = sampling.ddim_sample(model, state.params,
                                         jax.random.PRNGKey(5), k=k, n=n_sample,
                                         cache_interval=2, cache_mode="full")
                cached[str(k)] = {
                    "img_per_sec": round(n_sample / sdt, 2),
                    "max_abs_pixel_delta": round(
                        float(jnp.max(jnp.abs(a - b))), 6)}
            sub["ksweep_64px_img_per_sec"] = sweep
            sub["ksweep_64px_cached_interval2_full"] = cached
            # the sweep's other end: the few-step programs (steps=s is the
            # TOTAL number of model applications — a distilled student's
            # regime, ops/sampling.ddim_sample_fewstep). Same model/params
            # as the stride rows, so the img/s column is the pure
            # step-count win the distillation trades quality for.
            fewstep = {}
            for s in (1, 2, 4):
                mark(f"k-sweep fewstep steps={s}")
                np.asarray(sampling.ddim_sample_fewstep(
                    model, state.params, jax.random.PRNGKey(2), steps=s,
                    n=n_sample))
                best = float("inf")
                for seed in (3, 4):
                    t0 = time.time()
                    np.asarray(sampling.ddim_sample_fewstep(
                        model, state.params, jax.random.PRNGKey(seed),
                        steps=s, n=n_sample))
                    best = min(best, time.time() - t0)
                fewstep[str(s)] = round(n_sample / best, 2)
                log(f"k-sweep fewstep steps={s}: {best:6.2f}s → "
                    f"{n_sample / best:8.2f} img/s/chip")
            sub["ksweep_64px_fewstep_img_per_sec"] = fewstep

        if args.ksweep:
            section("ksweep", run_ksweep)

        def run_serving():
            # the serving subsystem (ddim_cold_tpu/serve): bucketed
            # continuous batching + AOT warmup. The engine must sustain
            # ≥ 0.9× the raw one-shot sampler's img/s at the same bucket
            # size while absorbing a MIXED request-size stream (coalescing,
            # padding, one request split across batches) with zero
            # serve-time compiles — overlap and batching pay for the
            # queueing machinery, or this leg says so.
            from ddim_cold_tpu import serve

            buckets = (2, 4) if args.smoke else (8, 32)
            k_serve = 400 if args.smoke else 20
            bmax = max(buckets)
            cfg = serve.SamplerConfig(k=k_serve)
            engine = serve.Engine(model, state.params, buckets=buckets)
            mark(f"serving warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, [cfg])
            # mixed sizes (one above bmax → forced split) summing to a bucket
            # multiple: zero pad rows, so the one-shot comparison below is
            # packing/overlap overhead only, not padding waste
            sizes = [bmax + 1, 1, bmax // 2, bmax, bmax // 2 - 1, bmax - 1]
            short = -(-sum(sizes) // bmax) * bmax - sum(sizes)
            if short:
                sizes.append(short)
            best = None
            for rep in range(2):  # keep the faster drain (time_ddim's rule)
                mark(f"serving drain rep {rep}")
                for i, n_req in enumerate(sizes):
                    engine.submit(seed=100 + i, n=n_req, config=cfg)
                report = engine.run()
                if best is None or report["img_per_sec"] > best["img_per_sec"]:
                    best = report
            oneshot_t = time_ddim(model, state.params, k_serve, bmax,
                                  "serving one-shot")
            oneshot_ips = bmax / oneshot_t
            sub["serving"] = {
                "img_per_sec": round(best["img_per_sec"], 2),
                "oneshot_img_per_sec": round(oneshot_ips, 2),
                "vs_oneshot": round(best["img_per_sec"] / oneshot_ips, 3),
                "p50_latency_s": round(best["latency"]["p50_s"], 4),
                "p95_latency_s": round(best["latency"]["p95_s"], 4),
                "p99_latency_s": round(best["latency"]["p99_s"], 4),
                "requests": best["latency"]["count"],
                "max_queue_depth": best["max_queue_depth"],
                "compiles_after_warmup": best["compiles"],
                "batches": best["batches"], "rows": best["rows"],
                "padded_rows": best["padded_rows"],
                "buckets": list(buckets), "k": k_serve,
                "warmup": {"new_compiles": wu["new_compiles"],
                           "programs": wu["programs"],
                           "cache_dir": wu["cache_dir"]},
            }
            log(f"serving: {best['img_per_sec']:.2f} img/s over "
                f"{best['rows']} rows ({best['batches']} batches, "
                f"{best['padded_rows']} pad) vs one-shot {oneshot_ips:.2f} "
                f"img/s at n={bmax} → ratio "
                f"{sub['serving']['vs_oneshot']}; compiles after warmup: "
                f"{best['compiles']}")
            if args.quant:
                # w8a16 serving: warm the quant programs (same zero-compiles
                # guard), drain the same mixed stream at quant config, and
                # record the int8 param-byte footprint the engine ships once
                cfg_q = serve.SamplerConfig(k=k_serve, quant="xla")
                mark("serving quant warmup", budget_s=2 * stall_s)
                wu_q = serve.warmup(engine, [cfg_q])
                best_q = None
                for rep in range(2):
                    mark(f"serving quant drain rep {rep}")
                    for i, n_req in enumerate(sizes):
                        engine.submit(seed=200 + i, n=n_req, config=cfg_q)
                    rq = engine.run()
                    if best_q is None or rq["img_per_sec"] > best_q["img_per_sec"]:
                        best_q = rq
                sub["serving"]["quant"] = {
                    "img_per_sec": round(best_q["img_per_sec"], 2),
                    "vs_float_serving": round(
                        best_q["img_per_sec"] / best["img_per_sec"], 3),
                    "compiles_after_warmup": best_q["compiles"],
                    "warmup_new_compiles": wu_q["new_compiles"],
                    "param_bytes": engine.stats["param_bytes"],
                    "param_bytes_quant": engine.stats["param_bytes_quant"],
                }
                log(f"serving w8a16: {best_q['img_per_sec']:.2f} img/s "
                    f"(float {best['img_per_sec']:.2f}); param bytes "
                    f"{engine.stats['param_bytes']} → "
                    f"{engine.stats['param_bytes_quant']}; compiles after "
                    f"warmup: {best_q['compiles']}")

        if args.serving:
            section("serving", run_serving)

        def run_fewstep():
            # the few-step distilled-sampling leg: k ∈ {1, 2, 4} served as
            # first-class SamplerConfig(steps=k) programs through ONE
            # warmed engine (ops/sampling.ddim_sample_fewstep — a single
            # compiled scan per k). Contracts that hold EVERYWHERE and ARE
            # the leg on CPU CI: zero compiles after warmup across every k
            # (student configs included — they alias the teacher's
            # executable via warmup dedup instead of compiling), and the
            # k=1 single-request latency strictly below the stride-k
            # baseline's on the same host (one model application cannot
            # lose to ⌈1999/k⌉ of them). On chip the per-k img/s rows are
            # the few-step throughput record PERF.md publishes. The bench
            # carries no trained student checkpoint, so the engine's
            # student slot gets a copy of the teacher tree — every number
            # here is value-independent (throughput, latency, compiles);
            # quality belongs to eval/fid.distilled_sampler_guard over a
            # real train/distill.py run.
            from ddim_cold_tpu import serve

            buckets = (2, 4) if args.smoke else (8, 32)
            k_base = 400 if args.smoke else 20
            bmax = max(buckets)
            student = jax.tree.map(lambda a: a, state.params)
            engine = serve.Engine(model, state.params, buckets=buckets,
                                  student_params=student)
            cfg_base = serve.SamplerConfig(k=k_base)
            fs_cfgs = {s: serve.SamplerConfig(steps=s) for s in (1, 2, 4)}
            cfg_student = serve.SamplerConfig(steps=2, student=True)
            mark(f"fewstep warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, [cfg_base, *fs_cfgs.values(),
                                       cfg_student])
            compiles = 0

            def timed_drain(seed, n_req, cfg, label):
                # one request per drain: the wall IS the request latency at
                # n=1 and the full-bucket throughput at n=bmax (no mixed
                # stream — the packing story is the serving leg's job)
                nonlocal compiles
                mark(f"fewstep drain {label}")
                t0 = time.perf_counter()
                t = engine.submit(seed=seed, n=n_req, config=cfg)
                r = engine.run()
                wall = time.perf_counter() - t0
                t.result(timeout=600)
                compiles += r["compiles"]
                return wall

            rows = {}
            for s, cfg in fs_cfgs.items():
                best_tp = best_lat = None
                for rep in range(2):  # keep the faster rep (time_ddim's rule)
                    tp = timed_drain(950, bmax, cfg, f"k={s} bucket rep {rep}")
                    lat = timed_drain(951, 1, cfg, f"k={s} n=1 rep {rep}")
                    best_tp = tp if best_tp is None else min(best_tp, tp)
                    best_lat = lat if best_lat is None else min(best_lat, lat)
                rows[str(s)] = {
                    "img_per_sec": round(bmax / best_tp, 2),
                    "latency_1_s": round(best_lat, 4)}
                log(f"fewstep k={s}: {rows[str(s)]['img_per_sec']} img/s "
                    f"(bucket {bmax}), n=1 latency {rows[str(s)]['latency_1_s']}s")
            base_lat = min(timed_drain(951, 1, cfg_base, f"baseline rep {rep}")
                           for rep in range(2))
            stu_lat = min(timed_drain(951, 1, cfg_student,
                                      f"student rep {rep}")
                          for rep in range(2))
            sub["fewstep"] = {
                "per_k": rows,
                "baseline": {"k": k_base, "latency_1_s": round(base_lat, 4)},
                "student_latency_1_s": round(stu_lat, 4),
                "k1_latency_vs_baseline": round(
                    rows["1"]["latency_1_s"] / base_lat, 3),
                "compiles_after_warmup": compiles,
                "warmup_new_compiles": wu["new_compiles"],
                "warmup_deduped": wu["deduped"],
                "warmup_programs": wu["programs"],
                "buckets": list(buckets),
                "student_source": "teacher-copy (structural/timing leg; "
                                  "quality via eval/fid "
                                  "distilled_sampler_guard)",
            }
            log(f"fewstep: baseline k={k_base} n=1 latency {base_lat:.4f}s "
                f"vs k=1 {rows['1']['latency_1_s']}s (ratio "
                f"{sub['fewstep']['k1_latency_vs_baseline']}); warmup "
                f"{wu['new_compiles']} compiles + {wu['deduped']} deduped; "
                f"compiles after warmup: {compiles}")
            if compiles:
                raise RuntimeError(
                    f"fewstep leg compiled {compiles} program(s) after "
                    "warmup — every (steps, bucket) program plus the "
                    "student alias must be AOT-warmed")
            if rows["1"]["latency_1_s"] >= base_lat:
                raise RuntimeError(
                    f"k=1 single-request latency {rows['1']['latency_1_s']}s "
                    f"is not below the k={k_base} baseline {base_lat:.4f}s "
                    "— the few-step program is not paying for itself")

        if args.fewstep:
            section("fewstep", run_fewstep)

        def run_obs():
            # the observability leg: tracing must be free when off and
            # near-free when on. Same mixed stream through one warmed
            # engine, tracing OFF then ON (best-of-2 each to damp host
            # noise) → the measured overhead PERF.md publishes. The traced
            # drain must yield a complete span tree per request (root →
            # stage children), bitwise-identical images, both exports must
            # round-trip, and a telemetry-config request must come back
            # with its step summary — all at zero compiles after warmup.
            from ddim_cold_tpu import serve
            from ddim_cold_tpu.obs import spans
            from ddim_cold_tpu.utils import profiling

            os.makedirs("results", exist_ok=True)
            buckets = (2, 4) if args.smoke else (8, 32)
            k_serve = 400 if args.smoke else 20
            bmax = max(buckets)
            cfg = serve.SamplerConfig(k=k_serve)
            cfg_tel = serve.SamplerConfig(
                k=k_serve, cache_interval=2, cache_mode="adaptive",
                cache_threshold=0.05, telemetry=True)
            engine = serve.Engine(model, state.params, buckets=buckets)
            mark(f"obs warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, [cfg, cfg_tel])
            sizes = [bmax + 1, 1, bmax // 2, bmax, bmax // 2 - 1, bmax - 1]
            short = -(-sum(sizes) // bmax) * bmax - sum(sizes)
            if short:
                sizes.append(short)

            def drain(seed0):
                tickets = [engine.submit(seed=seed0 + i, n=n_req, config=cfg)
                           for i, n_req in enumerate(sizes)]
                report = engine.run()
                return report, [np.asarray(t.result(timeout=600))
                                for t in tickets]

            # interleave off/on reps (best-of-3 each): host-side drift on a
            # ~1 s CPU smoke drain is larger than the overhead being
            # measured, and alternating cancels it instead of aliasing it
            spans.disable()
            n_before = len(spans.spans())
            best_off = outs_off = best_on = outs_on = None
            n_reps = 3
            for rep in range(n_reps):
                mark(f"obs tracing-off drain rep {rep}")
                r, outs = drain(500)
                if best_off is None or r["img_per_sec"] > best_off["img_per_sec"]:
                    best_off, outs_off = r, outs
                mark(f"obs tracing-on drain rep {rep}")
                with spans.tracing():
                    r, outs = drain(500)  # same seeds: bitwise oracle
                if best_on is None or r["img_per_sec"] > best_on["img_per_sec"]:
                    best_on, outs_on = r, outs
            with spans.tracing():
                # span-tree completeness: every request root carries ended
                # stage children for the pipeline the engine actually ran
                roots = [s for s in spans.spans()
                         if s.name == "engine.request" and s.ended]
                if len(roots) < n_reps * len(sizes):
                    raise RuntimeError(
                        f"traced drains produced {len(roots)} closed "
                        f"request spans for {n_reps * len(sizes)} requests "
                        "— span trees are incomplete")
                kids = {}
                for s in spans.spans():
                    kids.setdefault(s.parent_id, set()).add(s.name)
                for root in roots:
                    stages = kids.get(root.span_id, set())
                    if not {"plan", "assemble", "dispatch", "fetch"} <= stages:
                        raise RuntimeError(
                            f"request span {root.span_id} is missing stage "
                            f"children (got {sorted(stages)})")
                # one telemetry request, its dispatch under a span-keyed
                # profiler session — the span→profiler workflow PERF.md shows
                tel_root = spans.begin("obs.telemetry_leg")
                with profiling.span_trace("results/obs_profile", tel_root):
                    t_tel = engine.submit(seed=510, n=2, config=cfg_tel)
                    engine.run()
                    t_tel.result(timeout=600)
                tel_root.end()
                tel = t_tel.telemetry
                if tel is None:
                    raise RuntimeError("telemetry config returned no step "
                                       "summary on the ticket")
                chrome = spans.export_chrome("results/obs_trace.json")
                jsonl = spans.export_jsonl("results/obs_trace.jsonl")
                with open("results/obs_trace.json") as f:
                    if json.load(f) != json.loads(json.dumps(chrome)):
                        raise RuntimeError("chrome export did not round-trip")
                n_spans = len(spans.spans()) - n_before
            spans.clear()
            for a, b in zip(outs_off, outs_on):
                if not np.array_equal(a, b):
                    raise RuntimeError(
                        "tracing changed the sampled images — spans must "
                        "never touch numerics")
            compiles = best_off["compiles"] + best_on["compiles"]
            if compiles:
                raise RuntimeError(
                    f"obs leg compiled {compiles} program(s) after warmup")
            overhead = (best_off["img_per_sec"] / best_on["img_per_sec"] - 1.0
                        if best_on["img_per_sec"] else None)
            sub["obs"] = {
                "img_per_sec_tracing_off": round(best_off["img_per_sec"], 2),
                "img_per_sec_tracing_on": round(best_on["img_per_sec"], 2),
                "tracing_overhead_pct": (round(100 * overhead, 2)
                                         if overhead is not None else None),
                "traced_bitwise_equal": True,
                "spans_recorded": n_spans,
                "chrome_events": len(chrome["traceEvents"]),
                "jsonl_rows": len(jsonl),
                "telemetry": {k: tel[k] for k in
                              ("steps", "refreshes", "reuses",
                               "planned_refreshes", "promoted_refreshes",
                               "refresh_ratio")},
                "profile_dir": "results/obs_profile",
                "compiles_after_warmup": compiles,
                "warmup_new_compiles": wu["new_compiles"],
                "buckets": list(buckets), "k": k_serve,
            }
            log(f"obs: {best_off['img_per_sec']:.2f} img/s untraced vs "
                f"{best_on['img_per_sec']:.2f} traced "
                f"(overhead {sub['obs']['tracing_overhead_pct']}%); "
                f"{n_spans} spans, {len(chrome['traceEvents'])} chrome "
                f"events; telemetry {tel['refreshes']}r/{tel['reuses']}c; "
                f"compiles after warmup: {compiles}")

        if args.obs:
            section("obs", run_obs)

        def run_cache_adaptive():
            # the adaptive-cache leg (this PR's tentpole): the two adaptive
            # modes vs the fixed-interval cache they extend, one-shot and
            # served. On CPU (the CI gate) the RATIOS are noise — what the
            # leg proves there is the compile contract (every config is one
            # AOT program; nothing compiles after warmup — raise otherwise)
            # and the τ→0 bitwise-collapse guard. On chip the same rows are
            # the adaptive speedup record.
            from ddim_cold_tpu import serve
            from ddim_cold_tpu.ops import sampling

            n_ca = 4 if args.smoke else 16
            k_ca = 400 if args.smoke else 20
            # vit_tiny 64px is patch-8 → 64 patches + CLS = 65 tokens;
            # top-k 16 ≈ the liveliest quarter recomputed on reuse steps
            tok = 16
            legs = {
                "uncached": {},
                "fixed_full_i2": {"cache_interval": 2, "cache_mode": "full"},
                "adaptive_i4_t05": {"cache_interval": 4,
                                    "cache_mode": "adaptive",
                                    "cache_threshold": 0.05},
                "token_i2_k16": {"cache_interval": 2, "cache_mode": "token",
                                 "cache_tokens": tok},
            }
            times = {name: time_ddim(model, state.params, k_ca, n_ca,
                                     f"cache-adaptive {name}", **ck)
                     for name, ck in legs.items()}
            out = {name: {"img_per_sec": round(n_ca / t, 2),
                          "vs_uncached": round(times["uncached"] / t, 3),
                          "vs_fixed_i2": round(times["fixed_full_i2"] / t, 3)}
                   for name, t in times.items()}
            # τ→0 forces refresh on every gated step: bitwise = the exact
            # sampler, by construction — the cheapest end-to-end proof that
            # the gate's reuse branch never leaks into the degenerate case
            a = sampling.ddim_sample(model, state.params,
                                     jax.random.PRNGKey(5), k=k_ca, n=n_ca)
            b = sampling.ddim_sample(model, state.params,
                                     jax.random.PRNGKey(5), k=k_ca, n=n_ca,
                                     cache_interval=2, cache_mode="adaptive",
                                     cache_threshold=0.0)
            if not bool(jnp.array_equal(a, b)):
                raise RuntimeError("adaptive threshold=0 is not bitwise "
                                   "equal to the exact sampler")
            out["threshold0_bitwise_exact"] = True
            # served: one warmup over all three cache configs, then a mixed
            # drain per config. Adaptive is batch-coupled (batch-max drift):
            # the planner gives it one-batch-per-request, so its request
            # sizes stay within the largest bucket.
            buckets = (2, 4) if args.smoke else (8, 32)
            bmax = max(buckets)
            cfgs = {
                "fixed": serve.SamplerConfig(k=k_ca, cache_interval=2,
                                             cache_mode="full"),
                "adaptive": serve.SamplerConfig(k=k_ca, cache_interval=4,
                                                cache_mode="adaptive",
                                                cache_threshold=0.05),
                "token": serve.SamplerConfig(k=k_ca, cache_interval=2,
                                             cache_mode="token",
                                             cache_tokens=tok),
            }
            engine = serve.Engine(model, state.params, buckets=buckets)
            mark(f"cache-adaptive warmup buckets={buckets}",
                 budget_s=2 * stall_s)
            wu = serve.warmup(engine, list(cfgs.values()))
            served = {"warmup_new_compiles": wu["new_compiles"],
                      "programs": wu["programs"]}
            for name, cfg in cfgs.items():
                sizes = ([bmax - 1, 1, bmax] if cfg.batch_coupled
                         else [bmax + 1, 1, bmax // 2])
                mark(f"cache-adaptive drain {name}")
                for i, n_req in enumerate(sizes):
                    engine.submit(seed=300 + i, n=n_req, config=cfg)
                rep = engine.run()
                if rep["compiles"]:
                    raise RuntimeError(
                        f"cache-adaptive '{name}' drain compiled "
                        f"{rep['compiles']} program(s) after warmup — the "
                        "adaptive gate must live INSIDE one AOT program")
                served[name] = {"img_per_sec": round(rep["img_per_sec"], 2),
                                "compiles_after_warmup": rep["compiles"]}
            out["served"] = served
            sub["cache_adaptive"] = out
            log(f"cache-adaptive: {json.dumps(out)}")

        if args.cache_adaptive:
            section("cache_adaptive", run_cache_adaptive)

        def run_parallel():
            # the sequence-parallel leg (parallel/ulysses + the per-degree
            # (data, seq) meshes): the SAME full-bucket request served at
            # sp_degree ∈ {1, 2, all-local}. Two structural contracts hold
            # everywhere and ARE the leg on CPU CI: zero compiles after
            # warmup at every degree (an sp program is one AOT executable,
            # registry-keyed by (config, bucket) like any other), and the
            # degenerate sp_degree=1 bitwise-equal to the direct sampler.
            # sp>1 is allclose vs degree 1 (shard_map reorders reductions)
            # and records single-request latency per degree — the
            # batch-vs-sequence crossover evidence PERF.md publishes. The
            # >1.3× sp2-vs-sp1 latency gate only arms on real chips, where
            # sharding actually drops per-device FLOPs; CPU "devices" share
            # the same cores and the ratio is noise.
            from ddim_cold_tpu import serve
            from ddim_cold_tpu.ops import sampling

            n_dev = jax.local_device_count()
            if n_dev < 2:
                sub["parallel"] = {"skipped": (
                    f"{n_dev} local device(s) — sequence parallelism "
                    "shards over >= 2")}
                log("parallel: skipped (single local device)")
                return
            k_sp = 400 if args.smoke else 20
            degrees = [1]
            if n_dev % 2 == 0:
                degrees.append(2)
            if n_dev > 2:
                degrees.append(n_dev)  # all-local: seq over every device
            # one bucket every geometry can tile: the data axis at degree d
            # is n_dev // d, and ensure_program rejects a bucket the data
            # axis does not divide (the sp batch is data-sharded)
            bucket = max(2, max((n_dev // d for d in degrees if d > 1),
                                default=2))
            cfgs = {1: serve.SamplerConfig(k=k_sp)}
            for d in degrees[1:]:
                cfgs[d] = serve.SamplerConfig(k=k_sp, sp_mode="ulysses",
                                              sp_degree=d)
            engine = serve.Engine(model, state.params, buckets=(bucket,))
            mark(f"parallel warmup degrees={degrees} bucket={bucket}",
                 budget_s=2 * stall_s)
            wu = serve.warmup(engine, list(cfgs.values()))
            outs, rows, compiles = {}, {}, 0
            for d in degrees:
                best, best_r = None, None
                for rep in range(2):  # keep the faster drain
                    mark(f"parallel drain sp{d} rep {rep}")
                    t = engine.submit(seed=800, n=bucket, config=cfgs[d])
                    t0 = time.perf_counter()
                    r = engine.run()
                    wall = time.perf_counter() - t0
                    compiles += r["compiles"]
                    outs[d] = np.asarray(t.result(timeout=600))
                    if best is None or wall < best:
                        best, best_r = wall, r
                # ulysses needs the local head count divisible by the seq
                # axis; models.sp_clone falls back to ring otherwise
                resolved = ("ring" if d > 1 and model.num_heads % d
                            else cfgs[d].sp_mode)
                rows[d] = {
                    "sp_mode": resolved,
                    "mesh": {"data": n_dev // d, "seq": d} if d > 1 else None,
                    "latency_s": round(best, 4),
                    "p99_latency_s": round(best_r["latency"]["p99_s"], 4),
                    "img_per_sec": round(bucket / best, 2)}
            direct = np.asarray(sampling.ddim_sample(
                model, state.params, jax.random.PRNGKey(800), k=k_sp,
                n=bucket))
            bitwise = bool(np.array_equal(outs[1], direct))
            # sp tolerance is dtype-aware: this model's trunk is bf16, where
            # ONE reordered reduction moves an activation by ~1 ulp (0.0039
            # at 1.0) — the fp32 tests' 2e-5 would flag pure quantization
            sp_atol = 0.02 if model.dtype == jnp.bfloat16 else 2e-5
            for d in degrees[1:]:
                rows[d]["max_abs_delta_vs_sp1"] = round(
                    float(np.max(np.abs(outs[d] - outs[1]))), 6)
                rows[d]["speedup_vs_sp1"] = round(
                    rows[1]["latency_s"] / rows[d]["latency_s"], 3)
            sub["parallel"] = {
                "devices": n_dev, "bucket": bucket, "k": k_sp,
                "sp_atol": sp_atol,
                "degrees": {str(d): rows[d] for d in degrees},
                "sp1_bitwise_vs_direct": bitwise,
                "compiles_after_warmup": compiles,
                "warmup_new_compiles": wu["new_compiles"],
                "warmup_programs": wu["programs"],
            }
            log("parallel: " + ", ".join(
                f"sp{d} {rows[d]['latency_s']}s ({rows[d]['sp_mode']})"
                for d in degrees) + f"; compiles after warmup: {compiles}")
            if not bitwise:
                raise RuntimeError(
                    "sp_degree=1 is not bitwise the direct sampler — the "
                    "degenerate config must BE the existing program")
            for d in degrees[1:]:
                if not np.allclose(outs[d], outs[1], atol=sp_atol):
                    raise RuntimeError(
                        f"sp_degree={d} drifted "
                        f"{rows[d]['max_abs_delta_vs_sp1']} from the "
                        f"degree-1 program (atol {sp_atol}) — beyond the "
                        "sharded-reduction tolerance")
            if compiles:
                raise RuntimeError(
                    f"parallel leg compiled {compiles} program(s) after "
                    "warmup — every sp geometry must be AOT-warmed")
            if jax.default_backend() != "cpu" and 2 in rows:
                if rows[2]["speedup_vs_sp1"] < 1.3:
                    raise RuntimeError(
                        f"sp_degree=2 single-request speedup "
                        f"{rows[2]['speedup_vs_sp1']} < 1.3x — sequence "
                        "parallelism is not paying for its collectives on "
                        "this chip")
            if not args.smoke and jax.default_backend() != "cpu":
                # the north-star 200px geometry, k=20, sharded across ALL
                # local devices through a warmed engine — the single-request
                # latency the seq axis exists to cut (2501 tokens is where
                # attention dominates and the all-to-all pays). data axis is
                # 1 at the all-local degree, so any bucket tiles it.
                d200 = degrees[-1]
                ns = DiffusionViT(dtype=jnp.bfloat16,
                                  **MODEL_CONFIGS["oxford_flower_200_p4"])
                mark("parallel 200px param init", budget_s=2 * stall_s)
                nsp = ns.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 200, 200, 3)),
                              jnp.zeros((1,), jnp.int32))["params"]
                cfg200 = serve.SamplerConfig(k=20, sp_mode="ulysses",
                                             sp_degree=d200)
                eng200 = serve.Engine(ns, nsp, buckets=(4,))
                mark(f"parallel 200px warmup sp{d200}", budget_s=3 * stall_s)
                serve.warmup(eng200, [cfg200])
                t200 = eng200.submit(seed=801, n=4, config=cfg200)
                t0 = time.perf_counter()
                r200 = eng200.run()
                wall = time.perf_counter() - t0
                np.asarray(t200.result(timeout=600))
                sub["parallel"]["northstar_200px_sp"] = {
                    "sp_degree": d200, "bucket": 4, "k": 20,
                    "latency_s": round(wall, 3),
                    "img_per_sec": round(4 / wall, 2),
                    "compiles_after_warmup": r200["compiles"]}
                log(f"parallel 200px sp{d200}: {wall:.2f}s for 4 imgs; "
                    f"compiles after warmup: {r200['compiles']}")
                if r200["compiles"]:
                    raise RuntimeError(
                        "200px sp leg compiled after warmup — the sharded "
                        "north-star program must be AOT too")

        if args.parallel:
            section("parallel", run_parallel)

        def run_faults():
            # the robustness leg: same mixed stream twice through a
            # fault-tolerant engine — once DISARMED (the zero-overhead
            # guarantee: this must match the plain serving drain, and the
            # fault hooks must cost nothing on the fast path), once under a
            # FIXED seeded fault schedule (degraded mode: retries absorb
            # transients, bisection quarantines the one poisoned request,
            # everyone else completes). Recovery re-packs at the warmed
            # buckets, so compiles-after-warmup stays zero in BOTH drains.
            from ddim_cold_tpu import serve
            from ddim_cold_tpu.utils import faults as fj

            buckets = (2, 4) if args.smoke else (8, 32)
            k_serve = 400 if args.smoke else 20
            bmax = max(buckets)
            cfg = serve.SamplerConfig(k=k_serve)
            engine = serve.Engine(model, state.params, buckets=buckets)
            mark(f"faults warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, [cfg])
            sizes = [bmax + 1, 1, bmax // 2, bmax, bmax // 2 - 1, bmax - 1]
            short = -(-sum(sizes) // bmax) * bmax - sum(sizes)
            if short:
                sizes.append(short)

            def drain(seed0):
                for i, n_req in enumerate(sizes):
                    engine.submit(seed=seed0 + i, n=n_req, config=cfg)
                return engine.run()

            assert not fj.active()
            mark("faults clean drain")
            clean = drain(300)
            poison_rid = engine._next_rid + 2  # third request of the stream
            schedule = (
                fj.FaultSpec("serve.dispatch", "transient", rate=0.3,
                             seed=11),
                fj.FaultSpec("serve.dispatch", "permanent",
                             match=f"req:{poison_rid}|"),
                fj.FaultSpec("serve.fetch", "latency", rate=0.2, seed=5,
                             latency_s=0.02),
            )
            mark("faults chaos drain")
            with fj.inject(*schedule) as plan:
                chaos = drain(400)
                injected, by_site = len(plan.realized), plan.by_site()
            sub["faults"] = {
                "clean_img_per_sec": round(clean["img_per_sec"], 2),
                "chaos_img_per_sec": round(chaos["img_per_sec"], 2),
                "degraded_ratio": round(
                    chaos["img_per_sec"] / clean["img_per_sec"], 3)
                if clean["img_per_sec"] else None,
                "injected": injected, "by_site": by_site,
                "retries": chaos["retries"],
                "quarantined": chaos["quarantined"],
                "failed_tickets": chaos["failed_tickets"],
                "rows": chaos["rows"],
                "compiles_after_warmup": clean["compiles"] + chaos["compiles"],
                "warmup_new_compiles": wu["new_compiles"],
                "stream_sizes": sizes, "buckets": list(buckets), "k": k_serve,
            }
            serving = sub.get("serving")
            if serving:  # disarmed must match the plain-engine numbers
                sub["faults"]["disarmed_vs_serving"] = round(
                    clean["img_per_sec"] / serving["img_per_sec"], 3)
            log(f"faults: clean {clean['img_per_sec']:.2f} img/s, chaos "
                f"{chaos['img_per_sec']:.2f} img/s (ratio "
                f"{sub['faults']['degraded_ratio']}) under {injected} "
                f"injections {by_site}; retries {chaos['retries']}, "
                f"quarantined {chaos['quarantined']}, failed "
                f"{chaos['failed_tickets']}; compiles after warmup: "
                f"{sub['faults']['compiles_after_warmup']}")

        if args.faults:
            section("faults", run_faults)

        def run_fleet():
            # the fleet leg: one Router over TWO in-process replicas serves
            # the same mixed stream twice — clean, then under a seeded
            # chaos schedule that kills replica r0's dispatch outright
            # (permanent) and sprays transients at assembly and placement.
            # The contract being measured: survivors keep completing
            # (degraded throughput, not an outage), the dead replica is
            # drained AND replaced, and compiles-after-warmup stays 0
            # across every replica — the replacement warms from the same
            # (config, bucket) set, so it never compiles in service.
            from ddim_cold_tpu import serve
            from ddim_cold_tpu.utils import faults as fj

            buckets = (2, 4) if args.smoke else (8, 32)
            k_serve = 400 if args.smoke else 20
            bmax = max(buckets)
            cfg = serve.SamplerConfig(k=k_serve)
            sizes = [bmax + 1, 1, bmax // 2, bmax, bmax // 2 - 1, bmax - 1]
            mark(f"fleet spawn+warm 2 replicas buckets={buckets}",
                 budget_s=3 * stall_s)
            router = serve.Router(
                serve.local_factory(model, state.params, buckets=buckets),
                replicas=2, configs=[cfg], max_hedges=2)

            def drain_stream(seed0):
                t0 = time.perf_counter()
                tickets = [router.submit(seed=seed0 + i, n=n_req, config=cfg)
                           for i, n_req in enumerate(sizes)]
                errs = [t.exception(timeout=600) for t in tickets]
                wall = time.perf_counter() - t0
                rows = sum(n for n, e in zip(sizes, errs) if e is None)
                return errs, rows, wall

            assert not fj.active()
            mark("fleet clean drain")
            _, rows_c, wall_c = drain_stream(500)
            clean_ips = rows_c / wall_c if wall_c else 0.0
            schedule = (
                fj.FaultSpec("serve.dispatch", "permanent",
                             match="replica:r0|"),
                fj.FaultSpec("serve.assemble", "transient", rate=0.25,
                             seed=11),
                # scoped to r1: an unmatched place-transient can steer every
                # request AWAY from r0 and the kill never fires — the r0
                # placements must stay clean so the dispatch fault is hit
                fj.FaultSpec("router.place", "transient", rate=0.2, seed=12,
                             match="replica:r1|"),
            )
            mark("fleet chaos drain")
            with fj.inject(*schedule) as plan:
                errs, rows_x, wall_x = drain_stream(600)
                injected, by_site = len(plan.realized), plan.by_site()
                # let supervision finish the lifecycle: r0 retired, the
                # fleet healed back to 2 replicas (replacement warmed
                # inside the chaos scope — realism, not convenience)
                deadline = time.perf_counter() + 30
                while time.perf_counter() < deadline:
                    h = router.health()
                    if (h["retired_replicas"] >= 1
                            and h["active_replicas"] == 2):
                        break
                    time.sleep(0.05)
            chaos_ips = rows_x / wall_x if wall_x else 0.0
            health = router.drain(timeout=60)
            sub["fleet"] = {
                "replicas": 2,
                "clean_img_per_sec": round(clean_ips, 2),
                "chaos_img_per_sec": round(chaos_ips, 2),
                "degraded_ratio": round(chaos_ips / clean_ips, 3)
                if clean_ips else None,
                "injected": injected, "by_site": by_site,
                "survivors": sum(1 for e in errs if e is None),
                "failed_tickets": health["failed"],
                "hedges": health["hedges"],
                "failovers": health["failovers"],
                "replicas_retired": health["retired_replicas"],
                "replicas_spawned": health["replicas_spawned"],
                "compiles_after_warmup": health["compiles_after_warmup"],
                "stream_sizes": sizes, "buckets": list(buckets),
                "k": k_serve,
            }
            log(f"fleet: clean {clean_ips:.2f} img/s, chaos "
                f"{chaos_ips:.2f} img/s (ratio "
                f"{sub['fleet']['degraded_ratio']}) under {injected} "
                f"injections {by_site}; hedges {health['hedges']}, "
                f"failovers {health['failovers']}, retired "
                f"{health['retired_replicas']}, spawned "
                f"{health['replicas_spawned']}; compiles after warmup: "
                f"{health['compiles_after_warmup']}")
            if health["compiles_after_warmup"] != 0:
                raise RuntimeError(
                    "fleet zero-compile contract broken: "
                    f"{health['compiles_after_warmup']} compiles after "
                    "warmup (replacement must warm from the same "
                    "(config, bucket) set)")

        if args.fleet:
            section("fleet", run_fleet)

        def run_fleet_proc():
            # the out-of-process fleet leg: same contract as run_fleet, but
            # each replica is its own OS PROCESS behind serve/remote.py's
            # socket RPC, and the chaos is real — a SIGKILL inside r0
            # mid-drain (armed in the CHILD only, via its env) plus parent-
            # side rpc latency. What this leg proves and records:
            #   * survivors complete BITWISE vs direct sampling (failover
            #     re-places the dead replica's queued tickets);
            #   * a replacement spawns from the persistent compile cache the
            #     first replicas populated — spawn+warm wall time cold
            #     (empty cache) vs warm (replacement) is THE pre-warmed-
            #     spawn number;
            #   * compiles-after-warmup stays 0 fleet-wide (the spawn path
            #     asserts it per replica; the router sums it);
            #   * the autoscaler scales up under queue pressure and
            #     converges back to the floor without flapping.
            from ddim_cold_tpu import serve
            from ddim_cold_tpu.ops import sampling
            from ddim_cold_tpu.serve import remote as sv_remote
            from ddim_cold_tpu.utils import faults as fj

            buckets = (2, 4) if args.smoke else (8, 32)
            k_serve = 400 if args.smoke else 20
            bmax = max(buckets)
            cfg = serve.SamplerConfig(k=k_serve)
            sizes = [bmax, 1, bmax // 2, bmax - 1, bmax // 2 + 1, bmax]
            from ddim_cold_tpu.utils.platform import default_cache_dir

            tmp = tempfile.mkdtemp(prefix="ddim_fleet_proc_")
            # a fixed directory (a cache whose path moves never hits twice);
            # the children keep JAX_COMPILATION_CACHE_DIR when it is set
            # (serve/replica_main.py), so a value from outside still wins
            cache_dir = os.path.join(default_cache_dir(), "fleet_proc_cpu")
            cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
            params_npz = sv_remote.save_params_npz(
                os.path.join(tmp, "params.npz"),
                jax.device_get(state.params))
            spec = {"backend": "engine",
                    "model": dict(MODEL_CONFIGS["vit_tiny"],
                                  dtype="bfloat16"),
                    "params_npz": params_npz,
                    "engine": {"buckets": list(buckets)},
                    "cache_dir": cache_dir}
            # children always run on CPU — a chip has one owner, and this
            # process holds it — so this leg measures lifecycle latency
            # (spawn, warm, kill, recover) of CPU replicas, never device
            # throughput; the record says so (replica_platform). The kill
            # spec rides the child env so ONLY replica r0 ever arms it (its
            # 2nd work frame lands mid-stream — a SIGKILL mid-drain).
            child_env = {
                "JAX_PLATFORMS": "cpu",
                "DDIM_COLD_FAULTS":
                    "replica.kill:kill:at=1,match=replica:r0|"}
            reps = {}
            factory = sv_remote.remote_factory(
                spec, env=child_env, heartbeat_s=1.0, miss_budget=5,
                spawn_timeout_s=600.0, rpc_timeout_s=120.0)

            def tracking(rid):
                rep = factory(rid)
                reps[rid] = rep
                return rep

            mark(f"fleet-proc spawn+warm 2 subprocess replicas "
                 f"buckets={buckets}", budget_s=6 * stall_s)
            router = serve.Router(tracking, replicas=2, configs=[cfg],
                                  buckets=buckets, max_hedges=2,
                                  drain_timeout_s=300)
            try:
                mark("fleet-proc chaos stream", budget_s=6 * stall_s)
                t_stream = time.perf_counter()
                with fj.inject(fj.FaultSpec("rpc.latency", "latency",
                                            rate=0.2, seed=13,
                                            latency_s=0.02)) as plan:
                    tickets = [(700 + i,
                                router.submit(seed=700 + i, n=n_req,
                                              config=cfg))
                               for i, n_req in enumerate(sizes)]
                    # recovery clock: kill detected → replacement READY
                    t_detect = t_ready = None
                    deadline = time.perf_counter() + 600
                    while time.perf_counter() < deadline:
                        h = router.health()
                        now = time.perf_counter()
                        if t_detect is None and h["retired_replicas"] >= 1:
                            t_detect = now
                        if (t_detect is not None and t_ready is None
                                and h["active_replicas"] == 2):
                            t_ready = now
                            break
                        time.sleep(0.1)
                    errs = [t.exception(timeout=900) for _, t in tickets]
                    injected = len(plan.realized)
                wall = time.perf_counter() - t_stream
                survivors = sum(1 for e in errs if e is None)
                if survivors < len(sizes):
                    bad = next(e for e in errs if e is not None)
                    raise RuntimeError(
                        f"{len(sizes) - survivors} ticket(s) lost to the "
                        f"kill (failover must complete them): {bad}")
                # bitwise contract: every survivor row-set equals direct
                # sampling with the same seed (CPU parent only — a bf16 TPU
                # parent and a CPU child legitimately differ)
                bitwise = None
                if jax.default_backend() == "cpu":
                    mark("fleet-proc bitwise check vs direct")
                    for (seed, t), n_req in zip(tickets, sizes):
                        direct = np.asarray(sampling.ddim_sample(
                            model, state.params, jax.random.PRNGKey(seed),
                            k=k_serve, n=n_req))
                        if not np.array_equal(np.asarray(t.result()),
                                              direct):
                            raise RuntimeError(
                                f"survivor seed {seed} NOT bitwise vs "
                                "direct sampling after failover")
                    bitwise = True
                # autoscaler: queue pressure → up, then converge back to
                # the floor with no flapping (ticks driven here so the leg
                # is deterministic about WHEN decisions happen)
                mark("fleet-proc autoscale convergence", budget_s=6 * stall_s)
                scaler = serve.Autoscaler(
                    router, min_replicas=2, max_replicas=3,
                    queue_high=1.0, queue_low=0.5,
                    up_ticks=2, down_ticks=2, cooldown_s=0.0)
                actions = []
                burst = [router.submit(seed=800 + i, n=bmax, config=cfg)
                         for i in range(4)]
                deadline = time.perf_counter() + 900
                while time.perf_counter() < deadline:
                    actions.append(scaler.tick()["action"])
                    if all(t.done for t in burst):
                        break
                    time.sleep(0.5)
                for t in burst:
                    t.result(timeout=900)
                idle_tail = []
                for _ in range(8):  # drained fleet: must walk back to floor
                    idle_tail.append(scaler.tick()["action"])
                    time.sleep(0.05)
                actions += idle_tail
                ups = actions.count("up")
                downs = actions.count("down")
                if router.target != scaler.floor or ups != downs:
                    raise RuntimeError(
                        f"autoscaler did not converge: target "
                        f"{router.target} vs floor {scaler.floor}, "
                        f"{ups} ups / {downs} downs ({actions})")
                if any(a is not None for a in idle_tail[-4:]):
                    raise RuntimeError(
                        f"autoscaler flapping on an idle fleet: {idle_tail}")
                health = router.drain(timeout=300)
                if health["compiles_after_warmup"] != 0:
                    raise RuntimeError(
                        "fleet-proc zero-compile contract broken: "
                        f"{health['compiles_after_warmup']} compiles after "
                        "warmup (the replacement must warm from the "
                        "persistent cache)")
                # spawn+warm walls: r0/r1 paid the first compile (cold
                # unless an earlier run left the cache warm — recorded);
                # every later spawn warmed from the populated cache
                cold = [reps[r] for r in ("r0", "r1") if r in reps]
                warm = [rep for rid, rep in sorted(reps.items())
                        if rid not in ("r0", "r1")]
                def spawn_warm(rs):
                    return round(max(r.spawn_s + (r.warm_s or 0.0)
                                     for r in rs), 2) if rs else None
                sub["fleet_proc"] = {
                    "replicas": 2, "backend": "subprocess",
                    "replica_platform": child_env["JAX_PLATFORMS"],
                    "cache_warm_at_start": cache_warm,
                    "img_per_sec": round(sum(sizes) / wall, 2),
                    "survivors": survivors, "bitwise_vs_direct": bitwise,
                    "rpc_latency_injected": injected,
                    "failovers": health["failovers"],
                    "hedges": health["hedges"],
                    "replicas_retired": health["retired_replicas"],
                    "replicas_spawned": health["replicas_spawned"],
                    "compiles_after_warmup":
                        health["compiles_after_warmup"],
                    "kill_to_recovered_s":
                        round(t_ready - t_detect, 2)
                        if t_detect and t_ready else None,
                    "spawn_warm_cold_s": spawn_warm(cold),
                    "spawn_warm_s": spawn_warm(warm),
                    "replacement_new_compiles":
                        max((r.warm_report or {}).get("new_compiles", 0)
                            for r in warm) if warm else None,
                    "autoscale": {"scale_ups": ups, "scale_downs": downs,
                                  "final_target": router.target,
                                  "floor": scaler.floor},
                    "stream_sizes": sizes, "buckets": list(buckets),
                    "k": k_serve,
                }
                log(f"fleet-proc: {survivors}/{len(sizes)} tickets through "
                    f"the SIGKILL (bitwise={bitwise}), kill→recovered "
                    f"{sub['fleet_proc']['kill_to_recovered_s']}s, "
                    f"spawn+warm cold {sub['fleet_proc']['spawn_warm_cold_s']}s "
                    f"vs warm {sub['fleet_proc']['spawn_warm_s']}s, "
                    f"autoscale {ups} up / {downs} down → target "
                    f"{router.target}; compiles after warmup: "
                    f"{health['compiles_after_warmup']}")
            finally:
                try:
                    router.drain(timeout=60)
                except Exception:  # noqa: BLE001 — already drained above
                    pass
                for rep in reps.values():
                    try:
                        rep._proc.kill()  # no child outlives the bench
                    except Exception:  # noqa: BLE001 — already gone
                        pass
                shutil.rmtree(tmp, ignore_errors=True)

        if args.fleet_proc:
            section("fleet_proc", run_fleet_proc)

        def run_edit():
            # the guided-editing leg (ddim_cold_tpu/workloads): every task
            # is a SamplerConfig variant through the SAME engine, so one
            # warmup covers all four (task, bucket) program families plus
            # the preview-enabled variant. Each task then drains its own
            # mixed stream (per-task img/s — the padding/coalescing story
            # per workload), and a preview drain records
            # latency-to-first-frame: how long before the user sees the
            # first streamed x̂0 frame, against total completion. The
            # compile counter MUST stay frozen across all of it — edits
            # and previews reuse warmed programs — and the leg raises if
            # that contract breaks.
            from ddim_cold_tpu import serve, workloads

            buckets = (2, 4) if args.smoke else (8, 32)
            k_serve = 400 if args.smoke else 20
            t_edit = 1200 if args.smoke else 1800
            sr_level, pv_every = 3, 2
            bmax = max(buckets)
            H, W = model.img_size
            cfgs = {c.task: c for c in workloads.default_edit_configs(
                k=k_serve, t_start=t_edit, sr_level=sr_level)}
            pv_cfg = serve.SamplerConfig(task="draft", k=k_serve,
                                         t_start=t_edit,
                                         preview_every=pv_every)
            engine = serve.Engine(model, state.params, buckets=buckets)
            mark(f"edit warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, list(cfgs.values()) + [pv_cfg])
            r9 = np.random.RandomState(9)
            imgs = np.clip(r9.randn(bmax, H, W, model.in_chans),
                           -1.0, 1.0).astype(np.float32)
            m = np.zeros((H, W), np.float32)
            m[: H // 2] = 1.0  # top half known, bottom half synthesized
            low = imgs[:, ::2 ** sr_level, ::2 ** sr_level]  # the cold
            # operator itself — nearest-downsample at sr_level
            # one full bucket + a coalesced pair summing to a bucket: the
            # per-task number includes the packing machinery, zero pad rows
            sizes = [bmax, bmax // 2, bmax // 2]

            def submit_task(task, cfg, i, n_req):
                if task == "inpaint":
                    return engine.submit(seed=700 + i, x_init=imgs[:n_req],
                                         mask=m, config=cfg)
                if task == "superres":
                    return engine.submit(
                        x_init=workloads.superres_init(low[:n_req], H),
                        config=cfg)
                if task == "draft":
                    return engine.submit(seed=700 + i, x_init=imgs[:n_req],
                                         config=cfg)
                # interp: x_init is the endpoint PAIR, n the path length
                return engine.submit(seed=700 + i, n=n_req,
                                     x_init=imgs[:2], config=cfg)

            per_task = {}
            compiles = 0
            for task, cfg in cfgs.items():
                best = None
                for rep in range(2):  # keep the faster drain (time_ddim's rule)
                    mark(f"edit drain {task} rep {rep}")
                    for i, n_req in enumerate(sizes):
                        submit_task(task, cfg, i, n_req)
                    r = engine.run()
                    if best is None or r["img_per_sec"] > best["img_per_sec"]:
                        best = r
                    compiles += r["compiles"]
                per_task[task] = {
                    "img_per_sec": round(best["img_per_sec"], 2),
                    "rows": best["rows"], "batches": best["batches"]}
                log(f"edit {task}: {best['img_per_sec']:.2f} img/s over "
                    f"{best['rows']} rows ({best['batches']} batches)")
            # low-res consistency: one more superres drain whose output,
            # projected onto its anchors (workloads.superres_project), must
            # downsample BIT-EXACTLY back to the conditioning input — the
            # data-consistency contract eval/fid.py publishes. The RAW
            # output's anchor drift rides along as a quality metric: the
            # naive Algorithm-1 cold update predicts anchor pixels rather
            # than carrying them, so raw is never bit-exact by itself.
            from ddim_cold_tpu.eval import fid as fid_mod
            mark("edit superres consistency")
            t_sr = engine.submit(
                x_init=workloads.superres_init(low[:bmax], H),
                config=cfgs["superres"])
            r = engine.run()
            compiles += r["compiles"]
            sr_out = np.asarray(t_sr.result(timeout=600))
            raw_g = fid_mod.superres_consistency_guard(sr_out, low[:bmax])
            g = fid_mod.superres_consistency_guard(
                workloads.superres_project(sr_out, low[:bmax]), low[:bmax])
            per_task["superres"]["consistency"] = {
                "bit_exact": g["bit_exact"],
                "anchor_pixels": g["anchor_pixels"],
                "raw_max_abs_delta": raw_g["max_abs_delta"]}
            if not g["bit_exact"]:
                raise RuntimeError(
                    "superres low-res consistency broken: projected output "
                    f"downsamples {g['max_abs_delta']} away from its "
                    "conditioning input (must be bit-exact)")
            # preview drain: TWO full-bucket draft requests streaming x̂0
            # frames — previews are delivered per finished batch, so the
            # first request's frames arrive while the second batch is still
            # computing. The first callback firing stamps
            # latency-to-first-frame; against the total drain wall it is
            # the streaming story (a single-request drain would put the
            # first frame at ≈100% of its own wall by construction).
            first = {}
            mark("edit preview drain")
            t0 = time.perf_counter()
            tickets = [engine.submit(seed=900 + i, x_init=imgs[:bmax],
                                     config=pv_cfg) for i in range(2)]
            for t in tickets:
                t.add_preview_callback(
                    lambda step, frames: first.setdefault(
                        "s", time.perf_counter()))
            r = engine.run()
            total_s = time.perf_counter() - t0
            compiles += r["compiles"]
            n_frames = sum(sum(1 for _ in t.previews()) for t in tickets)
            first_s = (first["s"] - t0) if first else None
            sub["edit"] = {
                "per_task": per_task,
                "preview": {
                    "every": pv_every, "frames": n_frames,
                    "latency_to_first_frame_s":
                        None if first_s is None else round(first_s, 4),
                    "total_s": round(total_s, 4),
                    "first_frame_fraction":
                        None if first_s is None or not total_s
                        else round(first_s / total_s, 3)},
                "compiles_after_warmup": compiles,
                "warmup_new_compiles": wu["new_compiles"],
                "warmup_programs": wu["programs"],
                "stream_sizes": sizes, "buckets": list(buckets),
                "k": k_serve, "t_start": t_edit, "sr_level": sr_level,
            }
            log(f"edit preview: first frame at "
                f"{first_s if first_s is None else round(first_s, 3)}s of "
                f"{total_s:.3f}s total ({n_frames} frames); compiles after "
                f"warmup: {compiles}")
            if compiles != 0 or n_frames < 1:
                raise RuntimeError(
                    "edit-serving contract broken: "
                    f"{compiles} compiles after warmup, {n_frames} preview "
                    "frames (need 0 compiles and ≥1 frame before "
                    "completion)")

        if args.edit:
            section("edit", run_edit)

        def run_quant64():
            # w8a16 sampler legs at 64px (ops/quant.py), both dequant-matmul
            # modes against the float model's memoized timing: throughput,
            # paired same-rng pixel drift, and the param-byte saving the
            # serving engine banks on. Under --smoke the stride drops to the
            # serving leg's k=400 (5 reverse steps) so the CPU interpret-mode
            # Pallas leg stays inside the tier-1 budget.
            from ddim_cold_tpu.ops import quant as quant_mod
            from ddim_cold_tpu.ops import sampling

            k_q = 400 if args.smoke else 20
            qp = quant_mod.quantize_params(state.params)
            float_t = time_ddim(model, state.params, k_q, n_sample,
                                "64px float")
            img_f = np.asarray(sampling.ddim_sample(
                model, state.params, jax.random.PRNGKey(5), k=k_q, n=n_sample))
            modes = {}
            for mode in ("xla", "pallas"):
                qm = model.clone(quant=mode)
                sdt = time_ddim(qm, qp, k_q, n_sample, f"64px w8a16-{mode}")
                img_q = np.asarray(sampling.ddim_sample(
                    qm, qp, jax.random.PRNGKey(5), k=k_q, n=n_sample))
                modes[mode] = {
                    "img_per_sec": round(n_sample / sdt, 2),
                    "speedup_vs_float": round(float_t / sdt, 3),
                    "max_abs_pixel_delta": round(
                        float(np.max(np.abs(img_q - img_f))), 6)}
            sub["sampler_64px_w8a16"] = {
                "k": k_q, "n": n_sample,
                "float_img_per_sec": round(n_sample / float_t, 2),
                "param_bytes": quant_mod.param_bytes(state.params),
                "param_bytes_quant": quant_mod.param_bytes(qp),
                "modes": modes}

        if args.quant:
            section("quant_64px", run_quant64)

        # 200px north-star state, shared across run_northstar, the cached
        # legs and run_northstar_profile: the 200px param init is one of the
        # bench's longer silent windows and must be paid once, not re-paid
        # per section (the profile section used to re-init its own copy)
        ns_ctx = {"params": None, "flash_model": None}

        def ns_flash_model():
            if ns_ctx["flash_model"] is None:
                ns_ctx["flash_model"] = DiffusionViT(
                    dtype=jnp.bfloat16, use_flash=True,
                    flash_blocks=NS_FLASH_BLOCKS,
                    **MODEL_CONFIGS["oxford_flower_200_p4"])
            return ns_ctx["flash_model"]

        def ns_params_for(ns_model):
            if ns_ctx["params"] is None:
                mark("north-star 200px param init")
                ns_ctx["params"] = ns_model.init(
                    jax.random.PRNGKey(0),
                    jnp.zeros((1, 200, 200, 3)),
                    jnp.zeros((1,), jnp.int32))["params"]
            return ns_ctx["params"]

        def run_northstar():
            # the acceptance metric: 200px DDIM k=20 img/s/chip (BASELINE.json)
            n, k = 16, 20
            # attention paths: dense einsum (the reference semantics) and the
            # Pallas fused kernel; --xla-blockwise adds the pure-XLA blockwise
            # form. A leg that fails fails the section: on the chip a kernel
            # the compiler refuses is the finding, not a row to skip.
            impls = [(False, "_dense"), (True, "_flash")]
            if args.xla_blockwise:
                impls.append(("xla", "_xla"))
            for impl, suffix in impls:
                ns_model = (ns_flash_model() if impl is True else DiffusionViT(
                    dtype=jnp.bfloat16, use_flash=impl, flash_blocks=None,
                    **MODEL_CONFIGS["oxford_flower_200_p4"]))
                ns_params = ns_params_for(ns_model)
                sdt = time_ddim(ns_model, ns_params, k, n,
                                f"north-star 200px {suffix[1:]}")
                sub["sampler_throughput_200px_k20" + suffix] = {
                    "value": round(n / sdt, 2), "unit": "img/s/chip", "n": n, "k": k}
            # headline north-star alias = the fastest path that ran
            sub["sampler_throughput_200px_k20"] = {
                "value": max(sub["sampler_throughput_200px_k20" + s]["value"]
                             for _, s in impls),
                "unit": "img/s/chip", "n": n, "k": k}
            # best-achievable leg (separate submetric — the headline above stays
            # pinned to the n=16 definition BASELINE.json publishes): flash never
            # materializes the N² attention matrix (dense at N=2501 burns
            # ~100 MB/img/layer on the f32 softmax, which is what pins the paired
            # comparison at n=16), so the flash path can batch 4× higher — the
            # throughput a user actually gets.
            n_big = 64
            sdt = time_ddim(ns_flash_model(), ns_params, k, n_big,
                            f"north-star 200px flash n={n_big}")
            sub["sampler_throughput_200px_k20_flash_n64"] = {
                "value": round(n_big / sdt, 2), "unit": "img/s/chip",
                "n": n_big, "k": k}
            if args.flash_block_sweep:
                # kernel tuning: same params, alternative Pallas block
                # sizes. 4096 clamps to the padded N inside the kernel —
                # fully VMEM-resident K/V, a single chunk, no online-softmax
                # loop. The NS_FLASH_BLOCKS headline above stays the
                # comparable record; its config is also a sweep row, which
                # costs nothing extra — time_ddim memoizes by model value,
                # so that row reuses the headline timing.
                sweep = {}
                for bq, bkv in FLASH_BLOCK_SWEEP:
                    bm = DiffusionViT(dtype=jnp.bfloat16, use_flash=True,
                                      flash_blocks=(bq, bkv),
                                      **MODEL_CONFIGS["oxford_flower_200_p4"])
                    sdt = time_ddim(bm, ns_params, k, n,
                                    f"north-star flash {bq}x{bkv}")
                    sweep[f"{bq}x{bkv}"] = round(n / sdt, 2)
                sub["northstar_flash_block_sweep"] = sweep

        if not args.skip_northstar:
            section("northstar", run_northstar)

        def run_northstar_cached():
            # the tentpole leg: step-cached 200px sampling (ops/step_cache.py).
            # "full" reuse at interval=2 skips the whole transformer trunk on
            # every odd step (the ≥1.5× headline config); "delta" is the
            # Δ-DiT-style half-trunk variant recorded alongside for the
            # quality-first trade-off; "adaptive" is the error-gated delta
            # schedule (refresh only when on-device drift crosses τ) and
            # "token" the JiT-style top-k spatial recompute — the two
            # adaptive-cache rows, both still one compiled scan. Every row
            # carries a paired same-rng max-abs-pixel-delta guard against
            # the exact flash sampler. The cached fixed-interval speedup
            # target is ≥1.5× vs exact (≥3× vs the uncached dense path);
            # adaptive must hold ≥1.5× over the fixed interval=2 delta row.
            from ddim_cold_tpu.ops import sampling

            n, k = 16, 20
            # adaptive rides a SPARSER static schedule (interval=4): the
            # drift gate can only promote reuse→refresh, so at interval=2 it
            # could never beat the fixed row it gates — the ≥1.5×-vs-fixed-2
            # target comes from reusing 3 of 4 steps until drift says stop.
            # token top-k = 626 of 2501 (p4): recompute the liveliest
            # quarter of the tokens (CLS always live) on reuse steps.
            rows = (
                ("full", "sampler_throughput_200px_k20_cached", {}),
                ("delta", "sampler_throughput_200px_k20_cached_delta", {}),
                ("adaptive", "sampler_throughput_200px_k20_cached_adaptive",
                 {"cache_interval": 4, "cache_threshold": 0.05}),
                ("token", "sampler_throughput_200px_k20_cached_token",
                 {"cache_tokens": 626}),
            )
            cm = ns_flash_model()
            cp = ns_params_for(cm)
            # memoized — free when the northstar section already ran
            exact_t = time_ddim(cm, cp, k, n, "north-star 200px flash")
            img_exact = np.asarray(sampling.ddim_sample(
                cm, cp, jax.random.PRNGKey(5), k=k, n=n))
            for mode, name, extra in rows:
                ck = {"cache_interval": 2, "cache_mode": mode, **extra}
                sdt = time_ddim(cm, cp, k, n, f"north-star cached {mode}",
                                **ck)
                img_c = np.asarray(sampling.ddim_sample(
                    cm, cp, jax.random.PRNGKey(5), k=k, n=n, **ck))
                sub[name] = {
                    "value": round(n / sdt, 2), "unit": "img/s/chip",
                    "n": n, "k": k, **ck,
                    "speedup_vs_exact_flash": round(exact_t / sdt, 3),
                    "max_abs_pixel_delta": round(
                        float(np.max(np.abs(img_c - img_exact))), 6)}
            fixed = sub["sampler_throughput_200px_k20_cached_delta"]
            adapt = sub["sampler_throughput_200px_k20_cached_adaptive"]
            adapt["speedup_vs_fixed_delta"] = round(
                adapt["value"] / fixed["value"], 3)

        if not args.skip_northstar:
            section("northstar_cached", run_northstar_cached)

        def run_northstar_quant():
            # the w8a16 tentpole leg, armed for chip: the flash sampler over
            # int8 trunk weights at the north-star shape. Headline = the
            # faster dequant-matmul mode (the fused Pallas kernel vs the
            # XLA epilogue form — which wins on a real MXU is exactly what
            # this leg exists to measure); speedup is against the bf16 flash
            # leg's memoized timing, drift is the paired same-rng pixel
            # delta, and the param-byte line is the ≈4× H2D saving.
            from ddim_cold_tpu.ops import quant as quant_mod
            from ddim_cold_tpu.ops import sampling

            n, k = 16, 20
            cm = ns_flash_model()
            cp = ns_params_for(cm)
            qp = quant_mod.quantize_params(cp)
            exact_t = time_ddim(cm, cp, k, n, "north-star 200px flash")
            img_exact = np.asarray(sampling.ddim_sample(
                cm, cp, jax.random.PRNGKey(5), k=k, n=n))
            modes = {}
            for mode in ("pallas", "xla"):
                qm = cm.clone(quant=mode)
                sdt = time_ddim(qm, qp, k, n, f"north-star w8a16-{mode}")
                img_q = np.asarray(sampling.ddim_sample(
                    qm, qp, jax.random.PRNGKey(5), k=k, n=n))
                modes[mode] = {
                    "img_per_sec": round(n / sdt, 2),
                    "speedup_vs_bf16_flash": round(exact_t / sdt, 3),
                    "max_abs_pixel_delta": round(
                        float(np.max(np.abs(img_q - img_exact))), 6)}
            headline = max(modes.values(), key=lambda m: m["img_per_sec"])
            f = flops_util.vit_trunk_gemm_fraction(
                img_size=(200, 200), patch_size=4,
                **{kk: MODEL_CONFIGS["oxford_flower_200_p4"][kk]
                   for kk in ("embed_dim", "depth", "num_heads")})
            sub["sampler_throughput_200px_k20_flash_w8a16"] = {
                "value": headline["img_per_sec"], "unit": "img/s/chip",
                "n": n, "k": k,
                "speedup_vs_bf16_flash": headline["speedup_vs_bf16_flash"],
                "max_abs_pixel_delta": headline["max_abs_pixel_delta"],
                "param_bytes": quant_mod.param_bytes(cp),
                "param_bytes_quant": quant_mod.param_bytes(qp),
                "trunk_gemm_fraction": round(f, 4),
                "mixed_peak_tflops": flops_util.mixed_peak_tflops(chip, f),
                "modes": modes}

        if args.quant and not args.skip_northstar:
            section("northstar_quant", run_northstar_quant)

        def run_cached_quality():
            # distributional guard for the step cache at 64px (chip-cheap;
            # the 200px legs above carry the pixel-delta guard): Fréchet
            # distance between exact and cached sample streams from the SAME
            # rng under one extractor — 0 when the cache is harmless, and the
            # acceptance bound ("FID shift ≤ 0.5") reads directly off it
            from ddim_cold_tpu.eval import fid as fid_mod

            n_q = 32 if args.smoke else 256
            sub["cached_quality_64px"] = fid_mod.cached_sampler_guard(
                model, state.params, rng=jax.random.PRNGKey(17),
                n_samples=n_q, sample_batch=min(n_q, 64), k=20,
                cache_interval=2, cache_mode="full")
            log(f"cached quality 64px: {sub['cached_quality_64px']}")

        if not args.skip_sampler:
            section("cached_quality", run_cached_quality)

        def run_quant_quality():
            # paired Fréchet guard for the w8a16 trunk (same contract as the
            # step-cache guard above), plus the COMPOSED quant × step-cache
            # row the PERF.md composition table reports
            from ddim_cold_tpu.eval import fid as fid_mod

            n_q = 32 if args.smoke else 256
            k_q = 400 if args.smoke else 20
            sub["quant_quality_64px"] = fid_mod.quantized_sampler_guard(
                model, state.params, rng=jax.random.PRNGKey(19),
                n_samples=n_q, sample_batch=min(n_q, 64), k=k_q)
            log(f"quant quality 64px: {sub['quant_quality_64px']}")
            sub["quant_cached_quality_64px"] = fid_mod.quantized_sampler_guard(
                model, state.params, rng=jax.random.PRNGKey(19),
                n_samples=n_q, sample_batch=min(n_q, 64), k=k_q,
                cache_interval=2, cache_mode="full")
            log(f"quant×cache quality 64px: {sub['quant_cached_quality_64px']}")

        if args.quant and not args.skip_sampler:
            section("quant_quality", run_quant_quality)

        def run_northstar_profile():
            # one traced tuned-blocks flash sampling run (n=16, k=20): the
            # timeline that says where the remaining sampler time goes. The
            # model/params/compile are shared with the northstar sections
            # via ns_ctx — no second 200px param init; the trace adds one
            # extra timed-path execution of chip time.
            from ddim_cold_tpu.ops import sampling

            prof_model = ns_flash_model()
            prof_params = ns_params_for(prof_model)
            # warm the compile outside the trace window
            np.asarray(sampling.ddim_sample(
                prof_model, prof_params, jax.random.PRNGKey(2), k=20, n=16))
            out_dir = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "results", "profile_northstar")
            mark("north-star profile trace", budget_s=600)
            with jax.profiler.trace(out_dir):
                np.asarray(sampling.ddim_sample(
                    prof_model, prof_params, jax.random.PRNGKey(3), k=20, n=16))
            sub["northstar_profile"] = {"dir": "results/profile_northstar"}

        if args.profile_northstar and not args.skip_northstar:
            section("northstar_profile", run_northstar_profile)

        def run_attrib():
            # the attribution leg (obs/attrib.py): one warmed serving drain
            # captured under the profiler, device-busy time attributed to
            # the planted named scopes and joined with utils/flops.py →
            # per-scope MFU, roofline class, fusion candidates. Contracts
            # that hold EVERYWHERE: the captured drain compiles nothing
            # after warmup and its images are bitwise the uncaptured
            # drain's (attribution off = untouched numerics). The ≥90%
            # coverage floor is asserted on the capture when it carries
            # device lanes (real chip); a jax CPU trace records host
            # threads only, so there the floor runs over the checked-in
            # synthetic fixture — loudly labeled, the run_parallel rule
            # ("on CPU the structural contracts ARE the leg").
            import math

            from ddim_cold_tpu import serve
            from ddim_cold_tpu.obs import attrib, trend
            from ddim_cold_tpu.utils import profiling

            os.makedirs("results", exist_ok=True)
            if args.smoke or args.skip_northstar:
                a_model, a_params = model, state.params
                geom = dict(img_size=(64, 64), patch_size=8, mlp_ratio=1.0,
                            **{kk: MODEL_CONFIGS["vit_tiny"][kk]
                               for kk in ("embed_dim", "depth", "num_heads")})
                buckets, k_att, flash = (2, 4), 400, False
            else:
                # the shared 200px north-star state (ns_ctx): the attribution
                # evidence must be OF the north-star path, and the param init
                # is paid once across sections
                a_model = ns_flash_model()
                a_params = ns_params_for(a_model)
                geom = dict(img_size=(200, 200), patch_size=4, mlp_ratio=1.0,
                            **{kk: MODEL_CONFIGS["oxford_flower_200_p4"][kk]
                               for kk in ("embed_dim", "depth", "num_heads")})
                buckets, k_att, flash = (8, 16), 20, True
            bmax = max(buckets)
            cfg = serve.SamplerConfig(k=k_att)
            engine = serve.Engine(a_model, a_params, buckets=buckets)
            mark(f"attrib warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, [cfg])
            sizes = [bmax, bmax // 2, bmax - bmax // 2]

            def drain(seed0):
                tickets = [engine.submit(seed=seed0 + i, n=nr, config=cfg)
                           for i, nr in enumerate(sizes)]
                report = engine.run()
                return report, [np.asarray(t.result(timeout=600))
                                for t in tickets]

            mark("attrib uncaptured drain")
            r_off, outs_off = drain(700)
            trace_dir = "results/attrib_profile"
            shutil.rmtree(trace_dir, ignore_errors=True)
            mark("attrib profiler capture", budget_s=2 * stall_s)
            with profiling.trace(trace_dir, perfetto=True):
                r_on, outs_on = drain(700)  # same seeds: bitwise oracle
            for a, b in zip(outs_off, outs_on):
                if not np.array_equal(a, b):
                    raise RuntimeError(
                        "profiler capture changed the sampled images — "
                        "attribution must be bitwise-off when disabled")
            compiles = r_off["compiles"] + r_on["compiles"]
            if compiles:
                raise RuntimeError(
                    f"attrib leg compiled {compiles} program(s) after warmup")

            n_img = sum(sizes)
            calls = n_img * math.ceil(1999 / k_att)  # ViT.py ⌈1999/k⌉ steps
            per_img = flops_util.vit_scope_costs(flash=flash, quant=False,
                                                 **geom)
            costs = {s: {"flops": c["flops"] * calls,
                         "bytes": c["bytes"] * calls}
                     for s, c in per_img.items()}
            trace_source = trace_dir
            try:
                rep = attrib.attribute(attrib.load_trace(trace_dir),
                                       device_kind=chip, scope_costs=costs)
            except attrib.AttribError as e:
                rep = attrib.demo_report()  # old jax: no trace-event dump
                trace_source = f"synthetic fixture — {e}"
            if not rep["device_lanes"]:
                rep = attrib.demo_report()
                trace_source = ("synthetic fixture — the capture at "
                                f"{trace_dir} has no device lanes "
                                "(cpu backend records host threads only)")
            if rep["coverage"] is None or rep["coverage"] < attrib.COVERAGE_FLOOR:
                raise RuntimeError(
                    f"attribution coverage {rep['coverage']} below the "
                    f"{attrib.COVERAGE_FLOOR:.0%} floor — device time is "
                    "escaping the registered scopes")
            tr = trend.gate(os.path.dirname(os.path.abspath(__file__)))
            top = [
                {"scope": name, "self_s": node["self_s"],
                 "share_of_busy": node["share_of_busy"], "mfu": node["mfu"],
                 "achieved_tflops": node["achieved_tflops"],
                 "roofline": node["roofline"]}
                for name, node in attrib.ranked_scopes(rep)[:5]]
            sub["attrib"] = {
                "trace_source": trace_source,
                "device_lanes": rep["device_lanes"],
                "coverage": rep["coverage"],
                "device_busy_s": rep["device_busy_s"],
                "idle_s": rep["idle_s"],
                "busy_fraction": rep["busy_fraction"],
                "ridge_flops_per_byte": rep["ridge_flops_per_byte"],
                "top_scopes": top,
                "fusion_candidates": rep["fusion_candidates"][:3],
                "bitwise_off": True,
                "compiles_after_warmup": compiles,
                "warmup_new_compiles": wu["new_compiles"],
                "buckets": list(buckets), "k": k_att,
                "trend": {"exit_code": tr["exit_code"],
                          "statuses": tr["statuses"],
                          "bench_points": tr["bench_points"],
                          "multichip_points": tr["multichip_points"]},
            }
            hot = top[0] if top else {}
            log(f"attrib: coverage {100 * rep['coverage']:.1f}% of "
                f"{rep['device_busy_s']:.4f}s device-busy "
                f"({rep['device_lanes']} lane(s), source: {trace_source}); "
                f"hottest {hot.get('scope')} share={hot.get('share_of_busy')}"
                f" mfu={hot.get('mfu')} [{hot.get('roofline')}]; "
                f"{len(rep['fusion_candidates'])} fusion candidates; trend "
                f"gate exit {tr['exit_code']} {tr['statuses']}; compiles "
                f"after warmup: {compiles}")

        if args.attrib:
            section("attrib", run_attrib)

        def run_fusion():
            # the fused-trunk leg (PERF.md "Fused kernels"): one engine,
            # one param tree, two compiled programs — the unfused w8a16
            # sampler (quant="pallas": dequant matmuls + flash attention +
            # XLA Mlp) and the fused one (fused=True: qkv-dequant/flash/
            # proj megakernel + fused bias-GELU Mlp). Contracts that hold
            # EVERYWHERE: both drains compile nothing after warmup and the
            # fused images match the unfused ones — bitwise at f32 (the
            # fused kernels relocate the dequant/bias epilogues without
            # moving a single ulp; the fma contraction points and kv-chunk
            # boundaries are pinned identical), allclose at bf16 (the MXU
            # accumulates the two compositions in different block orders).
            # Speedup/per-step/MFU are the chip numbers; on CPU the Pallas
            # kernels run in interpret mode, so timing is structural only
            # and MFU is None (no peak table) — the run_parallel rule: on
            # CPU the structural contracts ARE the leg.
            import math
            import time as time_mod

            from ddim_cold_tpu import serve

            if args.smoke or args.skip_northstar:
                # f32 activations: the CPU smoke asserts the BITWISE half
                # of the oracle, not just allclose (the train model is bf16)
                f_model = model.clone(dtype=jnp.float32, use_flash=True,
                                      flash_blocks=NS_FLASH_BLOCKS)
                f_params = state.params
                geom = dict(img_size=(64, 64), patch_size=8, mlp_ratio=1.0,
                            **{kk: MODEL_CONFIGS["vit_tiny"][kk]
                               for kk in ("embed_dim", "depth", "num_heads")})
                buckets, k_f = (2, 4), 400
            else:
                f_model = ns_flash_model()
                f_params = ns_params_for(f_model)
                geom = dict(img_size=(200, 200), patch_size=4, mlp_ratio=1.0,
                            **{kk: MODEL_CONFIGS["oxford_flower_200_p4"][kk]
                               for kk in ("embed_dim", "depth", "num_heads")})
                buckets, k_f = (8, 16), 20
            bmax = max(buckets)
            # both configs share f_model.flash_blocks (the explicit blocks
            # pin the same kv-chunk boundaries into both programs — that
            # identity is what makes the f32 oracle bitwise, not allclose)
            cfgs = {"unfused": serve.SamplerConfig(k=k_f, quant="pallas"),
                    "fused": serve.SamplerConfig(k=k_f, quant="pallas",
                                                 fused=True)}
            engine = serve.Engine(f_model, f_params, buckets=buckets)
            mark(f"fusion warmup buckets={buckets}", budget_s=2 * stall_s)
            wu = serve.warmup(engine, list(cfgs.values()))
            sizes = [bmax, bmax // 2]  # exercise two buckets per program
            steps = math.ceil(1999 / k_f)  # DDIM scan length per request
            per_img_flops = flops_util.vit_scope_costs(
                **geom)["sampler/model"]["flops"]

            legs, outs = {}, {}
            for name, cfg in cfgs.items():
                mark(f"fusion drain {name}")
                t0 = time_mod.perf_counter()
                tickets = [engine.submit(seed=900 + i, n=nr, config=cfg)
                           for i, nr in enumerate(sizes)]
                report = engine.run()
                outs[name] = [np.asarray(t.result(timeout=600))
                              for t in tickets]
                dt = time_mod.perf_counter() - t0
                if report["compiles"]:
                    raise RuntimeError(
                        f"fusion {name} drain compiled {report['compiles']} "
                        "program(s) after warmup")
                n_img = sum(sizes)
                legs[name] = {
                    "seconds": round(dt, 4),
                    "img_per_sec": round(n_img / dt, 3),
                    "per_step_ms": round(1e3 * dt / (len(sizes) * steps), 3),
                    "mfu": flops_util.mfu(n_img * steps * per_img_flops,
                                          dt, chip)}
            exact = f_model.dtype == jnp.float32
            maxd = max(float(np.max(np.abs(
                a.astype(np.float32) - b.astype(np.float32))))
                for a, b in zip(outs["unfused"], outs["fused"]))
            if exact:
                ok = all(np.array_equal(a, b) for a, b in
                         zip(outs["unfused"], outs["fused"]))
                if not ok:
                    raise RuntimeError(
                        "fused sampler diverged from unfused at f32 — the "
                        f"fused kernels must be bitwise (max |Δ| {maxd})")
            elif maxd > 0.1:
                raise RuntimeError(
                    f"fused sampler pixel delta {maxd} exceeds the bf16 "
                    "allclose bound 0.1 vs the unfused program")
            sub["fusion"] = {
                "unfused": legs["unfused"], "fused": legs["fused"],
                "speedup": round(legs["unfused"]["seconds"]
                                 / legs["fused"]["seconds"], 3),
                "oracle": "bitwise" if exact else "allclose",
                "max_abs_pixel_delta": maxd,
                "compiles_after_warmup": 0,
                "warmup_new_compiles": wu["new_compiles"],
                "buckets": list(buckets), "k": k_f, "steps": steps,
            }
            log(f"fusion: {legs['unfused']['seconds']}s unfused → "
                f"{legs['fused']['seconds']}s fused "
                f"({sub['fusion']['speedup']}×), per-step "
                f"{legs['fused']['per_step_ms']}ms, mfu "
                f"{legs['fused']['mfu']}, oracle {sub['fusion']['oracle']} "
                f"(max |Δ| {maxd}), compiles after warmup 0")

        if args.fusion:
            section("fusion", run_fusion)

        # ------------------------------------------------- e2e with the data path
        if not args.skip_e2e:
            section("e2e", lambda: sub.update(_bench_e2e(args, model, state, log)))

        print(json.dumps(record))
    except Exception as e:  # noqa: BLE001 — emit-then-reraise, not swallow
        # a fatal error outside any section (e.g. headline OOM) must not cost
        # the whole record: the metadata + whatever sections finished are out
        # before the nonzero exit, same contract as the stall watchdog
        sub["fatal_error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(record))
        sys.stdout.flush()
        raise
    finally:
        wd.done()


def _bench_e2e(args, model, state, log):
    """Steps/s with ShardedLoader + the C++ pipeline feeding from disk —
    the number comparable to the reference's DataLoader-inclusive 702 img/s.
    Uses ./OxfordFlowers/train when present (the committed make_dataset
    recipe), else generates a temp folder from the same recipe."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.data import ColdDownSampleDataset, ShardedLoader

    n_imgs = 256 if args.smoke else 4096
    here = os.path.dirname(os.path.abspath(__file__))
    root, tmp = os.path.join(here, "OxfordFlowers", "train"), None
    if not os.path.isdir(root):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "make_dataset", os.path.join(here, "scripts", "make_dataset.py"))
        mk = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mk)
        tmp = tempfile.mkdtemp(prefix="bench_e2e_")
        _E2E_TMP["path"] = tmp
        log(f"e2e: generating {n_imgs}-image temp dataset")  # liveness beacon
        mk.write_split(tmp, "train", n_imgs, 64, 20220822)
        root = os.path.join(tmp, "train")
    try:
        from ddim_cold_tpu.data.loader import device_prefetch, group_batches
        from ddim_cold_tpu.ops import degrade
        from ddim_cold_tpu.train.step import make_train_step

        import numpy as _np

        out = {}
        # link diagnostic first: raw H2D bandwidth on a 4 MB payload. r03's
        # e2e gap (cold 613 img/s vs 4,089 synthetic at the same batch) is
        # the NETWORK-ATTACHED device link, not loader or compute — the
        # loader alone moves >10k img/s cold on this host. Recording the
        # link speed makes the e2e rows interpretable on any topology.
        payload = _np.zeros((4 << 20,), _np.uint8)
        bw = 0.0
        for _ in range(2):  # keep the faster rep (TCP slow-start warms)
            t0 = time.time()
            dev = jnp.asarray(payload)
            float(dev[0])  # real sync — block_until_ready can return early
            bw = max(bw, len(payload) / (1 << 20) / (time.time() - t0))
        out["h2d_bandwidth_mib_s"] = round(bw, 1)
        log(f"e2e: H2D link ≈ {bw:.0f} MiB/s")

        ds = ColdDownSampleDataset(root, imgSize=(64, 64), target_mode="chain")
        # the trainer's shipped data path: raw (base, t) batches, corruption
        # in-jit on device, H2D overlapped with compute (train/trainer.py).
        # On a network-attached device, group steps_per_dispatch batches into
        # one transfer + one dispatch (lax.scan over the group): n× fewer
        # round trips and n× larger payloads — the two levers a thin host
        # link responds to. Local backends keep spd=1 (nothing to amortize);
        # the env override exists so the grouped loop is CPU-testable before
        # it first runs on chip (tests/test_bench.py).
        spd = (int(os.environ.get("DDIM_COLD_E2E_SPD", "0"))
               or (1 if jax.default_backend() == "cpu" else 8))
        loader = ShardedLoader(ds, args.batch, shuffle=True, seed=42,
                               drop_last=True, raw=True)
        raw_step = make_train_step(
            model,
            prepare=degrade.make_cold_prepare(size=64, max_step=ds.max_step,
                                              chain=True),
            steps_per_dispatch=spd,
        )
        place = lambda b: jax.tree.map(jnp.asarray, b)  # noqa: E731
        # compile outside the timed loops with a synthetic batch matching the
        # dataset's ACTUAL ship dtype — uint8 when the loader ships raw bytes
        # (_uniform_u8), float32 otherwise. A float32 warmup against a uint8
        # loader would leave the first timed "cold" step paying a full jit
        # retrace under the new dtype signature, exactly what this warmup
        # exists to exclude (ADVICE r2 medium).
        _r = _np.random.RandomState(7)
        log("e2e: warmup compile")  # liveness beacon before the silent compile
        shape = (spd, args.batch) if spd > 1 else (args.batch,)
        if getattr(ds, "_uniform_u8", False):
            bases = _np.asarray(
                _r.randint(0, 256, size=shape + (64, 64, 3)), _np.uint8)
        else:
            bases = _np.asarray(_r.randn(*shape, 64, 64, 3), _np.float32)
        state, _, _ = raw_step(
            state,
            (jnp.asarray(bases),
             jnp.asarray(_r.randint(1, 7, size=shape), jnp.int32)),
            jax.random.PRNGKey(0), jnp.float32(5.0))
        for label in ("cold", "warm"):
            log(f"e2e: {label} epoch start")  # liveness beacon
            loader.set_epoch(0)
            ema = jnp.float32(5.0)
            t0, nb = time.time(), 0
            for b in device_prefetch(group_batches(loader, spd), place,
                                     depth=4):
                state, _, ema = raw_step(state, b, jax.random.PRNGKey(1), ema)
                nb += spd
                if nb * args.batch >= n_imgs:
                    break
            float(ema)
            dt = time.time() - t0
            ips = nb * args.batch / dt
            log(f"e2e {label} epoch: {nb} steps in {dt:.2f}s → {ips:.0f} img/s "
                "(disk → decode → base → device → degrade-in-jit → step, "
                f"{spd} steps/dispatch)")
            out[f"e2e_train_throughput_{label}"] = {
                "value": round(ips, 1), "unit": "img/s",
                "steps_per_dispatch": spd,
                "vs_baseline": round(ips / BASELINE_IMG_PER_SEC, 3)}
        return out
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
            _E2E_TMP["path"] = None


if __name__ == "__main__":
    main()
