#!/usr/bin/env python
"""Render bench records (the JSON line ``bench.py`` prints, saved to a file)
into PERF.md-style markdown tables — so a write-up is a paste, not a
transcription.

Usage: python scripts/perf_tables.py record.json [...]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddim_cold_tpu.utils.record import last_json_record  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fmt_pct(v):
    return "-" if v is None else f"{100 * v:.1f}%"


def render(path: str) -> str:
    rec = last_json_record(path)
    if rec is None:
        return f"<!-- {path}: no parseable record -->"
    sub = rec.get("submetrics", {})
    lines = [f"### {os.path.relpath(path, REPO)}", ""]
    revs = " · ".join(f"{lbl} `{sub[key]}`" for lbl, key in
                      (("kernel", "kernel_rev"), ("quant", "quant_rev"))
                      if sub.get(key))
    lines += [f"chip: **{rec.get('chip')}** · headline "
              f"**{rec.get('value')} img/s** @ b32 "
              f"({rec.get('vs_baseline')}× the 702 img/s 3090 baseline) · "
              f"{rec.get('ms_per_step')} ms/step · MFU {rec.get('mfu')}"
              + (f" · {revs}" if revs else ""), ""]
    rm = rec.get("run_meta")
    if rm:
        lines += [f"provenance: sha `{rm.get('git_sha')}` · jax "
                  f"{rm.get('jax')} / jaxlib {rm.get('jaxlib')} · ts "
                  f"{rm.get('timestamp')}", ""]

    rows = sub.get("batch_scaling")
    if rows:
        lines += ["| batch | ms/step | img/s | MFU |", "|---|---|---|---|"]
        for r in rows:
            mfu = r.get("mfu")
            lines.append(f"| {r['batch']} | {r['ms_per_step']} | "
                         f"{r['img_per_sec']} | "
                         f"{'' if mfu is None else f'{100 * mfu:.1f}%'} |")
        lines.append("")

    for name in ("scan_blocks", "remat"):
        r = sub.get(name)
        if r:
            plain = r.get("plain_ms_per_step",
                          r.get("unrolled_ms_per_step"))  # pre-r04 key name
            lines.append(
                f"* **{name}** b{r['batch']}: {r['ms_per_step']} ms/step "
                f"(compile {r['compile_s']}s) vs plain {plain} ms/step"
                + (f", MFU {100 * r['mfu']:.1f}%" if r.get("mfu") else ""))

    ns = {s: sub.get("sampler_throughput_200px_k20" + s)
          for s in ("", "_dense", "_flash", "_xla", "_flash_n64",
                    "_cached", "_cached_delta", "_cached_adaptive",
                    "_cached_token", "_flash_w8a16")}
    if any(ns.values()):
        lines.append("")
        lines.append("**200px k=20 north-star (img/s/chip):** "
                     + " · ".join(f"{(s or '_best')[1:]}={v['value']}"
                                  for s, v in ns.items() if v))
    ad = ns.get("_cached_adaptive")
    if ad:
        lines.append(
            f"adaptive cache leg: τ={ad.get('cache_threshold')} @ "
            f"interval={ad.get('cache_interval')} · "
            f"{ad.get('speedup_vs_exact_flash')}× vs exact flash"
            + (f" · {ad['speedup_vs_fixed_delta']}× vs fixed delta i2"
               if ad.get("speedup_vs_fixed_delta") is not None else "")
            + f" · pixel drift {ad.get('max_abs_pixel_delta')}")
    tk = ns.get("_cached_token")
    if tk:
        lines.append(
            f"token cache leg: top-k={tk.get('cache_tokens')} @ "
            f"interval={tk.get('cache_interval')} · "
            f"{tk.get('speedup_vs_exact_flash')}× vs exact flash · "
            f"pixel drift {tk.get('max_abs_pixel_delta')}")
    w8 = ns.get("_flash_w8a16")
    if w8:
        lines.append(
            f"w8a16 flash leg: {w8.get('speedup_vs_bf16_flash')}× vs bf16 "
            f"flash · pixel drift {w8.get('max_abs_pixel_delta')} · param "
            f"bytes {w8.get('param_bytes')} → {w8.get('param_bytes_quant')}"
            + (f" · trunk GEMM fraction {w8['trunk_gemm_fraction']}"
               if w8.get("trunk_gemm_fraction") is not None else ""))
    sweep = sub.get("northstar_flash_block_sweep")
    if sweep:
        lines.append("flash block sweep: "
                     + " · ".join(f"{k}→{v}" for k, v in sweep.items()))
    for key in ("northstar_error", "northstar_flash_error",
                "northstar_dense_error", "northstar_xla_error",
                "northstar_n64_error"):
        if key in sub:
            lines.append(f"`{key}`: {sub[key]}")

    ks = sub.get("ksweep_64px_img_per_sec")
    if ks:
        lines.append("")
        lines.append("**k-sweep 64px (img/s):** "
                     + " · ".join(f"k={k}: {v}" for k, v in ks.items()))
    ksf = sub.get("ksweep_64px_fewstep_img_per_sec")
    if ksf:
        lines.append("few-step 64px (img/s, steps = total model "
                     "applications): "
                     + " · ".join(f"s={k}: {v}" for k, v in ksf.items()))

    q64 = sub.get("sampler_64px_w8a16")
    if q64:
        lines.append("")
        lines.append(
            f"**w8a16 64px (k={q64.get('k')}, n={q64.get('n')}):** "
            + " · ".join(
                f"{m}={leg['img_per_sec']} img/s "
                f"({leg['speedup_vs_float']}× float, "
                f"drift {leg['max_abs_pixel_delta']})"
                for m, leg in q64.get("modes", {}).items()
                if "img_per_sec" in leg)
            + f" · float={q64.get('float_img_per_sec')} img/s · param bytes "
              f"{q64.get('param_bytes')} → {q64.get('param_bytes_quant')}")

    srv = sub.get("serving")
    if srv:
        lines.append("")
        lines.append(
            f"**serving:** {srv.get('img_per_sec')} img/s "
            f"({srv.get('vs_oneshot')}× one-shot) · p50 "
            f"{srv.get('p50_latency_s')}s / p95 {srv.get('p95_latency_s')}s"
            + (f" / p99 {srv['p99_latency_s']}s"
               if srv.get("p99_latency_s") is not None else "")
            + (f" over {srv['requests']} requests"
               if srv.get("requests") else "")
            + f" · compiles after warmup {srv.get('compiles_after_warmup')}")
        sq = srv.get("quant")
        if sq:
            lines.append(
                f"serving w8a16: {sq.get('img_per_sec')} img/s "
                f"({sq.get('vs_float_serving')}× float serving) · param bytes "
                f"{sq.get('param_bytes')} → {sq.get('param_bytes_quant')} · "
                f"compiles after warmup {sq.get('compiles_after_warmup')}")

    fs = sub.get("fewstep")
    if fs:
        per = fs.get("per_k", {})
        base = fs.get("baseline", {})
        lines.append("")
        lines.append(
            "**few-step serving (img/s · n=1 latency):** "
            + " · ".join(f"k={k}: {leg.get('img_per_sec')} / "
                         f"{leg.get('latency_1_s')}s"
                         for k, leg in per.items())
            + f" · baseline k={base.get('k')} latency "
              f"{base.get('latency_1_s')}s (k=1 ratio "
              f"{fs.get('k1_latency_vs_baseline')}) · warmup "
              f"{fs.get('warmup_new_compiles')} compiles + "
              f"{fs.get('warmup_deduped')} deduped · compiles after warmup "
              f"{fs.get('compiles_after_warmup')}")

    ca = sub.get("cache_adaptive")
    if ca:
        lines.append("")
        lines.append(
            "**adaptive cache (one-shot img/s):** "
            + " · ".join(f"{name}={leg['img_per_sec']} "
                         f"({leg['vs_fixed_i2']}× fixed-i2)"
                         for name, leg in ca.items()
                         if isinstance(leg, dict) and "img_per_sec" in leg)
            + f" · τ→0 bitwise {ca.get('threshold0_bitwise_exact')}")
        sv = ca.get("served", {})
        if sv:
            lines.append(
                "adaptive cache served: "
                + " · ".join(f"{name}={leg['img_per_sec']} img/s"
                             for name, leg in sv.items()
                             if isinstance(leg, dict))
                + f" · warmup compiles {sv.get('warmup_new_compiles')} · "
                  "compiles after warmup "
                + "/".join(str(leg.get("compiles_after_warmup"))
                           for leg in sv.values() if isinstance(leg, dict)))

    fl = sub.get("faults")
    if fl:
        lines.append("")
        lines.append(
            f"**robustness:** disarmed {fl.get('clean_img_per_sec')} img/s"
            + (f" ({fl['disarmed_vs_serving']}× plain serving)"
               if fl.get("disarmed_vs_serving") is not None else "")
            + f" · chaos {fl.get('chaos_img_per_sec')} img/s "
              f"({fl.get('degraded_ratio')}× disarmed) under "
              f"{fl.get('injected')} injections {fl.get('by_site')} · "
              f"retries {fl.get('retries')} · quarantined "
              f"{fl.get('quarantined')} · compiles after warmup "
              f"{fl.get('compiles_after_warmup')}")

    ft = sub.get("fleet")
    if ft:
        lines.append("")
        lines.append(
            f"**fleet:** {ft.get('replicas')} replicas · clean "
            f"{ft.get('clean_img_per_sec')} img/s · chaos "
            f"{ft.get('chaos_img_per_sec')} img/s "
            f"({ft.get('degraded_ratio')}× clean) under "
            f"{ft.get('injected')} injections {ft.get('by_site')} · "
            f"hedges {ft.get('hedges')} · failovers {ft.get('failovers')} · "
            f"replicas retired {ft.get('replicas_retired')}/spawned "
            f"{ft.get('replicas_spawned')} · compiles after warmup "
            f"{ft.get('compiles_after_warmup')}")

    fp = sub.get("fleet_proc")
    if fp:
        asc = fp.get("autoscale") or {}
        lines.append("")
        lines.append(
            f"**fleet (multi-process):** {fp.get('replicas')} subprocess "
            f"replicas · {fp.get('img_per_sec')} img/s through a SIGKILL "
            f"mid-drain · survivors {fp.get('survivors')} "
            f"(bitwise={fp.get('bitwise_vs_direct')}) · kill→recovered "
            f"{fp.get('kill_to_recovered_s')}s · spawn+warm cold "
            f"{fp.get('spawn_warm_cold_s')}s / warm {fp.get('spawn_warm_s')}s "
            f"· failovers {fp.get('failovers')} · retired "
            f"{fp.get('replicas_retired')}/spawned "
            f"{fp.get('replicas_spawned')} · autoscale "
            f"{asc.get('scale_ups')}↑/{asc.get('scale_downs')}↓ → target "
            f"{asc.get('final_target')} · compiles after warmup "
            f"{fp.get('compiles_after_warmup')}")

    ed = sub.get("edit")
    if ed:
        per = ed.get("per_task", {})
        pv = ed.get("preview", {})
        lines.append("")
        lines.append(
            "**editing workloads (img/s):** "
            + " · ".join(f"{task}={leg.get('img_per_sec')}"
                         for task, leg in per.items())
            + f" · k={ed.get('k')} · compiles after warmup "
              f"{ed.get('compiles_after_warmup')}")
        if pv:
            lines.append(
                f"streamed previews (every={pv.get('every')}): first frame "
                f"{pv.get('latency_to_first_frame_s')}s of "
                f"{pv.get('total_s')}s drain "
                f"({pv.get('first_frame_fraction')}× wall) · "
                f"{pv.get('frames')} frames")

    ob = sub.get("obs")
    if ob:
        tel = ob.get("telemetry", {})
        lines.append("")
        lines.append(
            f"**observability:** tracing overhead "
            f"{ob.get('tracing_overhead_pct')}% "
            f"({ob.get('img_per_sec_tracing_off')} img/s off → "
            f"{ob.get('img_per_sec_tracing_on')} on) · traced bitwise "
            f"{ob.get('traced_bitwise_equal')} · {ob.get('spans_recorded')} "
            f"spans / {ob.get('chrome_events')} chrome events · step "
            f"telemetry {tel.get('refreshes')}r/{tel.get('reuses')}c "
            f"(ratio {tel.get('refresh_ratio')}) · compiles after warmup "
            f"{ob.get('compiles_after_warmup')}")

    at = sub.get("attrib")
    if at:
        top = at.get("top_scopes", [])
        lines.append("")
        lines.append(
            f"**attribution:** {_fmt_pct(at.get('coverage'))} of device-busy "
            f"attributed · busy {at.get('device_busy_s')}s / idle "
            f"{at.get('idle_s')}s ({_fmt_pct(at.get('busy_fraction'))} busy) · "
            f"{at.get('device_lanes')} lane(s) · ridge "
            f"{at.get('ridge_flops_per_byte')} FLOP/byte · "
            f"{len(at.get('fusion_candidates', []))} fusion candidates · "
            f"compiles after warmup {at.get('compiles_after_warmup')} · "
            f"source {at.get('trace_source')}")
        if top:
            lines += ["", "| scope | self ms | share | TFLOP/s | MFU | bound |",
                      "|---|---|---|---|---|---|"]
            for s in top:
                lines.append(
                    f"| {s.get('scope')} | {1000 * s.get('self_s', 0.0):.3f} | "
                    f"{_fmt_pct(s.get('share_of_busy'))} | "
                    f"{s.get('achieved_tflops')} | {s.get('mfu')} | "
                    f"{s.get('roofline')} |")
        tr = at.get("trend")
        if tr:
            st = tr.get("statuses", {})
            lines.append(
                f"trend gate: exit {tr.get('exit_code')} over "
                f"{tr.get('bench_points')} bench + "
                f"{tr.get('multichip_points')} multichip points · "
                + (" · ".join(f"{k}={v}" for k, v in sorted(st.items()))
                   or "no checks"))

    fu = sub.get("fusion")
    if fu:
        uf, fd = fu.get("unfused", {}), fu.get("fused", {})
        lines.append("")
        lines.append(
            f"**fused trunk (k={fu.get('k')}, buckets={fu.get('buckets')}):** "
            f"{uf.get('per_step_ms')} ms/step unfused → "
            f"{fd.get('per_step_ms')} ms fused ({fu.get('speedup')}×) · "
            f"{fd.get('img_per_sec')} img/s · MFU {uf.get('mfu')} → "
            f"{fd.get('mfu')} · oracle {fu.get('oracle')} (max |Δ| "
            f"{fu.get('max_abs_pixel_delta')}) · compiles after warmup "
            f"{fu.get('compiles_after_warmup')}")

    pl = sub.get("parallel")
    if pl and not pl.get("skipped"):
        degs = pl.get("degrees", {})
        lines.append("")
        lines.append(
            "**sequence-parallel serving (single request, "
            f"bucket={pl.get('bucket')}, {pl.get('devices')} devices):** "
            + " · ".join(
                f"sp{d}={leg.get('latency_s')}s"
                + (f" (p99 {leg['p99_latency_s']}s)"
                   if leg.get("p99_latency_s") is not None else "")
                + (f" ({leg.get('speedup_vs_sp1')}× sp1, "
                   f"{leg.get('sp_mode')})" if d != "1" else "")
                for d, leg in degs.items())
            + f" · sp1 bitwise {pl.get('sp1_bitwise_vs_direct')} · "
              f"compiles after warmup {pl.get('compiles_after_warmup')}")
        ns_sp = pl.get("northstar_200px_sp")
        if ns_sp:
            lines.append(
                f"200px k=20 all-local sp{ns_sp.get('sp_degree')}: "
                f"{ns_sp.get('latency_s')}s / {ns_sp.get('img_per_sec')} "
                f"img/s (bucket {ns_sp.get('bucket')}) · compiles after "
                f"warmup {ns_sp.get('compiles_after_warmup')}")

    for key, label in (("cached_quality_64px", "cached quality 64px"),
                       ("quant_quality_64px", "w8a16 quality 64px"),
                       ("quant_cached_quality_64px",
                        "w8a16 × cache quality 64px")):
        g = sub.get(key)
        if g:
            dist = g.get("fid_exact_vs_cached", g.get("fid_exact_vs_quant"))
            lines.append("")
            lines.append(
                f"**{label}:** paired Fréchet {dist} · pixel drift "
                f"{g.get('max_abs_pixel_delta')} (n={g.get('n_samples')}, "
                f"k={g.get('k')}, interval={g.get('cache_interval')})")
    e2e = [(lbl, sub.get(f"e2e_train_throughput_{lbl}"))
           for lbl in ("cold", "warm")]
    if any(v for _, v in e2e):
        bw = sub.get("h2d_bandwidth_mib_s")
        lines.append("")
        lines.append("**e2e disk→step (img/s):** " + " · ".join(
            f"{lbl}={v['value']} ({v['vs_baseline']}×"
            + (f", spd={v['steps_per_dispatch']}" if "steps_per_dispatch" in v
               else "") + ")"
            for lbl, v in e2e if v)
            + (f" · H2D link ≈ {bw} MiB/s" if bw else ""))
    return "\n".join(lines)


def main(argv=None):
    paths = (argv or sys.argv)[1:]
    if not paths:
        print(__doc__.strip().splitlines()[-1], file=sys.stderr)
        return 2
    for p in paths:
        print(render(p))
        print()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
