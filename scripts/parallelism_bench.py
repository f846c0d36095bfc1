#!/usr/bin/env python
"""Measure parallelism-layout overhead: dp vs dp×{pipe,seq,tp,expert}.

The round-1 suite proved these layouts *correct* (gradient equivalence); this
script measures what each one *costs*, so the README can say when to use
which (VERDICT round 1: "risk of a shipped feature that's always slower than
dp for in-repo model sizes").

On the 8-virtual-CPU-device mesh the devices timeshare one host core, so
wall-clock ≈ TOTAL WORK across the mesh: a layout that burns FLOPs on GPipe
bubble steps or re-materializes activations shows up directly as a ratio > 1
vs plain dp on the same global batch. (It cannot show ICI-bound speedups —
that needs a real slice; what it isolates is the schedule/collective overhead
each layout adds.)

Writes one JSON line per layout:
    {"layout": "dp4_pipe2", "ms_per_step": ..., "vs_dp": ..., "baseline": ...}
where ``baseline`` names the denominator row: dense layouts ratio against
plain dp, the MoE rows against the SAME MoE model on plain dp (comparing MoE
to the dense baseline would conflate model cost with layout cost — the two
families' ``vs_dp`` values are not cross-comparable).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=16, help="global batch")
    ap.add_argument("--depth", type=int, default=8,
                    help="transformer depth (divisible by pipe stages)")
    ap.add_argument("--img", type=int, default=64)
    ap.add_argument("--patch", type=int, default=4,
                    help="4 → 257 tokens: long enough that seq sharding is real")
    ap.add_argument("--embed", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    args = ap.parse_args(argv)

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddim_cold_tpu.config import ExperimentConfig
    from ddim_cold_tpu.parallel import make_mesh, shard_batch, shard_train_state
    from ddim_cold_tpu.parallel.layout import layout_for_mesh
    from ddim_cold_tpu.train.step import create_train_state, make_train_step
    from ddim_cold_tpu.train.trainer import build_model

    n = args.devices
    # (mesh, config overrides): the two MoE rows isolate the ep LAYOUT cost
    # by comparing the same MoE model on plain dp vs dp×ep — comparing MoE
    # to the dense dp baseline would conflate model cost with layout cost
    layouts = {
        f"dp{n}": ({"data": n}, {}),
        f"dp{n//2}_pipe2": ({"data": n // 2, "pipe": 2}, {}),
        f"dp{n//2}_seq2": ({"data": n // 2, "seq": 2}, {}),
        f"dp{n//2}_tp2": ({"data": n // 2, "model": 2}, {}),
        f"moe_dp{n}": ({"data": n}, {"num_experts": 4}),
        f"moe_dp{n//2}_ep2": ({"data": n // 2, "expert": 2},
                              {"num_experts": 4}),
        # index-dispatch rows: same MoE model, sort/gather routing — the
        # ratio against the einsum rows is the dispatch-implementation cost
        # at this (short-sequence) scale; index exists for the O(N²·cf)
        # regimes the einsum rows can't reach (models/moe.py)
        f"moe_idx_dp{n}": ({"data": n},
                           {"num_experts": 4, "moe_dispatch": "index"}),
        f"moe_idx_dp{n//2}_ep2": ({"data": n // 2, "expert": 2},
                                  {"num_experts": 4, "moe_dispatch": "index"}),
    }
    if n % 4 == 0 and n >= 8:
        # composed layouts (round 5): pipe×tp rides GSPMD-auto 'model'
        # inside each stage; seq×tp with both sp strategies (ring keeps
        # heads tp-sharded through the rotation, ulysses all-to-alls each
        # tp group's local heads). Gated like __graft_entry__'s composed
        # legs — an n//4 mesh cannot cover 2 or 6 devices.
        layouts.update({
            f"dp{n//4}_pipe2_tp2": ({"data": n // 4, "pipe": 2, "model": 2},
                                    {}),
            f"dp{n//4}_seq2_tp2_ring": ({"data": n // 4, "seq": 2,
                                         "model": 2}, {}),
            f"dp{n//4}_seq2_tp2_ul": ({"data": n // 4, "seq": 2, "model": 2},
                                      {"sp_mode": "ulysses"}),
            # pipe×ep (round 5): expert banks GSPMD-auto inside the manual
            # pipe region, aux re-sown through the schedule; ratios against
            # the same-model moe_dp row like every MoE layout
            f"moe_dp{n//4}_pipe2_ep2": ({"data": n // 4, "pipe": 2,
                                         "expert": 2}, {"num_experts": 4}),
        })

    rng = np.random.RandomState(0)
    batch = (
        rng.randn(args.batch, args.img, args.img, 3).astype(np.float32),
        rng.randn(args.batch, args.img, args.img, 3).astype(np.float32),
        rng.randint(1, 7, size=(args.batch,)).astype(np.int32),
    )

    # ONE precision for every row, per backend: bf16 on real TPU (the MXU
    # path users run), f32 on the virtual-CPU mesh. CPU has no native bf16 —
    # XLA emulates it, so amp=True there measures each layout's emulation
    # surface as much as its schedule/collective overhead (measured: it
    # inverts the dp-vs-model-parallel ordering), and the bf16 tp-psum
    # inside the partially-manual pipelined shard_map CHECK-fails in XLA's
    # CPU AllReducePromotion pass outright (pipeline.py docstring).
    amp = jax.default_backend() == "tpu"
    results = {}
    for name, (mesh_shape, extra) in layouts.items():
        kw = dict(
            exp_name="pbench", amp=amp, batch_size=args.batch,
            image_size=(args.img, args.img), patch_size=args.patch,
            embed_dim=args.embed, depth=args.depth, head=args.heads,
            mesh=mesh_shape,
        )
        kw.update(extra)
        cfg = ExperimentConfig(**kw)
        mesh = make_mesh(mesh_shape)
        model = build_model(cfg, mesh=mesh)
        state = create_train_state(model, jax.random.PRNGKey(0), 1e-3, 1000,
                                   batch)
        specs, apply_fn = layout_for_mesh(
            model, mesh, state.params,
            n_microbatch=2 * int(mesh.shape.get("pipe", 1)))
        state = shard_train_state(state, mesh, specs)
        step = make_train_step(model, apply_fn)
        b = shard_batch(batch, mesh)
        ema = jnp.float32(5.0)

        with mesh:
            t0 = time.time()
            state, _, ema = step(state, b, jax.random.PRNGKey(1), ema)
            float(ema)
            compile_s = time.time() - t0
            t0 = time.time()
            for _ in range(args.steps):
                state, _, ema = step(state, b, jax.random.PRNGKey(1), ema)
            float(ema)
            dt = (time.time() - t0) / args.steps
        results[name] = dt
        print(f"[pbench] {name:12s} compile={compile_s:5.1f}s "
              f"{1000*dt:8.2f} ms/step", file=sys.stderr)

    # explicit per-row baselines; a missing baseline must fail loudly, never
    # silently ratio a row against the wrong model (moe vs dense) or the
    # wrong dispatch implementation (index vs einsum)
    baseline_of = {name: (f"moe_idx_dp{n}" if name.startswith("moe_idx_")
                          else f"moe_dp{n}" if name.startswith("moe_")
                          else f"dp{n}")
                   for name in results}
    # the index-dispatch dp row itself ratios against the einsum dp row:
    # that ratio IS the dispatch-implementation cost at this scale
    baseline_of[f"moe_idx_dp{n}"] = f"moe_dp{n}"
    for name, dt in results.items():
        ref_name = baseline_of[name]
        print(json.dumps({
            "layout": name, "ms_per_step": round(1000 * dt, 2),
            "vs_dp": round(dt / results[ref_name], 3),
            "baseline": ref_name,
            "note": "8 virtual CPU devices share one core: ratio ≈ total-work "
                    "overhead of the layout, not ICI speedup",
        }))


if __name__ == "__main__":
    main()
