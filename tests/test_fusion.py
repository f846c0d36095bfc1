"""Fused sampler-trunk kernel tests (ops/flash_attention.fused_trunk_attention
+ ops/quant.mlp_pallas + ops/tuning.py + the vit/serve wiring).

The contract ladder, strictest first:
* the engine serves the fused program as it serves any other
  (``tests/promise.py``: bitwise the direct fused sampler at the bucket's
  batch size, to a tolerance at the request's own n), at two buckets and
  composed with the step cache;
* the fused program agrees with the unfused ``QuantDense → flash →
  QuantDense`` + ``Dense → gelu → Dense`` composition at f32 to
  ``FUSED_ATOL`` (they are two XLA programs: the same sums in another
  order), and bitwise for the fused Mlp alone, the part that survives the
  sp gate, under sp_degree=2;
* ``fused=True`` + ``quant='xla'`` is refused at config construction AND at
  model call — 'xla' explicitly opts out of Pallas;
* every committed TUNED_BLOCKS entry is legal under exactly the rules
  graftcheck's kernels layer proves (P001 tile units, P002 double-buffered
  VMEM, P003 padding waste), and the enumerator's mirrored constants are
  pinned equal to analysis/kernel_checks.py's so they cannot drift;
* the w8a8 mode rides the paired-FID ``quantized_sampler_guard``;
* analysis/entries.py certifies every fused variant (P/M-rule coverage).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import promise
import pytest

from ddim_cold_tpu import serve
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops import quant, sampling, tiling, tuning
from ddim_cold_tpu.utils import flops as flops_util

# flash + explicit blocks: both the fused and unfused clones inherit the SAME
# kv-chunk boundaries (dense einsum attention would differ from the online
# softmax in round-off by far more than FUSED_ATOL)
TINY = dict(img_size=(32, 32), patch_size=8, embed_dim=64, depth=2,
            num_heads=4, total_steps=2000, use_flash=True,
            flash_blocks=(32, 32))
K = 500  # 4 reverse steps (tests/test_serve.py's budget)
#: fused against unfused, float32 on the CPU, [0, 1] images after 4 steps:
#: 1.19e-07 seen (6.0e-08 after one forward), in a quarter of the values
FUSED_ATOL = 5e-7


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 32, 32, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def warmed_fused(model_and_params):
    """One engine + warmed unfused/fused w8a16 programs at two buckets —
    the AOT compiles are the expensive part, shared across the tests."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(2, 4))
    cfg_u = serve.SamplerConfig(k=K, quant="pallas")
    cfg_f = serve.SamplerConfig(k=K, quant="pallas", fused=True)
    report = serve.warmup(eng, [cfg_u, cfg_f], persistent_cache=False)
    assert report["new_compiles"] == 4  # one program per (config, bucket)
    return eng, cfg_u, cfg_f


# ------------------------------------------------------------ engine parity

def _drain(eng, cfg, seeds_and_ns):
    tickets = [eng.submit(seed=s, n=n, config=cfg) for s, n in seeds_and_ns]
    eng.run()
    return [np.asarray(t.result(timeout=30)) for t in tickets]


def _assert_served_fused(how, got, model, params, seed, bucket, **cache):
    """The engine's rows for a fused request against the direct fused
    sampler on the same start state (``tests/promise.py``)."""
    promise.assert_sample_served(
        how, got, model.clone(quant="pallas", fused=True),
        quant.quantize_params(params), seed, K, (bucket,), **cache)


@pytest.mark.parametrize("how", promise.HOWS)
def test_engine_fused_bitwise_two_buckets(warmed_fused, how):
    """Acceptance: the fused program is served as the direct fused sampler
    computes it (``how``) from both warmed buckets, and its images are the
    unfused w8a16 program's to ``FUSED_ATOL``, with zero compiles after
    warmup — same param tree, same rng, different compiled program."""
    eng, cfg_u, cfg_f = warmed_fused
    compiles = eng.stats["compiles"]
    # 3 rows are one batch of bucket 4; then 1 row is one batch of bucket 2
    for seed, n, bucket in ((201, 3, 4), (202, 1, 2)):
        (u,) = _drain(eng, cfg_u, [(seed, n)])
        (f,) = _drain(eng, cfg_f, [(seed, n)])
        _assert_served_fused(how, f, eng.model, eng.params, seed, bucket)
        np.testing.assert_allclose(f, u, rtol=0, atol=FUSED_ATOL)
        assert np.isfinite(f).all()
    assert eng.stats["compiles"] == compiles


@pytest.mark.parametrize("how", promise.HOWS)
def test_engine_fused_cached_composition(model_and_params, how):
    """fused × step-cache composes: the cache is a trunk-structure hook
    (block-delta capture), independent of how each block computes."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(2,))
    cache = dict(cache_interval=2, cache_mode="full")
    cfg_u = serve.SamplerConfig(k=K, quant="pallas", **cache)
    cfg_f = serve.SamplerConfig(k=K, quant="pallas", fused=True, **cache)
    serve.warmup(eng, [cfg_u, cfg_f], persistent_cache=False)
    compiles = eng.stats["compiles"]
    (u,) = _drain(eng, cfg_u, [(211, 1)])
    (f,) = _drain(eng, cfg_f, [(211, 1)])
    assert eng.stats["compiles"] == compiles
    _assert_served_fused(how, f, model, params, 211, 2, **cache)
    np.testing.assert_allclose(f, u, rtol=0, atol=FUSED_ATOL)


@pytest.mark.skipif(jax.device_count() % 2 != 0,
                    reason="sp_degree=2 needs an even device count")
def test_engine_fused_sp2_composition(model_and_params):
    """fused × sp_degree=2: the fused ATTENTION is gated off under sp (the
    kernel owns the full sequence axis), so the sp×fused program is the sp
    attention + the fused w8a16 Mlp — still bitwise vs the sp unfused
    program (the Mlp is per-token; sharding doesn't reorder its reduction)."""
    model, params = model_and_params
    # the bucket must tile the sp data axis (devices / sp_degree)
    eng = serve.Engine(model, params, buckets=(4,))
    cfg_u = serve.SamplerConfig(k=K, quant="pallas", sp_mode="ulysses",
                                sp_degree=2)
    cfg_f = serve.SamplerConfig(k=K, quant="pallas", sp_mode="ulysses",
                                sp_degree=2, fused=True)
    serve.warmup(eng, [cfg_u, cfg_f], persistent_cache=False)
    compiles = eng.stats["compiles"]
    (a,) = _drain(eng, cfg_u, [(221, 4)])
    (b,) = _drain(eng, cfg_f, [(221, 4)])
    assert eng.stats["compiles"] == compiles
    np.testing.assert_array_equal(a, b)


def test_fused_param_tree_shared(model_and_params):
    """The fused clone declares the SAME param tree as the unfused one —
    fused=True switches the compiled program, never the checkpoint."""
    model, params = model_and_params
    fused = model.clone(quant="pallas", fused=True)
    unfused = model.clone(quant="pallas")
    qp = quant.quantize_params(params)
    x = jnp.zeros((1, 32, 32, 3))
    t = jnp.array([0], jnp.int32)
    tf = jax.eval_shape(lambda: fused.init(jax.random.PRNGKey(0), x, t))
    tu = jax.eval_shape(lambda: unfused.init(jax.random.PRNGKey(0), x, t))
    assert jax.tree_util.tree_structure(tf) == jax.tree_util.tree_structure(tu)
    # and the quantized tree drives the fused model directly
    out = fused.apply({"params": qp}, x, t, deterministic=True)
    assert np.isfinite(np.asarray(out)).all()


def test_fused_xla_refused(model_and_params):
    """quant='xla' explicitly opts out of Pallas; fused=True contradicts it
    — refused at config construction AND at model call, naming the fix."""
    model, _ = model_and_params
    with pytest.raises(ValueError, match="fused"):
        serve.SamplerConfig(k=K, quant="xla", fused=True)
    bad = model.clone(quant="xla", fused=True)
    with pytest.raises(ValueError, match="quant='pallas'"):
        bad.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                 jnp.array([0], jnp.int32))


# -------------------------------------------------- tuned-block table rules

def test_tuning_constants_pinned_to_kernel_checks():
    """The enumerator's mirrored constants must equal the verifier's — a
    drift would let tuning.py commit blocks graftcheck then rejects."""
    from ddim_cold_tpu.analysis import kernel_checks as kc

    assert tuning.DEVICE_KIND == kc.DEVICE_KIND
    assert tuning.WASTE_THRESHOLD == kc.WASTE_THRESHOLD
    assert tuning.PIPELINE_BUFFERS == kc.PIPELINE_BUFFERS
    # the tiling units tuning enumerates with ARE the P001 MIN_TILE rows
    for itemsize, (sub, lane) in kc.MIN_TILE.items():
        dt = {4: jnp.float32, 2: jnp.bfloat16, 1: jnp.int8}[itemsize]
        assert tiling.sublane_unit(dt) == sub
        assert tiling.LANE == lane


_DT = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}


def test_tuned_blocks_all_legal():
    """Every committed TUNED_BLOCKS entry obeys the P-rules it was
    enumerated under: sequence blocks are MIN_TILE sublane multiples (P001),
    padding waste stays under the ceiling (P003), and the kernel's
    double-buffered VMEM footprint fits the device (P002)."""
    from ddim_cold_tpu.analysis import kernel_checks as kc

    budget = flops_util.vmem_bytes(tuning.DEVICE_KIND)
    assert budget is not None
    for (kind, dt_name, geom), blocks in tuning.TUNED_BLOCKS.items():
        dt = _DT[dt_name]
        unit = kc.MIN_TILE[jnp.dtype(dt).itemsize][0]
        m = re.fullmatch(r"attn_n(\d+)_c(\d+)_h(\d+)", geom)
        if m:
            n, c, h = map(int, m.groups())
            bq, bkv = blocks
            for b in (bq, bkv):
                assert b % unit == 0, (geom, dt_name, blocks)
                assert tiling.round_up(n, b) / n <= tuning.WASTE_THRESHOLD
            cdt = jnp.float32 if dt == jnp.int8 else dt
            assert tuning.attn_vmem_bytes(
                bq, bkv, c, h, dt, compute_dtype=cdt) <= budget, (geom, dt_name)
            continue
        m = re.fullmatch(r"(mlpf?)_c(\d+)_h(\d+)", geom)
        if m:
            q = m.group(1) == "mlp"
            c, h = int(m.group(2)), int(m.group(3))
            (bm,) = blocks
            assert bm % unit == 0, (geom, dt_name, blocks)
            assert tuning.mlp_vmem_bytes(bm, c, h, c, dt,
                                         quant=q) <= budget, (geom, dt_name)
            continue
        m = re.fullmatch(r"dequant_m(\d+)_k(\d+)_n(\d+)", geom)
        assert m, f"unrecognized geometry tag {geom}"
        mm, k, n = map(int, m.groups())
        bm, bn, bk = blocks
        assert bm % unit == 0
        assert bn % tiling.LANE == 0
        # dual-dtype K axis: activation LANE dim AND int8-weight sublane dim
        assert bk % tiling.LANE == 0 and bk % kc.MIN_TILE[1][0] == 0
        assert tiling.round_up(mm, bm) / mm <= tuning.WASTE_THRESHOLD
        assert tuning.dequant_vmem_bytes(bm, bn, bk, dt) <= budget


def test_tuned_lookup_and_fallbacks():
    """lookup() prefix-matches the device kind; un-tuned geometries fall
    back to NS_FLASH_BLOCKS / the kernel default — never None."""
    from ddim_cold_tpu.ops.flash_attention import NS_FLASH_BLOCKS

    row = lambda dt, geom: tuning.TUNED_BLOCKS[("TPU v5 lite", dt, geom)]  # noqa: E731
    got = tuning.attn_blocks(2501, 256, 4, jnp.float32,
                             device_kind="TPU v5 lite core 1")
    assert got == row("float32", "attn_n2501_c256_h4")  # prefix match
    assert tuning.attn_blocks(2501, 256, 4, jnp.float32,
                              device_kind="cpu") == NS_FLASH_BLOCKS
    assert (tuning.mlp_block_m(256, 256, jnp.bfloat16,
                               device_kind="TPU v5 lite"),
            ) == row("bfloat16", "mlp_c256_h256")
    assert (tuning.mlp_block_m(256, 256, jnp.bfloat16, quant=False,
                               device_kind="TPU v5 lite"),
            ) == row("bfloat16", "mlpf_c256_h256")
    assert tuning.mlp_block_m(99, 99, jnp.float32,
                              device_kind="TPU v5 lite") == 256  # default


def test_static_picks_reproduce_committed_table():
    """`python -m ddim_cold_tpu.ops.tuning` provenance: the static model
    re-derives every committed attention and Mlp row exactly (whether the
    chip's compiler takes them is tests/test_chip_compile.py's question)."""
    rows = 16  # the sampler's batch the Mlp rows were picked at
    for (kind, dt_name, geom), blocks in tuning.TUNED_BLOCKS.items():
        dt = _DT[dt_name]
        m = re.fullmatch(r"attn_n(\d+)_c(\d+)_h(\d+)", geom)
        if m:
            n, c, h = map(int, m.groups())
            cdt = jnp.float32 if dt == jnp.int8 else dt
            assert tuning.pick_attn(n, c, h, dt,
                                    compute_dtype=cdt) == blocks, (dt_name, geom)
        m = re.fullmatch(r"(mlpf?)_c(\d+)_h(\d+)", geom)
        if m:
            c, h = int(m.group(2)), int(m.group(3))
            n = {256: 2501, 384: 626}[c]
            assert (tuning.pick_mlp(rows * n, c, h, c, dt,
                                    quant=m.group(1) == "mlp"),
                    ) == blocks, (dt_name, geom)


# ------------------------------------------------------------- w8a8 quality

def test_w8a8_sampler_guard_smoke(model_and_params):
    """The w8a8 mode (int8 activations, per-tensor dynamic scale) ships
    behind the SAME paired-FID guard as w8a16 — the guard runs end to end
    over the fused w8a8 sampler and its drift stays bounded (w8a8 is NOT
    bitwise vs float: activation requantization is a real approximation)."""
    from ddim_cold_tpu.eval import fid

    model, params = model_and_params
    rep = fid.quantized_sampler_guard(model, params,
                                      rng=jax.random.PRNGKey(13),
                                      n_samples=2, sample_batch=2, k=K,
                                      quant="w8a8")
    assert rep["quant_rev"] == quant.QUANT_REV
    assert np.isfinite(rep["fid_exact_vs_quant"])
    assert rep["max_abs_pixel_delta"] < 0.25  # 4-step drift of an ~1% eps gap


def test_w8a8_direct_sampler_close_to_float(model_and_params):
    """Direct (engine-free) fused w8a8 sampling stays near the float
    sampler and is deterministic."""
    model, params = model_and_params
    qp = quant.quantize_params(params)
    w8a8 = model.clone(quant="w8a8", fused=True)
    rng = jax.random.PRNGKey(31)
    exact = np.asarray(sampling.ddim_sample(model, params, rng, k=K, n=2))
    got = np.asarray(sampling.ddim_sample(w8a8, qp, rng, k=K, n=2))
    assert np.isfinite(got).all()
    assert np.abs(got - exact).max() < 0.25
    again = np.asarray(sampling.ddim_sample(w8a8, qp, rng, k=K, n=2))
    np.testing.assert_array_equal(got, again)


# ------------------------------------------------------------ P/M coverage

def test_kernel_entries_cover_fused_variants():
    """analysis/entries.py certifies every fused program and kernel variant
    the sampler can dispatch — the graftcheck P/M layers run over these."""
    from ddim_cold_tpu.analysis import entries as entries_mod

    names = {e.name for e in entries_mod.kernel_entries()}
    for want in ("ns200_w8a16_fused", "ns200_w8a8_fused",
                 "fused200_attn_f32", "fused200_attn_bf16",
                 "fused200_attn_w8a8", "mlp200_float_bf16",
                 "mlp200_w8a16_bf16", "mlp200_w8a8"):
        assert want in names, f"missing kernel entry {want}"
