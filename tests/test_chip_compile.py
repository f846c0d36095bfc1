"""Ask the chip's compiler, without the chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Interpret-mode
tests cannot see what it refuses — more scoped VMEM than a kernel may use, a
primitive the Pallas TPU lowering lacks, a Mosaic kernel inside a jit over
several devices — so every kernel a legal model can reach at the 200px
geometries is compiled here for ``TPU v5 lite``, at the real widths.

A compile that passes is not a chip run: nothing executes, and nothing here
says anything about results or speed. ``chip_smoke.py`` is the chip run.

Skipped where the topology cannot be described (no libtpu, or another process
holds it). The kernels gate on ``jax.default_backend()`` and the block table
is keyed by the local device kind, both of which are the CPU's here, so the
tests steer them — not an option of the program.
"""

import functools
import os
import re
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ddim_cold_tpu.models import MODEL_CONFIGS, DiffusionViT
from ddim_cold_tpu.ops import flash_attention as fa
from ddim_cold_tpu.ops import quant, tuning
from ddim_cold_tpu.parallel import ambient

KIND = "TPU v5 lite"
ROWS = 16                      # the sampler's batch (analysis/entries.NS_ROWS)
N, C, H, D = 2501, 256, 4, 64  # oxford_flower_200_p4 trunk
P4 = MODEL_CONFIGS["oxford_flower_200_p4"]


@pytest.fixture(scope="module")
def chip():
    """The described v5e 2×2 host, with the kernels' backend gate steered to
    ``tpu``, the persistent compile cache off (an entry compiled for a
    described chip cannot be read back without one, and warns) and the
    suite's ``float32`` matmul-precision pin lifted — the program runs at
    JAX's default, and Mosaic refuses an fp32-precision matmul on bf16."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be had
        pytest.skip(f"no TPU topology description here: {e}")
    assert topo.devices[0].device_kind == KIND
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    real_backend, real_kind = jax.default_backend, tuning._local_device_kind
    jax.default_backend = lambda: "tpu"
    tuning._local_device_kind = lambda: KIND
    try:
        yield topo.devices
    finally:
        jax.default_backend = real_backend
        tuning._local_device_kind = real_kind
        jax.config.update("jax_default_matmul_precision", precision)
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _struct(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _params(model, sds):
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *model.img_size, 3)),
        jnp.zeros((1,), jnp.int32))["params"])
    return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)


# --- cases: name → builder(devices) → (fn, args, custom calls expected) -----

def _flash_fwd(dtype, blocks, with_lse=False, n=N, packed=False):
    """The forward at explicit blocks, or (a ``None``) at those the kernel
    picks from the shape; ``with_lse`` is the launch the VJP's forward makes.
    4 heads of 64: q, k, v are read in place, two heads a lane group, from
    three ``(rows, n, 256)`` arrays or (``packed``) from the one
    ``(rows, n, 768)`` projection, and the token axis ends inside the last
    block."""
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        operands = ((sds((ROWS, n, 3 * C), dtype),) if packed
                    else (sds((ROWS, n, C), dtype),) * 3)
        return (lambda *operands: fa._flash_forward(
                    operands, H, D ** -0.5, *blocks, with_lse=with_lse),
                operands, 1)
    return build


LANE_GROUP = fa._heads_per_lane_group(H, D)  # 2: pure arithmetic


def _admitted(dtype, n=N):
    """Every block_q the forward VMEM model admits with the whole padded
    sequence as one K/V chunk (pure arithmetic: safe at import)."""
    n_pad = -(-n // 8) * 8
    return [bq for bq in (1024, 512, 256, 128)
            if fa._fwd_blocks(bq, None, n_pad, 128, dtype,
                              LANE_GROUP)[1] >= n_pad]


def _flash_bwd(dtype, blocks, packed=True, n=N):
    """The backward alone — ``dq`` and ``dkv`` at explicit blocks; both
    ``None``: the one ``dqkv`` launch where its VMEM row admits the shape
    (2,501 tokens: K/V blocks of 512 in bf16, 256 in f32), else ``dq`` and
    ``dkv`` at the blocks each picks: 4 heads of 64, two heads a lane group,
    q, k, v read in place from the packed ``(rows, n, 768)`` projection (or
    three ``(rows, n, 256)`` arrays), the context and the cotangent ``(rows,
    n, 256)``, the token axis ending inside the last block; the packed
    gradient is written whole by ``dqkv``, or begun by ``dq`` and completed
    by ``dkv``. Expects as many custom calls as ``_bwd_blocks`` names
    launches."""
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        operands = ((sds((ROWS, n, 3 * C), dtype),) if packed
                    else (sds((ROWS, n, C), dtype),) * 3)
        ctx = sds((ROWS, n, C), dtype)
        lse = sds((ROWS * H, -(-n // 128) * 128), jnp.float32)
        return (lambda operands, o, lse, g: fa._flash_backward(
                    operands, o, lse, g, H, D ** -0.5, *blocks),
                (operands, ctx, lse, ctx),
                len(fa._bwd_blocks(*blocks, -(-n // 8) * 8, 128, dtype,
                                   LANE_GROUP)))
    return build


def _bwd_admitted(kernel, dtype, n=N):
    """Every block of 1024, 512, 256, 128 at which the backward VMEM model
    admits ``kernel`` with its streamed side whole (pure arithmetic: safe at
    import) — as the explicit (block_q, block_kv) that asks for it."""
    n_pad = -(-n // 8) * 8
    asks = [(b, None) if kernel == "dq" else (None, b)
            for b in (1024, 512, 256, 128)]
    def streamed_side(ask):
        blocks = fa._bwd_blocks(*ask, n_pad, 128, dtype, LANE_GROUP)
        return blocks["dq"][1] if kernel == "dq" else blocks["dkv"][0]

    return [ask for ask in asks if streamed_side(ask) >= n_pad]


def _flash_grad(dtype):
    """Backward at NS_FLASH_BLOCKS: the f32 case only compiles because the
    backward shrinks the blocks it is given to its own budget
    (flash_attention._bwd_blocks)."""
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        q = sds((ROWS, N, H, D), dtype)
        loss = lambda q, k, v: fa.flash_attention(  # noqa: E731
            q, k, v, D ** -0.5, *fa.NS_FLASH_BLOCKS).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q), 3
    return build


def _dequant(n_out):
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        return (lambda x, w, s: quant.dequant_matmul(x, w, s, mode="pallas"),
                (sds((ROWS * N, C), jnp.bfloat16), sds((C, n_out), jnp.int8),
                 sds((n_out,), jnp.float32)), 1)
    return build


def _mlp(mode, dtype):
    """The fused Mlp at the block_m the model would pick for this geometry."""
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        act = jnp.int8 if mode == "w8a8" else dtype
        bm = tuning.mlp_block_m(C, C, act, quant=mode is not None,
                                device_kind=KIND)
        w = sds((C, C), jnp.float32 if mode is None else jnp.int8)
        vec = sds((C,), jnp.float32)
        scales = {} if mode is None else {"scale1": vec, "scale2": vec}
        return (lambda x, w1, b1, w2, b2, **s: quant.mlp_pallas(
                    x, w1, b1, w2, b2, mode=mode, block_m=bm, **s),
                (sds((ROWS, N, C), dtype), w, vec, w, vec), 1, scales)
    return build


def _fused_attn(dtype_name, geometry):
    """One committed TUNED_BLOCKS attention row at its own geometry."""
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        n, c, h = (int(part[1:]) for part in geometry.split("_")[1:])
        blocks = tuning.TUNED_BLOCKS[(KIND, dtype_name, geometry)]
        w8a8 = dtype_name == "int8"
        cdt = jnp.float32 if w8a8 else jnp.dtype(dtype_name)
        vec3, vec = sds((3 * c,), jnp.float32), sds((c,), jnp.float32)
        return (lambda x, wq, sq, bq, wp, sp, bp: fa.fused_trunk_attention(
                    x, wq, sq, bq, wp, sp, bp, num_heads=h,
                    scale=(c // h) ** -0.5, block_q=blocks[0],
                    block_kv=blocks[1], mode="w8a8" if w8a8 else "pallas"),
                (sds((ROWS, n, c), cdt), sds((c, 3 * c), jnp.int8), vec3, vec3,
                 sds((c, c), jnp.int8), vec, vec), 1)
    return build


def _forward(**kw):
    """The whole 200px/p4 forward in bf16: one kernel per flash layer, plus
    the float trunk's two token-wise kernels per block (``ln_qkv``,
    ``block_tail``) or four dequant matmuls per block under w8a16 — or,
    fused, one attention and one Mlp kernel per block at the committed
    TUNED_BLOCKS rows."""
    def build(devices):
        sds = _struct(SingleDeviceSharding(devices[0]))
        model = DiffusionViT(dtype=jnp.bfloat16, **kw, **P4)
        calls = (P4["depth"] if kw.get("use_flash") else 0) + (
            4 if kw.get("quant") else 2) * P4["depth"]
        if kw.get("fused"):
            calls = 2 * P4["depth"]
        return (lambda p, x, t: model.apply({"params": p}, x, t),
                (_params(model, sds), sds((ROWS, 200, 200, 3), jnp.float32),
                 sds((ROWS,), jnp.int32)), calls)
    return build


def _dp_train_step(devices):
    """One data-parallel train step over the four chips, with the flash
    kernels: the trunk geometry of the 200px/p4 model at depth 1 and without
    dropout (whose random bits are what makes the full step's compile take
    minutes). Each device must launch its own kernels — a jit over the mesh
    cannot partition them — and the gradients must be all-reduced."""
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    mesh = Mesh(np.asarray(devices), ("data",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    model = DiffusionViT(dtype=jnp.bfloat16, use_flash=True, drop_rate=0.0,
                         attn_drop_rate=0.0, drop_path_rate=0.0,
                         **dict(P4, depth=1))
    img = jnp.zeros((2, 200, 200, 3))
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.PRNGKey(0), 1e-3, 100,
        (img, img, jnp.zeros((2,), jnp.int32))))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), state)
    batch = tuple(jax.ShapeDtypeStruct(s, d, sharding=rows) for s, d in (
        ((8, 200, 200, 3), jnp.float32), ((8, 200, 200, 3), jnp.float32),
        ((8,), jnp.int32)))
    scalar = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=rep)  # noqa: E731
    with ambient(mesh):
        return make_train_step(model).lower(
            state, batch, scalar((2,), jnp.uint32), scalar((), jnp.float32)
        ).compile().as_text()


CASES = {
    **{f"flash_fwd-{np.dtype(dt).name}-{bq}x{bkv}": _flash_fwd(dt, (bq, bkv))
       for dt in (jnp.float32, jnp.bfloat16)
       for bq, bkv in ((256, 512), fa.NS_FLASH_BLOCKS)},
    **{f"flash_fwd-{np.dtype(dt).name}-auto{'-lse' * lse}{'-packed' * pk}":
       _flash_fwd(dt, (None, None), with_lse=lse, packed=pk)
       for dt in (jnp.float32, jnp.bfloat16) for lse in (False, True)
       for pk in (False, True)},
    **{f"flash_fwd-{np.dtype(dt).name}-{bq}xwhole": _flash_fwd(
           dt, (bq, None), with_lse=True)
       for dt in (jnp.float32, jnp.bfloat16) for bq in _admitted(dt)},
    **{f"flash_grad-{np.dtype(dt).name}": _flash_grad(dt)
       for dt in (jnp.float32, jnp.bfloat16)},
    **{f"flash_bwd-{np.dtype(dt).name}-auto{'-packed' * pk}": _flash_bwd(
           dt, (None, None), packed=pk)
       for dt in (jnp.float32, jnp.bfloat16) for pk in (False, True)},
    **{f"flash_bwd-{np.dtype(dt).name}-256x512": _flash_bwd(dt, (256, 512))
       for dt in (jnp.float32, jnp.bfloat16)},
    **{f"flash_bwd-{np.dtype(dt).name}-{kernel}-{bq}x{bkv}": _flash_bwd(
           dt, (bq, bkv))
       for dt in (jnp.float32, jnp.bfloat16) for kernel in ("dq", "dkv")
       for bq, bkv in _bwd_admitted(kernel, dt)},
    # dq at block_q 128 (K/V whole) against dkv streaming q at 256, on a
    # length neither divides: the statistics' blocks of the two differ
    **{f"flash_bwd-{np.dtype(dt).name}-auto-n{n}": _flash_bwd(
           dt, (None, None), n=n)
       for dt, n in ((jnp.float32, 4097), (jnp.bfloat16, 8000))},
    **{f"dequant_matmul-n{n_out}": _dequant(n_out) for n_out in (3 * C, C)},
    **{f"mlp_pallas-{mode or 'float'}-{np.dtype(dt).name}": _mlp(mode, dt)
       for mode, dt in ((None, jnp.float32), (None, jnp.bfloat16),
                        ("pallas", jnp.bfloat16), ("w8a8", jnp.bfloat16))},
    **{f"fused_trunk_attention-{geom}-{dt}": _fused_attn(dt, geom)
       for (kind, dt, geom) in tuning.TUNED_BLOCKS
       if kind == KIND and geom.startswith("attn_")},
    "forward-dense": _forward(),
    "forward-flash": _forward(use_flash=True),
    "forward-flash-w8a16": _forward(use_flash=True, quant="pallas"),
    "forward-fused-w8a16": _forward(use_flash=True, quant="pallas",
                                    fused=True),
}


@pytest.mark.parametrize("case", [*CASES, "dp4_train_step"])
def test_compiles_for_v5e(case, chip):
    if case == "dp4_train_step":
        text = _dp_train_step(chip)
        assert "all-reduce" in text, "no gradient all-reduce in the dp step"
        # forward and dqkv kernels of the one layer, on local rows
        assert text.count("tpu_custom_call") == 2
        return
    fn, args, want_calls, *kwargs = CASES[case](chip)
    text = jax.jit(fn).lower(*args, **(kwargs[0] if kwargs else {})
                             ).compile().as_text()
    assert text.count("tpu_custom_call") == want_calls


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bq", [512, 256, 128])
def test_fwd_vmem_model_admits_only_what_compiles(bq, dtype, chip):
    """At the model's edge: the longest sequence (to 128 tokens) at which
    ``_fwd_vmem_bytes`` still admits this block_q with K and V resident must
    compile, lse and all — the model is fitted to this compiler's refusals,
    so a drift shows here and not as a refused kernel on the chip."""
    n = max(n for n in range(1024, 16384, 128)
            if fa._fwd_blocks(bq, None, n, 128, dtype, LANE_GROUP) == (bq, n))
    assert fa._fwd_blocks(bq, None, n + 128, 128, dtype,
                          LANE_GROUP) == (bq, 512)
    fn, args, _ = _flash_fwd(jnp.dtype(dtype), (bq, None), with_lse=True,
                             n=n)(chip)
    assert jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call") == 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel,block", [
    ("dq", 512), ("dq", 256), ("dq", 128),
    ("dkv", 512), ("dkv", 256), ("dkv", 128),
    ("dqkv", 512), ("dqkv", 256), ("dqkv", 128)])
def test_bwd_vmem_model_admits_only_what_compiles(kernel, block, dtype, chip,
                                                  monkeypatch):
    """At the model's edge: the longest sequence (to 128 tokens) at which
    ``_bwd_vmem_bytes`` still admits ``kernel`` at this block with its
    streamed side whole — K and V for dq, q and do for dkv, q, o, do and the
    whole f32 dq for dqkv — must compile (both launches do: the other kernel
    takes what it picks there; the one ``dqkv`` launch is asked for at this
    block, where left alone it would take the largest of 512, 256, 128 its
    row admits). The model is fitted to this compiler's refusals, so a drift
    shows here and not as a refused kernel on the chip."""
    if kernel == "dqkv":
        isz = jnp.dtype(dtype).itemsize

        def whole(n):
            return fa._bwd_vmem_bytes("dqkv", n, block, 128, isz,
                                      LANE_GROUP) <= fa._SCOPED_VMEM_BYTES

        ask = (None, None)
    else:
        ask = (block, None) if kernel == "dq" else (None, block)

        def whole(n):
            return fa._bwd_blocks(*ask, n, 128, dtype, LANE_GROUP)[kernel] == (
                (block, n) if kernel == "dq" else (n, block))

    n = max(n for n in range(1024, 32768, 128) if whole(n))
    assert not whole(n + 128)
    if kernel == "dqkv":
        monkeypatch.setattr(fa, "_bwd_blocks",
                            lambda *a: {"dqkv": (n, block)})
    fn, args, calls = _flash_bwd(jnp.dtype(dtype), ask, n=n)(chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == calls
    assert ("%dqkv" in text) == (kernel == "dqkv")


def test_backward_reads_and_writes_where_the_gemms_do(chip):
    """What the in-place backward is for, read off the compiled depth-1 200px
    dp train step: ``dqkv`` takes the qkv GEMM's ``[images, 2501, 768]``
    result three times, the context and the cotangent ``[images, 2501,
    256]``, and its ONE result is the projection's whole ``[images, 2501,
    768]`` gradient (no second result: delta never leaves the kernel) — and
    NO instruction beside it produces a head-major or head-split array or a
    lane-replicated ``[.., tokens, 128]`` f32 spread of lse or delta, as the
    ``copy``, ``pad``, ``slice``, ``broadcast`` and ``concatenate``
    instructions of the head-major backward did (14 % of the dp4 cell's step:
    PERF.md section 6, PR 29). (The forward's own lane-replicated lse result,
    ``[images, 2, 2560, 128]``, and the instructions that cut it to one lane
    are exempt, by that shape.)"""
    import re

    text = _dp_train_step(chip)
    images = 2  # 8 over four chips
    results = re.findall(
        r"^\s*(?:ROOT )?%([\w.-]+) = \(?\w+\[([\d,]+)\]", text, re.M)
    tokens = range(N, 2560 + 1)  # true to lane-padded
    fwd_lse = f"{images},{C // 128},2560,128"
    found = {name: dims for name, dims in results  # a head's columns last
             if int(dims.split(",")[-1]) in (D, 128)
             and any(int(d) in tokens for d in dims.split(",")[:-1])
             and dims != fwd_lse}
    assert not found, found
    dqkv = re.search(r"%dqkv(?:\.\d+)* = (\w+)\[([\d,]+)\][^\n]*?"
                     r"custom-call\(([^)]*)\)", text)
    assert dqkv.group(2) == f"{images},{N},{3 * C}"
    operands = [op.strip() for op in dqkv.group(3).split(",")]
    assert operands[1] == operands[2] == operands[3]  # the projection, 3 times
    assert len(operands) == 6  # lse rows, the projection, context, cotangent
    assert "concatenate(" not in text


# --- the kernels' instruction names: what the benchmark's readers match -----

def _forward_depth1(devices):
    """The sampler's side: the 200px/p4 forward with the flash kernel, at
    depth 1 (the name does not depend on the depth)."""
    fn, args, _ = _forward(use_flash=True)(devices)
    model = DiffusionViT(dtype=jnp.bfloat16, use_flash=True,
                         **dict(P4, depth=1))
    sds = _struct(SingleDeviceSharding(devices[0]))
    return jax.jit(lambda p, x, t: model.apply({"params": p}, x, t)).lower(
        _params(model, sds), *args[1:]).compile().as_text()


@pytest.mark.parametrize("program,kernels", [
    ("forward", {"fwd", "ln_qkv", "block_tail"}),
    ("dp4_train_step", {"fwd", "dqkv"}),
])
def test_flash_kernels_keep_their_instruction_names(program, kernels, chip):
    """``benchmark/layer_metrics/flash_fwd_roofline.py`` finds the forward
    kernel as the ``tpu_custom_call`` instruction ``%fwd`` and the breakdown
    lists ``fwd``, ``dqkv`` and, for the sampler, ``ln_qkv`` and
    ``block_tail``: the names come from ``pallas_call(name=...)`` and must
    stay, with or without XLA's numeric suffix, whatever scope the kernels
    are launched under. The training step holds neither token-wise kernel:
    it traces ``deterministic=False`` (ops/block_kernels.py is inference
    only)."""
    import re

    text = (_forward_depth1(chip) if program == "forward"
            else _dp_train_step(chip))
    names = {m.group(1) for m in re.finditer(
        r"%(\w+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)}
    assert names == kernels


def test_forward_leaves_attention_operands_where_the_gemms_wrote_them(chip):
    """What the in-place forward is for, read off the compiled depth-1 200px
    forward: the kernel's operands are the qkv GEMM's ``[images, 2501, 768]``
    result and its first result is the ``[images, 2501, 256]`` context that
    ``proj`` reads, and NO instruction beside it produces a head-major or
    head-split array — ``[images·heads, tokens, 64 or 128]``, ``[images,
    heads, tokens, 64]``, ``[images, tokens, heads, 64]`` — as the ``copy``,
    ``pad`` and ``slice`` instructions of the head-major layout did (42 % of
    the sampler cell's device time: PERF.md section 6, PR 27)."""
    import re

    text = _forward_depth1(chip)
    results = re.findall(
        r"^\s*(?:ROOT )?%([\w.-]+) = \(?\w+\[([\d,]+)\]", text, re.M)
    tokens = range(N, 2560 + 1)  # true to lane-padded

    found = {name: dims for name, dims in results  # a head's columns last
             if int(dims.split(",")[-1]) in (D, 128)
             and any(int(d) in tokens for d in dims.split(",")[:-1])}
    assert not found, found
    fwd = re.search(r"%fwd(?:\.\d+)* = \(?(\w+)\[([\d,]+)\][^\n]*?"
                    r"custom-call\(([^)]*)\)", text)
    assert fwd.group(2) == f"{ROWS},{N},{C}"
    operands = {op.strip() for op in fwd.group(3).split(",")}
    assert len(operands) == 1, operands  # the projection, three times
    # ... which ``ln_qkv`` wrote, and ``block_tail`` reads the context: no
    # instruction stands between the three launches of a block
    assert re.search(re.escape(operands.pop()) + rf"(?:\.\d+)* = \w+\[{ROWS},"
                     rf"{N},{3 * C}\][^\n]*custom-call", text)
    tail = re.search(r"%block_tail(?:\.\d+)* = [^\n]*?custom-call\(([^)]*)\)",
                     text)
    assert re.search(r"%fwd(?:\.\d+)*\b", tail.group(1).split(",")[0])


# --- the token-wise kernels at the sampler cell's shape ---------------------

@pytest.mark.parametrize("images,tokens,width,hidden,dtype", [
    (288, N, C, C, jnp.bfloat16),         # flower200_sample_k20: 720,288 rows
    (ROWS, 577, 384, 384, jnp.float32),   # width 384, one ragged block
    (4, 16384, 256, 1024, jnp.bfloat16),  # the VMEM model's edge, mlp_ratio 4
    (4, 16384, 384, 1536, jnp.float32),
])
def test_tokenwise_kernels_lower_and_keep_their_names(images, tokens, width,
                                                      hidden, dtype, chip):
    """``ops/block_kernels.py`` at the row block the shape gives it: each is
    ONE ``tpu_custom_call``, named ``%ln_qkv`` / ``%block_tail`` (the ledger's
    breakdown lists them by that name), reading and writing the ``(images,
    tokens, width)`` arrays as they lie — no pad, no reshape of the ragged
    token axis."""
    import re

    from ddim_cold_tpu.ops import block_kernels as bk

    sds = _struct(SingleDeviceSharding(chip[0]))
    rows = bk.row_block(tokens, width, hidden, dtype)
    assert rows is not None
    act = sds((images, tokens, width), dtype)
    vec = lambda n: sds((n,), jnp.float32)  # noqa: E731
    mat = lambda k, n: sds((k, n), jnp.float32)  # noqa: E731
    for name, fn, args, out_width in (
            ("ln_qkv", bk.ln_qkv,
             (act, vec(width), vec(width), mat(width, 3 * width),
              vec(3 * width)), 3 * width),
            ("block_tail", bk.block_tail,
             (act, act, mat(width, width), vec(width), vec(width), vec(width),
              mat(width, hidden), vec(hidden), mat(hidden, width),
              vec(width)), width)):
        text = jax.jit(lambda *a, _fn=fn: _fn(*a, 1e-5, rows)).lower(
            *args).compile().as_text()
        calls = [line.strip().removeprefix("ROOT ")
                 for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == 1
        assert re.match(rf"%{name}(\.\d+)* = \w+\[{images},{tokens},"
                        rf"{out_width}\]", calls[0]), calls[0][:120]
        kind = {"bfloat16": "bf16", "float32": "f32"}[np.dtype(dtype).name]
        assert (f"operand_layout_constraints={{{kind}[{images},{tokens},"
                f"{width}]{{2,1,0}}" in calls[0])
        assert " pad(" not in text


@pytest.mark.parametrize("axes,pipelined", [
    ({"pipe": 2, "model": 2}, True),
    ({"pipe": 2, "model": 1}, True),   # an idle 'model' axis is enough
    ({"data": 2, "model": 2}, False),  # plain tensor parallelism
], ids=["pipe2_model2", "pipe2_idle_model", "data2_model2"])
def test_tokenwise_kernels_leave_tensor_parallel_meshes_to_gspmd(
        axes, pipelined, chip):
    """The 200px/p4 eval forward at sampler length where GSPMD partitions the
    trunk: through ``make_pipelined_apply``, whose shard_map is manual over
    ``pipe`` alone so that a ``model`` axis stays automatic, and over a plain
    data × model mesh with Megatron-sharded weights. jit cannot partition a
    Mosaic launch (``Mosaic kernels cannot be automatically partitioned``,
    with one device on ``model`` too), and per-device launches would gather
    the sharded weights whole — so these meshes keep the composition
    (``block_kernels._mesh_admits``): the program compiles, holds no custom
    call, and still reduces over the model axis where it has devices."""
    from ddim_cold_tpu.parallel import (
        make_pipelined_apply, param_partition_specs, pipeline_param_specs)

    shape = tuple(axes.values())
    mesh = Mesh(np.asarray(chip[:int(np.prod(shape))]).reshape(shape),
                tuple(axes))
    model = DiffusionViT(dtype=jnp.bfloat16, scan_blocks=pipelined, **P4)
    tree = _params(model, jax.ShapeDtypeStruct)
    specs = (pipeline_param_specs(tree, tensor_axes=("model",)) if pipelined
             else param_partition_specs(tree, axes=("model",)))
    sds = lambda spec: _struct(NamedSharding(mesh, spec))  # noqa: E731
    params = jax.tree.map(lambda s, spec: sds(spec)(s.shape, s.dtype),
                          tree, specs)
    rows = P() if pipelined else P("data")
    apply = (make_pipelined_apply(model, mesh, batch_axis=None, seq_axis=None,
                                  n_microbatch=2) if pipelined
             else model.apply)
    with ambient(mesh):
        text = jax.jit(apply).lower(
            {"params": params}, sds(rows)((4, 200, 200, 3), jnp.float32),
            sds(rows)((4,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" not in text
    if axes["model"] > 1:
        assert "all-reduce" in text


# --- the selective scan at Jamba2-3B's published shape ----------------------

SSM = dict(n=4, L=1025, d=5120, s=16)  # images, tokens, d_inner, states


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssm_scan_lowers_at_the_published_shape_and_keeps_its_name(dtype, chip):
    """``ops/selective_scan.py`` at 4 x 1,025 tokens x 5,120 channels x 16
    states, blocks from the shape: ONE ``tpu_custom_call``, named
    ``%ssm_scan`` (``benchmark/layer_metrics/ssm_scan_roofline.py`` matches
    it by that name), result ``[images, tokens, channels]``."""
    import re

    from benchmark.layer_metrics import ssm_scan_roofline
    from ddim_cold_tpu.ops import selective_scan as ss

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, d, s = (SSM[k] for k in "nLds")
    act, col = sds((n, L, d), dtype), sds((n, L, s), dtype)
    text = jax.jit(ss.selective_scan).lower(
        act, act, sds((d, s), jnp.float32), col, col, sds((d,), jnp.float32),
        act).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert re.match(r"%ssm_scan(\.\d+)* = ", calls[0])
    assert int(ssm_scan_roofline.NAME.match(calls[0]).group(2)) == n


# --- the masked forward and the grouped product at Laguna-S-2.1's shapes ----

LAGUNA = dict(n=4, L=4097, kv=8, hd=128, window=512)


#: NVIDIA-Nemotron-3-Super's one attention layer at the cell's 2,048 px: 32
#: query heads on 2 (16 a K/V head), 16,385 tokens, causal, no position term
NEMOTRON_ATTENTION = dict(n=1, L=16385, kv=2, hd=128)


def _one_launch(text):
    """The one ``tpu_custom_call`` line of a compiled module's text."""
    (call,) = [line.strip().removeprefix("ROOT ")
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    return call


def _as_a_trace(call):
    """A readers' view whose trace holds that one launch, for a millisecond."""
    return types.SimpleNamespace(
        config={"head_dim": 128},
        trace=types.SimpleNamespace(devices={0: {"ops": [(0, 10 ** 6, call)]}}))


def _operands(call):
    """How many operands the launch's line names."""
    inside = call[call.index("custom-call(") + len("custom-call("):
                  call.index("custom_call_target")]
    return len(re.findall(r"%[\w.\-]+", inside))


@pytest.mark.parametrize("shape,heads,window,fold", [
    (LAGUNA, 72, LAGUNA["window"], 9), (LAGUNA, 48, None, 6),
    (NEMOTRON_ATTENTION, 32, None, 8)],
    ids=["laguna72", "laguna48", "nemotron32"])
def test_fwd_masked_lowers_at_the_published_shapes_and_keeps_its_name(
        shape, heads, window, fold, chip):
    """``ops/flash_attention.py``'s masked forward at 4 x 4,097 tokens, 72
    (window 512) and 48 (full) query heads of 128 on 8 K/V heads, and at
    Nemotron's 16,385 tokens, 32 heads on 2; blocks and the heads a program
    folds from the shape (9, 6, 8 at q blocks of 256:
    ``kernels.flash_fwd_fold``), inside the default scoped VMEM: ONE
    ``tpu_custom_call``, named ``%fwd_masked``, three operands, the result
    where ``o_proj`` reads it
    (``benchmark/layer_metrics/flash_masked_fwd_roofline.py`` matches it by
    that name and reads the head count off its width,
    ``flash_masked_mixed_roofline.py`` its kind off the operands), which the
    ``%fwd`` reader does not match."""
    from benchmark.layer_metrics import flash_fwd_roofline
    from benchmark.layer_metrics import flash_masked_fwd_roofline as reader
    from benchmark.layer_metrics import flash_masked_mixed_roofline as mixed
    from ddim_cold_tpu.obs import metrics

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, kv, hd = (shape[k] for k in ("n", "L", "kv", "hd"))
    metrics.reset()
    call = _one_launch(jax.jit(lambda q, k, v: fa.masked_attention(
        q, k, v, hd ** -0.5, causal=True, window=window)).lower(
        sds((n, L, heads, hd), jnp.bfloat16), sds((n, L, kv, hd), jnp.bfloat16),
        sds((n, L, kv, hd), jnp.bfloat16)).compile().as_text())
    assert fa._kernels.by_key("kernels.flash_fwd_fold") == {str(fold): 1}
    metrics.reset()
    m = reader.NAME.match(call)
    assert m and [int(g) for g in m.groups()[1:]] == [n, L, heads * hd]
    assert _operands(call) == 3
    assert not flash_fwd_roofline.NAME.match(call)
    assert [e[:2] for e in reader.events(_as_a_trace(call))] == [(n, heads)]
    assert [e[:2] for e in mixed.events(_as_a_trace(call))] == [(n, False)]


def _bench_config(name):
    """A configuration of the benchmark, as its drivers read it."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def test_the_laguna_forward_traced_for_the_tpu_turns_q_in_the_launch(chip):
    """One trace of the cell's whole forward (``laguna_s21_ep2_px1024`` as the
    benchmark builds it, shapes only) on the TPU's path: every one of its
    five attention layers hands ``fwd_masked`` the q that ``q_proj`` wrote and
    the launch turns it (``kernels.flash_fwd_rotary`` = ``kernel``)."""
    from benchmark.drivers import sample_closed_moe
    from ddim_cold_tpu.obs import metrics

    config = _bench_config("laguna_s21_ep2_px1024")
    model = sample_closed_moe.build_model(config)
    x = jnp.zeros((1, *config["img_size"], 3), jnp.float32)
    t = jnp.zeros((1,), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, t)
    metrics.reset()
    jax.eval_shape(model.apply, params, x, t)
    assert fa._kernels.by_key("kernels.flash_fwd_rotary") == {"kernel": 5}
    assert fa._kernels.by_key("kernels.flash_fwd_mask") == {
        "causal": 2, "window": 3}
    metrics.reset()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("kind,heads", [("sliding_attention", 72),
                                        ("full_attention", 48)])
def test_laguna_attention_compiled_for_the_chip_turns_q_in_the_launch(
        kind, heads, dtype, chip):
    """``models/laguna.GatedAttention`` at the cell's shape (4 x 4,097 tokens,
    72 heads under the window, 48 under the causal mask, 8 K/V heads of 128),
    compiled: ONE launch, named ``%fwd_masked``, whose q operand is
    ``q_proj``'s own GEMM in the model's dtype — no pass over q between
    them. In float32 the model still compiles, and turns q the same way. The
    float32 values of q's size that stay are the GATE's (``jnp.repeat(gate,
    128)`` and its product with the context, both sides of this change have
    them): none is ``q_proj``'s and none is a roll's slice, which is what
    ``apply_rotary`` left of q (k, a ninth to a sixth as wide, keeps them)."""
    from benchmark.layer_metrics import flash_masked_fwd_roofline as reader
    from ddim_cold_tpu.models.laguna import GatedAttention

    config = _bench_config("laguna_s21_ep2_px1024")
    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, hd = LAGUNA["n"], LAGUNA["L"], LAGUNA["hd"]
    module = GatedAttention(config, kind, heads, dtype=dtype, param_dtype=dtype)
    x = jnp.zeros((n, L, config["hidden_size"]), dtype)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    text = jax.jit(module.apply).lower(
        jax.tree.map(lambda a: sds(a.shape, a.dtype), params),
        sds(x.shape, x.dtype)).compile().as_text()
    entry = text[text.index("ENTRY "):]
    made = {m.group(1): m for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\][^ ]* ([\w\-]+)\((.*)$",
        entry, flags=re.M)}
    calls = [m for m in made.values() if "tpu_custom_call" in m.group(5)]
    assert len(calls) == 1 and reader.NAME.match(calls[0].group(0).strip())
    # the launch the readers know: q, k, v and the two tables, the result as
    # wide as q — whatever group of heads a program of it folds
    launch = calls[0].group(0).strip().removeprefix("ROOT ")
    assert _operands(launch) == 5
    assert [e[:2] for e in reader.events(_as_a_trace(launch))] == [(n, heads)]
    wide = f"{n},{L},{heads * hd}"
    assert calls[0].group(3) == wide
    short = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    # the launch's first operand, through bitcasts, is q_proj's GEMM
    source = made[re.match(r"(%[\w.\-]+)", calls[0].group(5)).group(1)]
    while source.group(4) == "bitcast":
        source = made[re.match(r"(%[\w.\-]+)", source.group(5)).group(1)]
    assert "q_proj/dot_general" in source.group(5), source.group(0)
    assert (source.group(2), source.group(3)) == (short, wide)
    k_width = config["num_key_value_heads"] * hd
    for m in re.finditer(r"= f32\[([\d,]+)\][^\n]*", text):
        if int(m.group(1).split(",")[-1]) > k_width:  # wider than k is
            assert "_roll_static" not in m.group(0), m.group(0)
            assert dtype == jnp.float32 or "q_proj" not in m.group(0), m.group(0)


@pytest.mark.parametrize("rows,K,N", [
    (81940, 3072, 1024),    # the rows routed here on average: gate, up
    (163968, 3072, 1024),   # the expert layer's buffer: every assignment
    (163968, 1024, 3072),   # down
])
def test_moe_gmm_lowers_at_the_published_shapes_and_keeps_its_name(
        rows, K, N, chip):
    """``ops/grouped_matmul.py`` over 128 groups of Laguna-S-2.1's expert
    width, tiles from the shape: ONE ``tpu_custom_call``, named ``%moe_gmm``
    (``benchmark/layer_metrics/moe_gmm_roofline.py`` matches it by that name),
    result ``[rows in whole tiles, N]``."""
    from benchmark.layer_metrics import moe_gmm_roofline as reader
    from ddim_cold_tpu.ops import grouped_matmul as gm

    sds = _struct(SingleDeviceSharding(chip[0]))
    text = jax.jit(gm.grouped_matmul).lower(
        sds((rows, K), jnp.bfloat16), sds((128, K, N), jnp.bfloat16),
        sds((128,), jnp.int32)).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    m = reader.NAME.match(calls[0])
    assert m and [int(g) for g in m.groups()[1:]] == [-(-rows // 128) * 128, N]


@pytest.mark.parametrize("rows,K,F,groups,whole", [
    (163968, 3072, 1024, 128, False),  # Laguna-S-2.1: 4 x 4,097 tokens x 10
    (73856, 6144, 2048, 16, False),    # GLM-5.2: 9,217 tokens x 8, 16 held
    (163968, 3072, 1024, 128, True),   # the layer's two launches, open tail
])
def test_moe_gate_up_lowers_to_one_launch_of_the_same_name(
        rows, K, F, groups, whole, chip):
    """The expert MLP's first half at both expert cells' shapes, tiles and
    scoped VMEM from the shape: ONE ``tpu_custom_call`` for gate, up and
    ``SiLU(g) * u``, still named ``%moe_gmm`` with result ``[buffer rows,
    F]``, as ``moe_gmm_roofline`` matches it; the ``whole`` MLP is that launch
    and down's, two where it was three."""
    from benchmark.layer_metrics import moe_gmm_roofline as reader
    from ddim_cold_tpu.ops import grouped_matmul as gm

    sds = _struct(SingleDeviceSharding(chip[0]))
    bank = sds((groups, K, F), jnp.bfloat16)
    down = [sds((groups, F, K), jnp.bfloat16)] if whole else []
    text = jax.jit(gm.grouped_mlp if whole else gm.grouped_gate_up).lower(
        sds((rows, K), jnp.bfloat16), bank, bank, *down,
        sds((groups,), jnp.int32)).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    shapes = [[int(g) for g in reader.NAME.match(call).groups()[1:]]
              for call in calls]
    assert shapes == [[rows, F]] + [[rows, K]] * whole


# --- the selection and the selected forward at GLM-5.2's shapes -------------

GLM = dict(n=1, L=9217, heads=64, hd=256, index_heads=32, index_dim=128,
           top=2048)


def test_the_selection_lowers_at_the_published_shapes_without_a_sort(chip):
    """``ops/sparse_select.py`` at 9,217 tokens, 32 index heads of 128, the
    2,048 best: TWO ``tpu_custom_call``s, ``%dsa_index`` (float32 scores in
    whole blocks) and ``%dsa_select`` (their int8 selection), as the readers
    under ``benchmark/layer_metrics`` match them by name — and no ``sort`` or
    ``topk`` in the program."""
    from benchmark.layer_metrics import dsa_index_roofline, dsa_select_roofline
    from ddim_cold_tpu.ops import sparse_select as ss

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, J, D = (GLM[k] for k in ("n", "L", "index_heads", "index_dim"))
    text = jax.jit(lambda q, k, w: ss.select(q, k, w, GLM["top"])).lower(
        sds((n, L, J, D), jnp.bfloat16), sds((n, L, D), jnp.bfloat16),
        sds((n, L, J), jnp.float32)).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    length = ss.mask_length(L, jnp.bfloat16)
    for reader, call, dtype in ((dsa_index_roofline, calls[0], "f32"),
                                (dsa_select_roofline, calls[1], "s8")):
        m = reader.NAME.match(call)
        assert m and [int(g) for g in m.groups()[1:]] == [n, length, length]
        assert f" = {dtype}[" in call
    assert not re.search(r"\bsort\(|\btopk\(|TopK", text)


@pytest.mark.parametrize("pairing", [None, "interleave", "rotate_half"])
def test_fwd_selected_lowers_at_the_published_shapes_and_keeps_its_name(
        pairing, chip):
    """The attention forward over the selection at 9,217 tokens, 64 heads of
    256 read in place: ONE ``tpu_custom_call``, named ``%fwd_selected``
    (``benchmark/layer_metrics/flash_selected_fwd_roofline.py`` matches it by
    that name), which the ``%fwd_masked`` and ``%fwd`` readers do not match.
    Handed an unturned q and the rotation of each head's last 64 dims
    (``pairing``), the SAME one launch under the same name, which turns q
    itself: beside it the program makes the two ``(9728, 128)`` float32
    tables and nothing of q's size, in float32 or any other type."""
    from benchmark.layer_metrics import flash_fwd_roofline
    from benchmark.layer_metrics import flash_masked_fwd_roofline
    from benchmark.layer_metrics import flash_selected_fwd_roofline as reader
    from ddim_cold_tpu.ops import sparse_select as ss
    from ddim_cold_tpu.ops.rotary import Rotary

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, H, hd = (GLM[k] for k in ("n", "L", "heads", "hd"))
    length = ss.mask_length(L, jnp.bfloat16)
    head = sds((n, L, H, hd), jnp.bfloat16)
    rotary = pairing and Rotary(
        8000000.0 ** (-np.arange(0, 64, 2) / 64), 1.0, pairing, hd - 64)
    text = jax.jit(lambda q, k, v, keep: fa.selected_attention(
        q, k, v, hd ** -0.5, keep, rotary)).lower(
        head, head, head, sds((n, length, length), jnp.int8)
    ).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    m = reader.NAME.match(calls[0])
    assert m and [int(g) for g in m.groups()[1:]] == [n, L, H * hd]
    assert not flash_masked_fwd_roofline.NAME.match(calls[0])
    assert not flash_fwd_roofline.NAME.match(calls[0])
    made = set(re.findall(r"= (\w+\[[\d,]+\])", text))
    assert {a for a in made if a.endswith(f"[{n},{L},{H * hd}]")} == {
        f"bf16[{n},{L},{H * hd}]"}  # the launch's result
    assert (f"f32[{length},128]" in made) == bool(pairing)


def test_the_glm_forward_traced_for_the_tpu_turns_q_in_the_launch(chip):
    """One trace of the cell's whole forward (``glm52_ep16_px1536`` as the
    benchmark builds it, shapes only) on the TPU's path: every one of its five
    attention layers hands ``fwd_selected`` an unturned q and the launch
    turns it (``kernels.flash_fwd_rotary`` = ``kernel``)."""
    from benchmark.drivers import sample_closed_glm
    from ddim_cold_tpu.obs import metrics

    config = _bench_config("glm52_ep16_px1536")
    model = sample_closed_glm.build_model(config)
    x = jnp.zeros((1, *config["img_size"], 3), jnp.float32)
    t = jnp.zeros((1,), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, t)
    metrics.reset()
    jax.eval_shape(model.apply, params, x, t)
    assert fa._kernels.by_key("kernels.flash_fwd_rotary") == {"kernel": 5}
    assert fa._kernels.by_key("kernels.flash_fwd_mask") == {"selected": 5}
    metrics.reset()


# --- the latent forward at openPangu-Ultra-MoE's shapes ----------------------

PANGU = dict(n=1, L=9217, heads=128, nope=128, rot=64, vd=128)


def test_fwd_latent_lowers_at_the_published_shapes_and_keeps_its_name(chip):
    """The two-part-score forward at 9,217 tokens, 128 heads of 128 + 64
    query/key dims (the rotated 64 ONE row a token for all the heads) against
    128 value dims, bf16, blocks from the shape and the VMEM row: ONE
    ``tpu_custom_call``, named ``%fwd_latent``
    (``benchmark/layer_metrics/flash_latent_fwd_roofline.py`` matches it by
    that name), which the other three forwards' readers do not match; and
    nothing beside it but k_r laid twice over: no pad, no slice, no transpose
    of q, k or v."""
    from benchmark.layer_metrics import flash_fwd_roofline
    from benchmark.layer_metrics import flash_latent_fwd_roofline as reader
    from benchmark.layer_metrics import flash_masked_fwd_roofline
    from benchmark.layer_metrics import flash_selected_fwd_roofline

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, H, nope, rot, vd = (PANGU[k] for k in
                              ("n", "L", "heads", "nope", "rot", "vd"))
    bf = jnp.bfloat16
    text = jax.jit(lambda qn, qr, kn, kr, v: fa.latent_attention(
        qn, qr, kn, kr, v, (nope + rot) ** -0.5)).lower(
        sds((n, L, H, nope), bf), sds((n, L, H, rot), bf),
        sds((n, L, H, nope), bf), sds((n, L, rot), bf), sds((n, L, H, vd), bf)
    ).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    m = reader.NAME.match(calls[0])
    assert m and [int(g) for g in m.groups()[1:]] == [n, L, H * vd]
    for other in (flash_fwd_roofline, flash_masked_fwd_roofline,
                  flash_selected_fwd_roofline):
        assert not other.NAME.match(calls[0])
    # every array the program makes beside the result is k_r's size
    big = re.findall(r"= bf16\[1,9217,(\d+)\]", text)
    assert sorted(set(map(int, big)) - {H * nope, H * rot, H * vd, rot}) == [128]


# --- the chunked scan and the latent experts at Nemotron-3-Super's shapes ----

NEMOTRON = dict(n=1, L=16385, heads=128, head_dim=64, states=128, groups=8,
                chunk=128, held=128, latent=1024, width=2688, top=22)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssd_chunk_lowers_at_the_published_shape_and_keeps_its_name(dtype, chip):
    """``ops/ssd.py`` at 16,385 tokens (128 chunks of 128 and one token: the
    sequence ends inside the last block, nothing is padded), 128 heads of 64
    channels x 128 states in 8 groups: ONE ``tpu_custom_call``, named
    ``%ssd_chunk`` (``benchmark/layer_metrics/ssd_chunk_roofline.py`` matches
    it by that name and tells a launch that applies the gate by its operands:
    this one has six), result ``[images, tokens, heads x channels]``; x, B and
    C reach it as they are, no copy of their size beside it."""
    from benchmark.layer_metrics import ssd_chunk_roofline as reader
    from ddim_cold_tpu.ops import ssd

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, H, P_, N, G, Q = (NEMOTRON[k] for k in (
        "n", "L", "heads", "head_dim", "states", "groups", "chunk"))
    wide, shared = sds((n, L, H * P_), dtype), sds((n, L, G * N), dtype)
    per_head = sds((H,), jnp.float32)
    text = jax.jit(lambda *a: ssd.ssd_scan(*a, groups=G, chunk=Q)).lower(
        wide, sds((n, L, H), jnp.float32), per_head, shared, shared, per_head
    ).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    m = reader.NAME.match(calls[0])
    assert m and int(m.group(2)) == n
    assert f"[{n},{L},{H * P_}]" in calls[0].split(" custom-call(")[0]
    trace = types.SimpleNamespace(devices={0: {"ops": [(0, 1000, calls[0])]}})
    (images, gated, _), = reader.events(types.SimpleNamespace(trace=trace))
    assert (images, gated) == (n, False)


@pytest.mark.parametrize("K,N,whole", [
    (1024, 2688, False),   # up, under its squared ReLU
    (2688, 1024, False),   # down
    (1024, 2688, True),    # the layer's two launches, open tail
])
def test_moe_gmm_lowers_at_the_latent_experts_shapes_and_keeps_its_name(
        K, N, whole, chip):
    """The ungated expert MLP over 128 held experts in a 1,024-wide latent,
    the buffer of every assignment of 16,385 tokens x 22 (360,576 rows in
    whole tiles), tiles from the shape: ONE ``tpu_custom_call`` a product,
    named ``%moe_gmm`` with result ``[buffer rows, N]``, as
    ``moe_gmm_roofline``'s events (which ``moe_gmm_latent_roofline`` reads)
    match it."""
    from benchmark.layer_metrics import moe_gmm_roofline as reader
    from ddim_cold_tpu.ops import grouped_matmul as gm

    sds = _struct(SingleDeviceSharding(chip[0]))
    held, top, L = (NEMOTRON[k] for k in ("held", "top", "L"))
    rows = -(-L * top // 128) * 128
    assert rows == 360576
    bank = sds((held, K, N), jnp.bfloat16)
    sizes = sds((held,), jnp.int32)
    if whole:
        fn = lambda r, up, down, s: gm.grouped_mlp(r, None, up, down, s)
        args = (sds((rows, K), jnp.bfloat16), bank,
                sds((held, N, K), jnp.bfloat16), sizes)
    else:
        fn = gm.grouped_relu2 if K < N else gm.grouped_matmul
        args = (sds((rows, K), jnp.bfloat16), bank, sizes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    shapes = [[int(g) for g in reader.NAME.match(call).groups()[1:]]
              for call in calls]
    assert shapes == [[rows, N]] + [[rows, K]] * whole


# --- the gated delta-rule scan at Kimi-Linear's shapes -----------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_chunk_lowers_at_the_published_shape_and_keeps_its_name(dtype, chip):
    """``ops/kda.py`` at 16,385 tokens (128 chunks of 128 and one token: the
    sequence ends inside the last block, nothing is padded), 32 heads of a
    128 x 128 state: ONE ``tpu_custom_call``, named ``%kda_chunk``
    (``benchmark/layer_metrics/kda_chunk_roofline.py`` matches it by that name
    and tells a launch that applies the output gate by its operands: this one
    has five), result ``[images, tokens, heads x channels]``; q, k and v
    reach it as they are."""
    from benchmark.layer_metrics import kda_chunk_roofline as reader
    from ddim_cold_tpu.ops import kda

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, H, d = 1, 16385, 32, 128
    assert L == 128 * kda.CHUNK + 1 and kda.kernel_admits(H, d)
    wide = sds((n, L, H * d), dtype)
    text = jax.jit(lambda *a: kda.kda_scan(*a, d ** -0.5)).lower(
        wide, wide, wide, sds((n, L, H * d), jnp.float32),
        sds((n, L, H), jnp.float32)).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    m = reader.NAME.match(calls[0])
    assert m and int(m.group(2)) == n
    assert f"[{n},{L},{H * d}]" in calls[0].split(" custom-call(")[0]
    if dtype == jnp.bfloat16:  # q, k, v as handed
        assert all(f"%a_{i}_" in calls[0] for i in range(3))
    trace = types.SimpleNamespace(devices={0: {"ops": [(0, 1000, calls[0])]}})
    (images, gated, _), = reader.events(types.SimpleNamespace(trace=trace))
    assert (images, gated) == (n, False)


# --- the short convolution at the three state-space stacks' shapes -----------

@pytest.mark.parametrize("shape,bias,l2_head_dim,dtype", [
    ((1, 16385, 4096), False, None, jnp.bfloat16),    # Kimi's v
    ((1, 16385, 4096), False, 128, jnp.bfloat16),     # Kimi's q and k
    ((1, 16385, 10240), True, None, jnp.bfloat16),    # Nemotron's xBC
    ((4, 1025, 5120), True, None, jnp.bfloat16),      # Jamba's u
    ((1, 16385, 4096), False, 128, jnp.float32),      # a float32 model's
], ids=["kimi_v", "kimi_qk", "nemotron", "jamba", "float32"])
def test_causal_conv_lowers_at_the_published_shapes_and_keeps_its_name(
        shape, bias, l2_head_dim, dtype, chip):
    """``ops/short_conv.py`` through its dispatcher: ONE ``tpu_custom_call``,
    named ``%causal_conv``, whose first operand is ``u`` as it is handed (no
    float32 copy, nothing padded: 16,385 and 1,025 tokens end inside the last
    block) and whose result has ``u``'s shape and dtype; the counter reads
    ``kernel``."""
    from ddim_cold_tpu.obs import metrics
    from ddim_cold_tpu.ops import short_conv

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, d = shape
    assert short_conv.kernel_admits(d, 4, l2_head_dim)
    args = [sds(shape, dtype), sds((4, d), dtype)] + [sds((d,), dtype)] * bias
    metrics.reset()
    text = jax.jit(lambda u, w, b=None: short_conv.causal_conv(
        u, w, b, l2_head_dim=l2_head_dim)).lower(*args).compile().as_text()
    by_key = {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.causal_conv_schedule/by_key", {}))
    assert by_key == {"kernel": 1}
    metrics.reset()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and calls[0].startswith("%causal_conv")
    kind = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    head, operands = calls[0].split(" custom-call(")
    assert f"{kind}[{n},{L},{d}]" in head
    if n == 1 and dtype == jnp.bfloat16:
        # the parameter itself. (Alone, XLA lays a (4, 1,025, ·) parameter
        # out images-minor and copies it for the launch; inside a mixer's
        # program the projection writes the launch's layout: PERF.md, PR 46.)
        assert operands.startswith("%u")


# --- the masked forward and the ReLU-gated expert product at
# --- SmallThinker-21BA3B-Instruct's shapes --------------------------------

SMALLTHINKER = dict(n=1, L=16130, heads=28, kv=4, hd=128, window=4096,
                    theta=1.5e6, hidden=2560, width=768, experts=64,
                    rows=96896)  # 16,130 x 6 assignments in whole tiles


@pytest.mark.parametrize("windowed", [True, False])
def test_fwd_masked_lowers_at_the_smallthinker_shapes_and_says_its_kind(
        windowed, chip):
    """The masked forward at 16,130 tokens, 28 query heads on 4 K/V heads of
    128 (7 a K/V head): a window layer's (window 4,096 = eight chunks of 512,
    q turned in the launch by the default rotary, θ = 1.5 M) and a full
    layer's (causal, ``rotary=None``: no table, no turn). ONE
    ``tpu_custom_call`` each, named ``%fwd_masked``, the same result shape —
    and ``flash_masked_mixed_roofline`` tells them apart by their operands,
    five against three. Either kind's program folds all seven heads of a K/V
    head at q blocks of 256 (``kernels.flash_fwd_fold``)."""
    from benchmark.layer_metrics import flash_masked_fwd_roofline as by_width
    from benchmark.layer_metrics import flash_masked_mixed_roofline as reader
    from ddim_cold_tpu.models.laguna import rotary_frequencies
    from ddim_cold_tpu.obs import metrics
    from ddim_cold_tpu.ops.rotary import Rotary

    s = SMALLTHINKER
    sds = _struct(SingleDeviceSharding(chip[0]))
    rotary = (Rotary(*rotary_frequencies({"rope_theta": s["theta"]}, s["hd"]))
              if windowed else None)
    metrics.reset()
    call = _one_launch(jax.jit(lambda q, k, v: fa.masked_attention(
        q, k, v, s["hd"] ** -0.5, causal=True,
        window=s["window"] if windowed else None, rotary=rotary)).lower(
        sds((s["n"], s["L"], s["heads"], s["hd"]), jnp.bfloat16),
        *[sds((s["n"], s["L"], s["kv"], s["hd"]), jnp.bfloat16)] * 2,
        ).compile().as_text())
    assert fa._kernels.by_key("kernels.flash_fwd_fold") == {"7": 1}
    metrics.reset()
    assert _operands(call) == (5 if windowed else 3)
    assert [e[:2] for e in reader.events(_as_a_trace(call))] == [
        (s["n"], windowed)]
    m = by_width.NAME.match(call)
    assert [int(g) for g in m.groups()[1:]] == [s["n"], s["L"],
                                                s["heads"] * s["hd"]]


@pytest.mark.parametrize("rep,lanes,tokens,turned,dtype,fold", [
    (9, 128, 4097, True, jnp.float32, 9),    # the budget's 2,304 rows, f32
    (18, 128, 8200, True, jnp.bfloat16, 9),
    (4, 256, 9217, True, jnp.float32, 4),    # heads of 256: half the rows
    (4, 256, 9217, False, jnp.bfloat16, 4),
    (16, 128, 16385, False, jnp.float32, 8),
])
def test_the_fold_budget_admits_only_what_compiles(rep, lanes, tokens, turned,
                                                   dtype, fold, chip):
    """What :func:`_masked_fold` chooses at the edge of its row budget —
    nine heads of 128, four of 256, float32 too, with the in-launch turn's
    tables and scratch — compiles under the default scoped VMEM."""
    from ddim_cold_tpu.models.laguna import rotary_frequencies
    from ddim_cold_tpu.ops.rotary import Rotary

    sds = _struct(SingleDeviceSharding(chip[0]))
    rotary = (Rotary(*rotary_frequencies({"rope_theta": 1e6}, 128))
              if turned else None)
    assert fa._masked_fold(rep, tokens, lanes, dtype) == (fold, 256)
    q, kv = (sds((1, tokens, heads, lanes), dtype) for heads in (rep * 2, 2))
    _one_launch(jax.jit(lambda q, k, v: fa.masked_attention(
        q, k, v, 0.1, rotary=rotary)).lower(q, kv, kv).compile().as_text())


def _without_locations(text):
    """(sha256 of the lowered module with every Mosaic body taken out, sha256
    of each body as MLIR text without its source locations): what a launch
    lowers to, whatever line of ``flash_attention.py`` its code stands on."""
    import base64
    import hashlib
    import json

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    sha = lambda t: hashlib.sha256(t.encode()).hexdigest()[:12]
    config = re.compile(r'backend_config = "((?:[^"\\]|\\.)*)"')
    bodies = []
    for found in config.finditer(text):
        body = json.loads(found.group(1).replace("\\22", '"'))[
            "custom_call_config"]["body"]
        context = jax_mlir.make_ir_context()
        context.allow_unregistered_dialects = True  # stable_mosaic's version
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            bodies.append(sha(module.operation.get_asm(enable_debug_info=False)))
    return sha(config.sub('backend_config = "..."', text)), bodies


#: :func:`_without_locations` of the launches that keep one head a program,
#: lowered for the described chip. A change that MEANS to alter one of these
#: programs pins it anew; the fold must not. At the cells' 9,217 and 16,385
#: tokens the last q block holds one row, and the four were pinned anew by the
#: PR that gave that block folds on 32 rows; ``_whole``: the same launches one
#: token shorter, a whole number of blocks, taken on 533c891, that PR's
#: parent — a sequence without a short last block lowers to the module and
#: the Mosaic body it had.
ONE_HEAD_LAUNCHES = {
    "selected": ("af383f7c3848", ["f2087488c5ad"]),
    "selected_turned": ("2596f1c5065d", ["e69550338ded"]),
    "latent": ("8c2ffd4dec3c", ["8d3d1c3f1055"]),
    "masked_a_head_a_kv_head": ("369e4dba0d96", ["e3f951bdb1e9"]),
    "selected_whole": ("8c17a76ab9ba", ["a4888bc20b26"]),
    "selected_turned_whole": ("8d89f7fbc8e9", ["17f17afb0cbc"]),
    "latent_whole": ("5aa2ce0adb1b", ["2b89314f7db7"]),
    "masked_a_head_a_kv_head_whole": ("7dcad43ebb04", ["b1f9557a15b8"]),
}


@pytest.mark.parametrize("launch", sorted(ONE_HEAD_LAUNCHES))
def test_one_head_launches_lower_to_the_text_they_had(launch, chip):
    """``fwd_selected`` (GLM's shape, with and without the in-launch turn),
    ``fwd_latent`` (Pangu's) and ``fwd_masked`` with a K/V head a query head,
    lowered for the TPU: the module and the Mosaic body, source locations
    apart, are the pinned ones — at a whole number of blocks, what they were
    before the last q block had folds of its own."""
    from ddim_cold_tpu.ops import sparse_select as ss
    from ddim_cold_tpu.ops.rotary import Rotary

    sds = _struct(SingleDeviceSharding(chip[0]))
    bf = jnp.bfloat16
    pinned, less = ONE_HEAD_LAUNCHES[launch], launch.endswith("_whole")
    launch = launch.removesuffix("_whole")
    if launch.startswith("selected"):
        n, L, H, hd = (GLM[k] for k in ("n", "L", "heads", "hd"))
        L -= less
        length = ss.mask_length(L, bf)
        rotary = None if launch == "selected" else Rotary(
            8000000.0 ** (-np.arange(0, 64, 2) / 64), 1.0, "interleave",
            hd - 64)
        head = sds((n, L, H, hd), bf)
        lowered = jax.jit(lambda q, k, v, keep: fa.selected_attention(
            q, k, v, hd ** -0.5, keep, rotary)).lower(
            head, head, head, sds((n, length, length), jnp.int8))
    elif launch == "latent":
        n, L, H, nope, rot, vd = (PANGU[k] for k in
                                  ("n", "L", "heads", "nope", "rot", "vd"))
        L -= less
        lowered = jax.jit(lambda qn, qr, kn, kr, v: fa.latent_attention(
            qn, qr, kn, kr, v, (nope + rot) ** -0.5)).lower(
            sds((n, L, H, nope), bf), sds((n, L, H, rot), bf),
            sds((n, L, H, nope), bf), sds((n, L, rot), bf),
            sds((n, L, H, vd), bf))
    else:
        q = sds((1, 16385 - less, 8, 128), bf)
        lowered = jax.jit(lambda q, k, v: fa.masked_attention(
            q, k, v, 0.1)).lower(q, q, q)
    assert _without_locations(lowered.as_text()) == pinned


@pytest.mark.parametrize("driver,config,tail,bodies", [
    ("sample_closed_pangu", "pangu_ultra_ep32_px1536", {"32/1024": 5}, 1),
    ("sample_closed_kimi", "kimi_linear_ep2_px2048", {"32/1024": 1}, 1),
    ("sample_closed_glm", "glm52_ep16_px1536", {"32/512": 5}, 1),
    ("sample_closed_moe", "laguna_s21_ep2_px1024", {"32/256": 5}, 2),
    ("sample_closed_smallthinker", "smallthinker_21b_l8_px2032",
     {"32/256": 8}, 2),
    ("sample_closed_nemotron", "nemotron3_super_ep4_px2048", {"32/256": 1}, 1),
    ("sample_closed_longcat", "longcat_flash_omni_l4_px1536", {"32/1024": 8}, 1),
])
def test_a_cells_forward_folds_its_last_q_block_on_32_rows(
        driver, config, tail, bodies, chip, monkeypatch):
    """One trace of each sampler cell's whole forward that launches the
    masked body (shapes only, the TPU's path): every launch's last q block
    holds one row of the ``k² + 1`` tokens (two of SmallThinker's 16,130) and
    folds on 32 — ``kernels.flash_fwd_tail`` reads ``32/<q block>`` once a
    launch and never ``whole`` — and the body is traced once a distinct
    launch, the latent launch keeping its trace as the masked one does (five
    sites a Pangu forward, one trace)."""
    import importlib

    from ddim_cold_tpu.obs import metrics

    traced = []
    body = fa._fwd_masked_kernel
    monkeypatch.setattr(
        fa, "_fwd_masked_kernel",
        lambda *refs, **kw: traced.append(kw["tail"]) or body(*refs, **kw))
    config = _bench_config(config)
    model = importlib.import_module(
        f"benchmark.drivers.{driver}").build_model(config)
    x = jnp.zeros((1, *config["img_size"], 3), jnp.float32)
    t = jnp.zeros((1,), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, t)
    jax.clear_caches()  # the launches' kept traces: ``init``'s among them
    traced.clear()
    metrics.reset()
    jax.eval_shape(model.apply, params, x, t)
    assert fa._kernels.by_key("kernels.flash_fwd_tail") == tail
    assert traced == [32] * bodies
    metrics.reset()


@pytest.mark.parametrize("launch", ["gate_up_relu", "down", "whole_relu"])
def test_moe_gmm_lowers_at_the_smallthinker_shapes_and_keeps_its_name(
        launch, chip):
    """The experts' two launches over all 64 groups of a layer, 96,896 buffer
    rows: gate, up and ``relu(g) * u`` at K 2,560, F 768 as ONE launch, down
    at K 768, N 2,560, and the whole MLP as those two; every one named
    ``%moe_gmm`` with result ``[buffer rows, N]``, as ``moe_gmm_roofline``'s
    events (which ``moe_gmm_reglu_roofline`` reads) match it."""
    from benchmark.layer_metrics import moe_gmm_roofline as reader
    from ddim_cold_tpu.ops import grouped_matmul as gm

    s = SMALLTHINKER
    rows, K, F, G = s["rows"], s["hidden"], s["width"], s["experts"]
    sds = _struct(SingleDeviceSharding(chip[0]))
    up, down = sds((G, K, F), jnp.bfloat16), sds((G, F, K), jnp.bfloat16)
    sizes = sds((G,), jnp.int32)
    if launch == "down":
        fn, args, want = gm.grouped_matmul, (sds((rows, F), jnp.bfloat16),
                                             down, sizes), [[rows, K]]
    elif launch == "gate_up_relu":
        fn = functools.partial(gm.grouped_gate_up, act="relu")
        args, want = (sds((rows, K), jnp.bfloat16), up, up, sizes), [[rows, F]]
    else:
        fn = functools.partial(gm.grouped_mlp, act="relu")
        args = (sds((rows, K), jnp.bfloat16), up, up, down, sizes)
        want = [[rows, F], [rows, K]]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [[int(g) for g in reader.NAME.match(call).groups()[1:]]
            for call in calls] == want


# --- LongCat-Flash-Omni's shapes ---------------------------------------------

LONGCAT = dict(n=1, L=9217, heads=64, nope=128, rot=64, vd=128, hidden=6144,
               width=2048, held=16, rows=110720)  # 9,217 x 12 in whole tiles


def test_fwd_latent_lowers_at_the_longcat_shapes_and_keeps_its_name(chip):
    """The two-part-score forward at 9,217 tokens and 64 heads of 128 + 64 /
    128 — between Kimi's 32 and Pangu's 128 at Pangu's length — bf16: ONE
    ``tpu_custom_call`` named ``%fwd_latent`` with result ``[1, 9217, 64 x
    128]``, as ``flash_latent_fwd_roofline`` matches it."""
    from benchmark.layer_metrics import flash_latent_fwd_roofline as reader

    sds = _struct(SingleDeviceSharding(chip[0]))
    n, L, H, nope, rot, vd = (LONGCAT[k] for k in
                              ("n", "L", "heads", "nope", "rot", "vd"))
    bf = jnp.bfloat16
    text = jax.jit(lambda qn, qr, kn, kr, v: fa.latent_attention(
        qn, qr, kn, kr, v, (nope + rot) ** -0.5)).lower(
        sds((n, L, H, nope), bf), sds((n, L, H, rot), bf),
        sds((n, L, H, nope), bf), sds((n, L, rot), bf), sds((n, L, H, vd), bf)
    ).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    m = reader.NAME.match(calls[0])
    assert m and [int(g) for g in m.groups()[1:]] == [n, L, H * vd]


@pytest.mark.parametrize("launch", ["gate_up", "down", "whole"])
def test_moe_gmm_lowers_at_the_longcat_shapes_and_keeps_its_name(launch, chip):
    """The experts' two launches over the 16 held groups of a layer, 110,720
    buffer rows (9,217 tokens x 12 picks in whole tiles, ~2,304 of them
    held): gate, up and ``SiLU(g) * u`` at K 6,144, F 2,048 as ONE launch,
    down at K 2,048, N 6,144, and the whole MLP as those two; every one named
    ``%moe_gmm`` with result ``[buffer rows, N]``, as ``moe_gmm_roofline``'s
    events (which ``moe_gmm_zero_roofline`` reads) match it."""
    from benchmark.layer_metrics import moe_gmm_roofline as reader
    from ddim_cold_tpu.ops import grouped_matmul as gm

    c = LONGCAT
    rows, K, F, G = c["rows"], c["hidden"], c["width"], c["held"]
    sds = _struct(SingleDeviceSharding(chip[0]))
    up, down = sds((G, K, F), jnp.bfloat16), sds((G, F, K), jnp.bfloat16)
    sizes = sds((G,), jnp.int32)
    if launch == "down":
        fn, args, want = gm.grouped_matmul, (sds((rows, F), jnp.bfloat16),
                                             down, sizes), [[rows, K]]
    elif launch == "gate_up":
        fn = gm.grouped_gate_up
        args, want = (sds((rows, K), jnp.bfloat16), up, up, sizes), [[rows, F]]
    else:
        fn = gm.grouped_mlp
        args = (sds((rows, K), jnp.bfloat16), up, up, down, sizes)
        want = [[rows, F], [rows, K]]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [[int(g) for g in reader.NAME.match(call).groups()[1:]]
            for call in calls] == want
