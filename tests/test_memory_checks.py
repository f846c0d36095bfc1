"""GRAFT-M self-tests: the liveness walk on small known programs (with and
without donation, nested bodies), the over-budget and padded-token
fixtures, and the clean run over the 200px sampler entries + serve sweep.

The walk's arithmetic is checked against hand-counted byte schedules —
the fixtures use (1024,) f32 arrays so every aval is exactly 4 KiB and
the expected peaks are knowable constants."""

import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.analysis import entries, memory_checks
from ddim_cold_tpu.analysis.findings import load_baseline, write_baseline

KB4 = 1024 * 4  # bytes of one (1024,) f32
X = jax.ShapeDtypeStruct((1024,), jnp.float32)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# --------------------------------------------------------- liveness walk


def test_peak_counts_chain_liveness():
    # x -> y -> z: x retained (not donated) so the peak holds all three
    def f(x):
        y = x + 1.0
        return y * 2.0

    closed = jax.make_jaxpr(f)(X)
    assert memory_checks._jaxpr_peak(closed.jaxpr) == 3 * KB4
    # donating x lets it die after eqn 0: never three live at once
    assert memory_checks._jaxpr_peak(closed.jaxpr, donated=(True,)) == 2 * KB4


def test_peak_live_bytes_unwraps_pjit_donation():
    def f(x):
        y = x + 1.0
        return y * 2.0

    plain = jax.make_jaxpr(jax.jit(f))(X)
    donated = jax.make_jaxpr(jax.jit(f, donate_argnums=0))(X)
    assert memory_checks.peak_live_bytes(plain) == 3 * KB4
    assert memory_checks.peak_live_bytes(donated) == 2 * KB4


def test_peak_counts_fanout_operands():
    # non-donated x is caller-retained: at the last eqn x, a, b and the
    # output d are all live; donating x frees it after its last use (the
    # mul), dropping the peak by one block
    def f(x):
        a = x + 1.0
        b = x * 2.0
        return a + b

    closed = jax.make_jaxpr(f)(X)
    assert memory_checks._jaxpr_peak(closed.jaxpr) == 4 * KB4
    assert memory_checks._jaxpr_peak(closed.jaxpr, donated=(True,)) == 3 * KB4


def test_nested_scan_body_adds_interior_peak_once():
    # the scan body materializes temporaries above its carry; one
    # iteration's interior stands in for all (XLA reuses body buffers)
    def f(x):
        def body(c, _):
            t = c + 1.0
            return t * 2.0, ()

        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    closed = jax.make_jaxpr(f)(X)
    peak = memory_checks.peak_live_bytes(closed)
    assert 2 * KB4 <= peak <= 4 * KB4, peak


def test_consts_are_resident():
    big = np.ones((1024,), np.float32)

    def f(x):
        return x + jnp.asarray(big)

    closed = jax.make_jaxpr(f)(X)
    assert memory_checks.peak_live_bytes(closed) >= 2 * KB4


# --------------------------------------------------------------- M001


def test_m001_over_budget_program():
    def f(x):
        return (x + 1.0) * 2.0

    closed = jax.make_jaxpr(jax.jit(f))(X)
    fs = memory_checks.check_peak_hbm(closed, "fix", "fix.py",
                                      budget_bytes=2 * KB4)
    assert [(f_.rule, f_.subject) for f_ in fs] == [
        ("GRAFT-M001", "fix:peak")]
    assert "shrink the bucket" in fs[0].message
    assert memory_checks.check_peak_hbm(closed, "fix", "fix.py",
                                        budget_bytes=4 * KB4) == []


# --------------------------------------------------------------- M002


def test_m002_padded_token_axis_at_200px():
    # a pad-to-4096 class bug at N=2501: 64% padding, over the 30% line
    def f(x):
        return x * 2.0

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4096, 8), jnp.float32))
    fs = memory_checks.check_padding(closed, "fix", "fix.py", tokens=2501)
    assert [(f_.rule, f_.subject) for f_ in fs] == [
        ("GRAFT-M002", "fix:pad")]
    assert "64%" in fs[0].message
    # the in-tree streamed-kv worst case (3072/2501 = 1.228) passes
    c2 = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((3072, 8), jnp.float32))
    assert memory_checks.check_padding(c2, "fix", "fix.py", tokens=2501) == []


def test_m002_abstains_below_min_tokens():
    # at the TINY sweep's 5 tokens the [tokens, 2·tokens) window catches
    # batch/pixel dims — the check must abstain, not guess
    def f(x):
        return x * 2.0

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 16, 16, 3),
                                                    jnp.float32))
    assert memory_checks.check_padding(closed, "fix", "fix.py", tokens=5) == []
    assert 5 < memory_checks.MIN_PAD_TOKENS <= entries.NS_TOKENS


# ------------------------------------------------- baseline + clean tree


def test_m_finding_keys_round_trip(tmp_path):
    def f(x):
        return x + 1.0

    closed = jax.make_jaxpr(jax.jit(f))(X)
    fs = memory_checks.check_program(closed, "fix", "fix.py", tokens=2501,
                                     budget_bytes=KB4)
    assert _rules_of(fs) == ["GRAFT-M001"]
    base = tmp_path / "baseline.txt"
    write_baseline(str(base), fs)
    assert load_baseline(str(base)) == {f_.key for f_ in fs}


def _peak_through_composition(entry, monkeypatch) -> int:
    """``entry``'s peak with the float trunk's token-wise kernels turned
    away (``row_block`` answers None, as it does for a shape they do not
    take). The sampler's jit caches its trace by model and shapes, which the
    patch does not change, so the caches are dropped around the trace."""
    from ddim_cold_tpu.ops import block_kernels

    with monkeypatch.context() as m:
        m.setattr(block_kernels, "row_block", lambda *a, **k: None)
        jax.clear_caches()
        peak = memory_checks.peak_live_bytes(entry.trace())
    jax.clear_caches()
    return peak


def test_clean_in_tree_memory(kernel_traces, monkeypatch):
    """The acceptance gate: every 200px sampler program's donation-aware
    peak fits the v5e HBM budget and carries no over-threshold padding,
    and the peaks are sane (params + a 200px batch land well under a GiB
    at TINY depths, nonzero because params are resident)."""
    fs = memory_checks.run_memory_checks(serve_traces={},
                                         kernel_traces=kernel_traces)
    assert [f.render() for f in fs] == []
    peaks = {name: memory_checks.peak_live_bytes(c)
             for name, (e, c) in kernel_traces.items()
             if (e.meta or {}).get("memory")}
    assert set(peaks) == {"ns200_f32", "ns200_bf16", "ns200_w8a16",
                          "ns200_w8a16_fused", "ns200_w8a8_fused",
                          "ns200_fewstep4_bf16"}
    for name, peak in peaks.items():
        assert 10 * 2**20 < peak < 2**31, (name, peak)
    # quantized weights must not peak above the f32 build, like against
    # like. The float trunk's token-wise half runs as two kernels
    # (ops/block_kernels.py) whose intermediates live in VMEM and never in
    # this walk, while the unfused quantised trunk keeps the XLA composition:
    # so the unfused one is held against the f32 build forced through the
    # same composition (the guard as it stood before the kernels, whatever
    # path the float build takes), and the fused one against the kernels
    assert peaks["ns200_w8a16"] < _peak_through_composition(
        kernel_traces["ns200_f32"][0], monkeypatch)
    assert peaks["ns200_w8a16_fused"] < peaks["ns200_f32"]
    # fusing deletes intermediates; it must not grow the liveness peak
    assert peaks["ns200_w8a16_fused"] <= peaks["ns200_w8a16"] * 1.05
    # the few-step scan holds one sampler state, not k of them — its peak
    # stays in family with the stride sampler at the same dtype
    assert peaks["ns200_fewstep4_bf16"] <= peaks["ns200_bf16"] * 1.05
