"""graftcheck self-tests: one deliberately violating fixture per rule
(asserting the stable rule id, file, and — for source lint — line), the
baseline grammar, and the clean-tree run (zero non-baselined findings on
the repo as committed, which is what CI enforces).

Each jaxpr fixture is a tiny jitted function exhibiting exactly one hazard;
each AST fixture is a source snippet fed through ``lint_source`` so the
line numbers are knowable constants."""

import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.analysis import ast_checks, cli, collective_checks, entries
from ddim_cold_tpu.analysis import jaxpr_checks, sharding_checks, thread_checks
from ddim_cold_tpu.analysis.findings import (
    RULES, Finding, load_baseline, rule_layer, write_baseline)

SITES = ("serve.assemble", "ckpt.save")  # a registry slice for lint fixtures


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- jaxpr rules


def test_j001_low_precision_accumulation():
    f = jax.jit(lambda a, b: a @ b)
    x = jax.ShapeDtypeStruct((8, 16), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((16, 8), jnp.bfloat16)
    closed = jax.make_jaxpr(f)(x, w)
    fs = jaxpr_checks.check_accumulation(closed, "fix", "fix.py")
    assert _rules_of(fs) == ["GRAFT-J001"]
    assert fs[0].path == "fix.py" and "dot_general" in fs[0].subject

    # the designed pattern — bf16 operands, f32 accumulate — must pass
    g = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))
    assert jaxpr_checks.check_accumulation(
        jax.make_jaxpr(g)(x, w), "ok", "ok.py") == []


def test_j002_weak_typed_output():
    f = jax.jit(lambda: jnp.sin(1.0))  # python float → weak f32 out
    fs = jaxpr_checks.check_weak_types(jax.eval_shape(f), "fix", "fix.py")
    assert _rules_of(fs) == ["GRAFT-J002"]

    g = jax.jit(lambda: jnp.sin(jnp.float32(1.0)))
    assert jaxpr_checks.check_weak_types(jax.eval_shape(g), "ok", "ok.py") == []


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_j003_dropped_donation():
    @partial(jax.jit, donate_argnums=(0,))
    def f(x):
        return x.sum()  # () out can never alias the (8, 8) donation

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    fs = jaxpr_checks.check_donation(
        f.lower(x).args_info, jax.eval_shape(f, x), "fix", "fix.py")
    assert _rules_of(fs) == ["GRAFT-J003"]

    @partial(jax.jit, donate_argnums=(0,))
    def g(x):
        return x * 2.0  # same aval out — donation lands

    assert jaxpr_checks.check_donation(
        g.lower(x).args_info, jax.eval_shape(g, x), "ok", "ok.py") == []


def test_j003_expected_donation_absent():
    f = jax.jit(lambda x: x * 2.0)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    fs = jaxpr_checks.check_donation(
        f.lower(x).args_info, jax.eval_shape(f, x), "fix", "fix.py",
        expect_donation=True)
    assert [f_.subject for f_ in fs] == ["fix:<none-donated>"]


def test_j004_oversized_constant():
    big = jnp.asarray(np.ones((600, 600), np.float32))  # 1.44 MB closure
    f = jax.jit(lambda x: x + big)
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((600, 600), jnp.float32))
    fs = jaxpr_checks.check_constants(closed, "fix", "fix.py")
    assert _rules_of(fs) == ["GRAFT-J004"]
    # raising the threshold clears it — the knob the CLI exposes
    assert jaxpr_checks.check_constants(closed, "fix", "fix.py",
                                        max_bytes=2 << 20) == []


def test_j005_host_callback_in_scan():
    def body(c, _):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), c)
        return c + y, None

    f = jax.jit(lambda x: jax.lax.scan(body, x, None, length=3)[0])
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((), jnp.float32))
    fs = jaxpr_checks.check_host_callbacks(closed, "fix", "fix.py")
    assert _rules_of(fs) == ["GRAFT-J005"]
    assert fs[0].subject == "fix:pure_callback"

    # the same callback OUTSIDE a loop body is not this rule's business
    g = jax.jit(lambda x: jax.pure_callback(
        lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x))
    assert jaxpr_checks.check_host_callbacks(
        jax.make_jaxpr(g)(jax.ShapeDtypeStruct((), jnp.float32)),
        "ok", "ok.py") == []


def test_j007_while_primitive_flagged():
    # a while_loop anywhere in the program (nested under jit included) is a
    # data-dependent trip count — the exact thing the adaptive drift gate
    # must never introduce into a served sampler
    f = jax.jit(lambda x: jax.lax.while_loop(
        lambda v: v < 10.0, lambda v: v + 1.0, x))
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((), jnp.float32))
    fs = jaxpr_checks.check_static_trip_count(closed, "fix", "fix.py")
    assert _rules_of(fs) == ["GRAFT-J007"]
    assert fs[0].subject == "fix:while"

    # a static-trip scan (the gate's actual home) is clean
    g = jax.jit(lambda x: jax.lax.scan(
        lambda c, _: (c + 1.0, None), x, None, length=4)[0])
    assert jaxpr_checks.check_static_trip_count(
        jax.make_jaxpr(g)(jax.ShapeDtypeStruct((), jnp.float32)),
        "ok", "ok.py") == []


# -------------------------------------------------- serve signature (J006)


def test_serve_sweep_matches_test_serve_geometry():
    import tests.test_serve as ts

    assert entries.TINY == ts.TINY
    assert entries.K == ts.K


def test_j006_serve_signatures_stable_and_distinct():
    sigs_a = entries.serve_signatures(entries.Context())
    sigs_b = entries.serve_signatures(entries.Context())
    assert sigs_a == sigs_b  # retrace from a fresh model world → same programs
    assert len(set(sigs_a.values())) == len(sigs_a)  # all pairs distinct
    # every warmed (config, bucket) pair of tests/test_serve.py is covered
    assert {"ddim_k500:b4", "ddim_k500:b8", "ddim_k500_ci2:b4",
            "cold_l4:b8", "ddim_k500_t999:b4",
            "ddim_k500_qxla:b4"} <= set(sigs_a)
    assert entries.run_serve_signature_check() == []


def test_j006_collision_detected(monkeypatch):
    from ddim_cold_tpu.serve.batching import SamplerConfig

    # two labels, identical (config, bucket) → identical trace → collision
    monkeypatch.setattr(entries, "serve_sweep", lambda: [
        ("a", SamplerConfig(k=entries.K), (4,)),
        ("b", SamplerConfig(k=entries.K), (4,)),
    ])
    fs = entries.run_serve_signature_check()
    assert _rules_of(fs) == ["GRAFT-J006"]
    assert any(f.subject.startswith("collision:") for f in fs)


# --------------------------------------------------------------- AST rules


def test_a001_nondeterminism_in_traced_fn():
    src = textwrap.dedent("""\
        import time, random
        import numpy as np
        import jax

        @jax.jit
        def f(x):
            return x + time.time()

        def body(c, _):
            return c + np.random.rand(), None

        def outer(x):
            return jax.lax.scan(body, x, None, length=2)

        def host_only_helper():
            return time.time()  # NOT traced — must not be flagged
    """)
    fs = ast_checks.lint_source(src, "fix.py", sites=SITES)
    assert _rules_of(fs) == ["GRAFT-A001"]
    assert {(f.line, f.subject) for f in fs} == {
        (7, "f:time.time"), (10, "body:numpy.random.rand")}


def test_a001_jit_assignment_and_partial_forms():
    src = textwrap.dedent("""\
        import time
        from functools import partial
        import jax

        def g(x):
            return x + time.time()

        g_fast = jax.jit(g, static_argnums=())
        h = partial(jax.jit, donate_argnums=(0,))(g)
    """)
    fs = ast_checks.lint_source(src, "fix.py", sites=SITES)
    assert [(f.rule, f.line) for f in fs] == [("GRAFT-A001", 6)]


def test_a002_broad_except():
    src = textwrap.dedent("""\
        def f():
            try:
                pass
            except Exception:
                pass
            try:
                pass
            except Exception:  # noqa: BLE001 — justified
                pass
            try:
                pass
            except ValueError:
                pass
    """)
    fs = ast_checks.lint_source(src, "fix.py", sites=SITES)
    assert [(f.rule, f.line) for f in fs] == [("GRAFT-A002", 4)]


def test_a003_fault_sites():
    src = textwrap.dedent("""\
        from ddim_cold_tpu.utils import faults

        def a():
            faults.fire("serve.bogus")

        def b(name):
            faults.fire(name)

        def c():
            faults.fire("ckpt.save", tag="swap")
            faults.fire("ckpt.save", tag="swap")
            faults.fire("serve.assemble", tag=f"bucket:{4}")
    """)
    fs = ast_checks.lint_source(src, "fix.py", sites=SITES)
    assert _rules_of(fs) == ["GRAFT-A003"]
    subjects = {(f.line, f.subject) for f in fs}
    assert (4, "fire:serve.bogus") in subjects        # unregistered
    assert (7, "fire:<dynamic>") in subjects          # non-literal site
    assert (11, "fire:ckpt.save:swap") in subjects    # duplicate (site, tag)
    assert len(fs) == 3  # the dynamic-tag fire at line 12 is exempt


def test_a004_device_calls_in_host_only_module():
    src = textwrap.dedent("""\
        import numpy as np
        import jax.numpy as jnp

        def plan(rows):
            pad = np.zeros(4)
            return jnp.zeros(4) + pad
    """)
    fs = ast_checks.lint_source(src, "fix.py", sites=SITES, host_only=True)
    assert [(f.rule, f.line) for f in fs] == [("GRAFT-A004", 6)]
    # the same file outside the host-only set is fine
    assert ast_checks.lint_source(src, "fix.py", sites=SITES) == []


# ---------------------------------------------------------- sharding rules


def _tiny_float_params():
    return sharding_checks._tiny_params()


def test_s001_trunk_leaf_fell_through(monkeypatch):
    from ddim_cold_tpu.parallel import sharding

    params = _tiny_float_params()
    # simulate the regression class S001 guards: a rename that empties the
    # kernel pattern tables, so every trunk GEMM falls to replicated
    monkeypatch.setattr(sharding, "_COL_KERNELS", ())
    monkeypatch.setattr(sharding, "_ROW_KERNELS", ())
    fs = sharding_checks.check_param_tree(
        params, sharding.param_partition_specs(params), "float")
    assert _rules_of(fs) == ["GRAFT-S001"]
    subjects = {f.subject for f in fs}
    assert "float:blocks_0/attn/qkv/kernel" in subjects
    assert len(fs) == 8  # 4 trunk kernels × depth 2


def test_s002_unusable_specs():
    from jax.sharding import PartitionSpec as P

    params = {"a": jax.ShapeDtypeStruct((4,), jnp.float32),
              "b": jax.ShapeDtypeStruct((4, 4), jnp.float32),
              "c": jax.ShapeDtypeStruct((4,), jnp.float32)}
    specs = {"a": P(None, "model"),       # rank overflow
             "b": P("warp", None),        # unknown mesh axis
             "c": "model"}                # not a PartitionSpec
    fs = sharding_checks.check_param_tree(params, specs, "t")
    assert _rules_of(fs) == ["GRAFT-S002"]
    assert {f.subject for f in fs} == {"t:a", "t:b", "t:c"}


def test_s002_structure_mismatch():
    from jax.sharding import PartitionSpec as P

    params = {"a": jax.ShapeDtypeStruct((4,), jnp.float32),
              "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
    fs = sharding_checks.check_param_tree(params, {"a": P()}, "t")
    assert [(f.rule, f.subject) for f in fs] == [("GRAFT-S002", "t:b")]


# ---------------------------------------------------------- thread rules


def _tlint(src, lock_ranks=None):
    return thread_checks.lint_source(
        textwrap.dedent(src), "fix.py", lock_ranks=lock_ranks)


def test_t001_guarded_write_without_lock():
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []  # guarded-by: _lock

            def ok(self):
                with self._lock:
                    self._q.append(1)
                    self._q = []

            def bad(self):
                self._q.append(1)
    """)
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T001", 14, "W.bad:_q")]


def test_t001_requires_annotation_seeds_and_checks_callers():
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []  # guarded-by: _lock

            def _push(self, item):  # requires: _lock
                self._q.append(item)

            def good(self):
                with self._lock:
                    self._push(1)

            def bad(self):
                self._push(2)
    """)
    # _push's own body is clean (the annotation seeds its lockset); the
    # lock-free call site is the violation
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T001", 16, "W.bad:_push")]


def test_t002_rank_inversion_and_reentry():
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ok(self):
                with self._a:
                    with self._b:
                        pass

            def bad(self):
                with self._b:
                    with self._a:
                        pass

            def twice(self):
                with self._a:
                    with self._a:
                        pass
    """, lock_ranks={"_a": 0, "_b": 10})
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T002", 15, "W.bad:_b>_a"),
        ("GRAFT-T002", 20, "W.twice:_a>_a")]


def test_t002_cross_object_callee_rank():
    # `sink.inc(...)` is name-ranked at 30 (the obs surface); calling it
    # while holding an equal-ranked lock inverts the hierarchy
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._m = threading.Lock()

            def bad(self, sink):
                with self._m:
                    sink.inc("x")

            def ok(self, sink):
                sink.inc("x")
    """, lock_ranks={"_m": 30})
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T002", 9, "W.bad:_m>inc()")]


def test_t003_resolution_under_lock():
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self, t):
                with self._lock:
                    t._fail(RuntimeError("x"))

            def bad_cb(self, fn):
                with self._lock:
                    fn(self)

            def ok(self, t):
                t._fail(RuntimeError("x"))
    """)
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T003", 9, "W.bad:_fail"),
        ("GRAFT-T003", 13, "W.bad_cb:fn")]


def test_t004_blocking_wait_under_foreign_lock():
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition()
                self._ev = threading.Event()

            def bad(self):
                with self._lock:
                    self._ev.wait()

            def poll_ok(self, t):
                with self._lock:
                    t.exception(0)

            def cond_ok(self):
                with self._cond:
                    self._cond.wait()
    """)
    # the literal-0 poll and the Condition self-wait (which atomically
    # releases the condition) are the two legal forms
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T004", 11, "W.bad:wait")]


def test_t005_unguarded_lazy_init():
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._reg = None  # guarded-by: _lock

            def bad(self):
                if self._reg is None:
                    self._reg = {}
                return self._reg

            def ok(self):
                if self._reg is None:
                    with self._lock:
                        if self._reg is None:
                            self._reg = {}
                return self._reg
    """)
    # the unguarded write is ALSO a T001 — check-then-set without the lock
    # violates both; the double-checked `ok` form is clean for both
    assert [(f.rule, f.line, f.subject) for f in fs] == [
        ("GRAFT-T005", 9, "W.bad:_reg"),
        ("GRAFT-T001", 10, "W.bad:_reg")]


def test_thread_checks_nested_def_is_callback_context():
    # a nested def runs LATER on an arbitrary thread: writes inside it are
    # checked against an EMPTY lockset even when the def is created under
    # the lock
    fs = _tlint("""\
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []  # guarded-by: _lock

            def bad(self):
                with self._lock:
                    def later():
                        self._q.append(1)
                    return later
    """)
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-T001", "W.bad.later:_q")]


def test_thread_checks_clean_host_layer():
    """Every threaded host module passes the T-rules as committed — the
    slice of the clean-tree gate this layer owns."""
    assert thread_checks.lint_tree(cli.repo_root()) == []


# ------------------------------------------------------- collective rules


def _sp_mesh():
    from jax.sharding import Mesh

    if jax.device_count() < 2:
        pytest.skip("collective fixtures need >= 2 devices "
                    "(conftest forces 8 host devices)")
    return Mesh(np.array(jax.devices()[:2]), ("s",))


def _smap(fn, mesh):
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    return shard_map(fn, mesh=mesh, in_specs=P("s"), out_specs=P("s"),
                     check_vma=False)


def test_c001_divergent_cond_inside_manual_region():
    mesh = _sp_mesh()

    def inner(x):
        def refresh(v):
            return jax.lax.psum(v, "s") + jax.lax.psum(v * 2.0, "s")

        def reuse(v):
            return jax.lax.psum(v, "s")

        # the predicate is PER-SHARD (x differs per shard) — shards can
        # take different branches and rendezvous out of order
        return jax.lax.cond(x[0] > 0, refresh, reuse, x)

    closed = jax.make_jaxpr(_smap(inner, mesh))(jnp.zeros((2,), jnp.float32))
    fs = collective_checks.check_jaxpr(closed, "fix")
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-C001", "fix:cond-divergent")]

    def uniform(x):  # identical branch sequences — provably same rendezvous
        return jax.lax.cond(x[0] > 0,
                            lambda v: jax.lax.psum(v, "s"),
                            lambda v: jax.lax.psum(v * 2.0, "s"), x)

    closed = jax.make_jaxpr(_smap(uniform, mesh))(
        jnp.zeros((2,), jnp.float32))
    assert collective_checks.check_jaxpr(closed, "ok") == []


def test_c001_divergent_cond_outside_manual_region_is_exempt():
    """The drift-gate shape: a cond OUTSIDE shard_map whose branches carry
    different collective counts is safe — its scalar predicate is
    replicated, so every device takes the same branch together (the
    in-tree refresh-vs-reuse cond over the sp attention)."""
    mesh = _sp_mesh()

    def sm(times):
        def inner(v):
            for _ in range(times):
                v = jax.lax.psum(v, "s")
            return v
        return _smap(inner, mesh)

    def outer(x):
        return jax.lax.cond(jnp.sum(x) > 0, sm(2), sm(1), x)

    closed = jax.make_jaxpr(outer)(jnp.zeros((2,), jnp.float32))
    assert collective_checks.check_jaxpr(closed, "ok") == []


def test_c001_collective_in_while_inside_manual_region():
    mesh = _sp_mesh()

    def inner(x):
        return jax.lax.while_loop(
            lambda v: jnp.sum(v) < 10.0,
            lambda v: v + jax.lax.psum(v, "s"), x)

    closed = jax.make_jaxpr(_smap(inner, mesh))(jnp.zeros((2,), jnp.float32))
    fs = collective_checks.check_jaxpr(closed, "fix")
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-C001", "fix:while:psum")]


def test_c002_collective_outside_any_mesh():
    closed = jax.make_jaxpr(lambda x: jax.lax.psum(x, "s"),
                            axis_env=[("s", 2)])(
        jnp.zeros((2,), jnp.float32))
    fs = collective_checks.check_jaxpr(closed, "fix")
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-C002", "fix:psum:s:no-mesh")]


class _FakePrim:
    def __init__(self, name):
        self.name = name


class _FakeEqn:
    def __init__(self, name, params):
        self.primitive = _FakePrim(name)
        self.params = params


class _FakeJaxpr:
    def __init__(self, eqns):
        self.eqns = eqns


class _FakeMesh:
    axis_names = ("data",)


def test_c002_axis_absent_from_mesh():
    """jax itself refuses to trace a collective over an unbound axis name,
    so the absent-axis branch is exercised on a duck-typed jaxpr (the
    walker only reads .eqns/.primitive.name/.params — the same shapes a
    version-skewed trace would present)."""
    inner = _FakeJaxpr([_FakeEqn("ppermute", {"axis_name": "seq"})])
    sm = _FakeEqn("shard_map", {"mesh": _FakeMesh(), "auto": frozenset(),
                                "jaxpr": inner})
    fs = collective_checks.check_jaxpr(_FakeJaxpr([sm]), "fix")
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-C002", "fix:ppermute:seq")]


def test_collective_signature_orders_per_axis():
    mesh = _sp_mesh()

    def inner(x):
        g = jax.lax.all_gather(x, "s")
        return jax.lax.psum(x, "s") + jnp.sum(g)

    closed = jax.make_jaxpr(_smap(inner, mesh))(jnp.zeros((2,), jnp.float32))
    sig = collective_checks.collective_signature(closed, "fix")
    assert sig == {"s": ("all_gather", "psum")}
    # a static-trip scan's body is walked once — the per-iteration order
    # stands in for all iterations and stays deadlock-free by repetition

    def scanned(x):
        return jax.lax.scan(
            lambda c, _: (jax.lax.psum(c, "s"), None), x, None, length=3)[0]

    closed = jax.make_jaxpr(_smap(scanned, mesh))(
        jnp.zeros((2,), jnp.float32))
    assert collective_checks.check_jaxpr(closed, "ok") == []
    assert collective_checks.collective_signature(closed, "ok") == {
        "s": ("psum",)}


def test_c001_passes_over_the_sp_serve_sweep():
    """The acceptance gate for the pipeline-parallel precondition: every sp
    sweep entry traces to a non-empty seq-axis collective signature (the
    pass really sees the all_to_alls) and none violates C001/C002. Reuses
    one cached sweep trace — the same path `graftcheck` runs."""
    if jax.device_count() < 2:
        pytest.skip("sp sweep entries need >= 2 devices")
    traces: dict = {}
    entries.serve_signatures(entries.Context(), traces=traces)
    sp_subjects = [s for s in traces
                   if traces[s][0].sp_mode != "none"]
    assert sp_subjects  # the sweep must actually carry sp entries
    for subject in sp_subjects:
        _config, closed = traces[subject]
        assert collective_checks.check_jaxpr(closed, subject) == []
        sig = collective_checks.collective_signature(closed, subject)
        assert "seq" in sig and sig["seq"], (subject, sig)


# ------------------------------------------------------ baseline + CLI


def test_baseline_roundtrip(tmp_path):
    path = str(tmp_path / "base")
    fs = [Finding("GRAFT-A002", "b.py", "g:except Exception", 9),
          Finding("GRAFT-A002", "a.py", "f:except Exception", 3),
          Finding("GRAFT-A002", "a.py", "f:except Exception", 3)]
    assert write_baseline(path, fs) == 2  # sorted, deduped
    keys = load_baseline(path)
    assert keys == {"GRAFT-A002 a.py :: f:except Exception",
                    "GRAFT-A002 b.py :: g:except Exception"}
    assert all(f.key in keys for f in fs)
    assert load_baseline(str(tmp_path / "missing")) == set()


def test_baseline_rejects_malformed(tmp_path):
    path = tmp_path / "base"
    path.write_text("NOT-A-RULE something :: else\n")
    with pytest.raises(ValueError):
        load_baseline(str(path))


def test_cli_fix_baseline_then_clean(tmp_path, monkeypatch):
    # findings → exit 1; --fix-baseline captures them; --baseline → exit 0
    fake = [Finding("GRAFT-A002", "x.py", "f:except Exception", 1, "msg")]
    monkeypatch.setattr(cli, "collect", lambda *a, **k: sorted(fake))
    base = str(tmp_path / "allow")
    assert cli.main(["--only", "ast"]) == 1
    assert cli.main(["--only", "ast", "--fix-baseline", base]) == 0
    assert cli.main(["--only", "ast", "--baseline", base]) == 0


def test_baseline_roundtrip_thread_and_collective_findings(tmp_path):
    path = str(tmp_path / "base")
    fs = [Finding("GRAFT-T001", "ddim_cold_tpu/serve/engine.py",
                  "Engine.drain:_pending", 1033),
          Finding("GRAFT-C001", "ddim_cold_tpu/serve/engine.py",
                  "ddim_k500_ci2_sp2u:b4:cond-divergent", 0)]
    assert write_baseline(path, fs) == 2
    keys = load_baseline(path)
    assert all(f.key in keys for f in fs)
    assert {rule_layer(k.split(" ", 1)[0]) for k in keys} == {
        "threads", "collective"}


def test_cli_fix_baseline_only_refreshes_selected_layers(tmp_path,
                                                         monkeypatch):
    """--fix-baseline --only regenerates JUST the selected layers' rule
    families, carrying the other layers' reviewed lines over verbatim —
    adopting the T/C rules must not churn the A/J/S entries."""
    base = str(tmp_path / "allow")
    ast_f = Finding("GRAFT-A002", "x.py", "f:except Exception", 1)
    t_old = Finding("GRAFT-T001", "y.py", "W.bad:_q", 5)
    t_new = Finding("GRAFT-T003", "y.py", "W.bad:_fail", 9)

    monkeypatch.setattr(cli, "collect", lambda *a, **k: [ast_f, t_old])
    assert cli.main(["--fix-baseline", base]) == 0  # full: both layers
    assert load_baseline(base) == {ast_f.key, t_old.key}

    # the threads layer alone now reports a DIFFERENT finding: a partial
    # refresh swaps the T entry and keeps the ast entry untouched
    monkeypatch.setattr(cli, "collect", lambda *a, **k: [t_new])
    assert cli.main(["--only", "T", "--fix-baseline", base]) == 0
    assert load_baseline(base) == {ast_f.key, t_new.key}

    # a FULL --fix-baseline stays authoritative for everything (no carry)
    monkeypatch.setattr(cli, "collect", lambda *a, **k: [ast_f])
    assert cli.main(["--fix-baseline", base]) == 0
    assert load_baseline(base) == {ast_f.key}


def test_cli_only_accepts_family_letters_and_names():
    assert cli.parse_only(["T,C"]) == ("threads", "collective")
    assert cli.parse_only(["P,M"]) == ("kernels", "memory")
    assert cli.parse_only(["ast", "j"]) == ("ast", "jaxpr")
    assert cli.parse_only(["threads,threads"]) == ("threads",)
    assert cli.parse_only(["R,X"]) == ("protocol", "config")
    with pytest.raises(Exception):
        cli.parse_only(["z"])


def test_rule_table_covers_all_emitted_rules():
    assert set(RULES) == {
        "GRAFT-J001", "GRAFT-J002", "GRAFT-J003", "GRAFT-J004", "GRAFT-J005",
        "GRAFT-J006", "GRAFT-J007", "GRAFT-A001", "GRAFT-A002", "GRAFT-A003",
        "GRAFT-A004", "GRAFT-A005", "GRAFT-S001", "GRAFT-S002",
        "GRAFT-T001", "GRAFT-T002", "GRAFT-T003", "GRAFT-T004", "GRAFT-T005",
        "GRAFT-C001", "GRAFT-C002",
        "GRAFT-P001", "GRAFT-P002", "GRAFT-P003",
        "GRAFT-M001", "GRAFT-M002",
        "GRAFT-R001", "GRAFT-R002", "GRAFT-R003", "GRAFT-R004",
        "GRAFT-R005",
        "GRAFT-X001", "GRAFT-X002", "GRAFT-X003", "GRAFT-X004"}
    assert {rule_layer(r) for r in RULES} == set(cli.LAYERS)


# ------------------------------------------------------------- clean tree


def test_clean_tree_ast_and_sharding():
    root = cli.repo_root()
    assert ast_checks.lint_tree(root) == []
    assert sharding_checks.run_sharding_checks() == []


def test_clean_tree_full_collect():
    """The acceptance gate: zero non-baselined findings on the whole repo —
    all nine layers, the same set CI's `graftcheck --baseline` run
    enforces (the collective layer rides the jaxpr layer's sweep traces
    here exactly as it does in the CLI)."""
    fs = cli.collect(cli.repo_root())
    assert [f.render() for f in fs] == []


def test_fleet_layer_is_covered_by_a003_and_a004():
    """The fleet layer stays inside the static net: router.py/fleet.py are
    host-only modules (A004 — routing must never touch a device array) and
    every router fault site is registered (A003 — a typo'd site string
    would silently never fire)."""
    from ddim_cold_tpu.utils import faults

    for mod in ("ddim_cold_tpu/serve/router.py",
                "ddim_cold_tpu/serve/fleet.py",
                "ddim_cold_tpu/serve/batching.py"):
        assert mod in ast_checks.HOST_ONLY_MODULES, mod
    for site in ("router.place", "router.failover", "replica.spawn"):
        assert site in faults.SITES, site


def test_workload_sites_and_sweep_registered():
    """The editing workloads stay inside the static net: the preview
    delivery stage is a registered fault site (A003) and every task — plus
    the preview-enabled scan variants — appears in the J006 serve sweep, so
    the zero-compiles contract is proven for them too."""
    from ddim_cold_tpu.analysis import entries
    from ddim_cold_tpu.utils import faults

    assert "serve.preview" in faults.SITES
    labels = [label for label, _, _ in entries.serve_sweep()]
    for label in ("inpaint_k500", "inpaint_k500_pv2", "inpaint_k500_qxla",
                  "superres_l3", "superres_l3_ci2", "superres_l3_pv1",
                  "draft_k500_t1200", "draft_k500_t1200_ci2",
                  "interp_k500_t400", "ddim_k500_pv2", "ddim_k500_ci2_pv2"):
        assert label in labels, label
