"""``obs/scopes.py``: what ``note`` keeps and what it costs, and the map from
compiled instruction to layer — over toy programs, and over one toy forward of
every stack the repo runs (the vocabulary's own test)."""

import importlib
import json
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from ddim_cold_tpu.obs import scopes, spans

W = 16


@pytest.fixture(autouse=True)
def empty_record():
    scopes.clear()
    yield
    scopes.clear()


def weights():
    return {"a": jnp.ones((W, W)), "r": jnp.ones((W, 4)), "b": jnp.ones((4, W))}


def make_scan(attn="trunk/attn", traces=None):
    """A jitted scan of a toy denoiser under ``sampler/model`` → ``trunk/*``,
    its start state donated as the samplers donate theirs."""

    @partial(jax.jit, static_argnames=("k",), donate_argnums=(1,))
    def toy_scan(params, x, *, k):
        if traces is not None:
            traces.append(k)

        def body(x, _):
            with jax.named_scope("sampler/model"):
                with jax.named_scope(attn):
                    h = jnp.tanh(x @ params["a"])
                with jax.named_scope("trunk/moe"):
                    with jax.named_scope("trunk/route"):
                        r = jax.nn.softmax(h @ params["r"])
                    h = h + r @ params["b"]
            return x + h, None

        return jax.lax.scan(body, x, None, length=k)[0]

    return toy_scan


def run_noted(fn, name="toy"):
    params, x = weights(), jnp.ones((8, W))
    scopes.note(name, fn, (params, x), {"k": 3})
    return params, fn(params, x, k=3)


def jax_events(since: int) -> list:
    return [s.attrs["event"].rsplit("/", 1)[-1]
            for s in spans.layer_spans()[since:] if s.name.startswith("jax/")]


# ------------------------------------------------------------------ the map

def test_a_scan_puts_each_dot_and_fusion_in_its_layer_and_route_inside_moe_in_route():
    run_noted(make_scan())
    got = scopes.scope_map()
    text = scopes.programs()[0].text()
    parsed = scopes.parse_module(text)
    dots = {name: got[name] for name, e in parsed.items() if e["opcode"] == "dot"}
    assert sorted((e["scope"], e["layer"]) for e in dots.values()) == [
        ("trunk/attn", "attention"), ("trunk/moe", "experts"),
        ("trunk/route", "route")]
    fused = [got[n]["layer"] for n, e in parsed.items() if e["opcode"] == "fusion"]
    assert {"attention", "route"} <= set(fused)
    assert all(e["direction"] == "fwd" for e in got.values())
    # the scan's own loop is no layer's
    loops = [got[n] for n, e in parsed.items() if e["opcode"] == "while"]
    assert loops and all(e["layer"] == scopes.OUTSIDE for e in loops)
    assert set(next(iter(got.values()))) == {
        "scope", "layer", "direction", "mixed", "opcode", "traced"}


def test_a_train_step_marks_the_backward_and_the_update():
    """``make_train_step`` on a toy ViT: the first call is noted, instructions
    on a ``transpose(`` path read ``bwd``, the AdamW update ``optimizer``, and
    the block's two halves ``attention`` and ``mlp`` in both directions."""
    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=1, num_heads=2, total_steps=100)
    x, t = jnp.ones((2, 16, 16, 3)), jnp.array([1, 2])
    state = create_train_state(model, jax.random.PRNGKey(0), 1e-3, 10, (x, x, t))
    step = make_train_step(model)
    state, _, rec = step(state, (x, x, t), jax.random.PRNGKey(1), jnp.float32(5.0))
    step(state, (x, x, t), jax.random.PRNGKey(1), rec)
    assert [p.name for p in scopes.programs()] == ["train/step"]
    got = scopes.scope_map()
    seen = {(e["layer"], e["direction"]) for e in got.values()}
    assert {("attention", "fwd"), ("attention", "bwd"), ("mlp", "fwd"),
            ("mlp", "bwd"), ("optimizer", "fwd"), ("outside", "fwd"),
            ("outside", "bwd")} <= seen
    assert ("optimizer", "bwd") not in seen


HAND_MADE = """\
HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %tanh.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(f)/trunk/attn/tanh"}
  ROOT %add.2 = f32[8]{0} add(%tanh.1, %tanh.1), metadata={op_name="jit(f)/trunk/mlp/add"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %exp.3 = f32[8]{0} exponential(%param_0.1), metadata={op_name="jit(f)/transpose(jvp(trunk/mlp))/exp"}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.4 = f32[8]{0:T(128)} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/trunk/mlp/add"}
  %fusion.5 = f32[8]{0:T(128)} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.1
  %fwd.6 = (f32[8]{0:T(128)S(1)}, f32[8]{0}) custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/trunk/attn_window/flash_attention/fwd_masked/pallas_call"}
  ROOT %copy.7 = f32[8]{0} copy(%fusion.5), metadata={op_name="jit(f)/trunk/new_kind/copy"}
}
"""


def test_a_fusion_over_two_layers_is_mixed_and_goes_to_its_root():
    got = scopes.parse_module(HAND_MADE)
    assert (got["fusion.4"]["layer"], got["fusion.4"]["mixed"]) == ("mlp", True)
    # a fusion XLA gave no op_name takes its root's, direction included
    assert (got["fusion.5"]["layer"], got["fusion.5"]["direction"],
            got["fusion.5"]["mixed"]) == ("mlp", "bwd", False)
    assert (got["fwd.6"]["opcode"], got["fwd.6"]["scope"],
            got["fwd.6"]["layer"]) == ("custom-call", "trunk/attn_window", "attention")
    # a trunk scope the table lacks is named, and in no layer
    assert (got["copy.7"]["scope"], got["copy.7"]["layer"]) == (
        "trunk/new_kind", scopes.OUTSIDE)
    assert not got["x.1"]["traced"] and got["tanh.1"]["traced"]


def test_a_name_two_programs_give_to_different_layers_maps_to_nothing():
    run_noted(make_scan("trunk/attn"), "one")
    alone = scopes.scope_map()
    run_noted(make_scan("trunk/mamba"), "other")
    both = scopes.scope_map()
    assert len(scopes.programs()) == 2
    clash = {n for n, e in alone.items() if e["layer"] == "attention"}
    assert clash and not clash & set(both)
    agreed = {n for n, e in alone.items() if e["layer"] == "route"}
    assert agreed and all(both[n]["layer"] == "route" for n in agreed)


# ------------------------------------------------------------------ the note

def test_note_after_the_first_call_is_a_lookup_and_holds_no_array():
    fn = make_scan()
    with spans.layer("sampler/call") as call:
        with spans.layer("sampler/dispatch") as dispatch:
            params, out = run_noted(fn)
    since = len(spans.layer_spans())
    for _ in range(3):  # the donated start state is gone; its successor has its shape
        scopes.note("toy", fn, (params, out), {"k": 3})
    assert len(scopes.programs()) == 1
    assert spans.layer_spans()[since:] == []
    program = scopes.programs()[0]
    assert (program.span.name, program.span.parent_id) == (
        "scopes/note", dispatch.span_id)
    assert dispatch.parent_id == call.span_id
    assert program.span.attrs == {"program": "toy", "index": 0, "arrays": 4}
    leaves = jax.tree_util.tree_leaves((program.args, program.kwargs))
    assert [type(x) for x in leaves] == [jax.ShapeDtypeStruct] * 4 + [int]
    assert not any(isinstance(x, jax.Array) for x in leaves)
    # other shapes are another program
    scopes.note("toy", fn, (params, jnp.ones((4, W))), {"k": 3})
    assert len(scopes.programs()) == 2


def test_a_call_inside_somebody_elses_trace_is_not_noted():
    fn = make_scan()
    params = weights()
    step = scopes.noted("toy", fn)
    jax.make_jaxpr(lambda x: step(params, x, k=3))(jnp.ones((8, W)))
    assert scopes.programs() == []
    step(params, jnp.ones((8, W)), k=3)  # the first real call still is
    assert [p.name for p in scopes.programs()] == ["toy"]
    assert step.lower is not None and step._cache_size() >= 1


def test_scope_map_after_a_call_traces_and_compiles_nothing():
    traces = []
    run_noted(make_scan(traces=traces))
    assert traces == [3]
    since = len(spans.layer_spans())
    got = scopes.scope_map()
    assert got and traces == [3]  # the body was not run again
    events = jax_events(since)
    # the one event is JAX looking its cached trace up (≈ 20 µs)
    assert set(events) <= {"jaxpr_trace_duration"}, events
    again = len(spans.layer_spans())
    assert scopes.scope_map() == got
    assert jax_events(again) == []  # parsed once a program


def test_the_samplers_note_their_scan_once(tmp_path):
    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.ops import sampling

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=1, num_heads=2, total_steps=100)
    x, t = jnp.ones((2, 16, 16, 3)), jnp.array([1, 2])
    params = model.init(jax.random.PRNGKey(0), x, t)["params"]
    for seed in (1, 2):
        sampling.ddim_sample(model, params, jax.random.PRNGKey(seed), k=50, n=2)
    sampling.cold_sample(model, params, jax.random.PRNGKey(3), n=2, levels=2)
    sampling.ddim_sample_fewstep(model, params, jax.random.PRNGKey(4),
                                 steps=2, n=2)
    assert [p.name for p in scopes.programs()] == [
        "sampler/_ddim_scan_last", "sampler/_cold_impl",
        "sampler/_fewstep_impl"]
    doc = scopes.write(str(tmp_path / "scopes.json"))
    with open(tmp_path / "scopes.json") as f:
        assert json.load(f) == doc
    assert doc["layers"] == scopes.LAYERS
    assert {"attention", "mlp", "outside"} == {
        e["layer"] for e in doc["map"].values()}


def test_an_executable_with_another_builds_names_is_compiled_under_its_own():
    """The persistent compile cache keys a program without its metadata: a
    build that only renamed a scope is handed the older build's executable,
    ``op_name``s and all. The map must not read those."""
    def build(scope):
        @jax.jit
        def stale_probe(x, w):
            with jax.named_scope(scope):
                h = jnp.tanh(x @ w)
            return jnp.sin(h @ w) + 1.0
        return stale_probe

    x = w = jnp.full((24, 24), 0.5)
    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 0.0)
    try:
        build("trunk/attn")(x, w).block_until_ready()  # writes the entry
        renamed = build("trunk/mlp")
        renamed(x, w).block_until_ready()              # loads it
        scopes.note("probe", renamed, (x, w), {})
        layers = {e["layer"] for e in scopes.scope_map().values()}
    finally:
        jax.config.update(key, before)
    assert "mlp" in layers and "attention" not in layers
    assert scopes.programs()[0].rebuilt
    assert scopes.programs()[0].describe()["rebuilt"]


# ----------------------------------------------------------- the vocabulary

STACKS = {
    "vit": {"trunk/attn", "trunk/mlp"},
    "hybrid": {"trunk/attn", "trunk/mamba", "trunk/mlp"},
    "laguna": {"trunk/attn_full", "trunk/attn_window", "trunk/moe",
               "trunk/route", "trunk/mlp"},
    "glm": {"trunk/mla", "trunk/dsa_index", "trunk/moe", "trunk/route",
            "trunk/mlp"},
    "pangu": {"trunk/mla", "trunk/moe", "trunk/route", "trunk/mlp"},
    "nemotron": {"trunk/mamba2", "trunk/attn", "trunk/moe", "trunk/route"},
    "kimi": {"trunk/kda", "trunk/mla", "trunk/moe", "trunk/route",
             "trunk/mlp"},
    "smallthinker": {"trunk/attn_full", "trunk/attn_window", "trunk/moe",
                     "trunk/route"},
}


def toy_forward(stack):
    """(model, params, x, t): the toy its own test file drives."""
    if stack == "vit":
        from ddim_cold_tpu.models import DiffusionViT

        model = DiffusionViT(img_size=(16, 16), patch_size=4, embed_dim=32,
                             depth=6, num_heads=2, total_steps=100)
        x, t = jnp.ones((2, 16, 16, 3)), jnp.array([1, 2])
        return model, model.init(jax.random.PRNGKey(0), x, t)["params"], x, t
    fixture = importlib.import_module("test_" + stack)
    model, params = fixture.model_and_params("float32")
    return (model, params, *fixture.inputs())


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_every_scope_of_a_stack_is_in_the_table_and_its_layers_are_attributed(stack):
    """``nemotron.KINDS`` and kimi's ``f"trunk/{kind}"`` are no literals, so
    the check traces: every ``trunk/…`` scope a toy forward compiles to is a
    row of ``LAYERS``, the stack has the rows it should, and of the
    instructions on the program's own path at least 90 % are in a layer
    (what is left is the patch embedding, the final norm and the head)."""
    model, params, x, t = toy_forward(stack)
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).compile().as_text()
    traced = [e for e in scopes.parse_module(text).values() if e["traced"]]
    seen = {e["scope"] for e in traced} - {None}
    assert seen <= set(scopes.LAYERS), seen - set(scopes.LAYERS)
    assert seen == STACKS[stack]
    attributed = sum(e["layer"] != scopes.OUTSIDE for e in traced)
    assert attributed >= 0.9 * len(traced), (attributed, len(traced))
