"""Which layer every compiled instruction belongs to.

A device trace of this runtime names instructions, not scopes: ``%fusion.123``
says nothing of the layer it was compiled from. The compiled module does — each
instruction's ``metadata={op_name="jit(f)/…/trunk/moe/trunk/route/dot_general"}``
is the path of ``jax.named_scope``s it was traced under — and only the program
holds the compiled module. So the program keeps what it takes to ask for it
again:

* :func:`note` at a dispatch site (the samplers' ``sampler/dispatch``, the
  first call of a train step) records, once a distinct program, the jitted
  function, its static arguments, its array arguments as
  ``jax.ShapeDtypeStruct``s (the sharding of a committed array kept, so the
  lowering below is the call's own) and the ambient mesh. No buffer is held and
  a donated argument is never touched again. The record is one closed
  ``scopes/note`` span on the program's recorder (``obs/spans.py``), child of
  the span open on the thread, so it says which call first ran which program.
  A program already noted costs one flatten of its arguments and one lookup.
  Nothing is lowered, compiled or printed here.
* :func:`scope_map` does the work when somebody asks: for each noted program
  ``jitted.lower(*args, **kwargs).compile().as_text()`` (after the call: JAX's
  own caches, nothing traced or compiled anew), then one pass over every
  instruction of every computation of the optimized module.

The vocabulary is :data:`LAYERS`, scope → layer, and lives nowhere else.
"""

from __future__ import annotations

import contextlib
import json
import re
import threading
import time

import jax

from ddim_cold_tpu.obs import spans

__all__ = ["LAYERS", "OUTSIDE", "note", "noted", "scope_map", "programs",
           "write", "clear", "parse_module", "scope_of"]

#: scope → layer. The innermost of these on an instruction's path decides
#: (``trunk/route`` sits inside ``trunk/moe`` and comes out of it).
LAYERS = {
    "trunk/attn": "attention",
    "trunk/attn_full": "attention",
    "trunk/attn_window": "attention",
    "trunk/mla": "attention",
    "trunk/dsa_index": "attention",
    "trunk/mamba": "mixer",
    "trunk/mamba2": "mixer",
    "trunk/kda": "mixer",
    "trunk/moe": "experts",
    "trunk/route": "route",
    "trunk/mlp": "mlp",
    "train/optimizer": "optimizer",
}
#: everything else of a noted program: patch embedding, head, the sampler's
#: update, the loss
OUTSIDE = "outside"

_SCOPE = re.compile(r"(?:^|[/(\"])(trunk/\w+|train/optimizer)(?=$|[/)\"])")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def scope_of(op_name: str):
    """``(scope or None, direction)`` of one ``op_name`` path: the innermost
    scope of the vocabulary's form on it (a ``trunk/…`` the table lacks
    included: the map then says so), ``bwd`` where the path goes through a
    ``transpose(``."""
    found = _SCOPE.findall(op_name)
    return (found[-1] if found else None,
            "bwd" if "transpose(" in op_name else "fwd")


def _entry(scope, direction: str, mixed: bool = False) -> dict:
    return {"scope": scope, "layer": LAYERS.get(scope, OUTSIDE),
            "direction": direction, "mixed": mixed}


def parse_module(text: str) -> dict:
    """``{instruction name: {"scope", "layer", "direction", "mixed",
    "opcode", "traced"}}`` of every instruction of every computation (entry,
    ``while`` bodies, fused computations) of one optimized module's text. A fusion
    takes its own ``op_name`` (XLA gives it its root's; one without takes its
    root's here) and is ``mixed`` when its fused computation holds an
    instruction of another layer."""
    out: dict = {}
    bodies: dict = {}   # computation -> [(entry, is root, has an op_name)]
    fusions: list = []  # (instruction name, called computation, has an op_name)
    body = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                body = bodies.setdefault(c.group(1), [])
            continue
        root, name, rest = m.groups()
        opcode = _OPCODE.search(rest)
        op_name = _OP_NAME.search(rest)
        entry = _entry(*scope_of(op_name.group(1))) if op_name else _entry(
            None, "fwd")
        entry["opcode"] = opcode.group(1) if opcode else ""
        # on the program's own path (``jit(f)/…``), not a parameter's name or
        # a reducer's bare primitive
        entry["traced"] = bool(op_name) and "/" in op_name.group(1)
        out[name] = entry
        if body is not None:
            body.append((entry, bool(root), bool(op_name)))
        if entry["opcode"] == "fusion":
            called = _CALLS.search(rest)
            if called:
                fusions.append((name, called.group(1), bool(op_name)))
    for name, called, named in fusions:
        inside = bodies.get(called, [])
        entry = out[name]
        if not named:
            for held, is_root, has_name in inside:
                if is_root and has_name:
                    entry.update(scope=held["scope"], layer=held["layer"],
                                 direction=held["direction"])
        entry["mixed"] = any(has_name and held["layer"] != entry["layer"]
                             for held, _, has_name in inside)
    return out


#: a compiler option at its default value: same program, another in-memory key
_SAME_PROGRAM = {"xla_embed_ir_in_executable": False}


class Program:
    """One noted program: what it takes to lower it again, and its record."""

    __slots__ = ("name", "jitted", "args", "kwargs", "mesh", "span",
                 "instructions", "rebuilt")

    def __init__(self, name, jitted, args, kwargs, mesh, span):
        self.name, self.jitted = name, jitted
        self.args, self.kwargs, self.mesh, self.span = args, kwargs, mesh, span
        self.instructions = None  # parse_module's, once asked for
        self.rebuilt = False      # the call's executable had another build's names

    def text(self) -> str:
        """The optimized module with THIS build's metadata, lowered under the
        mesh the call ran under. The call's own executable when it carries
        every scope the lowering has; one loaded from a persistent compile
        cache may not (the cache's key leaves metadata out, so it hands back
        whatever build wrote the entry, with that build's ``op_name``s): it
        is then compiled once more with the metadata in the key, which gives
        the same instructions under their true names."""
        with (jax.set_mesh(self.mesh) if self.mesh is not None
              else contextlib.nullcontext()):
            lowered = self.jitted.lower(*self.args, **self.kwargs)
            text = lowered.compile().as_text()
            missing = (set(_SCOPE.findall(lowered.as_text(debug_info=True)))
                       - set(_SCOPE.findall(text)))
            if not missing:
                return text
            self.rebuilt = True
            key = "jax_compilation_cache_include_metadata_in_key"
            before = getattr(jax.config, key)
            jax.config.update(key, True)
            try:
                with spans.layer("scopes/rebuild", program=self.name,
                                 missing=sorted(missing)):
                    # an option at its default: the in-memory executable is
                    # keyed without it, so this compiles (or loads an entry
                    # whose key holds this build's metadata)
                    return lowered.compile(
                        compiler_options=_SAME_PROGRAM).as_text()
            finally:
                jax.config.update(key, before)

    def describe(self) -> dict:
        leaves = jax.tree_util.tree_leaves((self.args, self.kwargs))
        statics = [repr(x)[:200] for x in leaves
                   if not isinstance(x, jax.ShapeDtypeStruct)]
        return {"name": self.name, "arrays": len(leaves) - len(statics),
                "statics": statics,
                "mesh": None if self.mesh is None else dict(self.mesh.shape),
                "rebuilt": self.rebuilt,
                "span_id": self.span.span_id,
                "parent_id": self.span.parent_id,
                "trace_id": self.span.trace_id}


class _Aval(tuple):
    """What ``lower`` keys an array argument on: (shape, dtype, weak type,
    the sharding of a committed array or None)."""

    __slots__ = ()

    def struct(self) -> jax.ShapeDtypeStruct:
        shape, dtype, weak_type, sharding = self
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding,
                                    weak_type=weak_type)


def _abstract(x):
    """An array as its :class:`_Aval`, anything else (a static) as it is."""
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        return x
    return _Aval((x.shape, x.dtype, getattr(x, "weak_type", False),
                  x.sharding if getattr(x, "committed", False) else None))


class ScopeRecord:
    """The noted programs of a process and the map over them."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: dict = {}  # guarded-by: _lock (writes)

    def note(self, name: str, jitted, args: tuple, kwargs: dict) -> bool:
        """Keep the program of ``jitted(*args, **kwargs)`` unless it is
        kept already. False where there is nothing to keep: a call inside
        somebody else's trace is no dispatch."""
        leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return False
        abstract = [_abstract(x) for x in leaves]
        key = (name, jitted, tree, tuple(abstract))
        try:
            if key in self._programs:
                return True
        except TypeError:  # an unhashable static: jit refuses the call
            return False
        mesh = jax.sharding.get_mesh()
        args, kwargs = jax.tree_util.tree_unflatten(
            tree, [x.struct() if isinstance(x, _Aval) else x
                   for x in abstract])
        with self._lock:
            if key not in self._programs:
                span = spans.event(
                    "scopes/note", time.perf_counter_ns(), 0, program=name,
                    index=len(self._programs),
                    arrays=sum(isinstance(x, _Aval) for x in abstract))
                self._programs[key] = Program(
                    name, jitted, args, kwargs,
                    None if mesh.empty else mesh, span)
        return True

    def programs(self) -> list:
        return list(self._programs.values())

    def scope_map(self) -> dict:
        """``{instruction name: {"scope", "layer", "direction", "mixed",
        "opcode", "traced"}}`` (:func:`parse_module`'s entries) over every
        noted program. A name that two programs give to different layers
        maps to nothing."""
        merged: dict = {}
        dropped = set()
        for program in self.programs():
            if program.instructions is None:
                program.instructions = parse_module(program.text())
            for name, entry in program.instructions.items():
                if merged.setdefault(name, entry)["layer"] != entry["layer"]:
                    dropped.add(name)
        for name in dropped:
            del merged[name]
        return merged

    def write(self, path: str) -> dict:
        """The map and the programs it was built from, as JSON: what reduces
        a timeline taken beside it by layer."""
        doc = {"layers": LAYERS,
               "programs": [p.describe() for p in self.programs()],
               "map": self.scope_map()}
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()


class noted:
    """A jitted function whose first call is noted: the step of
    ``train/step.make_train_step``. Everything else (``lower``, ``trace``,
    ``_cache_size``) is the jitted function's own."""

    def __init__(self, name: str, jitted):
        self._name, self._jitted, self._noted = name, jitted, False

    def __call__(self, *args, **kwargs):
        if not self._noted:
            self._noted = note(self._name, self._jitted, args, kwargs)
        return self._jitted(*args, **kwargs)

    def __getattr__(self, attr):
        return getattr(self._jitted, attr)


_REC = ScopeRecord()
note = _REC.note
programs = _REC.programs
scope_map = _REC.scope_map
write = _REC.write
clear = _REC.clear
