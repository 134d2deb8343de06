"""What each compiled program's device operations are: the step from a
profiler trace's event (``%fusion.455``, a compiler's name) to the scope, the
pass and the opcode the program gave that instruction.

A device event of a trace carries its HLO instruction's result name and result
shape and nothing of the program that ran it. The program knows more: every
instruction's ``op_name`` holds the ``jax.named_scope`` path it was traced
under (``odtp_attention``, ``odtp_mlp/odtp_router``, ...) and, under
``value_and_grad`` and the remat policy, the transformations it went through.
So the owners of compiled programs (``trainer.InnerTrainer``,
``diloco.DiLoCoOptimizer``, ``serve.engine.ServeEngine``) register here, and a
reader of a trace asks for the tables::

    from opendiloco_tpu import obs
    found = obs.programs.tables()        # {"train_step": [Instruction, ...], ...}
    found.missing                        # {program: why it could not be lowered}
    obs.programs.ambiguous(found)        # pairs a trace cannot tell apart

and joins an event to an instruction by result name and result shape
(``result_shape`` of the event's text after `` = ``). Nothing is lowered,
compiled or parsed before ``tables()`` is called: with tracing off the whole
cost is ``register``'s one weak-set insertion per owner constructed.

An owner has ``program_texts() -> {program name: compiled text}``: it lowers
and compiles *again*, on demand, the programs it has run, at the shapes it ran
them (JAX's persistent compile cache answers where it is on), and gives the
exception in place of the text where it could not. ``Recipes`` is what an
owner keeps for that, and hands over in ``program_recipes()``; a capture's
``stop`` keeps the live owners' recipes (``keep``), so that a late reader finds
the stretch's programs though their owner is gone.
"""
from __future__ import annotations

import re
import threading
import weakref
from typing import NamedTuple, Optional

# the opcodes (and their ``-start`` / ``-done`` forms) that move data between
# chips or wait for it
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all")

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_SHAPE = re.compile(r"\(?(\w+\[[\d,]*\])")
_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# computations applied element by element inside one operation: a reduce's
# ``to_apply``, a select-and-scatter's two (a ``call``'s ``to_apply`` runs)
_APPLIED = re.compile(r"\b(?:to_apply|select|scatter)=%?([\w.\-]+)")
_SCOPE = re.compile(r"odtp_\w+")
_BRACKETS = re.compile(r"\[[^\]]*\]")
_NUMBERED = re.compile(r"(\.clone|\.\d+)+$")


class Instruction(NamedTuple):
    """One instruction of a compiled program as the device runs it."""

    name: str  # the result name as the trace prints it: ``%fusion.455``
    shape: str  # ``result_shape``: a tuple's first
    opcode: str  # ``fusion``, ``while``, a custom call's target, ...
    scope: Optional[str]  # the innermost ``odtp_*`` component of ``op_name``
    path: str  # every ``odtp_*`` component in order: ``odtp_mlp/odtp_router``
    pass_: str  # ``fwd`` | ``bwd`` | ``remat``


def result_shape(detail: str) -> str:
    """``bf16[8,128]{1,0} fusion(...`` -> ``bf16[8,128]`` (a tuple's first):
    of an instruction's text after `` = ``, and of a trace event's."""
    found = _SHAPE.match(detail)
    return found.group(1) if found else ""


def scope_path(op_name: str) -> list:
    """The ``odtp_*`` components of an ``op_name`` in order, found wherever
    they stand (``jvp(odtp_mlp)`` too); a primitive's parameters
    (``pallas_call[name=odtp_flash_fwd]``) are no component."""
    path: list = []
    for part in _BRACKETS.sub("", op_name).split("/"):
        for scope in _SCOPE.findall(part):
            if not path or path[-1] != scope:
                path.append(scope)
    return path


def pass_of(op_name: str) -> str:
    """Which pass of a differentiated step an ``op_name`` belongs to."""
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd"


def _opcode(rest: str, fused_by: Optional[str]) -> str:
    found = _OPCODE.search(rest)
    opcode = found.group(1) if found else ""
    if opcode == "custom-call":
        target = _TARGET.search(rest)
        return target.group(1) if target else opcode
    if opcode == "fusion" and fused_by:
        # the TPU compiler runs a reduce-scatter as a fusion that calls a
        # computation named for it (``%all-reduce-scatter.3``): that name
        base = _NUMBERED.sub("", fused_by)
        if base.startswith(COLLECTIVES):
            return base
    return opcode


def parse(text: str) -> list:
    """The instructions of a compiled program's text *as the device runs
    them*: every instruction outside a fused computation (a fusion runs as one
    operation under its own name) and outside a computation that one
    operation applies element by element (a reduce's); the instructions of
    ``while`` bodies and of called computations count."""
    computations: list = []  # (name, [instruction lines])
    inner: set = set()  # computations that run inside one operation
    lines: Optional[list] = None
    for line in text.splitlines():
        line = line.strip()
        header = _COMPUTATION.match(line)
        if header:
            lines = []
            computations.append((header.group(2), lines))
            continue
        if lines is None or " = " not in line:
            continue
        lines.append(line)
        rest = line.partition(" = ")[2]
        opcode = _OPCODE.search(rest)
        opcode = opcode.group(1) if opcode else ""
        if opcode == "fusion":
            inner.update(_CALLS.findall(rest))
        elif opcode != "call":
            inner.update(_APPLIED.findall(rest))
    out = []
    for name, lines in computations:
        if name in inner or "fused_computation" in name:
            continue
        for line in lines:
            lhs, _, rest = line.removeprefix("ROOT ").partition(" = ")
            op_name = _OP_NAME.search(rest)
            op_name = op_name.group(1) if op_name else ""
            path = scope_path(op_name)
            calls = _CALLS.search(rest)
            out.append(Instruction(
                lhs, result_shape(rest), _opcode(rest, calls.group(1) if calls else None),
                path[-1] if path else None, "/".join(path), pass_of(op_name),
            ))
    return out


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


# ---------------------------------------------------------------------------
# what an owner keeps
# ---------------------------------------------------------------------------


def abstract(tree, placed: bool = True):
    """``tree``'s arrays as ``jax.ShapeDtypeStruct``s (a donated array still
    has its shape, dtype and sharding), with their shardings where ``placed``:
    what a jit that takes its placement from its arguments needs to lower to
    the program it ran. Without them the lowering is the one the jit made for
    arrays of one device, and finds that executable again in the process."""
    import jax
    import numpy as np

    def one(x):
        sharding = getattr(x, "sharding", None) if placed else None
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)

    return jax.tree.map(one, tree)


class Recipes:
    """The programs an owner has run: per program name the shapes it ran at
    (``key``, anything comparable) and ``lower()``, which lowers it there
    again (-> ``jax.stages.Lowered``). ``note`` is a dictionary store and
    lowers nothing; ``texts`` and ``tables`` compile what they have no text of
    yet and keep a text and its parse for as long as its key stands. A
    ``lower`` holds jitted functions and shapes, never an owner's arrays: the
    newest capture keeps the recipes past their owner's life (``keep``)."""

    __slots__ = ("_recipes", "_made")

    def __init__(self):
        self._recipes: dict = {}
        self._made: dict = {}  # name -> (text, instructions)

    def __contains__(self, name: str) -> bool:
        return name in self._recipes

    def note(self, name: str, key, lower) -> None:
        held = self._recipes.get(name)
        if held is None or held[0] != key:
            self._recipes[name] = (key, lower)
            self._made.pop(name, None)

    def _make(self, at: int) -> dict:
        out = {}
        for name, (_, lower) in list(self._recipes.items()):
            made = self._made.get(name)
            if made is None:
                try:
                    text = lower().compile().as_text()
                except Exception as e:  # named in ``tables().missing``
                    out[name] = e
                    continue
                made = self._made[name] = (text, parse(text))
            out[name] = made[at]
        return out

    def texts(self) -> dict:
        """{program name: compiled text, or the exception lowering it raised}"""
        return self._make(0)

    def tables(self) -> dict:
        """{program name: [Instruction, ...], or the exception}"""
        return self._make(1)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


class Tables(dict):
    """``tables()``'s result: {program name: [Instruction, ...]}, and
    ``missing``: {program (or owner) name: why it is left out}."""

    def __init__(self):
        super().__init__()
        self.missing: dict = {}


_lock = threading.Lock()
_owners: "weakref.WeakSet" = weakref.WeakSet()
# the recipes of the owners that were alive when the newest capture stopped
_kept: list = []


def register(owner) -> None:
    """Keep a weak reference to an owner of compiled programs. It has
    ``program_recipes() -> Recipes`` (what it has run so far, noted and not
    lowered) and ``program_texts()`` (``program_recipes().texts()``); nothing
    else happens until ``tables()``."""
    _owners.add(owner)


def _live(missing: dict) -> list:
    found = []
    for owner in list(_owners):
        try:
            found.append(owner.program_recipes())
        except Exception as e:
            missing[type(owner).__name__] = repr(e)
    return found


def keep() -> None:
    """Hold on to the live owners' recipes (``obs.capture.stop`` calls this):
    a reader that runs after an owner is gone, as the benchmark's do once
    their driver has returned, still finds the programs of the stretch. What
    is held is jitted functions and shapes (an ``InnerTrainer`` with them: its
    jitted step is its own method), until the next capture's stop or
    ``obs.reset``. Nothing is lowered here; garbage is collected first, so
    that an owner whom only the last capture kept, or nobody, is not taken
    for a live one and kept for ever."""
    global _kept
    import gc

    with _lock:
        _kept = []
        gc.collect()
        _kept = _live({})


def tables() -> Tables:
    """The programs of the live owners and of those the newest capture kept,
    parsed: lowered and compiled again where that has not been done at their
    shapes yet. A lowering the owner's jit made itself finds its executable in
    the process (milliseconds); another is a compile, or seconds from the
    persistent cache: call it after whatever is being measured. A
    second owner's program of a name already taken is ``<name>#2``. Never
    raises: a program that could not be lowered and an owner whose
    ``program_recipes`` raised are in ``.missing``; an owner that is gone and
    was in no capture has left nothing to name."""
    out = Tables()
    with _lock:
        # a live owner's recipes are the object a capture kept of it
        for recipes in {id(r): r for r in [*_live(out.missing), *_kept]}.values():
            try:
                made = recipes.tables()
            except Exception as e:
                out.missing[type(recipes).__name__] = repr(e)
                continue
            for name, instructions in made.items():
                if isinstance(instructions, Exception):
                    out.missing[name] = repr(instructions)
                    continue
                taken, n = name, 1
                while taken in out:
                    n += 1
                    taken = f"{name}#{n}"
                out[taken] = instructions
    return out


def index(found: dict) -> dict:
    """{(result name, result shape): [(program, Instruction), ...]}: what an
    event of that name and shape can be."""
    by_pair: dict = {}
    for program, instructions in found.items():
        for ins in instructions:
            by_pair.setdefault((ins.name, ins.shape), []).append((program, ins))
    return by_pair


def ambiguous(found: dict) -> list:
    """The (result name, result shape) pairs that two programs hold under
    different (scope, pass): a trace whose events carry no program cannot
    tell which of the two an event of that name and shape was."""
    return sorted(
        pair for pair, held in index(found).items()
        if len({program for program, _ in held}) > 1
        and len({(ins.scope, ins.pass_) for _, ins in held}) > 1
    )


def save(path: str) -> dict:
    """``tables()``, the ``ambiguous`` pairs and what is missing as JSON at
    ``path``, to lie beside a trace and its ``odtp_capture.json``: an
    instruction is ``[name, shape, opcode, scope, path, pass]`` -> what was
    written."""
    import json
    import os

    found = tables()
    out = {
        "programs": {name: [list(ins) for ins in found[name]] for name in found},
        "ambiguous": [list(pair) for pair in ambiguous(found)],
        "missing": found.missing,
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
        f.write("\n")
    return out


def forget_kept() -> None:
    """Let go of what the newest capture kept."""
    global _kept
    with _lock:
        _kept = []


def reset() -> None:
    """Forget every owner and what a capture kept (tests)."""
    forget_kept()
    _owners.clear()
