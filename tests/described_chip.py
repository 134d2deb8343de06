"""What the chip-compile tests share: a described v5e, a cell's shapes on it,
the programs the serve cells run compiled once a session, and the readers of a
compiled program's text.

libtpu is installed here and compiles for a *described* v5e
(``jax.experimental.topologies``), so a program is lowered AND compiled at
published widths in bf16 without the chip. A compile that passes is not a chip
run; it only means the chip run will not die at its first compile.

A chip-compile file names this module in its ``pytest_plugins``, which gives it
the fixtures below (``topo`` and ``chip``, one a session; ``for_the_chip``, a
module's), and asks for ``for_the_chip`` in its ``pytestmark``.

A test file is one unit of the driver's ``--dist loadfile`` run, so the tests
are cut by subject into ``test_chip_compile_*.py`` and no file of them takes
more than about two minutes alone. A whole program is tens of seconds to
compile and several tests read each: :func:`once_a_session` keeps it for the
session, so the tests that read a program live in one file and none of them
mutates it (``as_text()``, ``memory_analysis()``).
"""

import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from opendiloco_tpu.models.ring_cache import cache_shape
from opendiloco_tpu.ops import decode_kernels

BF16 = jnp.bfloat16
HBM_BYTES = 16e9  # what a cell's sizing counts against


@pytest.fixture(scope="session")
def topo():
    """A described (not attached) v5e:2x2 host."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    # another process of this sandbox may hold libtpu's lock file
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"v5e topology cannot be described here: {e}")


@pytest.fixture(scope="session")
def chip(topo):
    """One chip of the described host."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def for_the_chip():
    """Around a module that compiles for the described chip: the decode
    kernels' wrappers lower the kernel (off the TPU they would interpret it;
    this is the chip's program), and the persistent compile cache is off (a
    deviceless executable can be written to the cache but not read back, and
    the next compile would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    patch = pytest.MonkeyPatch()
    patch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()
        patch.undo()


def compiled_text(chip, fn, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def flash_kernels(text):
    return sorted(re.findall(r"^\s*(?:ROOT )?%\w*?(odtp_flash_[a-z]+)[\w.]* = .*custom-call\(", text, re.M))


def narrow_kernel_arrays(text):
    """The operands and results of the flash kernels' calls in a compiled
    text whose minor dimension is under 128 (the rotary tables are a unit of
    128 lanes wide too: ``flash_attention.lanes_of``)."""
    narrow = []
    for line in text.splitlines():
        if not re.match(r"\s*(?:ROOT )?%\w*odtp_flash_[a-z]+[\w.]* = .*custom-call\(", line):
            continue
        for shape in re.findall(r"\b[a-z]+\d+\[[\d,]+\]", line.split(", custom_call_target")[0]):
            if int(shape[:-1].rsplit("[", 1)[1].split(",")[-1]) < 128:
                narrow.append(shape)
    return narrow


def kernel_windows(text: str, name: str) -> list[list[tuple]]:
    """The blocks of each call of the Mosaic kernel ``name`` in a compiled
    text, a list a call: its operands' (behind the scalar-prefetch vectors),
    then its results'. A ``tpu_custom_call`` carries its kernel serialized in
    its ``backend_config`` (MLIR bytecode, base64), whose ``window_params``
    hold each block's ``window_bounds``, squeezed dimensions as 1."""
    import base64

    from jax._src.lib.mlir import ir

    calls = []
    for line in text.splitlines():
        if not re.match(rf"\s*(?:ROOT )?%{name}[\w.]* = .*custom-call\(", line):
            continue
        body = re.search(r'"custom_call_config":\{"body":"([A-Za-z0-9+/=]+)"', line).group(1)
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True  # Mosaic's own, stable_mosaic
        with ctx:
            kernel = str(ir.Module.parse(base64.b64decode(body)))
        params = re.search(r"window_params = \[(.*?)\]\}", kernel).group(1)
        calls.append([
            tuple(int(d) for d in bounds.split(","))
            for bounds in re.findall(r"window_bounds = array<i64: ([\d, ]+)>", params)
        ])
    return calls


def serve_cell(config, workload, **cut):
    """-> (a benchmark configuration as ``LlamaConfig``, with ``cut`` laid
    over the published values, and its cell's engine options)."""
    import json

    from opendiloco_tpu.models.llama import LlamaConfig

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = LlamaConfig.from_dict({**json.load(f), **cut})
    with open(os.path.join(bench, "workloads", f"{workload}.json")) as f:
        return cfg, json.load(f)["engine"]


def olmoe_cell():
    return serve_cell("olmoe-1b-7b", "serve-olmoe-fewshot")


def on_chip(chip, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
    )


def program_bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


RESULT = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]")  # no tuples
# what may yield a cache- or layer-shaped array without moving one: the
# parameters themselves and their passage through the scan's tuples, and the
# kernel, whose cache results alias its operands
MOVES_NOTHING = ("parameter(", "get-tuple-element(", "tpu_custom_call", "bitcast(")


def cache_shaped_results(text: str, cache_shape: tuple) -> list[str]:
    """Instructions of a compiled program whose result has the dimensions of
    the cache or of one layer's pages, in any order (a copy, a transpose, a
    slice, an update, a scatter, a fresh buffer), other than those of
    ``MOVES_NOTHING``."""
    def dims(shape):
        return sorted(d for d in shape if d != 1)

    wanted = (dims(cache_shape), dims(cache_shape[1:]))
    found = []
    for line in text.splitlines():
        m = RESULT.match(line)
        if not m or any(k in line for k in MOVES_NOTHING):
            continue
        if dims(int(d) for d in m.group(3).split(",") if d) in wanted:
            found.append(line.strip()[:160])
    return found


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$"
)


def top_level(text: str) -> tuple[list, dict]:
    """A compiled program's instructions that run as operations of their own
    (outside the fused computations), each as (opcode, result dtype, result
    dimensions, the computation a fusion calls, the line's head), and every
    computation's root opcode."""
    roots, fused, found, name = {}, set(), [], None
    for line in text.splitlines():
        m = _COMPUTATION.match(line.strip())
        if m:
            name = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, _, result, dims, opcode, rest = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        called = re.search(r"calls=(%[\w.\-]+)", rest) if opcode == "fusion" else None
        if called:
            fused.add(called.group(1))
        if root:
            roots[name] = opcode
        found.append((name, opcode, result, shape, called and called.group(1), line.strip()[:160]))
    return [x[1:] for x in found if x[0] not in fused], roots


def leaf_shaped_casts(text: str, leaf_shapes: set, dtype: str = "bf16") -> list[str]:
    """Instructions of a compiled program that run as an operation of their
    own and are a ``convert``, or a fusion whose root is one, with a result in
    ``dtype`` of a weight leaf's shape: what ``_serving_boundary`` emits for a
    leaf that did not come in the compute dtype."""
    instructions, roots = top_level(text)
    return [
        line for opcode, result, shape, called, line in instructions
        if result == dtype and shape in leaf_shapes
        and (opcode == "convert" or roots.get(called) == "convert")
    ]


def bound(chip, cfg):
    """The parameters as ``ServeEngine._bind`` leaves them under bf16 compute
    (every leaf through the engine's own ``_fresh_copy``), as shapes on the
    described chip."""
    from opendiloco_tpu.models.llama import shapes
    from opendiloco_tpu.serve.engine import _fresh_copy

    leaves, treedef = jax.tree.flatten(shapes(cfg))
    held = jax.eval_shape(lambda xs: _fresh_copy(xs, BF16), leaves)
    assert all(x.dtype == BF16 for x in held)
    return on_chip(chip, jax.tree.unflatten(treedef, held))


# how often each program of :func:`once_a_session` was built: once
COMPILES: collections.Counter = collections.Counter()


def once_a_session(build):
    """``build(chip, *key)`` memoised on its name and ``key`` (``chip`` is the
    session's one): built when the session's first test asks for it, under
    the module's ``for_the_chip`` (so with the kernels as the chip lowers them,
    whichever test came first), and read by every later one."""
    built = {}

    @functools.wraps(build)
    def read(chip, *key):
        if key not in built:
            assert not decode_kernels._interpret(None), "lowered outside ``for_the_chip``"
            COMPILES[(build.__name__, *key)] += 1
            built[key] = build(chip, *key)
        return built[key]

    return read


def engine_program(chip, config, workload, program, bucket=None):
    """A serve cell's decode step or a prefill (its largest, or ``bucket``'s), published widths
    and the cell's cuts, lowered as the engine lowers it (kernel ``pallas``,
    counts where routed, caches and state donated) with the bf16 tree the
    engine holds, compiled for the described chip -> (the compiled program,
    the configuration, the parameters, the bytes the step carries). Each
    (config, workload, program, bucket) is compiled once a session."""
    if program == "prefill":
        bucket = bucket or max(serve_cell(config, workload)[1]["prefill_buckets"])
    return _engine_program(chip, config, workload, program, bucket)


@once_a_session
def _engine_program(chip, config, workload, program, bucket):
    from opendiloco_tpu.models import mamba
    from opendiloco_tpu.models.llama import decode_forward, prefill_forward

    cfg, engine = serve_cell(config, workload)
    params = bound(chip, cfg)
    moe = bool(cfg.num_experts)
    if program == "prefill":
        compiled = (
            jax.jit(lambda p, ids, n: prefill_forward(
                p, ids, n, cfg, decode_kernel="pallas", return_moe_counts=moe))
            .lower(
                params,
                jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
            ).compile()
        )
        return compiled, cfg, params, 0
    slots, rows = engine["num_slots"], engine["max_context"]
    width = (1, cfg.latent_row_dim) if cfg.latent else (cfg.kv_heads, cfg.head_dim)
    ring = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_attention_layers, slots, rows, *width), BF16, sharding=chip
    )
    carried = [ring] if cfg.latent else [ring, ring]  # a latent ring has no values
    if cfg.hybrid:
        ssm, conv = mamba.state_shapes(cfg, slots)
        carried += [
            jax.ShapeDtypeStruct(ssm, jnp.float32, sharding=chip),
            jax.ShapeDtypeStruct(conv, BF16, sharding=chip),
        ]
    if cfg.cca:  # what each layer's projection keeps of a slot's last token
        carried.append(jax.ShapeDtypeStruct(
            (cfg.num_hidden_layers, slots, cfg.cca_state_dim), BF16, sharding=chip))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    names = ("cca_state",) if cfg.cca else ("ssm_state", "conv_state")

    def step(p, tok, lens, ck, *rest):
        cv, *state = (None, *rest) if cfg.latent else rest
        return decode_forward(
            p, tok, lens, ck, cv, cfg, decode_kernel="pallas", return_moe_counts=moe,
            **dict(zip(names, state)),
        )

    compiled = (
        jax.jit(step, donate_argnums=tuple(range(3, 3 + len(carried))))
        .lower(params, vec, vec, *carried).compile()
    )
    return compiled, cfg, params, sum(x.size * x.dtype.itemsize for x in carried)


def ring_copies(text: str, shape: tuple) -> list[str]:
    """Instructions that make an array of a ring's shape anew: everything
    ``cache_shaped_results`` finds of the whole ring's dimensions but the
    updates in place (a slice update or the kernels' aliased outputs write
    into the ring they are given)."""
    whole = sorted(d for d in shape if d != 1)
    found = []
    for line in cache_shaped_results(text, shape):
        m = RESULT.match(line)
        if sorted(int(d) for d in m.group(3).split(",") if int(d) != 1) != whole:
            continue  # one layer's pages: judged by its caller
        if "dynamic-update-slice(" in line or "dynamic-update-slice_fusion" in line:
            continue
        found.append(line)
    return found


def f32_blocks_over(text: str, nbytes: float) -> list[str]:
    found = []
    for line in text.splitlines():
        m = RESULT.match(line)
        if m and m.group(2) == "f32":
            size = 4
            for d in m.group(3).split(","):
                size *= int(d) if d else 1
            if size > nbytes and "parameter(" not in line:
                found.append(line.strip()[:160])
    return found


