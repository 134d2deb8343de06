"""``obs.programs``: a compiled program's instructions by scope, pass and
opcode (``parse`` on texts written by hand), the registry of the programs'
owners (weak, lazy, memoised, never raising), and the owners themselves: a
tiny ``InnerTrainer``, ``DiLoCoOptimizer`` and ``ServeEngine`` on the CPU."""

import gc
import json

import numpy as np
import pytest

from opendiloco_tpu import obs
from opendiloco_tpu.obs import programs


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset()
    programs.reset()
    yield
    obs.reset()
    programs.reset()


def _pairs(instructions, scope):
    """(name, shape) of the instructions with ``scope`` along their path."""
    return {(i.name, i.shape) for i in instructions if scope in i.path.split("/")}


# ---------------------------------------------------------------------------
# parse
# ---------------------------------------------------------------------------

# the three fixtures of tests/benchmark/test_bench_granite_hybrid.py, which
# hold ``top_level_instructions``, the parse this one generalises
DECODE_TEXT = """HloModule jit__decode

%fused_computation.5 (p: bf16[4,8]) -> bf16[4,8] {
  %mul.1 = bf16[4,8]{1,0} multiply(%p, %p), metadata={op_name="jit(_decode)/while/body/odtp_ssm/mul"}
}

%region_1.2 (arg: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %fusion.7 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_decode)/while/body/odtp_ssm/mul"}
  %fusion.8 = bf16[4,16]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(_decode)/while/body/odtp_mlp/mul"}
  ROOT %tuple.3 = (s32[], bf16[4,8]{1,0}) tuple(%i, %fusion.7), metadata={op_name="jit(_decode)/while/body/odtp_ssm/add"}
}

ENTRY %main.9 (p0: bf16[4,8]) -> bf16[4,8] {
  %custom-call.2 = f32[2,4]{1,0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode)/odtp_ssm/dot_general"}
}
"""
SHARED_DECODE = """ENTRY %main.1 (p0: bf16[4,8]) -> bf16[4,8] {
  %fusion.7 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_decode)/odtp_ssm/mul"}
  %fusion.9 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(_decode)/odtp_ssm/add"}
}
"""
SHARED_PREFILL = """ENTRY %main.2 (p0: bf16[4,8]) -> bf16[4,8] {
  %fusion.7 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_prefill)/odtp_mlp/mul"}
  %fusion.9 = bf16[16,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(_prefill)/odtp_mlp/add"}
}
"""


def test_parse_gives_what_the_drivers_fixtures_ask():
    found = programs.parse(DECODE_TEXT)
    # a fusion's body is no instruction; a while body's instructions are
    assert _pairs(found, "odtp_ssm") == {
        ("%fusion.7", "bf16[4,8]"), ("%tuple.3", "s32[]"), ("%custom-call.2", "f32[2,4]")}
    assert _pairs(found, "odtp_mlp") == {("%fusion.8", "bf16[4,16]")}
    assert len(found) == 4 and not [i for i in found if i.name == "%mul.1"]
    by_name = {i.name: i for i in found}
    assert by_name["%fusion.7"].opcode == "fusion" and by_name["%tuple.3"].opcode == "tuple"
    # a custom call's target is its opcode
    assert by_name["%custom-call.2"].opcode == "tpu_custom_call"
    assert {i.pass_ for i in found} == {"fwd"}
    decode, prefill = programs.parse(SHARED_DECODE), programs.parse(SHARED_PREFILL)
    assert _pairs(decode, "odtp_ssm") == {("%fusion.7", "bf16[4,8]"), ("%fusion.9", "bf16[4,8]")}
    assert _pairs(decode, "odtp_ssm") & _pairs(prefill, "odtp_mlp") == {("%fusion.7", "bf16[4,8]")}


STEP_TEXT = """HloModule jit__train_step_impl, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %inner.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/odtp_mlp/add"}
}

%bitcast_fusion.2 (p: f32[8]) -> f32[8] {
  %inner.2 = f32[8]{0} bitcast(%p)
}

%all-reduce-scatter.3.clone (input: bf16[16,8]) -> bf16[4,8] {
  %all-reduce.9 = bf16[16,8]{1,0} all-reduce(%input), to_apply=%add.clone
  ROOT %dynamic-slice.4 = bf16[4,8]{1,0} dynamic-slice(%all-reduce.9, %i, %z)
}

%add.clone (a: bf16[], b: bf16[]) -> bf16[] {
  ROOT %add.77 = bf16[]{:T(256)} add(%a, %b)
}

%body.5 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.10 = f32[8]{0:T(1024)} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/closed_call/jvp()/while/body/closed_call/odtp_mlp/odtp_router/mul" stack_frame_id=7}
  %fusion.11 = f32[8]{0} fusion(%x), kind=kLoop, calls=%bitcast_fusion.2, metadata={op_name="jit(step)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/odtp_mlp/dot_general"}
  %fusion.12 = f32[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/odtp_attention/mul"}
  %odtp_flash_fwd.6 = (bf16[8,64]{1,0}, f32[8]{0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/closed_call/jvp()/while/body/closed_call/odtp_attention/odtp_flash_fwd/pallas_call"}
  %fusion.13 = bf16[4,8]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.3.clone, metadata={op_name="jit(step)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/odtp_mlp/dot_general"}
  %all-gather-start.1 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) all-gather-start(%w), dimensions={0}, metadata={op_name="jit(step)/while/body/closed_call/jvp()/while/body/closed_call/odtp_attention/dot_general"}
  %reduce.8 = f32[] reduce(%x, %zero), dimensions={0}, to_apply=%add.clone
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
}

ENTRY %main.20 (p0: f32[8]) -> f32[8] {
  %while.3 = (s32[], f32[8]{0}) while(%t), condition=%cond.4, body=%body.5
  %fusion.14 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(main)/transpose(jvp(odtp_lm_head_loss))/jit(take_along_axis)/scatter-add"}
  %fusion.15 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/odtp_optimizer_update/pallas_call[name=odtp_not_a_scope]/add"}
}
"""


def test_parse_reads_scope_path_pass_and_opcode():
    found = {i.name: i for i in programs.parse(STEP_TEXT)}
    # no instruction of a fused computation, however it is named, nor of a
    # computation a reduce or an all-reduce applies element by element
    assert not {"%inner.1", "%inner.2", "%all-reduce.9", "%dynamic-slice.4", "%add.77"} & set(found)
    assert set(found) == {
        "%fusion.10", "%fusion.11", "%fusion.12", "%odtp_flash_fwd.6", "%fusion.13",
        "%all-gather-start.1", "%reduce.8", "%gte.1", "%while.3", "%fusion.14", "%fusion.15"}
    # nested scopes: the innermost, and the path in order
    assert (found["%fusion.10"].scope, found["%fusion.10"].path) == (
        "odtp_router", "odtp_mlp/odtp_router")
    assert found["%odtp_flash_fwd.6"].path == "odtp_attention/odtp_flash_fwd"
    assert found["%odtp_flash_fwd.6"].opcode == "tpu_custom_call"
    assert found["%odtp_flash_fwd.6"].shape == "bf16[8,64]"  # a tuple's first
    # the three passes, from the transformations' markers
    assert [found[n].pass_ for n in ("%fusion.10", "%fusion.11", "%fusion.12")] == [
        "fwd", "bwd", "remat"]
    # a scope wrapped by a transformation is found; a primitive's parameter is none
    assert (found["%fusion.14"].scope, found["%fusion.14"].pass_) == ("odtp_lm_head_loss", "bwd")
    assert found["%fusion.15"].path == "odtp_optimizer_update"
    # no op_name: no scope, forward
    assert (found["%gte.1"].scope, found["%gte.1"].path, found["%gte.1"].pass_) == (None, "", "fwd")
    assert found["%while.3"].opcode == "while" and found["%reduce.8"].opcode == "reduce"
    # a reduce-scatter run as a fusion is named for the computation it calls
    assert found["%fusion.13"].opcode == "all-reduce-scatter"
    assert found["%fusion.11"].opcode == "fusion"
    collectives = {n for n, i in found.items() if programs.is_collective(i.opcode)}
    assert collectives == {"%fusion.13", "%all-gather-start.1"}
    # an instruction is six plain values, in the order the issue gives
    assert tuple(found["%fusion.10"]) == (
        "%fusion.10", "f32[8]", "fusion", "odtp_router", "odtp_mlp/odtp_router", "fwd")


@pytest.mark.parametrize("op_name, path, pass_", [
    ("jit(f)/odtp_mlp/mul", ["odtp_mlp"], "fwd"),
    ("jit(f)/jvp(odtp_mlp)/dot_general", ["odtp_mlp"], "fwd"),
    ("jit(f)/transpose(jvp(odtp_mlp))/checkpoint/odtp_router/mul", ["odtp_mlp", "odtp_router"], "bwd"),
    ("jit(f)/transpose(jvp())/checkpoint/rematted_computation/odtp_mlp/x", ["odtp_mlp"], "remat"),
    ("jit(f)/odtp_serve_decode/odtp_attention/odtp_attention/x",
     ["odtp_serve_decode", "odtp_attention"], "fwd"),
    ("jit(f)/pallas_call[name=odtp_flash_fwd grid=(1, 2)]", [], "fwd"),
    ("", [], "fwd"),
])
def test_scope_path_and_pass_of_an_op_name(op_name, path, pass_):
    assert programs.scope_path(op_name) == path
    assert programs.pass_of(op_name) == pass_


@pytest.mark.parametrize("detail, shape", [
    ("bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop", "bf16[8,128]"),
    ("(s32[], bf16[4,8]{1,0}) tuple(%i, %x)", "s32[]"),
    ("f32[] reduce(%x)", "f32[]"),
    ("", ""),
])
def test_result_shape_is_the_results_or_a_tuples_first(detail, shape):
    assert programs.result_shape(detail) == shape


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


class _Lowered:
    def __init__(self, text):
        self.text = text

    def compile(self):
        return self

    def as_text(self):
        return self.text


class _Owner:
    """Owns the programs of ``texts`` ({name: text, or an exception to
    raise}); counts how often each is lowered."""

    def __init__(self, texts):
        self.lowered = []
        self.recipes = programs.Recipes()
        for name, text in texts.items():
            self.run(name, 0, text)
        programs.register(self)

    def run(self, name, key, text):
        def lower():
            self.lowered.append(name)
            if isinstance(text, Exception):
                raise text
            return _Lowered(text)

        self.recipes.note(name, key, lower)

    def program_recipes(self):
        return self.recipes


def test_register_holds_no_owner_alive_and_lowers_nothing():
    owner = _Owner({"decode": SHARED_DECODE})
    assert owner.lowered == []  # registered and noted: nothing lowered yet
    assert list(programs.tables()) == ["decode"]
    del owner
    gc.collect()
    assert dict(programs.tables()) == {}


def test_tables_are_memoised_per_owner_and_shape():
    owner = _Owner({"decode": SHARED_DECODE, "prefill/16": SHARED_PREFILL})
    first = programs.tables()
    again = programs.tables()
    assert sorted(owner.lowered) == ["decode", "prefill/16"]  # once each
    assert again["decode"] is first["decode"]
    # the same shape noted again: still the text it has; another shape: lowered anew
    owner.run("decode", 0, SHARED_DECODE)
    programs.tables()
    assert owner.lowered.count("decode") == 1
    owner.run("decode", 1, SHARED_PREFILL)
    third = programs.tables()
    assert owner.lowered.count("decode") == 2
    assert _pairs(third["decode"], "odtp_mlp") and not _pairs(third["decode"], "odtp_ssm")


def test_tables_name_what_they_could_not_lower_and_never_raise():
    class Broken:
        def program_recipes(self):
            raise RuntimeError("no recipes")

    owner = _Owner({"decode": SHARED_DECODE, "prefill/16": ValueError("a shape never run")})
    broken = Broken()
    programs.register(broken)
    found = programs.tables()
    assert list(found) == ["decode"]
    assert "a shape never run" in found.missing["prefill/16"]
    assert "no recipes" in found.missing["Broken"]
    # what failed is tried again by the next call, what succeeded is not
    programs.tables()
    assert owner.lowered.count("prefill/16") == 2 and owner.lowered.count("decode") == 1


def test_two_owners_of_one_name_are_told_apart():
    a, b = _Owner({"decode": SHARED_DECODE}), _Owner({"decode": SHARED_DECODE})
    assert sorted(programs.tables()) == ["decode", "decode#2"]
    del a, b


def test_ambiguous_pairs_are_those_two_programs_hold_under_another_scope_or_pass():
    found = {"decode": programs.parse(SHARED_DECODE), "prefill/16": programs.parse(SHARED_PREFILL)}
    # the same name and shape under odtp_ssm here and odtp_mlp there; another
    # shape is another operation
    assert programs.ambiguous(found) == [("%fusion.7", "bf16[4,8]")]
    same = {"prefill/8": programs.parse(SHARED_PREFILL), "prefill/16": programs.parse(SHARED_PREFILL)}
    assert programs.ambiguous(same) == []  # held twice under one scope and pass
    by_pair = programs.index(found)
    assert [p for p, _ in by_pair[("%fusion.7", "bf16[4,8]")]] == ["decode", "prefill/16"]
    assert [p for p, _ in by_pair[("%fusion.9", "bf16[16,8]")]] == ["prefill/16"]


def test_a_capture_keeps_the_recipes_of_the_owners_alive_at_its_stop():
    """The benchmark's readers run after their driver has returned and its
    engine is gone: what the engine would have lowered is kept by the newest
    capture's stop, and let go by the next or by ``obs.reset``."""
    owner = _Owner({"decode": SHARED_DECODE})
    obs.capture.start()
    obs.capture.stop()
    del owner
    gc.collect()
    assert list(programs.tables()) == ["decode"]
    obs.capture.start()
    obs.capture.stop()  # no owner alive at this stop
    assert dict(programs.tables()) == {}
    owner = _Owner({"decode": SHARED_DECODE})
    obs.capture.start()
    obs.capture.stop()
    del owner
    obs.reset()  # lets go of what the capture kept: the owner is garbage now
    gc.collect()
    assert dict(programs.tables()) == {}


def test_save_writes_tables_ambiguous_pairs_and_what_is_missing(tmp_path):
    owner = _Owner({"decode": SHARED_DECODE, "prefill/16": SHARED_PREFILL,
                    "chunk": ValueError("never run")})
    path = tmp_path / "prof" / "odtp_programs.json"
    wrote = programs.save(str(path))
    assert json.loads(path.read_text()) == wrote
    assert sorted(wrote["programs"]) == ["decode", "prefill/16"]
    assert wrote["programs"]["decode"][0] == [
        "%fusion.7", "bf16[4,8]", "fusion", "odtp_ssm", "odtp_ssm", "fwd"]
    assert wrote["ambiguous"] == [["%fusion.7", "bf16[4,8]"]]
    assert "never run" in wrote["missing"]["chunk"]
    del owner


# ---------------------------------------------------------------------------
# the owners
# ---------------------------------------------------------------------------


def _trainer(tiny_cfg, remat=True):
    import jax

    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    tc = TrainerConfig(lr=1e-3, warmup_steps=2, total_steps=200, precision="fp32", remat=remat)
    trainer = InnerTrainer(tiny_cfg, tc, build_mesh("NO_SHARD", devices=jax.devices()[:1]))
    return trainer, trainer.init_state(jax.random.key(0))


def _batch(trainer, tiny_cfg, rows=4, seq=16):
    ids = (np.arange(rows * seq, dtype=np.int32).reshape(rows, seq) * 7) % tiny_cfg.vocab_size
    return trainer.shard_batch(ids, ids.copy(), accum=1)


def test_a_trainers_step_names_its_scopes_and_its_three_passes(tiny_cfg):
    trainer, state = _trainer(tiny_cfg)
    assert trainer.program_texts() == {}  # no step yet: nothing to lower
    state, _ = trainer.train_step(state, _batch(trainer, tiny_cfg))
    text = trainer.program_texts()["train_step"]
    for scope in ("odtp_attention", "odtp_mlp", "odtp_lm_head_loss", "odtp_optimizer_update"):
        assert scope in text
    found = programs.tables()
    assert list(found) == ["train_step"] and found.missing == {}
    step = found["train_step"]
    scopes = {s for i in step for s in i.path.split("/") if s}
    assert {"odtp_attention", "odtp_mlp", "odtp_lm_head_loss", "odtp_optimizer_update"} <= scopes
    assert {i.pass_ for i in step} == {"fwd", "bwd", "remat"}
    for scope in ("odtp_attention", "odtp_mlp"):  # what the policy recomputes
        assert {i.pass_ for i in step if scope in i.path.split("/")} == {"fwd", "bwd", "remat"}
    assert {i.pass_ for i in step if i.scope == "odtp_optimizer_update"} == {"fwd"}
    # the text is the step's at the shape it ran: another shape, another text
    state, _ = trainer.train_step(state, _batch(trainer, tiny_cfg, rows=2, seq=32))
    assert trainer.program_texts()["train_step"] != text
    assert trainer.attn_residual_bytes == 0  # lowering again leaves the gauges as they were


def test_without_remat_a_step_has_no_third_pass(tiny_cfg):
    trainer, state = _trainer(tiny_cfg, remat=False)
    trainer.train_step(state, _batch(trainer, tiny_cfg))
    assert {i.pass_ for i in programs.tables()["train_step"]} == {"fwd", "bwd"}


def test_inner_dispatch_carries_the_step_under_a_capture_and_costs_no_tracer_call_without(
        tiny_cfg, monkeypatch):
    from opendiloco_tpu.obs import trace

    trainer, state = _trainer(tiny_cfg)
    batch = _batch(trainer, tiny_cfg)
    state, _ = trainer.train_step(state, batch)  # compiles
    calls = []
    monkeypatch.setattr(trace.Tracer, "add_span", lambda self, *a, **k: calls.append(a))
    monkeypatch.setattr(trace.Tracer, "count", lambda self, *a, **k: calls.append(a))
    state, _ = trainer.train_step(state, batch)
    assert calls == [] and obs.tracer() is None
    monkeypatch.undo()
    assert trainer.steps_dispatched == 2
    obs.capture.start()
    for _ in range(3):
        state, _ = trainer.train_step(state, batch)
    cap = obs.capture.stop()
    spans = [s for s in cap.spans if s["name"] == "inner/dispatch"]
    assert [s["args"] for s in spans] == [
        {"step": n, "tokens": 4 * 16, "accum": 1} for n in (3, 4, 5)]
    assert all(s["t0"] >= cap.anchor_pc for s in spans)


@pytest.mark.parametrize("placement", ["device", "host"])
def test_an_optimizer_names_its_boundarys_programs_under_outer(tiny_cfg, placement):
    import jax

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld

    trainer, state = _trainer(tiny_cfg, remat=False)
    (backend,) = LoopbackWorld(1).make_backends()
    opt = DiLoCoOptimizer(
        trainer, backend,
        DilocoConfig(local_steps=2, backend="loopback", outer_placement=placement,
                     skip_load_from_peers=True),
        state, 4,
    )
    assert opt.program_texts() == {}
    for _ in range(2):
        state, _ = opt.step(state, _batch(trainer, tiny_cfg))
    jax.block_until_ready(state["params"])
    found = programs.tables()
    assert found.missing == {}
    outer = sorted(name for name in found if name.startswith("outer/"))
    if placement == "device":
        assert outer == ["outer/apply", "outer/pseudo_grad"]
    else:
        assert set(outer) <= {"outer/apply_delta"}  # the host's way back may be a put alone
    assert "train_step" in found
    for name in outer:  # the boundary's programs lie under none of the step's scopes
        assert {i.scope for i in found[name]} == {None}
    opt.drop_pending()


def test_an_engine_names_decode_and_the_buckets_it_has_run(tiny_cfg):
    import jax

    from opendiloco_tpu.models.llama import init_params
    from opendiloco_tpu.serve.engine import ServeEngine

    engine = ServeEngine(
        tiny_cfg, init_params(jax.random.key(0), tiny_cfg), num_slots=4, max_context=64,
        prefill_buckets=(16, 32), decode_kernel="xla",
    )
    assert engine.program_texts() == {}  # nothing has run
    tok, _ = engine.admit(0, [1, 2, 3, 4, 5])
    tokens, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
    tokens[0], lens[0] = tok, 5
    engine.decode_step(tokens, lens)
    ran = (engine._prefill._cache_size(), engine._decode._cache_size())
    found = programs.tables()
    assert sorted(found) == ["decode", "insert/16", "prefill/16"] and found.missing == {}
    # lowered again beside the engine's jits, not through them
    assert (engine._prefill._cache_size(), engine._decode._cache_size()) == ran
    paths = lambda name: {i.path for i in found[name]}
    assert {"odtp_serve_decode/odtp_attention", "odtp_serve_decode/odtp_mlp"} <= paths("decode")
    assert {"odtp_serve_prefill/odtp_attention", "odtp_serve_prefill/odtp_mlp"} <= paths("prefill/16")
    assert {i.pass_ for i in found["decode"]} == {"fwd"}
    engine.admit(1, list(range(1, 21)))  # the second bucket
    assert sorted(programs.tables()) == [
        "decode", "insert/16", "insert/32", "prefill/16", "prefill/32"]
    # the engine gone, a capture that saw it alive still names its programs
    obs.capture.start()
    obs.capture.stop()
    del engine
    gc.collect()
    assert "prefill/32" in programs.tables()
