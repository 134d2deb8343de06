"""A routed layer's experts read in place (ISSUE 33): the serving forwards
hand the grouped matmuls the whole expert stack and the layer's index
(``llama.InStack``, ``scan_layers(..., experts_in_place=True)``), training
keeps handing them the layer's own matrices, and the two forms are one
computation: the same pairs in the same groups through the same matrices.

So everything here is bit equality, on the CPU, of the form the chip runs
(``lax.ragged_dot`` over ``L * Eh`` groups of which one layer's have rows)
against the sliced form, which stays reachable as ``_routed_ffn`` with one
layer's leaves. Three tiny routed shapes, the tests' own of each model file:
plain top-k over one stack (OLMoE); a held share with ``first_local_expert``
8 in a hybrid whose second Mamba-2 run starts at layer 2 of its stack
(granite); a sigmoid router with a shared expert behind a leading dense
layer, where the expert stack's index is one behind the cache's (GLM). The
last two are where an index off by a run would hide.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_glm_flash
import test_granite_hybrid
import test_olmoe
from opendiloco_tpu.models import llama, mamba
from opendiloco_tpu.models.llama import (
    EXPERT_LEAVES, InStack, decode_forward, forward, layer_runs, prefill_forward,
)
from opendiloco_tpu.models.ring_cache import cache_shape

SHAPES = {
    "top-k": lambda: test_olmoe.model(8, seed=3)[1:],
    "held-share-hybrid": lambda: test_granite_hybrid.model(seed=3)[1:],
    "sigmoid-shared-behind-dense": lambda: test_glm_flash.model(seed=3)[1:],
}
ROWS = 24  # a slot's ring


def routed_stacks(cfg, params) -> dict:
    """kind -> that kind's stack, for the kinds whose layers are routed."""
    stacks = params["layers"] if cfg.layers_by_kind else {"attention": params["layers"]}
    return {kind: stack for kind, stack in stacks.items() if "router" in stack}


def ragged_dot_groups(fn, *args) -> list[int]:
    """The group counts (the length of the group sizes) of every grouped
    matmul that ``fn(*args)`` traces to, scans and transposes and all."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                found.append(eqn.invars[2].aval.shape[0])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def sliced(monkeypatch):
    """The forwards as they were: every leaf of a layer cut from its stack."""
    in_place = llama.scan_layers
    monkeypatch.setattr(
        llama, "scan_layers",
        lambda *a, experts_in_place=False, **kw: in_place(*a, **kw),
    )


def assert_same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
        )


def test_the_shapes_are_the_three():
    runs = {name: layer_runs(make()[0]) for name, make in SHAPES.items()}
    assert [(r.kind, r.start) for r in runs["held-share-hybrid"]] == [
        ("mamba", 0), ("attention", 0), ("mamba", 2)]
    dense, experts = runs["sigmoid-shared-behind-dense"]
    assert (dense.kind, experts.start, experts.state) == ("dense", 0, 1)
    cfg = SHAPES["held-share-hybrid"]()[0]
    assert (cfg.num_local_experts, cfg.first_local_expert) == (8, 8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_every_layers_ffn_in_place_is_its_ffn_cut_out(shape, dtype):
    """``_routed_ffn`` with the stack and an index against ``_routed_ffn`` with
    that layer's leaves, for every layer of every routed stack: output, aux
    loss and counts alike to the bit, with padding rows not counted."""
    cfg, params = SHAPES[shape]()
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    x = jax.random.normal(jax.random.key(7), (2, 11, cfg.hidden_size), dtype)
    live = jnp.arange(22) % 5 != 0
    for kind, stack in routed_stacks(cfg, params).items():
        depth = stack["router"].shape[0]
        outs, ffn = [], jax.jit(lambda x, w: llama._routed_ffn(cfg, x, w, live))
        for i in range(depth):
            layer = {name: leaf[i] for name, leaf in stack.items()}
            whole = {**layer, **{n: InStack(stack[n], jnp.int32(i)) for n in EXPERT_LEAVES}}
            got, want = ffn(x, whole), ffn(x, layer)
            assert_same_bits(got, want)
            outs.append(np.asarray(got[0].astype(jnp.float32)))
        # the layers differ, so an index that read another layer would show
        assert all(np.any(outs[0] != o) for o in outs[1:]), kind


@pytest.mark.parametrize("shape", list(SHAPES))
def test_prefill_in_place_is_the_sliced_prefill(shape, monkeypatch):
    cfg, params = SHAPES[shape]()
    ids = jnp.asarray(np.random.default_rng(5).integers(3, 100, (1, 16)), jnp.int32)

    def prefill(p, ids, n):  # jax keeps a function's trace: one a form
        return prefill_forward(p, ids, n, cfg, return_moe_counts=True)

    prefill_sliced = lambda *a: prefill(*a)
    held = cfg.held_experts
    depths = [s["router"].shape[0] for s in routed_stacks(cfg, params).values()]
    routed_layers = sum(r.count for r in layer_runs(cfg) if r.kind != "dense")
    groups = ragged_dot_groups(prefill, params, ids, jnp.int32(13))
    # one scan a run, three grouped matmuls a scan, over the kind's whole stack
    assert sorted(set(groups)) == sorted({d * held for d in depths})
    assert len(groups) == 3 * sum(r.kind != "dense" for r in layer_runs(cfg))
    got = jax.jit(prefill)(params, ids, jnp.int32(13))
    assert int(got[-1][0]) > 0 and routed_layers >= 2

    sliced(monkeypatch)
    assert set(ragged_dot_groups(prefill_sliced, params, ids, jnp.int32(13))) == {held}
    assert_same_bits(got, jax.jit(prefill_sliced)(params, ids, jnp.int32(13)))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_decode_step_in_place_is_the_sliced_step(shape, monkeypatch):
    """One step over rings and states that hold something, three of four
    slots live: logits, the rings, the recurrent states and conv tails and
    the counts."""
    cfg, params = SHAPES[shape]()
    slots, bf16 = 4, jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(11), 8))
    width = (1, cfg.latent_row_dim) if cfg.latent else (cfg.kv_heads, cfg.head_dim)
    ring = cache_shape(cfg.num_attention_layers, slots, ROWS, *width)
    ck = jax.random.normal(next(keys), ring, bf16)
    cv = None if cfg.latent else jax.random.normal(next(keys), ring, bf16)
    state = {}
    if cfg.hybrid:
        ssm, conv = mamba.state_shapes(cfg, slots)
        state = {"ssm_state": jax.random.normal(next(keys), ssm, jnp.float32),
                 "conv_state": jax.random.normal(next(keys), conv, bf16)}
    tokens = jnp.asarray([5, 0, 77, 31], jnp.int32)
    lens = jnp.asarray([9, 0, ROWS + 3, 1], jnp.int32)

    def step(p, ck, cv, state):  # jax keeps a function's trace: one a form
        return decode_forward(p, tokens, lens, ck, cv, cfg, return_moe_counts=True, **state)

    step_sliced = lambda *a: step(*a)
    depths = {s["router"].shape[0] for s in routed_stacks(cfg, params).values()}
    groups = ragged_dot_groups(step, params, ck, cv, state)
    assert set(groups) == {d * cfg.held_experts for d in depths}
    got = jax.jit(step)(params, ck, cv, state)
    assert len(got) == 4 + 2 * cfg.hybrid and int(got[-1][0]) > 0

    sliced(monkeypatch)
    assert set(ragged_dot_groups(step_sliced, params, ck, cv, state)) == {cfg.held_experts}
    assert_same_bits(got, jax.jit(step_sliced)(params, ck, cv, state))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_training_keeps_the_layers_own_experts(shape):
    """Differentiated, a stack read in place would give every layer a weight
    gradient of the stack's size: training's grouped matmuls, forward and
    backward, take one layer's matrices, and its loss and gradients are those
    of the layers run one by one through ``decoder_block`` with their own
    leaves (the scan against the unrolled loop: float32 rounding apart)."""
    cfg, params = SHAPES[shape]()
    ids = jnp.asarray(np.random.default_rng(9).integers(3, 100, (2, 12)), jnp.int32)

    def loss(p):
        logits, aux = forward(
            p, ids, cfg, compute_dtype=jnp.float32, remat=False, return_moe_aux=True)
        return llama.causal_lm_loss(logits, ids) + aux

    def loss_by_layer(p):
        positions = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), ids.shape)
        attn = lambda q, k, v: llama.xla_attention(q, k, v, causal=True)
        h, auxs = llama._embed(cfg, p, ids), []
        r = llama.router_carry(cfg, h)  # the block's carry is (h, the router's state)
        for run in layer_runs(cfg):
            block = llama.training_block(cfg, attn, positions, False, run.kind)
            stack = p["layers"][run.kind] if cfg.layers_by_kind else p["layers"]
            for i in range(run.start, run.start + run.count):
                (h, r), (_, aux) = block((h, r), {name: leaf[i] for name, leaf in stack.items()})
                auxs.append(aux)
        h, head = llama._final_norm_and_head(cfg, p, h)
        return llama.causal_lm_loss((h @ head).astype(jnp.float32), ids) + jnp.mean(jnp.stack(auxs))

    groups = ragged_dot_groups(jax.value_and_grad(loss), params)
    assert groups and set(groups) == {cfg.held_experts}
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    want, want_grads = jax.jit(jax.value_and_grad(loss_by_layer))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-5)
