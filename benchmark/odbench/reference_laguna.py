"""The plain reference of Laguna-S-2.1's block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
rings, no chunks, no grouped matmul, no batching of requests, and nothing
imported from the program: a full forward over one sequence. At the published
widths a routed layer's float32 weights are 2.7 GB, so the walk is a Python
loop over the layers that widens **one layer of the tree it is given at a
time** (of a routed layer one expert at a time, cut from the stack where it is
used), and attention goes a KV head at a time and holds the scores of a block
of queries at a time, so that 16,384 positions fit beside an engine that fills
three quarters of the chip. Published description: the keys of ``poolside/Laguna-S-2.1``'s
``config.json``. Parameters are the program's pytree (``layers``: a stack a
kind of layer, ``dense`` / ``attention`` / ``sliding``), read by name.

The layer, with x = rmsnorm(h, input_norm) (eps ``rms_norm_eps``) at positions
t, and the kinds from ``layer_types`` (full / sliding attention) and
``mlp_layer_types`` (a dense or a routed FFN under it):

1. q = x W_q as H heads of ``head_dim``, k = x W_k and v = x W_v as
   ``num_key_value_heads`` heads; H from ``num_attention_heads_per_layer`` (48
   in a full layer, 72 in a sliding one); query head i reads KV head i // (H /
   KV heads); scores q . k / sqrt(head_dim).
2. rotation by the kind's ``rope_parameters`` entry: the first ``head_dim x
   partial_rotary_factor`` values of each head of q and k, value i paired with
   value i + half of that part; the rest untouched. ``rope_type`` default:
   pair i turns at theta^(-2i/d). ``yarn``: f_i = theta^(-2i/d); low =
   floor(d ln(L / (beta_fast 2 pi)) / (2 ln theta)), high = ceil(d ln(L /
   (beta_slow 2 pi)) / (2 ln theta)), held to [0, d - 1], L =
   ``original_max_position_embeddings``; r_i = clip((i - low) / (high - low), 0,
   1); pair i turns at f_i (1 - r_i) + f_i / factor r_i; cos and sin are each
   multiplied by ``attention_factor``.
3. a full layer: query t reads every row s <= t. A sliding layer: rows s with
   0 <= t - s < ``sliding_window``.
4. the gate (``gating`` per head): g = sigmoid(x W_g) in R^H, o_h <- g_h o_h;
   h <- h + concat(o) W_o.
5. x' = rmsnorm(h, post_attn_norm). Dense: h <- h + down(silu(gate x') * up
   x'). Routed: p = softmax(x' W_r) over all ``num_experts``; the
   ``num_experts_per_tok`` largest (ties to the lower index); their p,
   normalised to sum 1 (``norm_topk_prob``), times
   ``moe_routed_scaling_factor``; h <- h + sum of the chosen experts **that
   this chip holds** (``num_local_experts`` from ``first_local_expert`` on;
   absent: all) + the shared SwiGLU, ungated.
6. final rmsnorm, logits = h W_head (untied).

What no config key fixes is in the configuration file's ``assumed``.
``faults`` (the tests and the readings) breaks one thing at a time, to show
which of them the comparison's limits catch: ``no_gate``, ``no_scaling`` (the
routed weights without the 2.5), ``sigmoid_scores`` (sigmoid in the softmax's
place), ``no_factor`` (cos and sin without ``attention_factor``), ``no_ramp``
(the full layers' pairs at their plain frequencies), ``rotate_whole`` (the
whole head of a full layer rotated), ``swap_rope`` (each kind under the other's
tables), ``window_minus`` / ``window_plus`` (a window of one row fewer or
more), ``heads_48`` (a sliding layer's heads from the 48th on contribute
nothing).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

BLOCK = 128  # queries whose scores are held at once
TOKENS = 2048  # tokens whose FFN intermediates are held at once


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


class _Ops:
    """How a walk multiplies: operands widened to float32 where they are used
    (a tree in bfloat16 is never widened whole), rounded to ``operands`` first
    (None: as they are), products accumulated in float32."""

    def __init__(self, operands=None):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        self.lo = f32 if operands is None else (lambda a: f32(f32(a).astype(operands)))

    def mm(self, a, b):
        return self.lo(a) @ self.lo(b)


def _blocked(fn, x, block: int):
    """``fn`` over x [T, ...] a block of rows at a time -> [T, ...]."""
    t = x.shape[0]
    block = min(block, t)
    pad = -t % block
    x = jnp.pad(x, ((0, pad), *((0, 0),) * (x.ndim - 1)))
    out = jax.lax.map(fn, x.reshape(-1, block, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:t]


def kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from the published keys: "dense" (a full layer over a
    dense SwiGLU), "attention" (a full layer over the routed FFN), "sliding"."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    ffns = cfg.get("mlp_layer_types") or ["sparse"] * n
    return [
        "sliding" if name == "sliding_attention" else "dense" if ffn == "dense" else "attention"
        for name, ffn in zip(cfg["layer_types"][:n], ffns[:n])
    ]


def frequencies(rope: dict, d: int, faults=()):
    """One ``rope_parameters`` entry's rotation over the first ``d`` values of
    a head -> (the d / 2 pairs' frequencies, the factor on cos and sin)."""
    theta = float(rope["rope_theta"])
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    f = theta ** (-i / d)
    if rope.get("rope_type", "default") != "yarn":
        return f, 1.0
    fit = lambda turns: d * math.log(rope["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (
        2 * math.log(theta))
    low, high = max(math.floor(fit(rope["beta_fast"])), 0), min(math.ceil(fit(rope["beta_slow"])), d - 1)
    r = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    if "no_ramp" not in faults:
        f = f * (1.0 - r) + f / rope["factor"] * r
    return f, 1.0 if "no_factor" in faults else float(rope["attention_factor"])


def _rotate(x, positions, rope: dict, head_dim: int, faults=(), whole: bool = False):
    """x [T, H, head_dim]: its first ``head_dim x partial_rotary_factor`` values
    rotated by position, value i paired with value i + half of that part."""
    d = head_dim if whole else int(head_dim * rope.get("partial_rotary_factor", 1.0))
    f, factor = frequencies(rope, d, faults)
    ang = positions.astype(jnp.float32)[:, None] * f
    cos, sin = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang) * factor)[:, None]
    x1, x2, rest = x[..., : d // 2], x[..., d // 2 : d], x[..., d:]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest), axis=-1)


def attention(x, w, cfg: dict, kind: str, ops, faults=()):
    """One layer's attention branch over x [T, D] (the layer's normed input),
    ``w`` the layer's weights -> [T, D]. A KV head at a time (its query heads'
    columns of ``q_proj`` and ``attn_gate``, its rows of ``o_proj``), so that
    72 heads' queries and outputs over 16k positions are never held at once."""
    t = x.shape[0]
    kh, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    h = w["q_proj"].shape[-1] // dh
    rep = h // kh
    sliding = kind == "sliding"
    ropes = cfg["rope_parameters"]
    name = "sliding_attention" if sliding != ("swap_rope" in faults) else "full_attention"
    turn = functools.partial(
        _rotate, positions=jnp.arange(t), rope=ropes[name], head_dim=dh, faults=faults,
        whole="rotate_whole" in faults and not sliding,
    )
    window = t
    if sliding:
        window = cfg["sliding_window"] + ("window_plus" in faults) - ("window_minus" in faults)
    # the rows a block of queries can read: its own and the ``reach`` before them
    reach = min(window - 1, t)
    block = min(BLOCK, t)
    pad = -t % block
    front = lambda a: jnp.pad(a, ((reach, pad), (0, 0)))
    live = jnp.ones((h,), jnp.float32)
    if sliding and "heads_48" in faults:
        live = (jnp.arange(h) < 48).astype(jnp.float32)

    def one_kv_head(acc, ws):
        wq, wk, wv, wg, wo, on = ws  # [D, rep dh], [D, dh], [D, dh], [D, rep], [rep dh, D], [rep]
        q = turn(ops.mm(x, wq).reshape(t, rep, dh))
        kp = front(turn(ops.mm(x, wk)[:, None])[:, 0])
        vp = front(ops.mm(x, wv))
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, rep, dh)

        def one_block(xs):
            b, qj = xs  # [block, rep, dh]
            first = b * block  # the block's first position; the span starts ``reach`` before it
            kb = jax.lax.dynamic_slice_in_dim(kp, first, block + reach, axis=0)
            vb = jax.lax.dynamic_slice_in_dim(vp, first, block + reach, axis=0)
            s = jnp.einsum("qrd,kd->rqk", ops.lo(qj), ops.lo(kb)) * dh**-0.5
            at = first + jnp.arange(block)[:, None]
            row = first - reach + jnp.arange(block + reach)[None]
            seen = (row >= 0) & (at - row >= 0) & (at - row < window)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("rqk,kd->qrd", ops.lo(p), ops.lo(vb))

        o = jax.lax.map(one_block, (jnp.arange(qb.shape[0]), qb)).reshape(-1, rep, dh)[:t]
        if "no_gate" not in faults:
            o = o * jax.nn.sigmoid(ops.mm(x, wg))[..., None]
        o = o * on[None, :, None]
        return acc + ops.mm(o.reshape(t, rep * dh), wo), None

    d = x.shape[-1]
    by_kv_head = (
        jnp.moveaxis(w["q_proj"].reshape(d, kh, rep * dh), 1, 0),
        jnp.moveaxis(w["k_proj"].reshape(d, kh, dh), 1, 0),
        jnp.moveaxis(w["v_proj"].reshape(d, kh, dh), 1, 0),
        jnp.moveaxis(w["attn_gate"].reshape(d, kh, rep), 1, 0),
        w["o_proj"].reshape(kh, rep * dh, d),
        live.reshape(kh, rep),
    )
    out, _ = jax.lax.scan(one_kv_head, jnp.zeros_like(x), by_kv_head)
    return out


def _swiglu(m, w, ops, pre=""):
    return ops.mm(
        jax.nn.silu(ops.mm(m, w[pre + "gate_proj"])) * ops.mm(m, w[pre + "up_proj"]),
        w[pre + "down_proj"],
    )


def routed_ffn(m, w, cfg: dict, ops, faults=()):
    """The routed FFN's branch over m [T, D]: softmax scores over all experts,
    the k largest, normalised and scaled, every held expert computed on every
    token and weighed by the token's choice (0 where it chose another), plus
    the shared SwiGLU -> branch [T, D]."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = ops.mm(m, w["router"])
    p = jax.nn.sigmoid(logits) if "sigmoid_scores" in faults else jax.nn.softmax(logits, axis=-1)
    gate, chosen = jax.lax.top_k(p, k)
    if cfg.get("norm_topk_prob", True):
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if "no_scaling" not in faults:
        gate = gate * cfg.get("moe_routed_scaling_factor", 1.0)
    weight = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32) * gate[..., None], axis=-2)
    held = w["gate_proj"].shape[-3]
    first = cfg.get("first_local_expert", 0) if held != e else 0
    weight = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=-1)  # [T, Eh]
    # an expert's three matrices, cut out of where the tree holds them: the
    # layer's [Eh, ...], or its kind's whole stack [L, Eh, ...] with the
    # layer's index beside it (``w["layer"]``: ``_jitted_layer``), so that no
    # layer's experts are copied whole
    of = lambda name, i: w[name][i] if w[name].ndim == 3 else w[name][w["layer"], i]

    def expert(i, acc):
        y = ops.mm(jax.nn.silu(ops.mm(m, of("gate_proj", i))) * ops.mm(m, of("up_proj", i)),
                   of("down_proj", i))
        return acc + jax.lax.dynamic_index_in_dim(weight, i, 1, keepdims=False)[:, None] * y

    out = jax.lax.fori_loop(0, held, expert, jnp.zeros_like(m))
    return out + _swiglu(m, w, ops, "shared_")


def layer_step(h, w, cfg: dict, kind: str, operands=None, faults=()):
    """One layer over h [T, D], its weights ``w`` as the tree holds them
    (widened where they are used) -> (h, the attention's branch before the
    residual [T, D]: what a check that looks at one layer alone compares)."""
    eps = cfg.get("rms_norm_eps", 1e-6)
    ops = _Ops(operands)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        branch = attention(_rmsnorm(h, f32(w["input_norm"]), eps), w, cfg, kind, ops, faults)
        h = h + branch
        ffn = (lambda m: _swiglu(m, w, ops)) if kind == "dense" else (
            lambda m: routed_ffn(m, w, cfg, ops, faults)
        )
        h = h + _blocked(ffn, _rmsnorm(h, f32(w["post_attn_norm"]), eps), TOKENS)
    return h, branch


EXPERTS = ("gate_proj", "up_proj", "down_proj")  # [Eh, ...] a routed layer


@functools.lru_cache(maxsize=None)
def _jitted_layer(cfg_json: str, kind, operands, faults):
    """A jitted ``layer_step`` a (configuration, kind, walk) over the kind's
    whole stack and the layer's index in it: the layer's weights are cut out
    inside, a routed layer's experts one at a time where they are used."""
    cfg = json.loads(cfg_json)

    def step(h, stack, i):
        routed = "router" in stack
        w = {name: a if routed and name in EXPERTS else a[i] for name, a in stack.items()}
        return layer_step(h, {**w, "layer": i}, cfg, kind, operands, faults)

    return jax.jit(step)


def forward(params, input_ids, cfg, operands=None, faults=(), rows=None, branches: bool = False):
    """Logits [1, R, V] float32 of ``input_ids`` [1, T] at the ``rows`` wanted
    ((start, count), the start may be an array; None: all T); with ``branches``
    also each layer's attention branch at those rows [L, R, D]. A Python loop
    over the layers, each under a jit of its own kind: one layer's float32
    weights at a time."""
    if input_ids.shape[0] != 1:
        raise ValueError("the reference walks one sequence at a time")
    eps = cfg.get("rms_norm_eps", 1e-6)
    frozen, faults = json.dumps(cfg, sort_keys=True), tuple(faults)
    start, count = (jnp.int32(0), input_ids.shape[1]) if rows is None else (
        jnp.asarray(rows[0], jnp.int32), rows[1])
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, count, axis=0)
    h = jnp.asarray(params["embed_tokens"][input_ids[0]], jnp.float32)
    seen, kept = {}, []
    for kind in kinds(cfg):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        h, branch = _jitted_layer(frozen, kind, operands, faults)(h, params["layers"][kind], jnp.int32(i))
        if branches:
            kept.append(cut(branch))
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(cut(h), jnp.asarray(params["final_norm"], jnp.float32), eps)
        logits = _Ops(operands).mm(h, jnp.asarray(params["lm_head"], jnp.float32))[None]
    return (logits, jnp.stack(kept)) if branches else logits
