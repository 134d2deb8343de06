"""The plain reference of Solar-Open2's block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
rings, no chunks and nothing imported from the program: a full forward over
one sequence. The kda layers run **the recurrence token by token** (never the
chunked form the program runs) with the convolution as shifted sums over the
whole sequence, the gqa layers a full causal softmax a block of queries at a
time, the router over all its experts with the held experts' terms. At the
published widths a layer's 40 held experts are 2.5 GB in float32 beside an
engine of 11 GB, so the walk is a Python loop over the layers that widens a
projection where it is used: a kda layer a group of heads at a time, a gqa
layer a KV head at a time, the experts one at a time out of their stack, a
block of tokens at a time. Published description: the keys of
``upstage/Solar-Open2-250B``'s ``config.json``; what no key fixes is in the
configuration file's ``assumed`` and marked *assumed* here. Parameters are the
program's pytree (``layers``: a stack a kind of layer, ``kda`` / ``attention``),
read by name.

The stream: ``h_0 = E[id]``; a layer adds ``A(norm(h))`` and then
``F(norm(h))``, RMSNorm of eps ``rms_norm_eps``, no bias; logits ``= W_head
norm(h)``, untied. Layer l is a gqa layer where l is in ``gqa_layers``, else a
kda layer. No rotation anywhere (``use_rope`` false).

A kda layer (Kimi Delta Attention, arXiv 2510.26692), x = norm(h), H =
``linear_attn_config.num_heads`` heads of D = its ``head_dim``, no grouping:
``q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))``, a causal
depthwise convolution of ``short_conv_kernel_size`` taps over time on each
channel, no bias (*assumed*: the family's short convolution, SiLU after it,
on all three; tap j of ``conv_weight`` [taps, 3 H D] multiplies the token
taps - 1 - j back); ``q <- q / |q|_2``, ``k <- k / |k|_2`` over a head's values
(``x rsqrt(sum x^2 + 1e-6)``), then ``q <- D^-1/2 q``; the decay, a vector a token
and head: ``g = -exp(A_log_h) softplus((x W_f1) W_f2 + dt_bias)``, ``a = exp(g)``
(``kda_use_full_proj`` false: low rank, *assumed* rank D); ``beta = 2 sigmoid(x
W_b)`` a head (``kda_allow_neg_eigval``); the state S [D (key), D (value)]
float32 from zero: ``S' = Diag(a_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T
k_t)^T``, ``o_t = S_t^T q_t``; ``out = W_o [rmsnorm_D(o_t; w) * sigmoid((x W_g1) W_g2 +
b_g)]``, the norm over each head's D values under one weight of D a layer
(*assumed*: the family's gated output norm, the gate low-rank as the decay's
with a bias on its second factor alone).

A gqa layer: q as ``num_attention_heads`` heads, k and v as
``num_key_value_heads``; query head i reads KV head i // (heads / KV heads); no
rotation, no QK norm; causal softmax of q . k / sqrt(head_dim) over every row;
``out = W_o (o * sigmoid(x W_g))``, W_g [D_model, H D] from the layer's normed
input, no bias (*assumed*: ``use_gqa_gate`` is the elementwise form of gated
attention, arXiv 2505.06708).

The expert layer (every layer): ``s = sigmoid(x W_r)`` over ``num_experts`` (the
router's width; ``n_routed_experts`` where a file is not cut); chosen: the
``num_experts_per_tok`` largest of ``s + b`` (b the selection bias: it chooses
and does not weigh); weights ``s_e / sum of the chosen s`` (``norm_topk_prob``)
times ``routed_scaling_factor``; ``F(x) = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)``
(*assumed*: the GLM-4.5 / DeepSeek-V3 family's router, which the keys name).
A tree that holds a share of the experts (its ``gate_proj`` [Eh, ...] from
``first_local_expert`` on) adds their terms alone, beside the shared expert's.

``faults`` (the tests and the readings) breaks one thing at a time, to show
which of them the comparison's limit catches; each name is said where it acts.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

BLOCK = 256  # queries whose scores over every row are held at once
TOKENS = 1024  # tokens whose FFN intermediates are held at once
HEADS = 8  # kda heads walked at once
CHUNK = 2048  # the tokens after which ``zero_state_chunks`` / ``zero_tail_chunks`` forget
EXPERTS = ("gate_proj", "up_proj", "down_proj")  # [Eh, ...] a layer


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
    """``fn`` over x [T, ...] (or a tuple of such arrays) a block of rows at a
    time -> [T, ...]."""
    t = jax.tree.leaves(x)[0].shape[0]
    block = min(block, t)
    pad = -t % block
    cut = lambda a: jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1))).reshape(-1, block, *a.shape[1:])
    out = jax.lax.map(fn, jax.tree.map(cut, x))
    return out.reshape(-1, *out.shape[2:])[:t]


def kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from ``gqa_layers``: "attention" or "kda"."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    gqa = set(cfg["gqa_layers"])
    return ["attention" if i in gqa else "kda" for i in range(n)]


def _conv(rows, taps_w, faults, ops):
    """The causal depthwise convolution of rows [T, C] under taps_w [taps, C]:
    ``sum_j w_j x_{t - (taps - 1 - j)}``, rows before the sequence zero: as
    ``taps`` shifted sums. ``zero_tail_chunks``: a token whose taps reach back
    past a multiple of ``CHUNK`` reads zeros there (a chunk that enters with a
    zero tail)."""
    t = rows.shape[0]
    taps = taps_w.shape[0]
    at = jnp.arange(t)
    out = jnp.zeros(rows.shape, jnp.float32)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(rows, ((back, 0), (0, 0)))[:t]
        if "zero_tail_chunks" in faults and back:
            shifted = jnp.where((at % CHUNK >= back)[:, None], shifted, 0.0)
        out = out + ops.lo(shifted) * jnp.asarray(taps_w[j], jnp.float32)
    return out


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(x, w, cfg: dict, ops, faults=()):
    """A kda layer's branch over x [T, D_model] (its normed input) -> [T,
    D_model]. A group of heads at a time; the recurrence token by token.
    Faults: ``no_silu``, ``no_l2``, ``no_q_scale``, ``beta_one`` (beta without its
    2), ``scalar_decay`` (one decay a head: the channels' mean), ``decay_after``
    (the decay after the update), ``no_delta`` (``S += beta k v^T``), ``bf16_state``,
    ``zero_state_chunks`` and ``zero_tail_chunks`` (a chunk that enters with a
    zero state, a zero tail), ``norm_all`` (the output norm over all H D
    values), ``no_kda_gate``."""
    t, _ = x.shape
    dh = cfg["linear_attn_config"]["head_dim"]
    h = cfg["linear_attn_config"]["num_heads"]
    hb = math.gcd(h, HEADS)
    eps = cfg.get("rms_norm_eps", 1e-5)
    f32 = jnp.float32
    act = (lambda a: a) if "no_silu" in faults else jax.nn.silu
    unit = (lambda a: a) if "no_l2" in faults else _unit
    scale = 1.0 if "no_q_scale" in faults else dh**-0.5
    two = 2.0 if cfg.get("kda_allow_neg_eigval", False) and "beta_one" not in faults else 1.0
    low = ops.mm(x, w["f_a_proj"])  # [T, D]: the decay's first factor
    beta = two * jax.nn.sigmoid(ops.mm(x, w["b_proj"]))  # [T, H]
    cols = lambda a, g, width=hb * dh: jax.lax.dynamic_slice_in_dim(a, g * width, width, axis=-1)

    def group(out, g):
        def qkv(name, part):  # the group's heads of one of the three, convolved
            taps_w = cols(jax.lax.dynamic_slice_in_dim(w["conv_weight"], part * h * dh, h * dh, 1), g)
            return act(_conv(ops.mm(x, cols(w[name], g)), taps_w, faults, ops)).reshape(t, hb, dh)

        q, k, v = qkv("q_proj", 0), qkv("k_proj", 1), qkv("v_proj", 2)
        q, k = unit(q) * scale, unit(k)
        dt = jax.nn.softplus(ops.mm(low, cols(w["f_b_proj"], g)) + jnp.asarray(cols(w["dt_bias"], g), f32))
        a_log = jnp.asarray(jax.lax.dynamic_slice_in_dim(w["A_log"], g * hb, hb), f32)
        logs = -jnp.exp(a_log)[None, :, None] * dt.reshape(t, hb, dh)  # g_t [T, hb, D]
        if "scalar_decay" in faults:
            logs = jnp.broadcast_to(jnp.mean(logs, axis=-1, keepdims=True), logs.shape)
        b = jax.lax.dynamic_slice_in_dim(beta, g * hb, hb, axis=1)  # [T, hb]

        def token(s, xs):
            i, qt, kt, vt, gt, bt = xs
            qt, kt, vt = ops.lo(qt), ops.lo(kt), ops.lo(vt)
            if "zero_state_chunks" in faults:
                s = jnp.where(i % CHUNK == 0, 0.0, s)
            decay = jnp.exp(gt)[:, :, None]
            if "decay_after" not in faults:
                s = decay * s
            read = 0.0 if "no_delta" in faults else jnp.einsum("hkv,hk->hv", s, kt)
            s = s + kt[:, :, None] * (bt[:, None] * (vt - read))[:, None, :]
            if "decay_after" in faults:
                s = decay * s
            if "bf16_state" in faults:
                s = s.astype(jnp.bfloat16).astype(f32)
            return s, jnp.einsum("hkv,hk->hv", s, qt)

        _, o = jax.lax.scan(
            token, jnp.zeros((hb, dh, dh), f32), (jnp.arange(t), q, k, v, logs, b), unroll=8
        )
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(t, hb * dh), g * hb * dh, 1), None

    o, _ = jax.lax.scan(group, jnp.zeros((t, h * dh), f32), jnp.arange(h // hb))
    norm = jnp.asarray(w["out_norm"], f32)
    if "norm_all" in faults:
        o = _rmsnorm(o, jnp.tile(norm, h), eps)
    else:
        o = _rmsnorm(o.reshape(t, h, dh), norm, eps).reshape(t, h * dh)
    if "no_kda_gate" not in faults:
        gate = ops.mm(ops.mm(x, w["g_a_proj"]), w["g_b_proj"]) + jnp.asarray(w["g_bias"], f32)
        o = o * jax.nn.sigmoid(gate)
    return ops.mm(o, w["o_proj"])


def _rotate(x, theta: float):
    """x [T, H, d] rotated whole by position, value i paired with i + d / 2."""
    t, _, d = x.shape
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)


def attention(x, w, cfg: dict, ops, faults=()):
    """A gqa layer's branch over x [T, D_model] -> [T, D_model]: a KV head and
    a block of queries at a time. Faults: ``no_gqa_gate``, ``headwise_gate`` (a
    gate a head, the head's first gate value, in the elementwise one's place),
    ``gqa_rope`` (q and k rotated at ``rope_theta``)."""
    t, _ = x.shape
    dh, nh, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rep = nh // kv
    at = jnp.arange(t)
    theta = float(cfg.get("rope_theta", 10000.0))

    def kv_head(out, j):
        q = ops.mm(x, jax.lax.dynamic_slice_in_dim(w["q_proj"], j * rep * dh, rep * dh, 1))
        q = q.reshape(t, rep, dh)
        k = ops.mm(x, jax.lax.dynamic_slice_in_dim(w["k_proj"], j * dh, dh, 1))
        v = ops.mm(x, jax.lax.dynamic_slice_in_dim(w["v_proj"], j * dh, dh, 1))
        if "gqa_rope" in faults:
            q, k = _rotate(q, theta), _rotate(k[:, None], theta)[:, 0]

        def queries(block):
            qb, pos = block
            s = jnp.einsum("trd,sd->trs", ops.lo(qb), ops.lo(k)) * dh**-0.5
            s = jnp.where(at[None, None, :] <= pos[:, None, None], s, -jnp.inf)
            return jnp.einsum("trs,sd->trd", ops.lo(jax.nn.softmax(s, axis=-1)), ops.lo(v))

        o = _blocked(queries, (q, at), BLOCK)
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(t, rep * dh), j * rep * dh, 1), None

    o, _ = jax.lax.scan(kv_head, jnp.zeros((t, nh * dh), jnp.float32), jnp.arange(kv))
    if "headwise_gate" in faults:
        gate = jax.nn.sigmoid(ops.mm(x, w["attn_gate"]).reshape(t, nh, dh)[:, :, :1])
        o = (o.reshape(t, nh, dh) * gate).reshape(t, nh * dh)
    elif "no_gqa_gate" not in faults and cfg.get("use_gqa_gate", False):
        o = o * jax.nn.sigmoid(ops.mm(x, w["attn_gate"]))
    return ops.mm(o, w["o_proj"])


def _swiglu(m, w, ops, prefix=""):
    gated = jax.nn.silu(ops.mm(m, w[prefix + "gate_proj"])) * ops.mm(m, w[prefix + "up_proj"])
    return ops.mm(gated, w[prefix + "down_proj"])


def routed_ffn(m, w, cfg: dict, ops, faults=(), shared: bool = True):
    """The expert layer's branch over m [T, D]: sigmoid scores over all the
    router's experts, the k largest under the selection bias, weighed by their
    scores normalised and scaled; every held expert computed on every token and
    weighed by the token's choice (0 where it chose another), plus (``shared``)
    the shared SwiGLU -> [T, D]. Faults: ``softmax_router`` (softmax scores),
    ``topk_among_held`` (the k chosen among the held experts alone),
    ``bias_weighed`` (the weights from the biased scores), ``no_shared``."""
    e = w["router"].shape[-1]
    k = cfg["num_experts_per_tok"]
    held = w["gate_proj"].shape[-3]
    first = cfg.get("first_local_expert", 0) if held != e else 0
    logits = ops.mm(m, w["router"])
    s = jax.nn.softmax(logits, axis=-1) if "softmax_router" in faults else jax.nn.sigmoid(logits)
    biased = s + jnp.asarray(w["router_bias"], jnp.float32)
    choose = biased
    if "topk_among_held" in faults:
        mine = (jnp.arange(e) >= first) & (jnp.arange(e) < first + held)
        choose = jnp.where(mine, biased, -jnp.inf)
    _, chosen = jax.lax.top_k(choose, k)
    gate = jnp.take_along_axis(biased if "bias_weighed" in faults else s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * cfg.get("routed_scaling_factor", 1.0)
    weight = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32) * gate[..., None], axis=-2)
    weight = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=-1)  # [T, Eh]
    # an expert's three matrices, cut out of where the tree holds them: the
    # layer's [Eh, ...], or its kind's whole stack [L, Eh, ...] with the layer's
    # index beside it (``w["layer"]``), so that no layer's experts are copied whole
    of = lambda name, i: w[name][i] if w[name].ndim == 3 else w[name][w["layer"], i]

    def expert(i, acc):
        y = ops.mm(jax.nn.silu(ops.mm(m, of("gate_proj", i))) * ops.mm(m, of("up_proj", i)),
                   of("down_proj", i))
        return acc + jax.lax.dynamic_index_in_dim(weight, i, 1, keepdims=False)[:, None] * y

    out = jax.lax.fori_loop(0, held, expert, jnp.zeros_like(m))
    if shared and "no_shared" not in faults:
        out = out + _swiglu(m, w, ops, "shared_")
    return out


def layer_step(h, w, cfg: dict, kind: str, operands=None, faults=()):
    """One layer over h [T, D], its weights ``w`` as the tree holds them
    (widened where they are used) -> h."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    ops = _Ops(operands)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(h, f32(w["input_norm"]), eps)
        mixer = kda if kind == "kda" else attention
        h = h + mixer(x, w, cfg, ops, faults)
        ffn = lambda m: routed_ffn(m, w, cfg, ops, faults)
        h = h + _blocked(ffn, _rmsnorm(h, f32(w["post_attn_norm"]), eps), TOKENS)
    return h


@functools.lru_cache(maxsize=None)
def _jitted_layer(cfg_json: str, kind, operands, faults):
    """A jitted ``layer_step`` a (configuration, kind, walk) over the kind's
    whole stack and the layer's index in it: the layer's weights are cut out
    inside, where they are used, the experts' never whole."""
    cfg = json.loads(cfg_json)

    def step(h, stack, i):
        w = {name: a if name in EXPERTS else a[i] for name, a in stack.items()}
        return layer_step(h, {**w, "layer": i}, cfg, kind, operands, faults)

    return jax.jit(step)


def forward(params, input_ids, cfg, operands=None, faults=(), rows=None):
    """Logits [1, R, V] float32 of ``input_ids`` [1, T] at the ``rows`` wanted
    ((start, count); None: all T). A Python loop over the layers, each under a
    jit of its own kind."""
    if input_ids.shape[0] != 1:
        raise ValueError("the reference walks one sequence at a time")
    eps = cfg.get("rms_norm_eps", 1e-5)
    frozen, faults = json.dumps(cfg, sort_keys=True), tuple(faults)
    t = input_ids.shape[1]
    start, count = (jnp.int32(0), t) if rows is None else (jnp.asarray(rows[0], jnp.int32), rows[1])
    h = jnp.asarray(params["embed_tokens"][input_ids[0]], jnp.float32)
    seen: dict = {}
    for kind in kinds(cfg):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        h = _jitted_layer(frozen, kind, operands, faults)(h, params["layers"][kind], jnp.int32(i))
    with jax.default_matmul_precision("highest"):
        h = jax.lax.dynamic_slice_in_dim(h, start, count, axis=0)
        h = _rmsnorm(h, jnp.asarray(params["final_norm"], jnp.float32), eps)
        return _Ops(operands).mm(h, jnp.asarray(params["lm_head"], jnp.float32))[None]
