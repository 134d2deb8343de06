"""The plain reference of the ZAYA1 block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
per-slot state, no grouped matmul, no batching of requests, and nothing
imported from the program. The two causal convolutions are shifted sums over
the whole sequence (the program's decode step reads the token before from a
per-slot state instead), the values' look-back is a shift, and every expert is
computed on every token and masked by the token's choice. Published
description: the ``zaya`` keys of ``Zyphra/ZAYA1-8B``'s ``config.json``; the
attention is CCA (Zyphra, arXiv 2510.04476), router and residual scaling are
the ZAYA1 report's (arXiv 2511.17127). Parameters are the program's pytree
(``layers``: one stack of like layers), read by name.

The model (RMSNorm eps ``rms_norm_eps``; vocabulary tied). Each layer is two
sublayers, f = CCA attention then f = the routed FFN, each entering as

    h <- (h + b_r) * a_r + (f(rmsnorm(h)) + b_f) * a_f          (four learned vectors a sublayer)
    logits = rmsnorm(h, final_norm) E^T

CCA over x = rmsnorm(h, input_norm), token t (Nh query heads, Nkv KV heads of
Dh = ``head_dim``; g(h) the KV group of query head h):

    q~ = x W_q (Nh Dh);  k~ = x W_k (Nkv Dh);  z = [q~ ; k~]
    c_t = w0[0] * z_{t-1} + w0[1] * z_t + b0                    depthwise, ``cca_time0`` = 2 taps
    d_t = W1[g, 0] c_{t-1}|g + W1[g, 1] c_t|g + b1              a Dh x Dh map a head and tap
    q'_h = d^q_h + (q~_h + k~_g(h)) / 2
    k'_g = d^k_g + (mean_{h in g} q~_h + k~_g) / 2
    q', k': each head times sqrt(Dh) / its L2 norm; k' further times temp_g
    v_t = [x_t W_v ; x_{t-1} W_vprev]                           first half of the KV heads own, second the token before's
    q', k' rotated by position over their first ``partial_rotary_factor`` x Dh values
    scores = q' . k' / sqrt(Dh), causal, softmax in float32; y = concat_heads(P v) W_o

with z, c and x zero before the sequence.

Routed FFN over m = rmsnorm(h, post_attn_norm), layer l:

    r^l = m W_d + b_d + gamma^l * r^{l-1}        (``router_hidden_size``; r^{-1} = 0; handed on as it is)
    s = rmsnorm(r^l, router_norm)
    logits = W_3 gelu(W_2 gelu(W_1 s + b_1) + b_2);  p = softmax(logits) in float32
    the token's expert is argmax(p + b_sel), its weight p of that expert
    out = p_e * down_e(silu(gate_e m) * (up_e m))

**Top-1 makes a flipped choice a whole FFN.** So the walk can *follow* choices
made elsewhere (``follow`` [B, T, L]: the expert of each token and layer, as
the program under test chose them) and reports beside the logits its own
choice and the margin of that choice, the largest biased score less the second
(``forward(..., with_choices=True)``): the cell's check compares logits along
the program's choices, and separately holds every differing choice to a small
reference margin.

Departures and what no config key fixes, each deliberate:

- The family description's "MoD" (a skip route) is not built: no config key
  sizes it and ``num_experts`` is 16, so the router has 16 outputs.
- The L2 normalisation is ``a / sqrt(mean(a^2) + eps)`` with the norms' eps
  (the program's), a weightless RMSNorm of the head; k's temperature is a
  plain factor per KV head.
- The rotation pairs value i of the rotated part with value i + its half.
- GELU is the exact one (erf). ``gamma`` is a vector of the router's width.
- ``faults`` (tests only) breaks one thing at a time, to show that the
  comparison's limit catches it: ``no_conv0``, ``no_conv1_back``,
  ``no_mean``, ``no_temp``, ``own_values_only``, ``full_rotary``,
  ``no_carry``, ``bias_weighed``, ``no_residual_scaling``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


class _Ops:
    """How a walk multiplies: operands rounded to ``operands`` first (None:
    as they are), products accumulated in float32."""

    def __init__(self, operands=None, faults=()):
        self.faults = tuple(faults)
        self.lo = (
            (lambda a: a) if operands is None
            else (lambda a: jnp.asarray(a.astype(operands), jnp.float32))
        )

    def mm(self, a, b):
        return self.lo(a) @ self.lo(b)


def _back(a):
    """a [B, T, ...] one token back: zero before the sequence."""
    return jnp.concatenate((jnp.zeros_like(a[:, :1]), a[:, :-1]), axis=1)


def _rotate(x, theta: float, rot: int):
    """x [B, T, H, d] rotated by position over its first ``rot`` values, value
    i paired with value i + rot/2; the rest as it is."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv  # [T, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2 : rot], x[..., rot:]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest), axis=-1)


def _theta(cfg: dict) -> float:
    """The rotation's base: a file's own ``rope_theta``, else the published
    ``rope_parameters`` entry of the one kind of layer there is."""
    return cfg.get("rope_theta") or cfg["rope_parameters"]["hybrid"]["rope_theta"]


def _sizes(cfg: dict):
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    return nh, nkv, cfg["head_dim"]


def cca_qkv(x, w, cfg: dict, ops=None):
    """CCA's projections over x [B, T, D] -> (q [B, T, Nh, Dh], k and v [B, T,
    Nkv, Dh]), q and k rotated."""
    ops = ops or _Ops()
    b, t, _ = x.shape
    nh, nkv, dh = _sizes(cfg)
    rep, eps = nh // nkv, cfg.get("rms_norm_eps", 1e-5)
    zq, zk = ops.mm(x, w["q_proj"]), ops.mm(x, w["k_proj"])
    z = jnp.concatenate((zq, zk), axis=-1)
    w0, w1 = w["cca_conv0_weight"], w["cca_conv1_weight"]
    c = z if "no_conv0" in ops.faults else _back(z) * w0[0] + z * w0[1] + w["cca_conv0_bias"]
    heads = lambda a: a.reshape(b, t, nh + nkv, dh)
    d = jnp.einsum("bthi,hio->btho", ops.lo(heads(c)), ops.lo(w1[:, 1]))
    if "no_conv1_back" not in ops.faults:
        d = d + jnp.einsum("bthi,hio->btho", ops.lo(heads(_back(c))), ops.lo(w1[:, 0]))
    d = d + w["cca_conv1_bias"].reshape(nh + nkv, dh)
    zq, zk = zq.reshape(b, t, nkv, rep, dh), zk.reshape(b, t, nkv, 1, dh)
    q, k = d[:, :, :nh], d[:, :, nh:]
    if "no_mean" not in ops.faults:
        q = q + ((zq + zk) / 2).reshape(b, t, nh, dh)
        k = k + (jnp.mean(zq, axis=3) + zk[:, :, :, 0]) / 2
    unit = lambda a: a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)
    q, k = unit(q), unit(k)
    if "no_temp" not in ops.faults:
        k = k * w["cca_k_temp"][:, None]
    own = ops.mm(x, w["v_proj"])
    before = own if "own_values_only" in ops.faults else _back(ops.mm(x, w["v_prev_proj"]))
    v = jnp.concatenate((own, before), axis=-1).reshape(b, t, nkv, dh)
    rot = dh if "full_rotary" in ops.faults else int(dh * cfg.get("partial_rotary_factor", 1.0))
    return _rotate(q, _theta(cfg), rot), _rotate(k, _theta(cfg), rot), v


def cca_attention(x, w, cfg: dict, ops=None):
    """The CCA sublayer's branch over x [B, T, D], causal."""
    ops = ops or _Ops()
    b, t, _ = x.shape
    nh, nkv, dh = _sizes(cfg)
    q, k, v = cca_qkv(x, w, cfg, ops)
    k, v = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, v))
    s = jnp.einsum("bihd,bjhd->bhij", ops.lo(q), ops.lo(k)) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhij,bjhd->bihd", ops.lo(p), ops.lo(v)).reshape(b, t, nh * dh)
    return ops.mm(o, w["o_proj"])


def router(m, w, cfg: dict, carried, ops=None):
    """-> (p [B, T, E] float32, the biased scores p + b_sel, r^l to hand on)."""
    ops = ops or _Ops()
    r = ops.mm(m, w["router_down"]) + w["router_down_bias"]
    if carried is not None and "no_carry" not in ops.faults:
        r = r + w["router_gamma"] * carried
    s = _rmsnorm(r, w["router_norm"], cfg.get("rms_norm_eps", 1e-5))
    for fc in ("router_fc1", "router_fc2"):
        s = jax.nn.gelu(ops.mm(s, w[fc]) + w[fc + "_bias"], approximate=False)
    p = jax.nn.softmax(ops.mm(s, w["router"]), axis=-1)
    return p, p + w["router_bias"], r


def routed_ffn(m, w, cfg: dict, carried, follow=None, ops=None):
    """The MoE sublayer's branch over m [B, T, D] -> (out, r^l, the walk's own
    choice [B, T] int32, its margin [B, T]: the largest biased score less the
    second). ``follow`` [B, T]: the experts to take in place of its own."""
    ops = ops or _Ops()
    p, biased, r = router(m, w, cfg, carried, ops)
    own = jnp.argmax(biased, axis=-1).astype(jnp.int32)
    top = jnp.sort(biased, axis=-1)
    margin = top[..., -1] - top[..., -2]
    chosen = own if follow is None else follow
    onehot = jax.nn.one_hot(chosen, p.shape[-1], dtype=jnp.float32)
    weight = (biased if "bias_weighed" in ops.faults else p) * onehot  # [B, T, E]

    def expert(acc, e):
        gate, up, down, w_e = e  # w_e [B, T]: 0 where e was not chosen
        y = ops.mm(jax.nn.silu(ops.mm(m, gate)) * ops.mm(m, up), down)
        return acc + w_e[..., None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(m),
        (w["gate_proj"], w["up_proj"], w["down_proj"], jnp.moveaxis(weight, -1, 0)),
    )
    return out, r, own, margin


def _residual(h, f, w, sub: str, ops):
    if "no_residual_scaling" in ops.faults:
        return h + f
    stream = (h + w[f"{sub}_stream_bias"]) * w[f"{sub}_stream_scale"]
    return stream + (f + w[f"{sub}_branch_bias"]) * w[f"{sub}_branch_scale"]


def _head(h, table, ops, block: int = 32768):
    """h [..., D] against the tied table [V, D] -> [..., V], the table upcast
    a block of rows at a time (262,272 rows are 2.1 GB in float32)."""
    parts = [
        ops.mm(h, jnp.asarray(table[i : i + block], jnp.float32).T)
        for i in range(0, table.shape[0], block)
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _walk(params: dict, input_ids, cfg: dict, operands=None, faults=(), follow=None, rows=None):
    """-> (logits [B, T', V], own choices [B, T, L], margins [B, T, L]).

    ``operands``: a dtype below float32 to which both operands of every
    matrix multiplication are rounded first (the products still accumulate in
    float32): the reference as a lower precision would compute it, for the
    readings that place a tolerance. ``rows`` (start, count): the positions
    whose logits are wanted (None: all T). A layer's weights are upcast one
    layer at a time, so that the walk fits beside an engine."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ops = _Ops(operands, faults)
    choices, margins = [], []
    with jax.default_matmul_precision("highest"):
        h = f32(params["embed_tokens"][input_ids])
        carried = None
        for i in range(cfg["num_hidden_layers"]):
            w = {name: f32(leaf[i]) for name, leaf in params["layers"].items()}
            x = _rmsnorm(h, w["input_norm"], eps)
            h = _residual(h, cca_attention(x, w, cfg, ops), w, "attn", ops)
            m = _rmsnorm(h, w["post_attn_norm"], eps)
            out, carried, own, margin = routed_ffn(
                m, w, cfg, carried, None if follow is None else follow[..., i], ops
            )
            h = _residual(h, out, w, "ffn", ops)
            choices.append(own)
            margins.append(margin)
        if rows is not None:  # the start may be traced, the count not
            h = jax.lax.dynamic_slice_in_dim(h, rows[0], rows[1], axis=1)
        h = _rmsnorm(h, f32(params["final_norm"]), eps)
        logits = _head(h, params["embed_tokens"], ops)
    return logits, jnp.stack(choices, axis=-1), jnp.stack(margins, axis=-1)


def forward(params: dict, input_ids, cfg: dict, operands=None, faults=(), follow=None,
            rows=None, with_choices: bool = False):
    """Logits [B, T, V] float32 of ``input_ids`` [B, T] (of the ``rows``
    wanted); with ``with_choices`` also the walk's own expert of each token
    and layer [B, T, L] and that choice's margin."""
    out = _walk(params, input_ids, cfg, operands, faults, follow, rows)
    return out if with_choices else out[0]


def loss(params: dict, input_ids, labels, cfg: dict):
    """Mean next-token cross-entropy of positions 0..T-2 (no aux loss: the
    selection bias balances the load)."""
    logits = _walk(params, input_ids, cfg)[0]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_and_grad_norm(params: dict, input_ids, labels, cfg: dict):
    """-> (loss, global L2 norm of d loss / d params), both float32."""
    value, grads = jax.value_and_grad(loss)(params, input_ids, labels, cfg)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
