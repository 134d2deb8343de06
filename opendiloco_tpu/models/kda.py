"""The Kimi-delta linear-attention mixer of a ``solar_open2`` stack (layers
outside ``gqa_layers``, held as the kind "kda"), in the forms the program
runs: the chunked form over a run of tokens that *enters with a state and a
convolution's tail* (training, a whole prompt, a prompt's chunk), the
token-by-token recurrence (the definition: the tests' check of the chunked
form) and the one-step form over the slots (decode: :func:`step` in XLA, and
on the chip ``ops.decode_kernels.kda_step``, the same equations as one kernel
over the stacked states).

Per token t and head h, with x the block's normed input (Kimi Delta Attention,
arXiv 2510.26692; the ``kda_*`` and ``linear_attn_config`` keys of a
``solar_open2`` ``config.json``; H heads of D = ``head_dim``, no grouping):

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
              a causal depthwise convolution of ``kda_short_conv`` taps over
              time on each of the 3 H D channels, no bias
    q, k = q / |q|_2, k / |k|_2     over a head's D values; q <- D^-1/2 q
    g_t  = -exp(A_log_h) softplus((x W_f1) W_f2 + dt_bias)   in R^D, a_t = exp(g_t)
    beta = 2 sigmoid(x W_b)         one scalar a head (``kda_allow_neg_eigval``;
                                    without it sigmoid alone)
    S'   = Diag(a_t) S_{t-1}        S [D (key), D (value)] float32, S_{-1} = 0
    S_t  = S' + beta_t k_t (v_t - S'^T k_t)^T       the decay first, then the delta rule
    o_t  = S_t^T q_t
    out  = W_o [rmsnorm_D(o_t; w) * sigmoid((x W_g1) W_g2 + b_g)]

What a run of tokens leaves behind is ``S`` after its last real token and the
last ``kda_short_conv - 1`` rows of x W_q | x W_k | x W_v *before* the
convolution (the tail): the next chunk of the prompt, or the decode step,
enters with both and hands its own on. Decays, their sums and the state are
float32; matrix products take operands in x's dtype and accumulate in float32,
as ``lightning.chunked``'s and ``mamba.ssm_chunked``'s.

**The chunked form and its exponents.** Over a block of C tokens from an
entering S_0, with G_t the sum of g up to and with t (a channel; <= 0,
falling): ``A_ts = beta_t sum_d k_td k_sd exp(G_td - G_sd)`` for s < t,
``(I + A) U = Diag(beta) (V - (K * exp(G)) S_0)`` (a unit lower-triangular
solve a block and head), ``o_t = S_0^T (q_t * exp(G_t)) + sum_{s<=t} P_ts u_s``
with ``P_ts = sum_d q_td k_sd exp(G_td - G_sd)``, and ``S_C = Diag(exp(G_C))
S_0 + sum_s (k_s * exp(G_C - G_s)) u_s^T``. Split as ``(k_t * exp(G_t)) .
(k_s / exp(G_s))`` the second factor overflows float32 within 64 tokens of a
strong decay (g reaches -1.6 a token under the family's initialisation). So
no exponent here is ever taken of a positive number: a block is
:data:`BLOCK` tokens in sub-blocks of :data:`SUB`; a pair inside one
sub-block takes ``exp(G_t - G_s)`` itself, channel by channel (the family's
kernel does the same); a pair across sub-blocks is split at the *query's*
sub-block's start R, ``(k_t * exp(G_t - R)) . (k_s * exp(R - G_s))``, both
exponents <= 0 since s lies before R. The solve does not wait for S_0:
``U = U_0 - W S_0`` with ``U_0 = T beta V``, ``W = T beta (K * exp(G))``, ``T =
(I + A)^-1``, all blocks at once; the scan over the blocks carries S alone.

Leaves of one layer (``llama.shapes``): ``q_proj``, ``k_proj``, ``v_proj`` [D_model,
H D], ``o_proj`` [H D, D_model], ``conv_weight`` [taps, 3 H D] (row taps - 1
multiplies the current token; q | k | v channels), ``f_a_proj`` [D_model, D],
``f_b_proj`` [D, H D], ``dt_bias`` [H D], ``A_log`` [H], ``b_proj`` [D_model, H],
``g_a_proj`` [D_model, D], ``g_b_proj`` [D, H D], ``g_bias`` [H D], ``out_norm`` [D].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# tokens a block of the chunked form (one triangular solve a block and head,
# one step of the scan that carries the state) and a sub-block of it (inside
# which a pair's decay is exponentiated as the difference it is)
BLOCK = 64
SUB = 16
_L2_EPS = 1e-6


def state_shapes(cfg, slots: int) -> tuple[tuple, tuple]:
    """Per kda layer and slot the state [H, D, D] and the convolution's tail
    [taps - 1, 3 H D] -> the two storage shapes, layers leading: the states'
    [Lk, S, H, D, D] (a block of a slot's heads is what a grid step of
    ``ops.decode_kernels.kda_step`` takes from the stack and hands back), the
    tails' [Lk, taps - 1, S, 3 H D], **a row of the
    window before the slots** (the decode step shifts every slot's tail by a
    row: with the slots leading the chip's compiler keeps the tails in this
    order inside the scan over the layers all the same, and re-lays all of
    them, 57 MB at 128 slots, in every step)."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    lk = cfg.num_kda_layers
    return (lk, slots, h, d, d), (lk, cfg.kda_short_conv - 1, slots, 3 * h * d)


def project(x: jax.Array, layer: dict) -> jax.Array:
    """x [..., D_model] -> x W_q | x W_k | x W_v [..., 3 H D], before the
    convolution: what a tail keeps the last rows of."""
    return jnp.concatenate([x @ layer["q_proj"], x @ layer["k_proj"], x @ layer["v_proj"]], axis=-1)


def _l2(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _heads(cfg, conv: jax.Array, dtype):
    """The convolved rows float32 [..., 3 H D] -> q, k, v [..., H, D] in
    ``dtype``: SiLU, then q and k to unit length a head and q by D^-1/2."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    q, k, v = jnp.split(jax.nn.silu(conv).reshape(*conv.shape[:-1], 3 * h, d), 3, axis=-2)
    return (_l2(q) * d**-0.5).astype(dtype), _l2(k).astype(dtype), v.astype(dtype)


def decay_and_beta(cfg, x: jax.Array, layer: dict):
    """x [..., D_model] -> (g [..., H, D] float32, the log of the decay a key
    channel, < 0; beta [..., H] float32)."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    f = ((x @ layer["f_a_proj"]) @ layer["f_b_proj"]).astype(jnp.float32)
    dt = jax.nn.softplus(f + layer["dt_bias"].astype(jnp.float32)).reshape(*x.shape[:-1], h, d)
    g = -jnp.exp(layer["A_log"].astype(jnp.float32))[:, None] * dt
    beta = jax.nn.sigmoid((x @ layer["b_proj"]).astype(jnp.float32))
    return g, beta * 2.0 if cfg.kda_allow_neg_eigval else beta


def conv_inputs(
    cfg, x: jax.Array, layer: dict, tail: Optional[jax.Array] = None,
    length: Optional[jax.Array] = None,
):
    """What the recurrence reads of a run x [B, T, D_model] that enters with
    ``tail`` [B, taps - 1, 3 H D] (None: zeros, a sequence's start) -> (q, k, v
    [B, T, H, D], g [B, T, H, D] float32, beta [B, T, H] float32, the tail the
    run leaves: the rows before the convolution of its last ``taps - 1`` real
    tokens, ``length`` of the T being real)."""
    b, t, _ = x.shape
    taps = cfg.kda_short_conv
    rows = project(x, layer)
    if tail is None:
        tail = jnp.zeros((b, taps - 1, rows.shape[-1]), rows.dtype)
    window = jnp.concatenate([tail.astype(rows.dtype), rows], axis=1)  # [B, T + taps - 1, 3 H D]
    # rows [end, end + taps - 1) of ``window`` are positions end - (taps - 1) .. end - 1
    left = jax.lax.dynamic_slice_in_dim(window, t if length is None else length, taps - 1, axis=1)
    w = layer["conv_weight"].astype(jnp.float32)
    conv = sum(window[:, j : j + t].astype(jnp.float32) * w[j] for j in range(taps))
    q, k, v = _heads(cfg, conv, x.dtype)
    g, beta = decay_and_beta(cfg, x, layer)
    return q, k, v, g, beta, left


def _pairs(a: jax.Array, k: jax.Array, gr: jax.Array, strict: bool) -> jax.Array:
    """``sum_d a_td k_sd exp(gr_td - gr_sd)`` for the pairs of each sub-block,
    s < t (``strict``) or s <= t, else 0: a, k, gr [..., n, SUB, D] float32 ->
    [..., n, SUB, SUB]. The difference is exponentiated, never its parts."""
    sub = a.shape[-2]
    below = jnp.tril(jnp.ones((sub, sub), bool), -1 if strict else 0)
    diff = jnp.where(below[..., None], gr[..., :, None, :] - gr[..., None, :, :], -jnp.inf)
    return jnp.sum(a[..., :, None, :] * k[..., None, :, :] * jnp.exp(diff), axis=-1)


def chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: Optional[jax.Array] = None, length: Optional[jax.Array] = None,
    block: int = BLOCK, sub: int = SUB,
) -> tuple[jax.Array, jax.Array]:
    """The recurrence over q, k, v [B, T, H, D] under the log-decays g [B, T,
    H, D] and beta [B, T, H] (float32) from the entering ``state`` [B, H, D, D]
    float32 (None: zeros, a sequence's start) -> (o [B, T, H, D] float32, the
    state after the last real token). ``length`` (traced scalar) is the count
    of real tokens of a right-padded run: beyond it a token neither decays the
    state nor writes to it. ``block`` is whole sub-blocks of ``sub`` tokens
    (the module's docstring has the form)."""
    b, t, h, d = q.shape
    f32, cd = jnp.float32, q.dtype
    if block % sub:
        raise ValueError(f"a block of {block} tokens is not whole sub-blocks of {sub}")
    if length is not None:
        live = (jnp.arange(t) < length)[None, :, None]
        g, beta = jnp.where(live[..., None], g, 0.0), jnp.where(live, beta, 0.0)
    pad = -t % block
    if pad:  # a padded token has decay 1 and beta 0: it changes nothing
        rows = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v, g = (jnp.pad(x, rows) for x in (q, k, v, g))
        beta = jnp.pad(beta, rows[:3])
    nc, n = (t + pad) // block, block // sub
    # [B, nc, H, block, ...]: a block's tokens and channels minor-most
    q, k, v, g = (jnp.moveaxis(x.reshape(b, nc, block, h, d), 3, 2) for x in (q, k, v, g))
    beta = jnp.moveaxis(beta.reshape(b, nc, block, h), 3, 2)[..., None]  # [B, nc, H, block, 1]
    acs = jnp.cumsum(g, axis=3)  # G: the log-decay up to and with each token of its block, <= 0
    # R: G just before each sub-block's first token; G - R is a sub-block's own sum
    starts = jnp.concatenate([jnp.zeros_like(acs[..., :1, :]), acs[..., sub - 1 : -1 : sub, :]], axis=3)
    own = acs - jnp.repeat(starts, sub, axis=3)  # [B, nc, H, block, D], <= 0

    def subs(x):
        return x.reshape(*x.shape[:3], n, sub, x.shape[-1])

    qf, kf = q.astype(f32), k.astype(f32)
    a_in = _pairs(subs(kf), subs(kf), subs(own), strict=True)  # [B, nc, H, n, sub, sub]
    p_in = _pairs(subs(qf), subs(kf), subs(own), strict=False)
    kq = jnp.stack([kf, qf], axis=3) * jnp.exp(own)[:, :, :, None]  # a query's side, from R on
    a_rows, p_rows = [], []
    for i in range(n):  # sub-block i's rows against every sub-block before it
        at = slice(i * sub, (i + 1) * sub)
        row = []
        if i:
            # a key's side: from its token up to R, <= 0 as it lies before R
            to_start = jnp.exp(starts[..., i : i + 1, :] - acs[..., : i * sub, :])
            row.append(jnp.einsum(
                "bchjtd,bchsd->bchjts", kq[..., at, :].astype(cd),
                (kf[..., : i * sub, :] * to_start).astype(cd), preferred_element_type=f32,
            ))
        zeros = jnp.zeros((*kq.shape[:4], sub, block - (i + 1) * sub), f32)
        inside = jnp.stack([a_in[..., i, :, :], p_in[..., i, :, :]], axis=3)
        both = jnp.concatenate([*row, inside, zeros], axis=-1)  # [B, nc, H, 2, sub, block]
        a_rows.append(both[:, :, :, 0])
        p_rows.append(both[:, :, :, 1])
    a = jnp.concatenate(a_rows, axis=3) * beta  # [B, nc, H, block, block], strictly lower
    p = jnp.concatenate(p_rows, axis=3)  # lower, the diagonal with it
    decayed = jnp.exp(acs)  # from the block's start: <= 1
    rhs = jnp.concatenate([v.astype(f32), kf * decayed], axis=-1) * beta
    solved = jax.lax.linalg.triangular_solve(
        a + jnp.eye(block, dtype=f32), rhs, left_side=True, lower=True, unit_diagonal=True,
    )
    u0, w = solved[..., :d], solved[..., d:]  # U = U_0 - W S_0
    # o = P U + (Q * exp(G)) S_0 = P U_0 + ((Q * exp(G)) - P W) S_0
    o_own = jnp.einsum("bchts,bchsd->bchtd", p.astype(cd), u0.astype(cd), preferred_element_type=f32)
    q_state = qf * decayed - jnp.einsum(
        "bchts,bchsd->bchtd", p.astype(cd), w.astype(cd), preferred_element_type=f32
    )
    reads = jnp.concatenate([w, q_state], axis=3).astype(cd)  # [B, nc, H, 2 block, D]: what reads S_0
    to_end = (kf * jnp.exp(acs[..., -1:, :] - acs)).astype(cd)  # a key's side up to the block's end
    end_decay = decayed[..., -1, :]  # [B, nc, H, D]

    def carry(s, xs):
        reads_c, u0_c, to_end_c, dec = xs
        read = jnp.einsum("bhtk,bhkv->bhtv", reads_c, s.astype(cd), preferred_element_type=f32)
        u = u0_c - read[:, :, :block]
        s = dec[..., None] * s + jnp.einsum(
            "bhtk,bhtv->bhkv", to_end_c, u.astype(cd), preferred_element_type=f32
        )
        return s, read[:, :, block:]  # o's part that waits for the state

    if state is None:
        state = jnp.zeros((b, h, d, d), f32)
    state, o_state = jax.lax.scan(
        carry, state.astype(f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (reads, u0, to_end, end_decay)),
    )
    o = o_own + jnp.moveaxis(o_state, 0, 1)
    return jnp.moveaxis(o, 2, 3).reshape(b, nc * block, h, d)[:, :t], state


def recurrence(q, k, v, g, beta, state=None):
    """The same, token by token: the definition, and the tests' reference of
    :func:`chunked` -> (o [B, T, H, D] float32, the state after the last)."""
    b, t, h, d = q.shape
    f32 = jnp.float32

    def one(s, xs):
        qt, kt, vt, gt, bt = (x.astype(f32) for x in xs)
        s = jnp.exp(gt)[..., None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    if state is None:
        state = jnp.zeros((b, h, d, d), f32)
    state, o = jax.lax.scan(one, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def step_inputs(cfg, x: jax.Array, layer: dict, tail: jax.Array, live: jax.Array):
    """What the one-step form reads of one token a slot, x [S, D_model], behind
    the slots' tails [taps - 1, S, 3 H D] (``state_shapes``) -> (q, k, v, g [S,
    H, D] float32, g the log of the decay a key channel, beta [S, H] float32,
    the new tails: shifted by a row; a slot that holds no sequence, ``live`` [S]
    false, keeps its tail)."""
    f32 = jnp.float32
    with jax.named_scope("odtp_kda_conv"):
        window = jnp.concatenate([tail, project(x, layer)[None].astype(tail.dtype)], axis=0)
        conv = jnp.sum(window.astype(f32) * layer["conv_weight"].astype(f32)[:, None], axis=0)
        q, k, v = (a.astype(f32) for a in _heads(cfg, conv, x.dtype))
        g, beta = decay_and_beta(cfg, x, layer)
    return q, k, v, g, beta, jnp.where(live[None, :, None], window[1:], tail)


def step_state(q, k, v, g, beta, state: jax.Array, live: jax.Array):
    """The one-step form's recurrence in XLA: a token's rows (:func:`step_inputs`)
    and the slots' states [S, H, D, D] float32 -> (o [S, H, D] float32, the new
    states; a slot that holds no sequence keeps its own).

    This form reads the state twice and writes it once: ``S'^T k`` and ``S'^T
    q`` in one pass over it (``o = S'^T q + (k . q) u``), the update in a
    second, and its caller writes the layer's states into the stack. It is the
    decode step off the chip and where a head's state is no whole tile, and the
    tests' reference of ``ops.decode_kernels.kda_step``, which visits a live
    slot's state once where it lies in the stack."""
    with jax.named_scope("odtp_kda"):
        a = jnp.exp(g)
        read = jnp.einsum("shkv,shjk->shjv", state, jnp.stack([a * k, a * q], axis=2))
        u = beta[..., None] * (v - read[:, :, 0])
        new = a[..., None] * state + k[..., :, None] * u[..., None, :]
        o = read[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * u
        new = jnp.where(live[:, None, None, None], new, state)
    return o, new


def step(cfg, x: jax.Array, layer: dict, state: jax.Array, tail: jax.Array, live: jax.Array):
    """One token a slot in XLA: x [S, D_model], the slots' states [S, H, D, D]
    float32 and tails (:func:`step_inputs`, :func:`step_state`) -> (o [S, H, D]
    float32, the new states, the new tails). A slot that holds no sequence
    (``live`` [S] false: it may be one whose prompt is arriving in chunks, and
    its state and tail are that prompt's) keeps both."""
    *rows, tail = step_inputs(cfg, x, layer, tail, live)
    return (*step_state(*rows, state, live), tail)


def gated_out(cfg, o: jax.Array, x: jax.Array, layer: dict) -> jax.Array:
    """o float32 [..., H, D] normed over each head's D values (one learned
    weight of D a layer), then gated value by value by sigmoid((x W_g1) W_g2 +
    b_g) from the layer's normed input x, projected."""
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * layer["out_norm"].astype(jnp.float32)
    gate = ((x @ layer["g_a_proj"]) @ layer["g_b_proj"]).astype(jnp.float32)
    gate = jax.nn.sigmoid(gate + layer["g_bias"].astype(jnp.float32))
    o = o.reshape(*o.shape[:-2], -1) * gate
    return o.astype(x.dtype) @ layer["o_proj"]
