"""The lightning linear-attention mixer of a ``minicpm_sala`` stack
(``mixer_types`` entries ``"lightning-attn"``, held as the kind "lightning"),
in the two forms the program runs: the chunked form over a run of tokens that
*enters with a state* (training, a whole prompt, a prompt's chunk) and the
one-step recurrence over the slots (decode).

Per token t and head h, with x the block's normed input (Lightning Attention,
arXiv 2401.04658; the ``lightning_*`` keys of a ``minicpm_sala``
``config.json``; heads of ``head_dim``, no grouping):

    q, k, v = x W_q, x W_k, x W_v        each [H, D]
    q, k = rope(rmsnorm_D(q)), rope(rmsnorm_D(k))   one weight of D a layer
    S_t  = lambda_h S_{t-1} + k_t^T v_t  S [H, D, D], float32, S_{-1} = 0
    o_t  = D^-1/2 q_t S_t                no softmax, no normaliser
    out  = W_o (rmsnorm_{H D}(o) * sigmoid(x W_g))

``lambda_h = exp(-g_h)`` is a constant of the layer and the head: the rates
``g`` are data (``LlamaConfig.lightning_decays``, [lightning layers, H], by
:func:`decay_rates` where a configuration states no table of its own), never
a weight: they stay float32 whatever the compute dtype.

What a run of tokens leaves behind is ``S`` after its last real token; the
next chunk of the prompt, or the decode step, takes it and hands its own on.
Decays, their cumulative sums and the state are float32; matrix products take
operands in x's dtype and accumulate in float32, as ``mamba.ssm_chunked``'s.

Leaves of one layer (``llama.shapes``): ``q_proj``, ``k_proj``, ``v_proj``,
``out_gate`` [D_model, H D], ``o_proj`` [H D, D_model], ``q_norm``, ``k_norm``
[D], ``out_norm`` [H D].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# tokens a block of the chunked form: inside a block every token reads every
# earlier one through a [BLOCK, BLOCK] matrix of decays, across blocks one
# state a block is carried by a short scan. 256 is ``mamba_chunk_size``'s
# value, under which the same einsums ran on the chip (PERF.md section 6)
BLOCK = 256


def decay_rates(layer_indices, heads: int, depth: int) -> tuple:
    """The Lightning Attention family's rule for the rates g (lambda = exp(-g)):
    ``g_h = 2^(-8 (h + 1) / heads) * (1 - l / (depth - 1) + 1e-5)`` with ``l``
    the layer's index among the ``depth`` published layers -> a tuple of one
    tuple of ``heads`` floats a layer of ``layer_indices``."""
    slopes = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return tuple(
        tuple(float(s) for s in slopes * (1.0 - l / max(depth - 1, 1) + 1e-5))
        for l in layer_indices
    )


def state_shape(cfg, slots: int) -> tuple:
    """Per lightning layer and slot the state [H, D, D], layers leading."""
    return (cfg.num_lightning_layers, slots, cfg.num_attention_heads, cfg.head_dim, cfg.head_dim)


def rates(cfg, li) -> jax.Array:
    """Layer ``li``'s (traced: its index among the lightning layers) rates
    [H] float32."""
    return jnp.asarray(cfg.lightning_decays, jnp.float32)[li]


def chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
    state: Optional[jax.Array] = None, length: Optional[jax.Array] = None,
    block: int = BLOCK,
) -> tuple[jax.Array, jax.Array]:
    """The recurrence over q, k, v [B, T, H, D] from the entering ``state`` [B,
    H, D, D] float32 (None: zeros, a sequence's start) under the rates g [H]
    -> (o [B, T, H, D] float32, scaled by D^-1/2; the state after the last
    real token).

    In blocks of ``block`` tokens: ``O = ((Q K^T) * Dec) V + Lam (Q S_in)``
    with ``Dec_ts = lambda^(t - s)`` for s <= t and ``Lam_t = lambda^(t + 1)``,
    the states entering each block by a scan over the blocks. ``length``
    (traced scalar) is the count of real tokens of a right-padded run: beyond
    it a token neither decays the state nor enters it, so the state stays what
    the last real token left."""
    b, t, h, d = q.shape
    f32 = jnp.float32
    n = min(int(block), t)
    step = -g.astype(f32)  # [H], the log of lambda
    live = jnp.ones((t,), bool) if length is None else jnp.arange(t) < length
    k = jnp.where(live[None, :, None, None], k, 0)
    pad = -t % n
    if pad:
        rows = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, rows), jnp.pad(k, rows), jnp.pad(v, rows)
        live = jnp.pad(live, (0, pad))
    nc = (t + pad) // n
    q, k, v = (x.reshape(b, nc, n, h, d) for x in (q, k, v))
    # the log-decay up to and with each token of its block [nc, n, H], <= 0
    acs = jnp.cumsum(live.reshape(nc, n, 1).astype(f32) * step, axis=1)
    acs_h = jnp.moveaxis(acs, -1, 1)  # [nc, H, n]
    causal = jnp.tril(jnp.ones((n, n), bool))
    decay = jnp.exp(jnp.where(causal, acs_h[..., :, None] - acs_h[..., None, :], -jnp.inf))
    qk = jnp.einsum("bcqhd,bcshd->bchqs", q, k, preferred_element_type=f32)
    o = jnp.einsum(
        "bchqs,bcshd->bcqhd", (qk * decay).astype(q.dtype), v, preferred_element_type=f32
    )
    # each block's own contribution to the state at its end, then the states
    # entering each block
    to_end = jnp.exp(acs[:, -1:, :] - acs)  # [nc, n, H]
    own = jnp.einsum(
        "bcshd,bcshe->bchde", (k.astype(f32) * to_end[None, ..., None]).astype(q.dtype), v,
        preferred_element_type=f32,
    )
    block_decay = jnp.exp(acs[:, -1, :])  # [nc, H]

    def carry(s, xs):
        dec, new = xs
        return dec[None, :, None, None] * s + new, s

    if state is None:
        state = jnp.zeros((b, h, d, d), f32)
    state, entering = jax.lax.scan(carry, state.astype(f32), (block_decay, jnp.moveaxis(own, 1, 0)))
    o = o + jnp.exp(acs)[None, ..., None] * jnp.einsum(
        "bcqhd,cbhde->bcqhe", q, entering.astype(q.dtype), preferred_element_type=f32
    )
    return (o * d**-0.5).reshape(b, nc * n, h, d)[:, :t], state


def recurrence(q, k, v, g, state=None):
    """The same, token by token: the definition, and the tests' reference of
    :func:`chunked` -> (o [B, T, H, D] float32, the state after the last)."""
    b, t, h, d = q.shape
    f32 = jnp.float32
    lam = jnp.exp(-g.astype(f32))[None, :, None, None]

    def one(s, xs):
        qt, kt, vt = (x.astype(f32) for x in xs)
        s = lam * s + kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("bhd,bhde->bhe", qt, s) * d**-0.5

    if state is None:
        state = jnp.zeros((b, h, d, d), f32)
    state, o = jax.lax.scan(one, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 1), state


def step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, state: jax.Array, live: jax.Array):
    """One token a slot: q, k, v [S, H, D], the slots' states [S, H, D, D]
    float32 -> (o [S, H, D] float32, the new states). A slot that holds no
    sequence (``live`` [S] false: it may be one whose prompt is arriving in
    chunks, and its state is that prompt's) keeps its state."""
    f32 = jnp.float32
    d = q.shape[-1]
    lam = jnp.exp(-g.astype(f32))[None, :, None, None]
    new = lam * state + k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
    o = jnp.einsum("shd,shde->she", q.astype(f32), new) * d**-0.5
    return o, jnp.where(live[:, None, None, None], new, state)


def gated_out(cfg, o: jax.Array, x: jax.Array, layer: dict) -> jax.Array:
    """o float32 [..., H, D] normed over all H D values (a learned weight),
    then gated by sigmoid(x W_g) from the layer's normed input x, projected."""
    o = o.reshape(*o.shape[:-2], -1)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * layer["out_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid((x @ layer["out_gate"]).astype(jnp.float32))
    return (o * gate).astype(x.dtype) @ layer["o_proj"]
