"""The Mamba-2 mixer of a hybrid stack (``layer_types`` entries ``"mamba"``),
in the two forms the program runs: the chunked form over a whole sequence
(training and prefill) and the one-step recurrence over the slots (decode).

Per token t, with x the block's normed input (Mamba-2, arXiv 2405.21060;
the ``mamba_*`` keys of a ``granitemoehybrid`` ``config.json``; one group):

    [z | xBC | dt] = x W_in                 (H*P | H*P + 2N | H)
    xBC  = silu(causal_depthwise_conv_K(xBC) + b)
    [u | B | C] = xBC                       u as [H, P]; B, C [N]
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)        per head
    S_t  = exp(dt_t A) S_{t-1} + dt_t u_t (x) B_t          S [H, P, N], float32
    y_t  = S_t C_t + D u_t
    out  = rmsnorm(y * silu(z)) W_out       the norm over all H*P

What a sequence leaves behind is ``S`` after its last token and the last
K - 1 inputs of the convolution (the "conv tail"): the decode step takes both
and hands both on. Decays, their cumulative sums and the state are float32
whatever the compute dtype; matrix products take operands in x's dtype and
accumulate in float32.

Leaves of one layer (``llama.shapes``): ``in_proj`` [D, 2HP + 2N + H],
``conv_weight`` [K, HP + 2N] (row K - 1 multiplies the current token; channels
minor-most, as the chip tiles them), ``conv_bias``, ``dt_bias``, ``A_log``,
``D`` [H], ``mixer_norm`` [HP], ``out_proj`` [HP, D].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def sizes(cfg) -> tuple[int, int, int, int, int]:
    """-> (heads H, head size P, state size N, conv width K, conv channels)."""
    h, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    return h, p, n, cfg.mamba_d_conv, h * p + 2 * n


def in_proj_width(cfg) -> int:
    h, p, n, _, _ = sizes(cfg)
    return 2 * h * p + 2 * n + h


def state_shapes(cfg, slots: int) -> tuple[tuple, tuple]:
    """Per mixer layer and slot: the recurrent state [H, P, N] and the conv
    tail [K - 1, channels] -> the two storage shapes, layers leading."""
    h, p, n, k, c = sizes(cfg)
    lm = cfg.num_mamba_layers
    return (lm, slots, h, p, n), (lm, slots, k - 1, c)


def _project(cfg, x, layer):
    """x [..., D] -> (z [..., HP], xBC [..., C], dt [..., H]) before the conv."""
    h, p, _, _, c = sizes(cfg)
    zxbcdt = x @ layer["in_proj"]
    return (
        zxbcdt[..., : h * p],
        zxbcdt[..., h * p : h * p + c],
        zxbcdt[..., h * p + c :],
    )


def _split_conv(cfg, xbc):
    """The convolved, activated xBC [..., C] -> (u [..., H, P], B, C [..., N])."""
    h, p, n, _, _ = sizes(cfg)
    u = xbc[..., : h * p].reshape(*xbc.shape[:-1], h, p)
    return u, xbc[..., h * p : h * p + n], xbc[..., h * p + n :]


def _dt_and_decay(layer, dt):
    """Raw dt [..., H] -> (dt after softplus, A per head), float32."""
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32)
    )
    return dt, -jnp.exp(layer["A_log"].astype(jnp.float32))


def _gated_out(cfg, y, z, layer):
    """y float32 [..., HP] gated by z, normed over the whole width, projected."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + cfg.rms_norm_eps)
    g = (g * layer["mixer_norm"].astype(jnp.float32)).astype(z.dtype)
    return g @ layer["out_proj"]


def ssm_chunked(
    cfg, x: jax.Array, layer: dict, length: Optional[jax.Array] = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The mixer over whole sequences x [B, T, D] -> (out [B, T, D], the
    state after the last token [B, H, P, N] float32, the conv tail
    [B, K - 1, C]).

    The recurrence in chunks of ``mamba_chunk_size`` tokens: inside a chunk
    every token reads every earlier one through a [Q, Q] matrix of decays
    (sums of ``dt A`` between them, exponentiated) times ``C_t . B_s``;
    across chunks one state per chunk is carried by a short sequential scan.
    ``length`` (traced scalar) is the true length of a right-padded
    sequence: beyond it ``dt`` is 0 and the conv's input is 0, so the state
    stays what the last real token left and the tail is that token's."""
    b, t, _ = x.shape
    h, p, n, k, c = sizes(cfg)
    q = min(int(cfg.mamba_chunk_size), t)
    z, xbc, dt = _project(cfg, x, layer)
    live = None if length is None else (jnp.arange(t) < length)[None, :, None]
    if live is not None:
        xbc = jnp.where(live, xbc, 0)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))  # [B, T + K - 1, C]
    # rows [end, end + K - 1) of ``padded`` are positions end - (K - 1) .. end - 1
    tail = jax.lax.dynamic_slice_in_dim(
        padded, t if length is None else length, k - 1, axis=1
    )
    w = layer["conv_weight"].astype(jnp.float32)
    conv = sum(padded[:, j : j + t].astype(jnp.float32) * w[j] for j in range(k))
    xbc = jax.nn.silu(conv + layer["conv_bias"].astype(jnp.float32)).astype(x.dtype)
    u, bm, cm = _split_conv(cfg, xbc)
    dt, a = _dt_and_decay(layer, dt)
    if live is not None:
        dt = jnp.where(live, dt, 0.0)

    pad = (-t) % q  # whole chunks: a padded token has dt 0 and changes nothing
    if pad:
        u, bm, cm, dt = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (u, bm, cm, dt)
        )
    nc = (t + pad) // q
    u = u.reshape(b, nc, q, h, p)
    bm, cm = bm.reshape(b, nc, q, n), cm.reshape(b, nc, q, n)
    dt = dt.reshape(b, nc, q, h)
    acs = jnp.cumsum(dt * a, axis=2)  # [B, nc, Q, H], <= 0, falling
    du = dt[..., None] * u.astype(jnp.float32)  # [B, nc, Q, H, P]

    # inside a chunk: y_t += sum_{s <= t} exp(acs_t - acs_s) (C_t . B_s) dt_s u_s
    acs_h = jnp.moveaxis(acs, -1, 2)  # [B, nc, H, Q]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(
        jnp.where(causal, acs_h[..., :, None] - acs_h[..., None, :], -jnp.inf)
    )
    cb = jnp.einsum("bcqn,bcsn->bcqs", cm, bm, preferred_element_type=jnp.float32)
    y = jnp.einsum(
        "bchqs,bcshp->bcqhp", (cb[:, :, None] * decay).astype(x.dtype),
        du.astype(x.dtype), preferred_element_type=jnp.float32,
    )

    # each chunk's own contribution to the state at its end, then the states
    # entering each chunk by a scan over the chunks
    to_end = jnp.exp(acs[:, :, -1:, :] - acs)  # [B, nc, Q, H]
    chunk_state = jnp.einsum(
        "bcshp,bcsn->bchpn", (du * to_end[..., None]).astype(x.dtype), bm,
        preferred_element_type=jnp.float32,
    )
    chunk_decay = jnp.exp(acs[:, :, -1, :])  # [B, nc, H]

    def carry(state, xs):
        dec, own = xs
        return dec[..., None, None] * state + own, state

    state, entering = jax.lax.scan(
        carry, jnp.zeros((b, h, p, n), jnp.float32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(chunk_state, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, H, P, N]
    y = y + jnp.exp(acs)[..., None] * jnp.einsum(
        "bcqn,bchpn->bcqhp", cm, entering.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    y = y + layer["D"].astype(jnp.float32)[:, None] * u.astype(jnp.float32)
    y = y.reshape(b, nc * q, h * p)[:, :t]
    return _gated_out(cfg, y, z, layer), state, tail


def ssm_step(
    cfg, x: jax.Array, layer: dict, state: jax.Array, tail: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One token a slot: x [S, D], the slots' states [S, H, P, N] float32 and
    conv tails [S, K - 1, C] -> (out [S, D], the new state, the new tail)."""
    z, xbc, dt = _project(cfg, x, layer)
    window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
    conv = jnp.sum(
        window.astype(jnp.float32) * layer["conv_weight"].astype(jnp.float32), axis=1
    )
    xbc = jax.nn.silu(conv + layer["conv_bias"].astype(jnp.float32)).astype(x.dtype)
    u, bm, cm = _split_conv(cfg, xbc)
    uf = u.astype(jnp.float32)  # [S, H, P]
    dt, a = _dt_and_decay(layer, dt)  # [S, H]
    state = (
        jnp.exp(dt * a)[..., None, None] * state
        + (dt[..., None] * uf)[..., None] * bm.astype(jnp.float32)[:, None, None, :]
    )
    y = jnp.einsum("shpn,sn->shp", state, cm.astype(jnp.float32))
    y = y + layer["D"].astype(jnp.float32)[:, None] * uf
    out = _gated_out(cfg, y.reshape(x.shape[0], -1), z, layer)
    return out, state, window[:, 1:]
