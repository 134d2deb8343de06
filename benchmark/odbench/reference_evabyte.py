"""The plain reference of the EvaByte block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
windows cut out of the sequence and nothing imported from the program: the
scores of the whole sequence against every key and every pooled row, under
the mask the equations define; the pooled rows built by a loop over the
chunks (``lax.map``). The layers are walked with one ``lax.scan`` over the
stacked weights, each layer's upcast inside the step, and the queries in
blocks, so that 4,120 positions at the published widths fit beside an engine.
Published description: EVA, arXiv 2302.04542 (exact attention on a local set,
one control-variate term per remote chunk), in the form of EvaByte's
modelling code as ``benchmark/configs/evabyte-6.5b.json`` states it under
``assumed``. Parameters are the program's pytree (stacked ``[L, ...]`` leaves
under ``layers``), read by name.

Positions are cut into chunks of ``c = chunk_size`` and windows of ``w =
window_size``. Per layer, with ``x = h`` entering:

    a  = rmsnorm(x) * (1 + input_norm)
    q, k, v = a Wq, a Wk, a Wv as heads;  q, k rotated by absolute position
    for head n and chunk j (positions m in [c j, c j + c)):
        p_m   = softmax over the chunk's positions of (phi_n . k_m)
        kbar_j = sum_m p_m k_m + mu_n;   vbar_j = sum_m p_m v_m
    for the query at t, W = t // w:
        s_m = q_t . k_m / sqrt(d)      over m with m // w == W and m <= t
        r_j = q_t . kbar_j / sqrt(d)   over j < (w / c) W
        o_t = softmax over both at once, applied to (v_m, vbar_j)
    h  = x + o Wo
    b  = rmsnorm(h) * (1 + post_attn_norm)
    h  = h + (silu(b Wgate) * (b Wup)) Wdown

then ``logits = (rmsnorm(h) * (1 + final_norm)) W_head`` as ``num_pred_heads``
vocabularies side by side, head i's in columns ``[i V, (i + 1) V)``; head i at
position t predicts token ``t + 1 + i``. Loss: the mean over the heads of each
head's mean cross-entropy over the positions that have its target.

``visible="chunk"`` is the reading that has to fail wherever this reference
is a yardstick: every pooled row readable from its *chunk's* end on (``c (j +
1) <= t``), the chunks of the query's own window among them, which is what a
ring that slid, or a pooled row read a window early, would amount to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # queries a block: [heads, 512, T + T / c] scores at once
# what a masked score reads: finite, so that a row with nothing to read (a
# block's padding, cut off again) is no NaN that a gradient could carry
_NEVER = jnp.finfo(jnp.float32).min


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rotate(x, theta):
    """[B, T, H, D] rotated by absolute position, half-rotation layout."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rounder(operands):
    """Both operands of every matrix multiplication rounded to ``operands``
    first (None: as they are); the products accumulate in float32."""
    if operands is None:
        return lambda a: a
    return lambda a: jnp.asarray(a.astype(operands), jnp.float32)


def pooled_rows(k, v, phi, mu, chunk: int):
    """k, v [B, T, H, D] -> (kbar, vbar [B, J, H, D]), J = ceil(T / chunk): a
    loop over the chunks; a last chunk that the sequence leaves short pools
    the positions it has."""
    t = k.shape[1]
    j = -(-t // chunk)
    rows = ((0, 0), (0, j * chunk - t), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, rows), jnp.pad(v, rows)

    def pool(i):
        ks = jax.lax.dynamic_slice_in_dim(kp, i * chunk, chunk, axis=1)  # [B, c, H, D]
        vs = jax.lax.dynamic_slice_in_dim(vp, i * chunk, chunk, axis=1)
        there = (i * chunk + jnp.arange(chunk) < t)[None, :, None]
        s = jnp.where(there, jnp.sum(ks * phi, axis=-1), _NEVER)  # [B, c, H]
        p = jax.nn.softmax(s, axis=1)[..., None]
        return jnp.sum(p * ks, axis=1) + mu, jnp.sum(p * vs, axis=1)

    kbar, vbar = jax.lax.map(pool, jnp.arange(j))  # [J, B, H, D]
    return jnp.moveaxis(kbar, 0, 1), jnp.moveaxis(vbar, 0, 1)


def eva_attention(q, k, v, phi, mu, cfg: dict, lo, visible: str = "window"):
    """o [B, T, H, D] by the equations above, the queries a block at a time."""
    b, t, nh, d = q.shape
    chunk, window = cfg["chunk_size"], cfg["window_size"]
    kbar, vbar = pooled_rows(k, v, phi, mu, chunk)
    j = kbar.shape[1]
    m_pos, j_pos = jnp.arange(t), jnp.arange(j)
    size = min(QUERY_BLOCK, t)
    blocks = -(-t // size)
    qp = jnp.pad(q, ((0, 0), (0, blocks * size - t), (0, 0), (0, 0)))

    def block(i):
        t_pos = i * size + jnp.arange(size)
        qs = jax.lax.dynamic_slice_in_dim(qp, i * size, size, axis=1)
        own = (m_pos[None, :] // window == t_pos[:, None] // window) & (
            m_pos[None, :] <= t_pos[:, None]
        )
        if visible == "window":
            before = j_pos[None, :] < (window // chunk) * (t_pos[:, None] // window)
        elif visible == "chunk":
            before = chunk * (j_pos[None, :] + 1) <= t_pos[:, None]
        else:
            raise ValueError(f"visible {visible!r}: 'window' or 'chunk'")
        s = jnp.einsum("bihd,bmhd->bhim", lo(qs), lo(k)) / jnp.sqrt(jnp.float32(d))
        r = jnp.einsum("bihd,bjhd->bhij", lo(qs), lo(kbar)) / jnp.sqrt(jnp.float32(d))
        both = jnp.concatenate(
            (jnp.where(own[None, None], s, _NEVER), jnp.where(before[None, None], r, _NEVER)),
            axis=-1,
        )
        p = jax.nn.softmax(both, axis=-1)
        return jnp.einsum("bhim,bmhd->bihd", lo(p[..., :t]), lo(v)) + jnp.einsum(
            "bhij,bjhd->bihd", lo(p[..., t:]), lo(vbar)
        )

    out = jax.lax.map(block, jnp.arange(blocks))  # [blocks, B, Q, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, blocks * size, nh, d)[:, :t]


def forward(params: dict, input_ids, cfg: dict, operands=None, visible: str = "window",
            rows=None):
    """Logits [B, T, num_pred_heads * V] float32 of ``input_ids`` [B, T].

    ``operands``: a dtype below float32 to which both operands of every
    matrix multiplication are rounded first: the reference as a lower
    precision would compute it, for the readings that place a tolerance.
    ``rows`` (start, count): the positions whose logits are wanted (None: all
    T; the start may be traced, the count not)."""
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = cfg["hidden_size"] // nh
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = float(cfg.get("rope_theta", 10000.0))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    lo = _rounder(operands)
    mm = lambda a, w: lo(a) @ lo(w)
    b, t = input_ids.shape
    with jax.default_matmul_precision("highest"):
        h = f32(params["embed_tokens"])[input_ids]

        def layer(h, w):
            w = {name: f32(leaf) for name, leaf in w.items()}
            a = _rmsnorm(h, w["input_norm"], eps)
            q = _rotate(mm(a, w["q_proj"]).reshape(b, t, nh, dh), theta)
            k = _rotate(mm(a, w["k_proj"]).reshape(b, t, nkv, dh), theta)
            v = mm(a, w["v_proj"]).reshape(b, t, nkv, dh)
            rep = lambda x: jnp.repeat(x, nh // nkv, axis=-2)
            o = eva_attention(
                q, rep(k), rep(v), rep(w["adaptive_phi"]), rep(w["adaptive_mu_k"]),
                cfg, lo, visible,
            )
            h = h + mm(o.reshape(b, t, nh * dh), w["o_proj"])
            m = _rmsnorm(h, w["post_attn_norm"], eps)
            h = h + mm(jax.nn.silu(mm(m, w["gate_proj"])) * mm(m, w["up_proj"]), w["down_proj"])
            return h, None

        h, _ = jax.lax.scan(layer, h, params["layers"])
        if rows is not None:
            h = jax.lax.dynamic_slice_in_dim(h, rows[0], rows[1], axis=1)
        return mm(_rmsnorm(h, f32(params["final_norm"]), eps), f32(params["lm_head"]))


def loss(params: dict, input_ids, labels, cfg: dict):
    """The mean over the heads of head i's mean cross-entropy of ``labels[:, 1
    + i:]`` under its logits of positions 0..T-2-i."""
    heads, t = cfg.get("num_pred_heads", 1), input_ids.shape[1]
    logits = forward(params, input_ids, cfg)
    logits = logits.reshape(*logits.shape[:-1], heads, -1)
    total = 0.0
    for i in range(heads):
        logp = jax.nn.log_softmax(logits[:, : t - 1 - i, i], axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, 1 + i :, None], axis=-1)[..., 0]
        total = total + jnp.mean(nll)
    return total / heads


def loss_and_grad_norm(params: dict, input_ids, labels, cfg: dict):
    """-> (loss, global L2 norm of d loss / d params), both float32."""
    value, grads = jax.value_and_grad(loss)(params, input_ids, labels, cfg)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
