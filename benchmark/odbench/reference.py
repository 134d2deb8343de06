"""The plain reference: the decoder block written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
rematerialization, and nothing imported from the program (the layers are
walked with one ``lax.scan`` over the stacked weights, so that the program
the compiler sees is one layer long). One model family so far:
pre-norm decoder, RMSNorm, rotary positions in the half-rotation layout,
grouped-query causal attention, SwiGLU feed-forward, tied or untied head
(SmolLM2 / Llama ``config.json`` keys). Parameters are the program's pytree
(stacked ``[L, ...]`` leaves under ``layers``), read by name.

Per layer, with ``x = h`` entering:

    a  = rmsnorm(x, input_norm)
    q, k, v = a Wq, a Wk, a Wv;  q, k rotated by position
    s  = q k^T / sqrt(head_dim), masked to j <= i;  p = softmax(s)
    h  = x + (p v) Wo
    b  = rmsnorm(h, post_attn_norm)
    h  = h + (silu(b Wgate) * (b Wup)) Wdown

then ``logits = rmsnorm(h, final_norm) W_head`` with ``W_head = E^T`` when
tied. Loss: mean next-token cross-entropy of positions 0..T-2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotate(x, theta):
    """[B, T, H, D] rotated by absolute position, half-rotation layout."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(params: dict, input_ids, cfg: dict):
    """Logits [B, T, V] float32 of ``input_ids`` [B, T]."""
    d = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = d // nh
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = float(cfg.get("rope_theta", 10000.0))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed_tokens"])
        h = embed[input_ids]
        b, t = input_ids.shape
        causal = jnp.tril(jnp.ones((t, t), bool))

        def layer(h, w):
            w = {name: f32(leaf) for name, leaf in w.items()}
            a = _rmsnorm(h, w["input_norm"], eps)
            q = _rotate((a @ w["q_proj"]).reshape(b, t, nh, dh), theta)
            k = _rotate((a @ w["k_proj"]).reshape(b, t, nkv, dh), theta)
            v = (a @ w["v_proj"]).reshape(b, t, nkv, dh)
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
            s = jnp.einsum("bihd,bjhd->bhij", q, k) / jnp.sqrt(jnp.float32(dh))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhij,bjhd->bihd", p, v).reshape(b, t, nh * dh)
            h = h + o @ w["o_proj"]
            m = _rmsnorm(h, w["post_attn_norm"], eps)
            h = h + (jax.nn.silu(m @ w["gate_proj"]) * (m @ w["up_proj"])) @ w["down_proj"]
            return h, None

        h, _ = jax.lax.scan(layer, h, params["layers"])
        h = _rmsnorm(h, f32(params["final_norm"]), eps)
        head = embed.T if cfg.get("tie_word_embeddings", False) else f32(params["lm_head"])
        return h @ head


def loss(params: dict, input_ids, labels, cfg: dict):
    """Mean cross-entropy of ``labels[:, 1:]`` under ``logits[:, :-1]``."""
    logits = forward(params, input_ids, cfg)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_and_grad_norm(params: dict, input_ids, labels, cfg: dict):
    """-> (loss, global L2 norm of d loss / d params), both float32."""
    value, grads = jax.value_and_grad(loss)(params, input_ids, labels, cfg)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
