"""The plain reference of the OLMoE block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
sorting or grouping of tokens, and nothing imported from the program: every
expert is computed on every token and masked by the token's top-k weights,
so the reference shares no routing code with what it checks. The layers are
walked with one ``lax.scan`` over the stacked weights and the experts with
another, so the program the compiler sees is one expert of one layer long.
Published description: OLMoE, arXiv 2409.02060, and the ``olmoe`` model of
its ``config.json`` keys (``num_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``router_aux_loss_coef``). Parameters are the program's
pytree (stacked ``[L, ...]`` leaves under ``layers``; experts ``[L, E, ...]``),
read by name.

Per layer, with ``x = h`` entering:

    a  = rmsnorm(x, input_norm)
    q  = rmsnorm(a Wq, q_norm);  k = rmsnorm(a Wk, k_norm)   # over the full width, before the heads
    v  = a Wv;  q, k rotated by position (half-rotation layout)
    s  = q k^T / sqrt(head_dim), masked to j <= i;  p = softmax(s);  h = x + (p v) Wo
    b  = rmsnorm(h, post_attn_norm)
    r  = softmax(b Wr)                                      # over the experts
    S  = the k largest of r; weights r_e as they are (renormalised over S only under norm_topk_prob)
    h  = h + sum over e in S of  r_e * (silu(b Wgate_e) * (b Wup_e)) Wdown_e

then ``logits = rmsnorm(h, final_norm) W_head`` (untied, or ``E^T`` when tied).
Loss: mean next-token cross-entropy of positions 0..T-2, plus
``router_aux_loss_coef`` x L_LB plus ``router_z_loss_coef`` x L_RZ, with
L_LB = E x sum_e f_e P_e (f_e the share of tokens that chose e, summed over
the k places; P_e the mean router probability) and L_RZ the mean squared
logsumexp of the router logits.

Departures from the published code, each deliberate:

- L_LB and L_RZ are computed per layer and averaged over the layers, as the
  program does. HF's ``load_balancing_loss_func`` concatenates all layers'
  router logits and takes f_e and P_e over all of them at once (the product
  of two means over layers, not the mean of the products); the training code
  of the paper (megablocks) sums per-layer products and also divides by k.
  With balanced routing the three agree to a constant factor.
- ``router_z_loss_coef`` is not a key of the published ``config.json``; the
  paper gives 0.001, which is the default here.
- S is taken by a threshold at the k-th largest probability, so a token whose
  k-th and (k+1)-th probabilities are exactly equal gets both experts. Random
  float32 weights do not produce such a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Z_LOSS_COEF = 0.001  # arXiv 2409.02060, section 2


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


def _walk(params: dict, input_ids, cfg: dict, operands=None):
    """-> (logits [B, T, V], L_LB per layer [L], L_RZ per layer [L]).

    ``operands``: a dtype below float32 to which both operands of every
    matrix multiplication are rounded first (the products still accumulate in
    float32): the reference as a lower precision would compute it, for the
    readings that place a tolerance (``tools/olmoe_check_readings.py``)."""
    d = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    dh = d // nh
    n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = float(cfg.get("rope_theta", 10000.0))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    lo = (lambda a: a) if operands is None else (lambda a: f32(a.astype(operands)))
    mm = lambda a, b: lo(a) @ lo(b)
    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed_tokens"])
        h = embed[input_ids]
        b, t = input_ids.shape
        causal = jnp.tril(jnp.ones((t, t), bool))

        def layer(h, w):
            w = {name: f32(leaf) for name, leaf in w.items()}
            a = _rmsnorm(h, w["input_norm"], eps)
            q = _rmsnorm(mm(a, w["q_proj"]), w["q_norm"], eps).reshape(b, t, nh, dh)
            k = _rmsnorm(mm(a, w["k_proj"]), w["k_norm"], eps).reshape(b, t, nkv, dh)
            q, k = _rotate(q, theta), _rotate(k, theta)
            v = mm(a, w["v_proj"]).reshape(b, t, nkv, dh)
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
            s = jnp.einsum("bihd,bjhd->bhij", lo(q), lo(k)) / jnp.sqrt(jnp.float32(dh))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhij,bjhd->bihd", lo(p), lo(v)).reshape(b, t, nh * dh)
            h = h + mm(o, w["o_proj"])

            m = _rmsnorm(h, w["post_attn_norm"], eps)
            router_logits = mm(m, w["router"])  # [B, T, E]
            r = jax.nn.softmax(router_logits, axis=-1)
            kth = jnp.sort(r, axis=-1)[..., n_exp - top_k, None]
            chosen = r >= kth
            weight = jnp.where(chosen, r, 0.0)
            if cfg.get("norm_topk_prob", False):
                weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

            def expert(acc, e):
                gate, up, down, w_e = e  # w_e [B, T]: 0 where e was not chosen
                y = mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)
                return acc + w_e[..., None] * y, None

            ffn, _ = jax.lax.scan(
                expert, jnp.zeros_like(h),
                (w["gate_proj"], w["up_proj"], w["down_proj"], jnp.moveaxis(weight, -1, 0)),
            )
            share = jnp.mean(chosen.astype(jnp.float32), axis=(0, 1))  # f_e, sums to k
            balance = n_exp * jnp.sum(share * jnp.mean(r, axis=(0, 1)))
            z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
            return h + ffn, (balance, z)

        h, (balance, z) = jax.lax.scan(layer, h, params["layers"])
        h = _rmsnorm(h, f32(params["final_norm"]), eps)
        head = embed.T if cfg.get("tie_word_embeddings", False) else f32(params["lm_head"])
        return mm(h, head), balance, z


def forward(params: dict, input_ids, cfg: dict, operands=None):
    """Logits [B, T, V] float32 of ``input_ids`` [B, T]."""
    return _walk(params, input_ids, cfg, operands)[0]


def loss_terms(params: dict, input_ids, labels, cfg: dict):
    """-> (cross-entropy of ``labels[:, 1:]`` under ``logits[:, :-1]``, L_LB,
    L_RZ), the last two averaged over the layers and not yet weighted."""
    logits, balance, z = _walk(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll), jnp.mean(balance), jnp.mean(z)


def loss(params: dict, input_ids, labels, cfg: dict):
    """The training loss: cross-entropy + coefficients x (L_LB, L_RZ)."""
    xent, balance, z = loss_terms(params, input_ids, labels, cfg)
    return (
        xent
        + cfg.get("router_aux_loss_coef", 0.01) * balance
        + cfg.get("router_z_loss_coef", Z_LOSS_COEF) * z
    )


def loss_and_grad_norm(params: dict, input_ids, labels, cfg: dict):
    """-> (loss, global L2 norm of d loss / d params), both float32."""
    value, grads = jax.value_and_grad(loss)(params, input_ids, labels, cfg)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
