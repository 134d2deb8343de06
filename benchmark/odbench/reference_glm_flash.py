"""The plain reference of the GLM-4.7-Flash block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
batching of requests, and nothing imported from the program. Attention is in
the **rebuilt form only**: every head's keys and values are computed from the
token's latent through ``kv_b_proj`` and one softmax runs over the whole
sequence (the program rebuilds them in training and prefill too, and in decode
never does: it absorbs ``kv_b_proj`` into the query and the output and reads
the cached latent itself). Every expert that is held is computed on every
token and masked by the token's weights, so the reference shares neither the
attention's decode form nor the routing's sort with what it checks.
Published description: the ``glm4_moe_lite`` keys of ``zai-org/GLM-4.7-Flash``'s
``config.json``; the block is DeepSeek-V2's latent attention (arXiv 2405.04434)
and DeepSeek-V3's router (arXiv 2412.19437, "noaux_tc"). Parameters are the
program's pytree (``layers/dense`` and ``layers/attention``, each stacked over
the layers of its kind), read by name: the first ``first_k_dense_replace``
layers are ``dense``, the others ``attention``.

The model (RMSNorm eps ``rms_norm_eps``; no bias anywhere; no multipliers):

    h0     = E[ids]
    h     += attn(rmsnorm(h, input_norm))
    h     += ffn_l(rmsnorm(h, post_attn_norm))
    logits = rmsnorm(h, final_norm) W_head                       (untied)

Latent attention (Nh heads; x the normed input; R = ``kv_lora_rank``, nope =
``qk_nope_head_dim``, rope = ``qk_rope_head_dim``, v = ``v_head_dim``):

    c_q    = rmsnorm(x W_qa, q_a_norm)                           (q_lora_rank)
    q      = c_q W_qb                      per head [q_nope (nope) | q_rope (rope)]
    [c_kv (R) | k_r (rope)] = x W_kva;     c_kv = rmsnorm(c_kv, kv_a_norm)
    q_rope, k_r  rotated by position over all their rope values (theta
                 ``rope_theta``); k_r is one head that all Nh share
    [k_nope (nope) | v (v)] = c_kv W_kvb   per head
    k      = [k_nope | k_r]
    scores = q . k / sqrt(nope + rope), causal, softmax in float32
    y      = concat_heads(P v) W_o

``rope_scaling`` is null, so the scores take no further scale.

FFN of the leading ``first_k_dense_replace`` layers: SwiGLU of width
``intermediate_size``. Of the others: ``s = sigmoid(x W_r)`` in float32 over
the router's width; the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
router's selection bias; ``n_group`` = ``topk_group`` = 1, so no group limit)
are the token's experts; their weights are ``s`` itself (without ``b``),
divided by their sum + 1e-20 (``norm_topk_prob``) and multiplied by
``routed_scaling_factor``; expert = down(silu(gate x) * (up x)) at width
``moe_intermediate_size``; beside them ``n_shared_experts`` shared experts as
one SwiGLU of ``n_shared_experts x moe_intermediate_size`` for every token. A
file cut to one chip's share holds experts ``[first_local_expert,
first_local_expert + n_routed_experts)`` of the router's ``num_experts``: the
routing is over all of them, the held ones' part of the sum is computed, the
others add nothing.

Departures, each deliberate:

- **The prediction module is not built.** ``num_nextn_predict_layers`` 1 adds
  one more block that reads the last layer's hidden state and the next
  token's embedding and predicts the token after next: a drafter and a
  training loss. It feeds nothing back, so the next-token logits computed
  here do not depend on it.
- The rotation pairs value i with value i + rope/2 (the half-rotation layout
  the program's other models use). A published checkpoint that interleaves
  the pairs is a fixed permutation of ``q_b_proj``'s and ``kv_a_proj``'s
  rotated columns, which seeded random weights cannot tell apart.
- HF stores an expert's and the shared experts' ``gate_proj`` and ``up_proj``
  as separate matrices per expert module; here they are the program's
  stacked leaves. Same numbers.
- The chosen set is taken by a threshold at the k-th largest biased score, so
  a token whose k-th and (k+1)-th are exactly equal gets both. Random float32
  weights do not produce such a token.
- ``faults`` (tests only) breaks one thing at a time, to show that the
  comparison's limit catches it: ``no_kv_norm``, ``rope_on_nope``,
  ``bias_weighed``, ``softmax_scores``, ``no_scale``, ``values_from_tail``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _experts(cfg: dict) -> tuple[int, int, int]:
    """-> (the router's width, experts held, the first one's index)."""
    held = cfg["n_routed_experts"]
    return cfg.get("num_experts", held), held, cfg.get("first_local_expert", 0)


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


def _rotate(x, theta: float, first: int = 0):
    """x [B, T, ..., d] rotated by position over all d values, value i
    paired with value i + d/2; the sequence starts at position ``first``."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (first + jnp.arange(t, dtype=jnp.float32))[:, None] * inv  # [T, d/2]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate(
        (x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)), axis=-1
    )


def latent_rows(x, w, cfg: dict, ops=None):
    """The latent projection alone, over x [B, T, D] -> (q [B, T, Nh, nope +
    rope] with its rotated part rotated, the rows [B, T, R + rope]: the
    normed latent, then the rotated shared key part)."""
    ops = ops or _Ops()
    b, t, _ = x.shape
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps, theta = cfg.get("rms_norm_eps", 1e-5), cfg["rope_theta"]
    c_q = _rmsnorm(ops.mm(x, w["q_a_proj"]), w["q_a_norm"], eps)
    q = ops.mm(c_q, w["q_b_proj"]).reshape(b, t, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    if "rope_on_nope" in ops.faults:  # the rotation on the wrong part
        q_nope = jnp.concatenate((_rotate(q_nope[..., :dr], theta), q_nope[..., dr:]), axis=-1)
    else:
        q_rope = _rotate(q_rope, theta)
    kv = ops.mm(x, w["kv_a_proj"])
    c_kv, k_r = kv[..., :r], kv[..., r:]
    if "no_kv_norm" not in ops.faults:
        c_kv = _rmsnorm(c_kv, w["kv_a_norm"], eps)
    if "rope_on_nope" not in ops.faults:
        k_r = _rotate(k_r, theta)
    return jnp.concatenate((q_nope, q_rope), axis=-1), jnp.concatenate((c_kv, k_r), axis=-1)


def latent_attention(x, w, cfg: dict, ops=None):
    """Latent attention over x [B, T, D] in the rebuilt form, causal."""
    ops = ops or _Ops()
    b, t, _ = x.shape
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q, rows = latent_rows(x, w, cfg, ops)
    c_kv, k_r = rows[..., :r], rows[..., r:]
    kv = ops.mm(c_kv, w["kv_b_proj"]).reshape(b, t, nh, dn + dv)
    if "rope_on_nope" in ops.faults:
        kv = jnp.concatenate(
            (_rotate(kv[..., :dr], cfg["rope_theta"]), kv[..., dr:]), axis=-1
        )
    k = jnp.concatenate(
        (kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (b, t, nh, dr))), axis=-1
    )
    v = kv[..., dn:]
    if "values_from_tail" in ops.faults:  # another R of the row's R + rope values
        v = ops.mm(rows[..., dr:], w["kv_b_proj"]).reshape(b, t, nh, dn + dv)[..., dn:]
    s = jnp.einsum("bihd,bjhd->bhij", ops.lo(q), ops.lo(k)) / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhij,bjhd->bihd", ops.lo(p), ops.lo(v)).reshape(b, t, nh * dv)
    return ops.mm(o, w["o_proj"])


def router_weights(m, w, cfg: dict, ops=None):
    """-> each token's weight for every expert of the router [B, T, E]: 0
    where the expert was not chosen."""
    ops = ops or _Ops()
    n_exp = _experts(cfg)[0]
    logits = ops.mm(m, w["router"])
    score = jax.nn.softmax(logits, axis=-1) if "softmax_scores" in ops.faults else jax.nn.sigmoid(logits)
    biased = score + w["router_bias"]
    kth = jnp.sort(biased, axis=-1)[..., n_exp - cfg["num_experts_per_tok"], None]
    chosen = biased >= kth
    weight = jnp.where(chosen, biased if "bias_weighed" in ops.faults else score, 0.0)
    if cfg.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if "no_scale" not in ops.faults:
        weight = weight * cfg["routed_scaling_factor"]
    return weight


def routed_part(m, w, cfg: dict, ops=None):
    """The held experts' part of the routed FFN over m [B, T, D]: routed over
    all the router's experts, summed over those ``w`` holds."""
    ops = ops or _Ops()
    _, held, first = _experts(cfg)
    weight = jnp.moveaxis(router_weights(m, w, cfg, ops)[..., first : first + held], -1, 0)

    def expert(acc, e):
        gate, up, down, w_e = e  # w_e [B, T]: 0 where e was not chosen
        y = ops.mm(jax.nn.silu(ops.mm(m, gate)) * ops.mm(m, up), down)
        return acc + w_e[..., None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(m), (w["gate_proj"], w["up_proj"], w["down_proj"], weight)
    )
    return routed


def _swiglu(m, gate, up, down, ops):
    return ops.mm(jax.nn.silu(ops.mm(m, gate)) * ops.mm(m, up), down)


def shared_experts(m, w, ops=None):
    ops = ops or _Ops()
    return _swiglu(m, w["shared_gate_proj"], w["shared_up_proj"], w["shared_down_proj"], ops)


def _walk(params: dict, input_ids, cfg: dict, operands=None, faults=()):
    """-> logits [B, T, V].

    ``operands``: a dtype below float32 to which both operands of every
    matrix multiplication are rounded first (the products still accumulate in
    float32): the reference as a lower precision would compute it, for the
    readings that place a tolerance. A layer's weights are upcast one layer
    at a time, so that the walk fits beside an engine."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ops = _Ops(operands, faults)
    dense = cfg.get("first_k_dense_replace", 0)
    with jax.default_matmul_precision("highest"):
        h = f32(params["embed_tokens"])[input_ids]
        for layer in range(cfg["num_hidden_layers"]):
            kind, i = ("dense", layer) if layer < dense else ("attention", layer - dense)
            w = {name: f32(leaf[i]) for name, leaf in params["layers"][kind].items()}
            h = h + latent_attention(_rmsnorm(h, w["input_norm"], eps), w, cfg, ops)
            m = _rmsnorm(h, w["post_attn_norm"], eps)
            if kind == "dense":
                h = h + _swiglu(m, w["gate_proj"], w["up_proj"], w["down_proj"], ops)
            else:
                h = h + routed_part(m, w, cfg, ops) + shared_experts(m, w, ops)
        h = _rmsnorm(h, f32(params["final_norm"]), eps)
        return ops.mm(h, f32(params["lm_head"]))


def forward(params: dict, input_ids, cfg: dict, operands=None, faults=()):
    """Logits [B, T, V] float32 of ``input_ids`` [B, T]."""
    return _walk(params, input_ids, cfg, operands, faults)


def loss(params: dict, input_ids, labels, cfg: dict):
    """Mean next-token cross-entropy of positions 0..T-2 (the configuration
    trains its router's balance through the selection bias, without an aux
    loss; the prediction module's loss is not built)."""
    logits = _walk(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_and_grad_norm(params: dict, input_ids, labels, cfg: dict):
    """-> (loss, global L2 norm of d loss / d params), both float32."""
    value, grads = jax.value_and_grad(loss)(params, input_ids, labels, cfg)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
