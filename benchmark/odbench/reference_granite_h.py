"""The plain reference of the granite-4.0-h hybrid, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
batching of requests, no chunking, and nothing imported from the program. The
Mamba-2 recurrence is a **sequential scan over the tokens** (the program runs
the chunked form in prefill and a one-step form in decode); every expert that
is held is computed on every token and masked by the token's weights, so the
reference shares neither the scan nor the routing with what it checks.
Published description: the ``granitemoehybrid`` keys of
``ibm-granite/granite-4.0-h-small``'s ``config.json`` (``layer_types``,
``mamba_*``, ``shared_intermediate_size``, the four multipliers,
``position_embedding_type``), and Mamba-2, arXiv 2405.21060. Parameters are the
program's pytree (``layers/mamba`` and ``layers/attention``, each stacked over
the layers of its kind), read by name, walked in the order of the first
``num_hidden_layers`` entries of ``layer_types``.

The model (``x`` is the layer input ``h`` under RMSNorm, eps ``rms_norm_eps``):

    h0     = E[ids] * embedding_multiplier
    h     += residual_multiplier * mixer(rmsnorm(h, input_norm))
    h     += residual_multiplier * (routed(m) + shared(m)),  m = rmsnorm(h, post_attn_norm)
    logits = rmsnorm(h, final_norm) E^T / logits_scaling            (tied)

Mamba-2 mixer (H heads of size P, one group, state N, conv width K):

    [z | xBC | dt] = x W_in                     (HP | HP + 2N | H), no bias
    xBC  = silu(conv_K(xBC) + b)                causal, depthwise: out_t = sum_j w_j in_{t-(K-1)+j}
    [u | B | C] = xBC                           u as [H, P]
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t u_t (x) B_t        S [H, P, N]
    y_t  = S_t C_t + D u_t
    out  = (rmsnorm(y * silu(z)) * w) W_out     the norm over all HP (one group)

Attention layer: GQA, **no rotation**, scores scaled by ``attention_multiplier``
(1/128 for heads of 128, not 1/sqrt(128)), causal.

Routed FFN: float32 logits over ``num_experts``; the ``num_experts_per_tok``
largest; weights = softmax over those logits; expert = down(silu(gate m) *
(up m)). A file cut to one chip's share holds experts ``[first_local_expert,
first_local_expert + num_local_experts)`` only: the routing is over all of
them, the held ones' part of the sum is computed, the others add nothing.
Shared MLP: the same SwiGLU at ``shared_intermediate_size``, every token.

Departures, each deliberate:

- HF stores ``shared_mlp.input_linear`` and an expert's ``input_linear`` as
  gate and up fused in one matrix, and the conv as ``[C, 1, K]``; here they are
  the program's separate leaves and ``conv_weight`` ``[K, C]``. Same numbers.
- The chosen set is taken by a threshold at the k-th largest logit, so a token
  whose k-th and (k+1)-th logits are exactly equal gets both. Random float32
  weights do not produce such a token.
- HF's mixer clamps ``dt`` to ``time_step_limit`` (0, inf): no clamp.
- ``faults`` (tests only) breaks one thing at a time, to show that the
  comparison's limit catches it: ``no_D``, ``no_z_gate``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _experts(cfg: dict) -> tuple[int, int, int]:
    """-> (the router's width, experts held, the first one's index)."""
    held = cfg["num_local_experts"]
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


def mamba_mixer(x, w, cfg: dict, ops=None):
    """The Mamba-2 mixer over x [B, T, D] with one layer's weights ``w``: the
    recurrence token by token -> (out [B, T, D], the state after the last
    token [B, H, P, N])."""
    ops = ops or _Ops()
    b, t, _ = x.shape
    hm, pm, ns, kc = (
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
    )
    eps = cfg.get("rms_norm_eps", 1e-5)
    zxbcdt = ops.mm(x, w["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [hm * pm, 2 * hm * pm + 2 * ns], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(padded[:, j : j + t] * w["conv_weight"][j] for j in range(kc))
    xbc = jax.nn.silu(conv + w["conv_bias"])
    u, bm, cm = jnp.split(xbc, [hm * pm, hm * pm + ns], axis=-1)
    u = u.reshape(b, t, hm, pm)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [B, T, H]
    a = -jnp.exp(w["A_log"])

    def token(state, xs):  # one token of every sequence
        u_t, b_t, c_t, dt_t = xs  # [B, H, P], [B, N], [B, N], [B, H]
        state = (
            jnp.exp(dt_t * a)[..., None, None] * state
            + (dt_t[..., None] * u_t)[..., None] * b_t[:, None, None, :]
        )
        return state, jnp.einsum("bhpn,bn->bhp", ops.lo(state), ops.lo(c_t))

    state, y = jax.lax.scan(
        token, jnp.zeros((b, hm, pm, ns), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (u, bm, cm, dt)),
    )
    y = jnp.moveaxis(y, 0, 1)
    if "no_D" not in ops.faults:
        y = y + w["D"][:, None] * u
    y = y.reshape(b, t, hm * pm)
    if "no_z_gate" not in ops.faults:
        y = y * jax.nn.silu(z)
    return ops.mm(_rmsnorm(y, w["mixer_norm"], eps), w["out_proj"]), state


def attention_mixer(x, w, cfg: dict, ops=None):
    """GQA over x [B, T, D], causal, no rotation, scores scaled by
    ``attention_multiplier``."""
    ops = ops or _Ops()
    b, t, d = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // nh
    q = ops.mm(x, w["q_proj"]).reshape(b, t, nh, dh)
    k = jnp.repeat(ops.mm(x, w["k_proj"]).reshape(b, t, nkv, dh), nh // nkv, axis=2)
    v = jnp.repeat(ops.mm(x, w["v_proj"]).reshape(b, t, nkv, dh), nh // nkv, axis=2)
    s = jnp.einsum("bihd,bjhd->bhij", ops.lo(q), ops.lo(k)) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhij,bjhd->bihd", ops.lo(p), ops.lo(v)).reshape(b, t, nh * dh)
    return ops.mm(o, w["o_proj"])


def routed_part(m, w, cfg: dict, ops=None):
    """The held experts' part of the routed FFN over m [B, T, D]: routed over
    all the router's experts, summed over those ``w`` holds."""
    ops = ops or _Ops()
    n_exp, held, first = _experts(cfg)
    router_logits = ops.mm(m, w["router"])  # [B, T, E]
    kth = jnp.sort(router_logits, axis=-1)[..., n_exp - cfg["num_experts_per_tok"], None]
    chosen = router_logits >= kth
    weight = jax.nn.softmax(jnp.where(chosen, router_logits, -jnp.inf), axis=-1)
    weight = jnp.moveaxis(weight[..., first : first + held], -1, 0)  # the held experts'

    def expert(acc, e):
        gate, up, down, w_e = e  # w_e [B, T]: 0 where e was not chosen
        y = ops.mm(jax.nn.silu(ops.mm(m, gate)) * ops.mm(m, up), down)
        return acc + w_e[..., None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(m), (w["gate_proj"], w["up_proj"], w["down_proj"], weight)
    )
    return routed


def shared_mlp(m, w, ops=None):
    ops = ops or _Ops()
    return ops.mm(
        jax.nn.silu(ops.mm(m, w["shared_gate_proj"])) * ops.mm(m, w["shared_up_proj"]),
        w["shared_down_proj"],
    )


def _walk(params: dict, input_ids, cfg: dict, operands=None, faults=()):
    """-> logits [B, T, V].

    ``operands``: a dtype below float32 to which both operands of every
    matrix multiplication are rounded first (the products still accumulate in
    float32): the reference as a lower precision would compute it, for the
    readings that place a tolerance."""
    eps, res = cfg.get("rms_norm_eps", 1e-5), cfg["residual_multiplier"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ops = _Ops(operands, faults)
    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed_tokens"])
        h = embed[input_ids] * cfg["embedding_multiplier"]
        seen = {"mamba": 0, "attention": 0}
        for kind in cfg["layer_types"][: cfg["num_hidden_layers"]]:
            i = seen[kind]
            seen[kind] += 1
            w = {name: f32(leaf[i]) for name, leaf in params["layers"][kind].items()}
            x = _rmsnorm(h, w["input_norm"], eps)
            mixed = mamba_mixer(x, w, cfg, ops)[0] if kind == "mamba" else attention_mixer(x, w, cfg, ops)
            h = h + res * mixed
            m = _rmsnorm(h, w["post_attn_norm"], eps)
            h = h + res * (routed_part(m, w, cfg, ops) + shared_mlp(m, w, ops))
        h = _rmsnorm(h, f32(params["final_norm"]), eps)
        return ops.mm(h, embed.T) / cfg["logits_scaling"]


def forward(params: dict, input_ids, cfg: dict, operands=None, faults=()):
    """Logits [B, T, V] float32 of ``input_ids`` [B, T]."""
    return _walk(params, input_ids, cfg, operands, faults)


def loss(params: dict, input_ids, labels, cfg: dict):
    """Mean next-token cross-entropy of positions 0..T-2 (the configuration
    states no router aux loss: ``router_aux_loss_coef`` 0)."""
    logits = _walk(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_and_grad_norm(params: dict, input_ids, labels, cfg: dict):
    """-> (loss, global L2 norm of d loss / d params), both float32."""
    value, grads = jax.value_and_grad(loss)(params, input_ids, labels, cfg)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
