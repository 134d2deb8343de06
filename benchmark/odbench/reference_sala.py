"""The plain reference of MiniCPM-SALA's block, written from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
rings, no chunks and nothing imported from the program: a full forward over
one sequence. The lightning layers run **the recurrence token by token** (not
the chunked form the program runs), the ``minicpm4`` layers score every pooled
key and read their blocks through a mask over every row. At the published
widths a layer's float32 weights are 1.14 GB, so the walk is a Python loop
over the layers that widens a projection where it is used: a lightning layer
a group of heads at a time, an attention layer a KV head and a block of
queries at a time, the SwiGLU a block of tokens and of columns at a time, so
that 32,768 positions fit beside an engine that fills four fifths of the chip.
Published description: the keys of ``openbmb/MiniCPM-SALA``'s ``config.json``;
what no key fixes is in the configuration file's ``assumed`` and marked
*assumed* here. Parameters are the program's pytree (``layers``: a stack a
kind of layer, ``lightning`` / ``attention``), read by name.

The stream: ``h_0 = scale_emb E[id]``; a layer adds ``s A(norm(h))`` and then
``s F(norm(h))`` with ``s = scale_depth / sqrt(published depth)`` (*assumed*:
the MiniCPM family's; the published depth is the length of ``mixer_types``
whatever ``num_hidden_layers`` a cut file runs), RMSNorm of eps
``rms_norm_eps``; ``F`` a SwiGLU; logits ``= W_head (norm(h) / (hidden_size /
dim_model_base))``, untied.

A ``lightning-attn`` layer, x = norm(h): q, k, v = x W_q, x W_k, x W_v as
``lightning_nh`` heads of ``lightning_head_dim``; RMSNorm over each head's
values of q and of k under one weight a layer (``qk_norm``; *assumed*: per
head, before the rotation); rotation of all of a head's values by position at
base ``rope_theta`` (value i paired with value i + half: the Hugging Face
layout); ``S_t = lambda_h S_{t-1} + k_t^T v_t`` from ``S_{-1} = 0``, ``o_t =
head_dim^-1/2 q_t S_t``, no softmax, no normaliser; ``lambda_h = exp(-g_h)``,
``g_h = 2^(-8 (h + 1) / heads) (1 - l / (published depth - 1) + 1e-5)`` with
``l`` the layer's index in the published list (*assumed*: the Lightning
Attention family's rule, arXiv 2401.04658; a file's ``lightning_decays`` table
replaces it); ``out = W_o (rmsnorm_all(o) * sigmoid(x W_g))`` (*assumed*: the
norm over all heads' values under a learned weight, then the gate).

A ``minicpm4`` layer: q as ``num_attention_heads`` heads, k and v as
``num_key_value_heads``; query head i reads KV head i // (heads / KV heads);
the same RMSNorm per head on q and k; **no rotation**; scores q . k /
sqrt(head_dim). The selection (``sparse_config``, *assumed*: MiniCPM4's): pooled
keys ``K^c_j = mean(k_i, stride j <= i < stride j + kernel)`` per KV head; a
query at t sees window j when ``stride j + kernel - 1 <= t``; per head a
softmax over the windows it sees of ``head_dim^-1/2 q . K^c_j``, added up over
a KV group's heads; a block of ``block_size`` rows takes the largest of the
scores of the windows that overlap it; the query reads block 0 (``init_blocks``),
the ``window_size / block_size`` blocks that end with its own and by largest
score among the others as many as make ``topk`` in all (*assumed*: ``topk``
counts the forced blocks), every block up to its own where those are fewer;
one softmax over the rows s <= t of the chosen blocks. ``dense_len``: a prompt
of fewer tokens reads every row, and so does a later position (a decode
step's) while the sequence up to it holds fewer (``prompt_len`` says where the
prompt ends; None: the whole sequence is the prompt). ``out = W_o (o *
sigmoid(x W_g))``, the gate a value each of o's (*assumed*: elementwise).

``follow`` [R, Ls, Kh, blocks] makes the walk read those blocks at the rows
compared (the engine's own choice: a bfloat16 score exchanges blocks next to
the last one chosen), and ``with_choices`` hands back the walk's own choice
and its block scores there, for the exchange distances. ``faults`` (the tests
and the readings) breaks one thing at a time, to show which of them the
comparison's limits catch; each name is said where it acts.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

BLOCK = 64  # queries whose scores over every row are held at once
TOKENS = 2048  # tokens whose FFN intermediates are held at once
COLUMNS = 4096  # columns of the SwiGLU widened at once
HEADS = 8  # lightning heads walked at once
CHUNK = 2048  # the tokens after which ``zero_state_chunks`` forgets the state


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
    """``fn`` over x [T, ...] a block of rows at a time -> [T, ...]."""
    t = x.shape[0]
    block = min(block, t)
    pad = -t % block
    x = jnp.pad(x, ((0, pad), *((0, 0),) * (x.ndim - 1)))
    out = jax.lax.map(fn, x.reshape(-1, block, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:t]


def kinds(cfg: dict, layers: int | None = None) -> list:
    """Each layer's kind from ``mixer_types``: "lightning" or "attention"."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    return ["lightning" if m == "lightning-attn" else "attention" for m in cfg["mixer_types"][:n]]


def decay_rates(cfg: dict):
    """The lightning layers' rates g [lightning layers run, heads]: the
    file's table, or the family's rule by the published layer index."""
    if cfg.get("lightning_decays"):
        return jnp.asarray(cfg["lightning_decays"], jnp.float32)
    heads, depth = cfg["num_attention_heads"], len(cfg["mixer_types"])
    slopes = [2.0 ** (-8.0 * (h + 1) / heads) for h in range(heads)]
    at = [i for i, kind in enumerate(kinds(cfg)) if kind == "lightning"]
    return jnp.asarray(
        [[s * (1.0 - l / max(depth - 1, 1) + 1e-5) for s in slopes] for l in at], jnp.float32
    )


def _rotate(x, theta: float):
    """x [T, H, d] rotated whole by position, value i paired with i + d / 2."""
    t, _, d = x.shape
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)


def _head_norm(x, w, eps, faults):
    """The RMSNorm per head of q or k [T, H, d]."""
    return x if "no_qk_norm" in faults else _rmsnorm(x, jnp.asarray(w, jnp.float32), eps)


def lightning(x, w, cfg: dict, rates, ops, faults=()):
    """A lightning layer's branch over x [T, D] (its normed input) under the
    rates g [H] -> [T, D]. A group of heads at a time; the recurrence token by
    token. Faults: ``no_lightning_rope``, ``norm_after_rope``, ``no_qk_norm``,
    ``no_scale`` (head_dim^-1/2 dropped), ``bf16_state``, ``zero_state_chunks``
    (the state forgotten every ``CHUNK`` tokens: a chunk that enters with a
    zero state), ``norm_per_head`` (the output norm a head at a time),
    ``gate_before_norm``, ``no_out_gate``."""
    t, d = x.shape
    dh = cfg["head_dim"]
    h = w["q_proj"].shape[-1] // dh
    hb = math.gcd(h, HEADS)
    eps, theta = cfg.get("rms_norm_eps", 1e-6), float(cfg.get("rope_theta", 10000.0))
    f32 = jnp.float32
    scale = 1.0 if "no_scale" in faults else dh**-0.5

    def heads_of(name, g):  # a group's columns [D, hb dh]
        return jax.lax.dynamic_slice_in_dim(w[name], g * hb * dh, hb * dh, axis=1)

    def turn(a, norm):
        if "norm_after_rope" in faults:
            return _head_norm(_rotate(a, theta), norm, eps, faults)
        a = _head_norm(a, norm, eps, faults)
        return a if "no_lightning_rope" in faults else _rotate(a, theta)

    def group(out, g):
        q = turn(ops.mm(x, heads_of("q_proj", g)).reshape(t, hb, dh), w["q_norm"])
        k = turn(ops.mm(x, heads_of("k_proj", g)).reshape(t, hb, dh), w["k_norm"])
        v = ops.mm(x, heads_of("v_proj", g)).reshape(t, hb, dh)
        lam = jnp.exp(-jax.lax.dynamic_slice_in_dim(rates, g * hb, hb))[:, None, None]

        def token(s, xs):
            i, qt, kt, vt = xs
            if "zero_state_chunks" in faults:
                s = jnp.where(i % CHUNK == 0, 0.0, s)
            s = lam * s + ops.lo(kt)[:, :, None] * ops.lo(vt)[:, None, :]
            if "bf16_state" in faults:
                s = s.astype(jnp.bfloat16).astype(f32)
            return s, jnp.einsum("hd,hde->he", ops.lo(qt), s) * scale

        _, o = jax.lax.scan(token, jnp.zeros((hb, dh, dh), f32), (jnp.arange(t), q, k, v), unroll=8)
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(t, hb * dh), g * hb * dh, 1), None

    o, _ = jax.lax.scan(group, jnp.zeros((t, h * dh), f32), jnp.arange(h // hb))
    gate = 1.0 if "no_out_gate" in faults else jax.nn.sigmoid(ops.mm(x, w["out_gate"]))
    norm = jnp.asarray(w["out_norm"], f32)
    if "gate_before_norm" in faults:
        o = _rmsnorm(o * gate, norm, eps)
    elif "norm_per_head" in faults:
        o = _rmsnorm(o.reshape(t, h, dh), norm.reshape(h, dh), eps).reshape(t, h * dh) * gate
    else:
        o = _rmsnorm(o, norm, eps) * gate
    return ops.mm(o, w["o_proj"])


def pooled_keys(k, sizes: dict):
    """k [T, d] -> [J, d]: window j the mean of rows [stride j, stride j + kernel)."""
    kernel, stride = sizes["kernel_size"], sizes["kernel_stride"]
    j = max((k.shape[0] - kernel) // stride + 1, 0)
    starts = stride * jnp.arange(j)
    return jax.vmap(lambda s: jnp.mean(jax.lax.dynamic_slice_in_dim(k, s, kernel, 0), axis=0))(starts)


def block_scores(q, pooled, at, sizes: dict, blocks: int, ops, faults=()):
    """Queries q [Q, rep, d] of one KV group at positions ``at`` [Q] over its
    pooled keys [J, d] -> block scores [Q, blocks]. Faults: ``early_windows``
    (a window seen from its first row on), ``block_means`` (a block takes the
    mean of its windows' scores)."""
    kernel, stride, bs = sizes["kernel_size"], sizes["kernel_stride"], sizes["block_size"]
    j = pooled.shape[0]
    closes = stride * jnp.arange(j) + (0 if "early_windows" in faults else kernel - 1)
    seen = closes[None] <= at[:, None]  # [Q, J]
    s = jnp.einsum("qrd,jd->rqj", ops.lo(q), ops.lo(pooled)) * q.shape[-1] ** -0.5
    s = jnp.where(seen, s, -jnp.inf)
    p = jnp.where(seen, jnp.exp(s - jnp.max(jnp.where(seen, s, -1e30), axis=-1, keepdims=True)), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.sum(p / jnp.where(total > 0, total, 1.0), axis=0)  # [Q, J]
    first, last = stride * jnp.arange(j), stride * jnp.arange(j) + kernel  # a window's rows [first, last)
    b = jnp.arange(blocks)
    over = (first[None] < (b[:, None] + 1) * bs) & (last[None] > b[:, None] * bs)  # [blocks, J]
    if "block_means" in faults:
        return jnp.einsum("qj,bj->qb", p, over.astype(p.dtype)) / jnp.maximum(over.sum(-1), 1)
    return jnp.max(jnp.where(over[None], p[:, None], 0.0), axis=-1)


def choose(scores, at, dense, sizes: dict, faults=()):
    """Block scores [Q, blocks] -> the blocks each query reads, bool. Faults:
    ``topk_beside_forced`` (``topk`` by score beside the forced ones),
    ``first_blocks`` (the first ``topk``)."""
    bs, topk = sizes["block_size"], sizes["topk"]
    blocks = scores.shape[-1]
    b = jnp.arange(blocks)[None]
    own = (at // bs)[:, None]
    causal = b <= own
    forced = (b < sizes["init_blocks"]) | (b > own - sizes["window_size"] // bs)
    if "first_blocks" in faults:
        return causal & ((b < topk) | dense[:, None])
    if "topk_beside_forced" in faults:
        ranked = jnp.where(causal & ~forced, scores, -jnp.inf)
        kth = jax.lax.top_k(ranked, min(topk, blocks))[0][:, -1:]
        return causal & (forced | (ranked >= kth) | dense[:, None])
    ranked = jnp.where(causal, jnp.where(forced, jnp.inf, scores), -jnp.inf)
    _, idx = jax.lax.top_k(ranked, min(topk, blocks))  # ties to the lower block
    picked = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return causal & (picked | dense[:, None])


def block_attention(x, w, cfg: dict, ops, faults=(), prompt_len=None, rows=None, follow=None):
    """A ``minicpm4`` layer's branch over x [T, D] -> ([T, D], the walk's own
    choice at the ``rows`` compared [R, Kh, blocks], its block scores there).
    ``follow`` [R, Kh, blocks]: the blocks read at those rows instead. Faults
    beside the selection's: ``sparse_rope`` (q and k rotated), ``no_qk_norm``,
    ``no_attn_gate``."""
    t, d = x.shape
    sizes = cfg["sparse_config"]
    kh, dh, bs = cfg["num_key_value_heads"], cfg["head_dim"], sizes["block_size"]
    rep = w["q_proj"].shape[-1] // dh // kh
    eps, theta = cfg.get("rms_norm_eps", 1e-6), float(cfg.get("rope_theta", 10000.0))
    blocks = -(-t // bs)
    n = t if prompt_len is None else prompt_len
    at_all = jnp.arange(t)
    # a prompt's positions by the prompt's length, a later one by the rows up to it
    dense_all = jnp.where(at_all < n, n, at_all + 1) < sizes["dense_len"]
    start, count = rows
    at_rows = start + jnp.arange(count)
    block = min(BLOCK, t)
    pad = -t % block

    def read(qj, k, v, chosen, at):  # [Q, rep, d] over every row under the blocks' mask
        s = jnp.einsum("qrd,kd->rqk", ops.lo(qj), ops.lo(k)) * dh**-0.5
        seen = jnp.repeat(chosen, bs, axis=-1)[:, :t] & (jnp.arange(t)[None] <= at[:, None])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", ops.lo(p), ops.lo(v))

    def one_kv_head(acc, ws):
        wq, wk, wv, wg, wo, given = ws
        q = _head_norm(ops.mm(x, wq).reshape(t, rep, dh), w["q_norm"], eps, faults)
        k = _head_norm(ops.mm(x, wk)[:, None], w["k_norm"], eps, faults)
        if "sparse_rope" in faults:
            q, k = _rotate(q, theta), _rotate(k, theta)
        k, v = k[:, 0], ops.mm(x, wv)
        pooled = pooled_keys(k, sizes)

        def selection(qj, at, dense):
            scores = block_scores(qj, pooled, at, sizes, blocks, ops, faults)
            return choose(scores, at, dense, sizes, faults), scores

        def one_block(xs):
            qj, at, dense = xs
            return read(qj, k, v, selection(qj, at, dense)[0], at)

        rows_of = lambda a: jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1))).reshape(-1, block, *a.shape[1:])
        o = jax.lax.map(one_block, (rows_of(q), rows_of(at_all), rows_of(dense_all)))
        o = o.reshape(-1, rep, dh)[:t]
        # the rows compared: the walk's own choice and scores, and the blocks read there
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, count, axis=0)
        dense_rows = jax.lax.dynamic_slice_in_dim(dense_all, start, count)
        own, scores = selection(q_rows, at_rows, dense_rows)
        if given is not None:
            o = jax.lax.dynamic_update_slice_in_dim(o, read(q_rows, k, v, given, at_rows), start, 0)
        o = o.reshape(t, rep * dh)
        if "no_attn_gate" not in faults:
            o = o * jax.nn.sigmoid(ops.mm(x, wg))
        return acc + ops.mm(o, wo), (own, scores)

    by_kv_head = (
        jnp.moveaxis(w["q_proj"].reshape(d, kh, rep * dh), 1, 0),
        jnp.moveaxis(w["k_proj"].reshape(d, kh, dh), 1, 0),
        jnp.moveaxis(w["v_proj"].reshape(d, kh, dh), 1, 0),
        jnp.moveaxis(w["attn_gate"].reshape(d, kh, rep * dh), 1, 0),
        w["o_proj"].reshape(kh, rep * dh, d),
        None if follow is None else jnp.moveaxis(follow, 1, 0),
    )
    out, (own, scores) = jax.lax.scan(one_kv_head, jnp.zeros_like(x), by_kv_head)
    return out, jnp.moveaxis(own, 0, 1), jnp.moveaxis(scores, 0, 1)


def swiglu(m, w, ops):
    """The FFN's branch over m [T, D], a block of columns at a time."""
    d, f = w["gate_proj"].shape
    cols = math.gcd(f, COLUMNS)
    by_columns = (
        jnp.moveaxis(w["gate_proj"].reshape(d, f // cols, cols), 1, 0),
        jnp.moveaxis(w["up_proj"].reshape(d, f // cols, cols), 1, 0),
        w["down_proj"].reshape(f // cols, cols, d),
    )

    def columns(acc, ws):
        gate, up, down = ws
        return acc + ops.mm(jax.nn.silu(ops.mm(m, gate)) * ops.mm(m, up), down), None

    return jax.lax.scan(columns, jnp.zeros_like(m), by_columns)[0]


def layer_step(h, w, cfg: dict, kind: str, rates=None, operands=None, faults=(), prompt_len=None,
               rows=None, follow=None):
    """One layer over h [T, D] -> (h, the attention layer's own choice and
    block scores at the rows compared, or two None). Faults: ``depth_cut`` (the
    branches scaled by the cut's depth, not the published one)."""
    eps = cfg.get("rms_norm_eps", 1e-6)
    ops = _Ops(operands)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    depth = cfg["num_hidden_layers"] if "depth_cut" in faults else len(cfg["mixer_types"])
    s = cfg.get("scale_depth", 1.0) / math.sqrt(depth)
    own = scores = None
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(h, f32(w["input_norm"]), eps)
        if kind == "lightning":
            branch = lightning(x, w, cfg, rates, ops, faults)
        else:
            branch, own, scores = block_attention(x, w, cfg, ops, faults, prompt_len, rows, follow)
        h = h + s * branch
        ffn = lambda m: swiglu(m, w, ops)
        h = h + s * _blocked(ffn, _rmsnorm(h, f32(w["post_attn_norm"]), eps), TOKENS)
    return h, own, scores


@functools.lru_cache(maxsize=None)
def _jitted_layer(cfg_json: str, kind, operands, faults, rows_count, following):
    """A jitted ``layer_step`` a (configuration, kind, walk) over the kind's
    whole stack and the layer's index in it: the layer's weights are cut out
    inside, where they are used."""
    cfg = json.loads(cfg_json)

    def step(h, stack, i, rates, prompt_len, start, follow):
        w = {name: a[i] for name, a in stack.items()}
        return layer_step(h, w, cfg, kind, rates, operands, faults, prompt_len,
                          (start, rows_count), follow if following else None)

    return jax.jit(step)


def forward(params, input_ids, cfg, operands=None, faults=(), rows=None, prompt_len=None,
            follow=None, with_choices: bool = False):
    """Logits [1, R, V] float32 of ``input_ids`` [1, T] at the ``rows`` wanted
    ((start, count); None: all T); with ``with_choices`` also the attention
    layers' own choice [R, Ls, Kh, blocks] bool and block scores there. A
    Python loop over the layers, each under a jit of its own kind. Faults of
    the stream: ``no_emb_scale``, ``no_head_scale``, ``depth_cut``,
    ``decay_next_layer`` (each lightning layer under the next one's rates)."""
    if input_ids.shape[0] != 1:
        raise ValueError("the reference walks one sequence at a time")
    eps = cfg.get("rms_norm_eps", 1e-6)
    frozen, faults = json.dumps(cfg, sort_keys=True), tuple(faults)
    t = input_ids.shape[1]
    start, count = (jnp.int32(0), t) if rows is None else (jnp.asarray(rows[0], jnp.int32), rows[1])
    n = jnp.int32(t if prompt_len is None else prompt_len)
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, count, axis=0)
    h = jnp.asarray(params["embed_tokens"][input_ids[0]], jnp.float32)
    if "no_emb_scale" not in faults:
        h = h * cfg.get("scale_emb", 1.0)
    rates = decay_rates(cfg)
    if "decay_next_layer" in faults:
        rates = jnp.roll(rates, -1, axis=0)
    seen, own, scores = {}, [], []
    for kind in kinds(cfg):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        given = None if follow is None or kind != "attention" else jnp.asarray(follow)[:, i]
        step = _jitted_layer(frozen, kind, operands, faults, count, given is not None)
        h, o, s = step(
            h, params["layers"][kind], jnp.int32(i), rates[i] if kind == "lightning" else None,
            n, start, given,
        )
        if kind == "attention":
            own.append(o)
            scores.append(s)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(cut(h), jnp.asarray(params["final_norm"], jnp.float32), eps)
        if "no_head_scale" not in faults:
            h = h / (cfg["hidden_size"] / cfg.get("dim_model_base", cfg["hidden_size"]))
        logits = _Ops(operands).mm(h, jnp.asarray(params["lm_head"], jnp.float32))[None]
    if with_choices:
        return logits, jnp.stack(own, axis=1), jnp.stack(scores, axis=1)
    return logits


def exchange_distance(own, given, scores, at, sizes: dict):
    """How far apart, in the walk's own block scores, the blocks lie that it
    would have read and ``given`` does not, and those ``given`` reads in their
    place: own, given [..., blocks] bool, scores [..., blocks], for queries at
    positions ``at`` [...] -> (blocks differing [...], the largest score left
    out less the smallest taken in, over the standard deviation of the query's
    scores over the blocks it may choose among: those up to its own that no
    rule forces; 0 where the sets are equal)."""
    import numpy as np

    own, given, scores = np.asarray(own), np.asarray(given), np.asarray(scores, np.float64)
    b, bs = np.arange(own.shape[-1]), sizes["block_size"]
    last = (np.asarray(at) // bs)[..., None]
    free = (b <= last) & (b >= sizes["init_blocks"]) & (b <= last - sizes["window_size"] // bs)
    left_out, taken = own & ~given, given & ~own
    # the score decides among the free blocks alone: a forced block that differs counts, unranked
    high = np.where(left_out & free, scores, -np.inf).max(axis=-1)
    low = np.where(taken & free, scores, np.inf).min(axis=-1)
    # a set that is too short left nothing out; one too long took nothing in:
    # the distance is then to the walk's last chosen, or first unchosen, score
    high = np.where(np.isfinite(high), high, np.where(~own & free, scores, -np.inf).max(axis=-1))
    low = np.where(np.isfinite(low), low, np.where(own & free, scores, np.inf).min(axis=-1))
    n = np.maximum(free.sum(-1), 1)
    mean = np.where(free, scores, 0.0).sum(-1) / n
    spread = np.sqrt(np.where(free, (scores - mean[..., None]) ** 2, 0.0).sum(-1) / n)
    differing = (left_out | taken).sum(axis=-1)
    distance = np.where(differing > 0, (high - low) / np.maximum(spread, 1e-30), 0.0)
    return differing, np.where(np.isfinite(distance), distance, 0.0)
