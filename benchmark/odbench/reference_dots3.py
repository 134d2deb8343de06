"""The plain reference of dots3-note-prev's language-model block, written
from its equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
chunks, no absorbed form, no grouped matmul, no batching of requests, and
nothing imported from the program: a full forward over one sequence. At the
published widths a layer's float32 weights are 3.5 GB, so the walk is a Python
loop over the layers that widens **one layer of the tree it is given at a
time**, and the scores of the indexer and of the attention are held a block of
queries at a time, so that 24,576 positions fit beside an engine. Published
description: the keys of ``dots-studio/dots3-note-prev``'s ``config.json``
(DeepSeek-V2/V3 latent attention at two geometries, DeepSeek-V3.2's lightning
indexer, a sliding window, a head-wise gate, DeepSeek-V3's ``noaux_tc``
router). Parameters are the program's pytree (``layers``: a stack a kind of
layer, ``dense`` / ``attention`` / ``sliding``), read by name.

The layer, with x = rmsnorm(h, input_norm) (eps ``rms_norm_eps``) at
positions t, and ``kind`` from ``layer_types`` and ``first_k_dense_replace``
(the first k layers are full layers over a dense SwiGLU):

1. latent attention at the kind's sizes (full: the top-level keys; sliding:
   the ``swa_*`` keys): c_q = s_q rmsnorm(x W_qa; g_qa), q = c_q W_qb as H
   heads of (nope + rope), the last ``rope`` values rotated (theta the kind's,
   value i paired with value i + rope/2); [c | k_r] = x W_kva, c_kv = s_kv
   rmsnorm(c; g_kva), k_r rotated, one for all heads; k_h = [c_kv W_uk,h |
   k_r], v_h = c_kv W_uv,h (``kv_b_proj`` [R, H (nope + v)], head-major: a
   head's nope columns, then its v columns); scores q . k / sqrt(nope + rope).
   ``apply_mla_qkv_lora_rescale``: s_q = sqrt(hidden / q_lora_rank), s_kv =
   sqrt(hidden / kv_lora_rank) (1 without it).
2. a full layer's indexer: qI[t, j] = (c_q W_Iq)_j in R^Di, j < Hi; kI[s] =
   layernorm(x_s W_Ik; weight, bias) in R^Di; w_t = x_t W_Iw in R^Hi; the first
   ``qk_rope_head_dim`` values of each index head and of the key rotated by the
   layer's theta (value i paired with value i + rope/2 within that part), the
   rest not. I[t, s] = Hi^-1/2 Di^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s]);
   S_t = the min(topk, t + 1) positions s <= t of largest I, ties to the lower
   position, one set for all heads; attention over S_t.
3. a sliding layer: no indexer; query t reads rows s with 0 <= t - s <
   ``sliding_window_size``.
4. the gate (``attention_gate_type`` / ``swa_attention_gate_type`` headwise):
   g = sigmoid(x W_g) in R^H, o_h <- g_h o_h; h <- h + concat(o) W_o.
5. x' = rmsnorm(h, post_attn_norm). Dense: h <- h + down(silu(gate x') * up
   x'). Routed: s = sigmoid(x' W_r) over all E; the ``num_experts_per_tok``
   largest of s + b (``e_score_correction_bias``); their s, normalised to sum 1
   (``norm_topk_prob``), times ``routed_scaling_factor``; h <- h + sum of the
   chosen experts **that this chip holds** (``num_local_experts`` from
   ``first_local_expert`` on; absent: all) + the shared SwiGLU.
6. final rmsnorm, logits = h W_head (untied).

**A flipped row is a near-tie, not an error** (as ``reference_keye``): the walk
can *follow* sets chosen elsewhere (``follow`` [R, Lf, topk] int32, -1 where a
set is shorter: the rows of each of the ``rows`` wanted positions in each full
layer, as the program under test chose them) and reports beside the logits,
for those positions, its own sets, how many rows differ and how far apart *in
its own scores* the exchanged rows lie.

What no config key fixes is in the configuration file's ``assumed``.
``faults`` (the tests and the readings) breaks one thing at a time, to show
that the comparison's limits catch it: ``no_gate``, ``no_rescale``,
``window_minus`` / ``window_plus`` (a window of one row fewer or more),
``index_rotate_whole`` (the index query and key rotated over all their
values), ``no_relu``, ``first_rows``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

BLOCK = 128  # queries whose scores are held at once
HEADS = 16  # heads whose queries, keys and values are held at once
TOKENS = 2048  # tokens whose FFN intermediates are held at once


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layernorm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


class _Ops:
    """How a walk multiplies: operands widened to float32 where they are used
    (a tree in bfloat16 is never widened whole), rounded to ``operands`` first
    (None: as they are), products accumulated in float32."""

    def __init__(self, operands=None, faults=()):
        self.faults = tuple(faults)
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


def _rotate(x, positions, theta: float):
    """x [T, H, d] rotated by position, value i paired with value i + d/2."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)


def kinds(cfg: dict) -> list:
    """Each layer's kind, from the published keys: "dense" (a full layer over
    a dense SwiGLU), "attention" (a full layer over the routed FFN), "sliding"."""
    dense = cfg.get("first_k_dense_replace", 0)
    return [
        "sliding" if name == "sliding_attention" else "dense" if i < dense else "attention"
        for i, name in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]])
    ]


def geometry(cfg: dict, kind: str) -> dict:
    """One kind's latent attention, under plain names."""
    pre = "swa_" if kind == "sliding" else ""
    g = {
        "heads": cfg[f"{pre}num_attention_heads"], "q_rank": cfg[f"{pre}q_lora_rank"],
        "kv_rank": cfg[f"{pre}kv_lora_rank"], "nope": cfg[f"{pre}qk_nope_head_dim"],
        "rope": cfg[f"{pre}qk_rope_head_dim"], "v": cfg[f"{pre}v_head_dim"],
        "theta": cfg[f"{pre}rope_theta"], "gate": cfg.get(f"{pre}attention_gate_type", "none"),
        "window": cfg["sliding_window_size"] if kind == "sliding" else 0,
        "topk": 0 if kind == "sliding" else cfg.get("index_topk", 0),
    }
    rescale = cfg.get("apply_mla_qkv_lora_rescale", False)
    g["s_q"] = (cfg["hidden_size"] / g["q_rank"]) ** 0.5 if rescale else 1.0
    g["s_kv"] = (cfg["hidden_size"] / g["kv_rank"]) ** 0.5 if rescale else 1.0
    return g


def index_scores(qi, ki, wi, cfg: dict, ops):
    """I [Q, T] of index queries qi [Q, Hi, Di] under weights wi [Q, Hi]
    against index keys ki [T, Di], with the published constants."""
    s = jnp.einsum("qhd,td->qht", ops.lo(qi), ops.lo(ki))
    if "no_relu" not in ops.faults:
        s = jax.nn.relu(s)
    scale = cfg["index_n_heads"] ** -0.5 * cfg["index_head_dim"] ** -0.5
    return jnp.sum(s * wi[..., None], axis=1) * scale


def select(scores, seen, topk: int, faults=()):
    """-> (the set as bool [Q, T]: the min(topk, rows seen) rows of largest
    score among ``seen``, ties to the lower position; the gap between the
    topk-th and the next score, inf where nothing is left out)."""
    q, t = scores.shape
    col = jnp.arange(t)[None]
    masked = jnp.where(seen, jnp.where(scores == 0, 0.0, scores), -jnp.inf)  # -0.0 is 0.0
    if "first_rows" in faults:  # the earliest rows, whatever their scores
        masked = jnp.where(seen, -col.astype(jnp.float32), -jnp.inf)
    if t <= topk:
        return seen, jnp.full((q,), jnp.inf)
    vals, idx = jax.lax.top_k(masked, topk + 1)  # descending, ties by the lower index
    kth, at = vals[:, topk - 1 : topk], idx[:, topk - 1 : topk]
    chosen = seen & ((masked > kth) | ((masked == kth) & (col <= at)))
    gap = jnp.where(jnp.isfinite(vals[:, topk]), vals[:, topk - 1] - vals[:, topk], jnp.inf)
    return chosen, gap


def _set_of(rows, t: int):
    """Row indices [Q, K] (-1: none) as a set, bool [Q, T]."""
    hit = jnp.zeros((rows.shape[0], t + 1), bool)
    hit = hit.at[jnp.arange(rows.shape[0])[:, None], jnp.where(rows < 0, t, rows)].set(True)
    return hit[:, :t]


def attention(x, w, cfg: dict, kind: str, ops, follow=None, rows=None):
    """The attention sublayer's branch over x [T, D] -> (branch [T, D], and for
    a full layer under an indexer, for the ``rows`` wanted (start, count): the
    walk's own sets [R, T] bool, the rows differing from ``follow`` [R], the
    exchanged rows' distance in the walk's own scores relative to the spread of
    the query's scores [R], the gap at the topk-th score [R] and the two rows
    farthest apart [R, 2]; None for a layer without an indexer).

    Held at once: the rows each query reads [T, T] bool (a full layer's
    selection, found a block of queries at a time), and the queries, keys and
    values of ``HEADS`` heads, whose scores are held a block of queries at a
    time."""
    g, eps = geometry(cfg, kind), cfg.get("rms_norm_eps", 1e-6)
    t = x.shape[0]
    h, dn, dr, dv, r = g["heads"], g["nope"], g["rope"], g["v"], g["kv_rank"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    at_all = jnp.arange(t)
    s_q = 1.0 if "no_rescale" in ops.faults else g["s_q"]
    s_kv = 1.0 if "no_rescale" in ops.faults else g["s_kv"]
    c_q = s_q * _rmsnorm(ops.mm(x, w["q_a_proj"]), f32(w["q_a_norm"]), eps)
    row = ops.mm(x, w["kv_a_proj"])
    c_kv = s_kv * _rmsnorm(row[:, :r], f32(w["kv_a_norm"]), eps)
    k_r = _rotate(row[:, None, r:], at_all, g["theta"])  # [T, 1, rope]: one for all heads
    topk = g["topk"]
    window = g["window"] + ("window_plus" in ops.faults) - ("window_minus" in ops.faults)
    start, count = (0, t) if rows is None else rows
    col = jnp.arange(t)[None]
    block = min(BLOCK, t)
    pad = -t % block
    padded = lambda a: jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1)))
    given = None if follow is None else _set_of(follow, t)  # [R, T]
    if topk:
        hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        rot = di if "index_rotate_whole" in ops.faults else dr

        def turn(a, at):  # [Q, H, Di]: the first ``rot`` values by position
            return jnp.concatenate((_rotate(a[..., :rot], at, g["theta"]), a[..., rot:]), -1)

        ki = _layernorm(
            ops.mm(x, w["index_k"]), f32(w["index_k_norm"]), f32(w["index_k_norm_bias"]), eps
        )
        ki = turn(ki[:, None], at_all)[:, 0]
        c_q_all, x_all = padded(c_q), padded(x)

        def sets_of(first, n):
            """The walk's own sets of the ``n`` queries from position ``first``."""
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, first, n, axis=0)
            at = first + jnp.arange(n)
            qi = turn(ops.mm(cut(c_q_all), w["index_q"]).reshape(n, hi, di), at)
            scores = index_scores(qi, ki, ops.mm(cut(x_all), w["index_w"]), cfg, ops)
            seen = col <= at[:, None]
            return (*select(scores, seen, topk, ops.faults), scores, seen)

        def block_reads(first):
            reads = sets_of(first, block)[0]
            if given is not None:  # the wanted positions among these read the given sets
                at = first + jnp.arange(block)
                i = jnp.clip(at - start, 0, count - 1)
                wanted = ((at >= start) & (at < start + count))[:, None]
                reads = jnp.where(wanted, given[i], reads)
            return reads

        reads_all = jax.lax.map(block_reads, jnp.arange(0, t + pad, block))  # [nb, B, T]

    def reads_of(b):  # the rows the queries of block b read, [B, T]
        if topk:
            return reads_all[b]
        at = (b * block + jnp.arange(block))[:, None]
        return (col <= at) & ((at - col < window) if window else True)

    heads = min(HEADS, h)
    by_group = lambda m, per: jnp.moveaxis(  # [in, H * per] -> [H / heads, in, heads, per]
        jnp.asarray(m).reshape(m.shape[0], h // heads, heads, per), 1, 0
    )
    gate = None
    if g["gate"] == "headwise" and "no_gate" not in ops.faults:
        gate = jax.nn.sigmoid(ops.mm(x, w["attn_gate"]))  # [T, H]

    def group(ws):
        w_q, w_kv = ws  # [Rq, heads, nope + rope], [R, heads, nope + v]
        q = jnp.einsum("tr,rhd->thd", ops.lo(c_q), ops.lo(w_q))
        q = jnp.concatenate((q[..., :dn], _rotate(q[..., dn:], at_all, g["theta"])), axis=-1)
        kv = jnp.einsum("tr,rhd->thd", ops.lo(c_kv), ops.lo(w_kv))
        k = jnp.concatenate((kv[..., :dn], jnp.broadcast_to(k_r, (t, heads, dr))), axis=-1)
        v = kv[..., dn:]
        q_all = padded(q)

        def one_block(b):
            reads = reads_of(b)[None]
            qb = jax.lax.dynamic_slice_in_dim(q_all, b * block, block, axis=0)
            s = jnp.einsum("qhd,thd->hqt", ops.lo(qb), ops.lo(k)) / jnp.sqrt(jnp.float32(dn + dr))
            p = jax.nn.softmax(jnp.where(reads, s, -jnp.inf), axis=-1)
            p = jnp.where(reads, p, 0.0)  # a padding query reads nothing
            return jnp.einsum("hqt,thd->qhd", ops.lo(p), ops.lo(v))

        return jax.lax.map(one_block, jnp.arange((t + pad) // block)).reshape(-1, heads, dv)[:t]

    o = jax.lax.map(group, (by_group(w["q_b_proj"], dn + dr), by_group(w["kv_b_proj"], dn + dv)))
    o = jnp.moveaxis(o, 0, 1).reshape(t, h, dv)  # [H / heads, T, heads, v] -> [T, H, v]
    if gate is not None:
        o = o * gate[..., None]
    branch = ops.mm(o.reshape(t, h * dv), w["o_proj"])
    if not topk:
        return branch, None
    own, gap, scores, seen = sets_of(start, count)
    differing = jnp.zeros((count,), jnp.int32)
    distance = jnp.zeros((count,), jnp.float32)
    worst = jnp.full((count, 2), -1, jnp.int32)
    if given is not None:
        ours, theirs = own & ~given, given & ~own  # exchanged: ours out, theirs in
        differing = jnp.maximum(jnp.sum(ours, axis=-1), jnp.sum(theirs, axis=-1)).astype(jnp.int32)
        spread = jnp.sqrt(jnp.sum(jnp.where(seen, scores, 0.0) ** 2, -1) / jnp.sum(seen, -1))
        out_scores = jnp.where(ours, scores, -jnp.inf)
        in_scores = jnp.where(theirs, scores, jnp.inf)
        high, low = jnp.max(out_scores, axis=-1), jnp.min(in_scores, axis=-1)
        last = jnp.min(jnp.where(own, scores, jnp.inf), axis=-1)
        high = jnp.where(jnp.any(ours, axis=-1), high, last)
        low = jnp.where(jnp.any(theirs, axis=-1), low, last)
        distance = jnp.where(differing > 0, (high - low) / spread, 0.0)
        worst = jnp.stack((jnp.argmax(out_scores, -1), jnp.argmin(in_scores, -1)), -1)
        worst = jnp.where(differing[:, None] > 0, worst, -1).astype(jnp.int32)
    return branch, (own, differing, distance, gap, worst)


def _swiglu(m, w, ops, pre=""):
    return ops.mm(
        jax.nn.silu(ops.mm(m, w[pre + "gate_proj"])) * ops.mm(m, w[pre + "up_proj"]),
        w[pre + "down_proj"],
    )


def routed_ffn(m, w, cfg: dict, ops):
    """The routed FFN's branch over m [T, D]: sigmoid scores over all experts,
    the k largest under the selection bias, their scores normalised and scaled,
    every held expert computed on every token and masked by the token's choice,
    plus the shared SwiGLU -> branch [T, D]."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(ops.mm(m, w["router"]))
    _, chosen = jax.lax.top_k(s + jnp.asarray(w["router_bias"], jnp.float32), k)
    gate = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate * cfg.get("routed_scaling_factor", 1.0)
    weight = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32) * gate[..., None], axis=-2)
    held = w["gate_proj"].shape[0]
    first = cfg.get("first_local_expert", 0) if held != e else 0
    weight = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=-1)  # [T, Eh]

    def expert(acc, xs):
        g, u, d, w_e = xs
        y = ops.mm(jax.nn.silu(ops.mm(m, g)) * ops.mm(m, u), d)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(m), (w["gate_proj"], w["up_proj"], w["down_proj"], weight.T)
    )
    return out + _swiglu(m, w, ops, "shared_")


def layer_step(h, w, cfg: dict, kind: str, operands=None, faults=(), follow=None, rows=None):
    """One layer over h [T, D], its weights ``w`` as the tree holds them
    (widened where they are used) -> (h, the indexer's facts or None)."""
    eps = cfg.get("rms_norm_eps", 1e-6)
    ops = _Ops(operands, faults)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(h, f32(w["input_norm"]), eps)
        branch, facts = attention(x, w, cfg, kind, ops, follow, rows)
        h = h + branch
        ffn = (lambda m: _swiglu(m, w, ops)) if kind == "dense" else (
            lambda m: routed_ffn(m, w, cfg, ops)
        )
        h = h + _blocked(ffn, _rmsnorm(h, f32(w["post_attn_norm"]), eps), TOKENS)
    return h, facts


@functools.lru_cache(maxsize=None)
def _jitted_layer(cfg_json: str, kind, operands, faults, count):
    """A jitted ``layer_step`` a (configuration, kind, walk)."""
    cfg = json.loads(cfg_json)
    return jax.jit(lambda h, w, follow, start: layer_step(
        h, w, cfg, kind, operands, faults, follow, None if count is None else (start, count),
    ))


def forward(params, input_ids, cfg, operands=None, faults=(), follow=None, rows=None,
            with_choices: bool = False):
    """Logits [1, R, V] float32 of ``input_ids`` [1, T] at the ``rows`` wanted
    ((start, count), the start may be an array; None: all T); with
    ``with_choices`` also, for those positions and the full layers under an
    indexer in order, the walk's own sets [R, Lf, T] bool, the rows in which
    each differs from ``follow`` [R, Lf], how far apart in the walk's scores the
    exchanged rows lie [R, Lf], the gap at the topk-th score [R, Lf] and the
    two rows farthest apart [R, Lf, 2]. A Python loop over the layers, each
    under a jit of its own kind: one layer's float32 weights at a time."""
    if input_ids.shape[0] != 1:
        raise ValueError("the reference walks one sequence at a time")
    eps = cfg.get("rms_norm_eps", 1e-6)
    frozen, faults = json.dumps(cfg, sort_keys=True), tuple(faults)
    start, count = (jnp.int32(0), None) if rows is None else (jnp.asarray(rows[0], jnp.int32), rows[1])
    h = jnp.asarray(params["embed_tokens"][input_ids[0]], jnp.float32)
    seen, facts, full = {}, [], 0
    for kind in kinds(cfg):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        w = jax.tree.map(lambda a: a[i], params["layers"][kind])
        given = None
        if kind != "sliding" and cfg.get("index_topk", 0):
            given = None if follow is None else jnp.asarray(follow)[:, full]
            full += 1
        h, fact = _jitted_layer(frozen, kind, operands, faults, count)(h, w, given, start)
        if fact is not None:
            facts.append(fact)
    with jax.default_matmul_precision("highest"):
        if count is not None:
            h = jax.lax.dynamic_slice_in_dim(h, start, count, axis=0)
        h = _rmsnorm(h, jnp.asarray(params["final_norm"], jnp.float32), eps)
        logits = _Ops(operands).mm(h, jnp.asarray(params["lm_head"], jnp.float32))[None]
    if not with_choices:
        return logits
    return (logits, *(jnp.stack(part, axis=1) for part in zip(*facts)))
