"""The plain reference of Keye-VL-2.0's language-model block, written from its
equations.

``jax.numpy``, float32, highest matmul precision, no kernels, no cache, no
chunks, no grouped matmul, no batching of requests, and nothing imported from
the program: a full forward over one sequence, the scores of the indexer and of
the attention held a block of queries at a time so that 16,384 positions fit
beside an engine. Published description: the keys of
``Kwai-Keye/Keye-VL-2.0-30B-A3B``'s ``config.json`` (a Qwen3-MoE block: every
number under that family's key) and its ``sa_config``, DeepSeek sparse
attention's lightning indexer (DeepSeek-V3.2-Exp's report: I[t, s] = sum_j
w[t, j] relu(q[t, j] . k[s]), the top-k of it, attention over the chosen).
Parameters are the program's pytree (``layers``: one stack of like layers),
read by name.

The layer, with x = rmsnorm(h, input_norm) (eps ``rms_norm_eps``) and
positions t (three rows T, H, W; equal for token ids):

1. q = x W_q as Nh heads of Dh, k = x W_k, v = x W_v as Nkv heads, no bias;
   per head q <- rmsnorm(q, g_q), k <- rmsnorm(k, g_k), the weights [Dh] shared
   by the heads; rotated, theta ``rope_theta``, value i paired with value i +
   Dh/2, all Dh values: frequency pair i turns by row T for i < m0, row H for
   the next m1 pairs, row W for the rest (``rope_scaling.mrope_section``).
2. the indexer: qI[t, j] = rot(x_t W_Iq)_j in R^Di for j < Hi; kI[s] =
   rot(layernorm(x_s W_Ik; weight, bias)) in R^Di, one head; w_t = x_t W_Iw in
   R^Hi; both rotated whole by row T. I[t, s] = Hi^-1/2 Di^-1/2 sum_j w[t, j]
   relu(qI[t, j] . kI[s]) for s <= t. (The two constants are positive and
   change no order: the program leaves them out.)
3. S_t = the min(topk, t + 1) positions s <= t of largest I[t, s], ties to the
   lower position; one set a token and layer, shared by the heads.
4. o_t = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s / sqrt(Dh)) v_s per head
   (KV head n // (Nh / Nkv)); h <- h + o W_o.
5. x' = rmsnorm(h, post_attn_norm); p = softmax(x' W_r) over all E experts; the
   ``num_experts_per_tok`` largest, renormalised to sum 1; h <- h + sum_e p_e /
   sum p * down_e(silu(gate_e x') * (up_e x')) over the chosen experts **that
   this chip holds** (``num_local_experts`` from ``first_local_expert`` on;
   absent: all): the others' terms are another chip's and add nothing here.
6. final rmsnorm, logits = h W_head (untied).

**A flipped row is a near-tie, not an error.** bfloat16 scoring against float32
exchanges rows whose scores lie next to the topk-th. So the walk can *follow*
sets chosen elsewhere (``follow`` [R, L, topk] int32, -1 where a set is
shorter: the rows of each of the ``rows`` wanted positions in each layer, as
the program under test chose them) and reports beside the logits, for those
positions, its own sets, how many rows differ and how far apart *in its own
scores* the exchanged rows lie (``forward(..., with_choices=True)``): the
cell's check compares logits along the program's choices at the rows compared,
and separately holds the exchanged rows to a small reference margin.
Positions that are not among ``rows`` read the walk's own sets.

Departures and what no config key fixes, each deliberate (the configuration
file's ``assumed`` has the reasons): QK-norm per head; the indexer reads the
layer's normed x; LayerNorm with bias on the index key (eps
``rms_norm_eps``), rotation of all Di values; ``q_chunk_size`` /
``kv_chunk_size`` tile a kernel and enter no equation; selection by token; no
Hadamard rotation; the vision tower and the indexer's alignment loss are not
built. ``faults`` (the readings tool and the tests) breaks one thing at a
time, to show that the comparison's limits catch it: ``no_relu``,
``first_rows`` (the first topk rows, not the largest), ``drop_rows`` (one
chosen row in a hundred left out), ``chunk_blind`` (a query reads no row
before its own chunk of ``q_chunk_size``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 256  # queries whose scores are held at once


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layernorm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


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


def _sa(cfg: dict) -> dict:
    return cfg["sa_config"]


def _rotate(x, positions, theta: float, sections=None):
    """x [T, H, d] rotated by position, value i paired with value i + d/2.
    ``positions`` [T], or [3, T] with ``sections``: pair i by its run's row."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions.ndim == 2:
        row = jnp.asarray([r for r, n in enumerate(sections) for _ in range(n)])
        pos = positions.astype(jnp.float32)[row].T  # [T, d/2]: pair i's own row
    else:
        pos = positions.astype(jnp.float32)[:, None]
    ang = pos * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)


def index_parts(x, w, cfg: dict, positions, ops=None):
    """The indexer's three over x [T, D] -> (qI [T, Hi, Di], kI [T, Di], w [T, Hi])."""
    ops = ops or _Ops()
    sa, eps, theta = _sa(cfg), cfg.get("rms_norm_eps", 1e-6), cfg["rope_theta"]
    t = x.shape[0]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    temporal = positions[0] if positions.ndim == 2 else positions
    qi = _rotate(ops.mm(x, w["index_q"]).reshape(t, hi, di), temporal, theta)
    ki = _layernorm(ops.mm(x, w["index_k"]), w["index_k_norm"], w["index_k_norm_bias"], eps)
    ki = _rotate(ki[:, None], temporal, theta)[:, 0]
    return qi, ki, ops.mm(x, w["index_w"])


def index_scores(qi, ki, wi, cfg: dict, ops=None):
    """I [Q, T] of index queries qi [Q, Hi, Di] under weights wi [Q, Hi]
    against index keys ki [T, Di], with the published constants."""
    ops = ops or _Ops()
    sa = _sa(cfg)
    s = jnp.einsum("qhd,td->qht", ops.lo(qi), ops.lo(ki))
    if "no_relu" not in ops.faults:
        s = jax.nn.relu(s)
    scale = sa["indexer_num_heads"] ** -0.5 * sa["indexer_head_dim"] ** -0.5
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
    if "drop_rows" in faults:  # one chosen row in a hundred, by position, is lost
        rank = jnp.cumsum(chosen.astype(jnp.int32), axis=-1)
        chosen = chosen & (rank % 100 != 7)
    gap = jnp.where(jnp.isfinite(vals[:, topk]), vals[:, topk - 1] - vals[:, topk], jnp.inf)
    return chosen, gap


def _set_of(rows, t: int):
    """Row indices [Q, K] (-1: none) as a set, bool [Q, T]."""
    hit = jnp.zeros((rows.shape[0], t + 1), bool)
    hit = hit.at[jnp.arange(rows.shape[0])[:, None], jnp.where(rows < 0, t, rows)].set(True)
    return hit[:, :t]


def sparse_attention(x, w, cfg: dict, positions, ops=None, follow=None, rows=None):
    """The attention sublayer's branch over x [T, D] -> (branch [T, D], and for
    the ``rows`` wanted (start, count; None: every position) the walk's own
    sets [R, T] bool, the rows differing from ``follow`` [R] and the exchanged
    rows' distance in the walk's own scores, relative to the spread of the
    query's scores [R] (zeros without ``follow``), the gap at the topk-th
    score [R], and which two rows lie farthest apart [R, 2]: the best the
    follower left out, the worst it took; -1 where none). ``follow`` [R,
    topk]: the sets those positions read instead."""
    ops = ops or _Ops()
    t = x.shape[0]
    nh, nkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa, eps, theta = _sa(cfg), cfg.get("rms_norm_eps", 1e-6), cfg["rope_theta"]
    topk, sections = sa["topk"], cfg["rope_scaling"]["mrope_section"]
    q = _rmsnorm(ops.mm(x, w["q_proj"]).reshape(t, nh, dh), w["q_norm"], eps)
    k = _rmsnorm(ops.mm(x, w["k_proj"]).reshape(t, nkv, dh), w["k_norm"], eps)
    v = ops.mm(x, w["v_proj"]).reshape(t, nkv, dh)
    where = positions if positions.ndim == 2 else jnp.stack((positions,) * 3)
    q, k = _rotate(q, where, theta, sections), _rotate(k, where, theta, sections)
    qi, ki, wi = index_parts(x, w, cfg, positions, ops)
    start, count = (0, t) if rows is None else rows
    block = min(BLOCK, t)
    pad = -t % block
    padded = lambda a: jnp.pad(a, ((0, pad), *((0, 0),) * (a.ndim - 1)))
    q_all, qi_all, wi_all = padded(q), padded(qi), padded(wi)
    col = jnp.arange(t)[None]
    given = None if follow is None else _set_of(follow, t)  # [R, T]

    def sets_of(at, qi, wi):
        """The walk's own sets of the queries at positions ``at`` [Q, 1]."""
        seen = col <= at
        if "chunk_blind" in ops.faults:
            seen = seen & (col >= at // sa["q_chunk_size"] * sa["q_chunk_size"])
        scores = index_scores(qi, ki, wi, cfg, ops)
        return (*select(scores, seen, topk, ops.faults), scores, seen)

    def one_block(first):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, first, block, axis=0)
        at = first + jnp.arange(block)[:, None]  # the queries' positions
        reads = sets_of(at, cut(qi_all), cut(wi_all))[0]
        if given is not None:  # the wanted positions among these read the given sets
            r = jnp.clip(at[:, 0] - start, 0, count - 1)
            wanted = ((at[:, 0] >= start) & (at[:, 0] < start + count))[:, None]
            reads = jnp.where(wanted, given[r], reads)
        qb = cut(q_all).reshape(block, nkv, nh // nkv, dh)
        s = jnp.einsum("qgrd,tgd->grqt", ops.lo(qb), ops.lo(k)) / jnp.sqrt(jnp.float32(dh))
        p = jax.nn.softmax(jnp.where(reads[None, None], s, -jnp.inf), axis=-1)
        p = jnp.where(reads[None, None], p, 0.0)  # a padding query reads nothing
        return jnp.einsum("grqt,tgd->qgrd", ops.lo(p), ops.lo(v)).reshape(block, nh * dh)

    o = jax.lax.map(one_block, jnp.arange(0, t + pad, block)).reshape(-1, nh * dh)[:t]
    # the wanted positions' own sets, once more and by themselves (a [T, T]
    # block of scores is never kept)
    take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, count, axis=0)
    own, gap, scores, seen = sets_of(
        (start + jnp.arange(count))[:, None], take(qi_all), take(wi_all)
    )
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
        # a set that is short of rows took nothing in their place, one with rows
        # too many left nothing out: the distance is then to the walk's last chosen score
        last = jnp.min(jnp.where(own, scores, jnp.inf), axis=-1)
        high = jnp.where(jnp.any(ours, axis=-1), high, last)
        low = jnp.where(jnp.any(theirs, axis=-1), low, last)
        distance = jnp.where(differing > 0, (high - low) / spread, 0.0)
        # which rows those are: the best the follower left out, the worst it took
        worst = jnp.stack((jnp.argmax(out_scores, -1), jnp.argmin(in_scores, -1)), -1)
        worst = jnp.where(differing[:, None] > 0, worst, -1).astype(jnp.int32)
    return ops.mm(o, w["o_proj"]), own, differing, distance, gap, worst


def routed_ffn(m, w, cfg: dict, ops=None, experts=None):
    """The FFN's branch over m [T, D]: softmax over all experts, the k largest
    renormalised, every held expert computed on every token and masked by the
    token's choice -> (branch [T, D], the experts chosen [T, k]). ``experts``
    [T, k]: the experts each token takes instead of its own k largest (the
    readings tool's witness: a rounded walk given the float32 walk's experts
    shows what of its exchanged rows came from experts flipped at a near-tie)."""
    ops = ops or _Ops()
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    p = jax.nn.softmax(ops.mm(m, w["router"]), axis=-1)
    gate, chosen = jax.lax.top_k(p, k)
    if experts is not None:
        gate, chosen = jnp.take_along_axis(p, experts, axis=-1), experts
    if cfg.get("norm_topk_prob", True):
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
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
    return out, chosen


def _walk(params, input_ids, cfg, operands=None, faults=(), follow=None, rows=None,
          positions=None, experts=None):
    """One sequence ``input_ids`` [1, T] -> (logits [1, R, V], own sets [R, L,
    T] bool, differing [R, L], distance [R, L], gap [R, L], worst [R, L, 2]) over the ``rows``
    wanted (start, count; the start may be traced, the count not; None: all),
    and the experts every token took [L, T, k] (``experts``: those it is given
    to take). A layer's weights are upcast one layer at a time, so that the
    walk fits beside an engine."""
    if input_ids.shape[0] != 1:
        raise ValueError("the reference walks one sequence at a time")
    eps = cfg.get("rms_norm_eps", 1e-6)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ops = _Ops(operands, faults)
    t = input_ids.shape[1]
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)
    kept = []

    def layer(h, xs):
        w, given, taken = xs
        w = jax.tree.map(f32, w)
        x = _rmsnorm(h, w["input_norm"], eps)
        branch, *facts = sparse_attention(x, w, cfg, positions, ops, given, rows)
        h = h + branch
        ffn, chosen = routed_ffn(_rmsnorm(h, w["post_attn_norm"], eps), w, cfg, ops, taken)
        return h + ffn, (*facts, chosen)

    with jax.default_matmul_precision("highest"):
        h = f32(params["embed_tokens"][input_ids[0]])
        given = None if follow is None else jnp.moveaxis(jnp.asarray(follow), 1, 0)  # [L, R, K]
        h, kept = jax.lax.scan(layer, h, (params["layers"], given, experts))  # None: no leaf
        *kept, chosen = kept
        if rows is not None:
            h = jax.lax.dynamic_slice_in_dim(h, rows[0], rows[1], axis=0)
        h = _rmsnorm(h, f32(params["final_norm"]), eps)
        logits = ops.mm(h, f32(params["lm_head"]))
    return (logits[None], *(jnp.moveaxis(a, 0, 1) for a in kept), chosen)


def forward(params, input_ids, cfg, operands=None, faults=(), follow=None, rows=None,
            with_choices: bool = False, positions=None, experts=None, with_experts: bool = False):
    """Logits [1, R, V] float32 of ``input_ids`` [1, T] at the ``rows`` wanted
    (None: all T); with ``with_choices`` also, for those positions, the walk's
    own sets [R, L, T] bool, the rows in which each differs from ``follow`` [R,
    L], how far apart in the walk's scores the exchanged rows lie [R, L]
    (relative to the query's scores' spread), the gap at the topk-th score
    [R, L] and the two rows farthest apart [R, L, 2]; with ``with_experts``
    then the experts every token took [L, T, k] (``experts``: those it is
    given to take). ``positions`` [T] or [3, T] (None: 0..T-1, three equal
    rows)."""
    *out, chosen = _walk(params, input_ids, cfg, operands, faults, follow, rows, positions, experts)
    out = tuple(out) if with_choices else (out[0],)
    out = (*out, chosen) if with_experts else out
    return out if len(out) > 1 else out[0]


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross-entropy of positions 0..T-2 of one sequence (no
    aux loss: the catalog's config has no key for one)."""
    logits = _walk(params, input_ids, cfg)[0]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
