"""Jitted inference engine: prefill, batched incremental decode, weight
hot-swap.

The engine owns its OWN device copy of the weights plus the slot-paged
ring KV cache. That copy, ``engine.params``, is the one tree its programs
take as their argument, and it is held in ``compute_dtype``: the masters
arrive in float32 (or whatever the caller trains in), are rounded to the
compute dtype once as they are bound (``_bind``: construction,
``install_params``, ``install_wire``) and the float32 values are not kept,
so no prefill or decode program starts by casting the whole tree again
(``weight_binds`` counts the bindings, ``weights_resident_bytes`` is what
they left on the device). The engine exposes a handful of device
operations to the scheduler loop — ``admit`` (prefill a prompt into a
free slot, optionally continuing from a reused prefix), ``decode_step``
(one token for every live slot), and ``maybe_swap`` (adopt a newer master
snapshot from the outer plane). All are called from a single scheduler
thread; the engine is deliberately not thread-safe so the jits can donate
the cache buffers without a lock.

A cold admission comes in two halves, so that a loop can enqueue every
program of an iteration before it reads any of them: ``admit_enqueue``
makes the arguments and enqueues the prompt's programs, whose insert also
writes the first token into a ``[S]`` vector on the device, and the next
``decode_step`` takes the slot's token from that vector, reads the
admission's token once the step is enqueued behind it (a wait for the
prompt's own program, no more) and only then its own. ``admit_resolve``
reads at once instead, for a caller that steps nothing there, and the
blocking ``admit`` is the two halves and the logits row: the same jitted
programs whichever way. ``admissions_deferred`` counts the admissions a
step fed on the device.

A decode step comes the same way. ``decode_step`` blocks: it enqueues the
step and reads it. ``step_ahead`` is what a loop calls once an iteration: it
enqueues the next step first and then reads the one enqueued by the call
before it, so the chip finds the next step queued when the one before ends.
A slot that rides both takes its token from the earlier step's output, which
stays on the device (``prev``, as ``first`` for an admission's token): the
same jitted program whichever way, and ``steps_ahead`` counts the steps that
were enqueued while the step before them was unread. Either call reads the
admissions that the step it reads fed on the device, before that step's tokens:
the prompts enqueued between two steps are read by the call after the one
that reads the first of the two.

A prompt longer than every bucket, under learned sparse attention, and every
prompt of a stack with sliding layers (no whole prompt is inserted into a ring
that wraps), is admitted in chunks of ``q_chunk_size`` tokens (the model's
configuration's or, where that names none, the engine's own: ``prefill_chunk``): ``admit_begin`` names the slot
and enqueues nothing, each ``admit_chunk`` enqueues one program over the
slot's rows so far (one compile for every prompt length), and the last chunk's
leaves the first token on the device as a cold admission's insert does, so the
:class:`Admission` is from there on like any other: fed by the next step, read
behind it. A loop gives a prefilling slot a chunk an iteration, between two
decode steps; ``admit`` runs them back to back.

Prefix reuse (scheduler-driven, off by default): ``admit(..., prefix_src,
prefix_len)`` ring-copies a live slot's prefix K/V and prefills only the
suffix (the continued prefill).

Hot-swap pulls codec-encoded snapshots (``DiLoCoOptimizer.
master_snapshot_wire``, the fp16 ``ODTP_STATE_CODEC`` path) and rebinds
``self.params`` between decode steps. The KV cache is untouched by
design: cached K/V stays consistent with the weights that produced it,
which is the standard serving trade for not re-prefilling every live
request on each outer round — and the staleness knob bounds how far the
weights may lag (DiLoCo-fresh serving, arXiv 2311.08105).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from opendiloco_tpu import obs
from opendiloco_tpu.diloco.compression import get_codec
from opendiloco_tpu.models.llama import (
    LlamaConfig,
    chunk_attn_form,
    chunk_prefill_forward,
    chunk_tile,
    decode_forward,
    causal_prefill_heads,
    latent_chunk_attn_form,
    prefill_forward,
)
from opendiloco_tpu.models import kda
from opendiloco_tpu.models.ring_cache import (
    cache_insert,
    cca_state_insert,
    eva_insert,
    eva_pooled_rows,
    fetch_pages,
    init_cca_state,
    init_eva_state,
    index_insert,
    init_index_cache,
    init_kda_state,
    init_kv_cache,
    init_lightning_state,
    init_pooled_cache,
    init_ssm_state,
    prefix_copy,
    state_insert,
)
from opendiloco_tpu.models.traits import refuse
from opendiloco_tpu.ops.attention import band_block
from opendiloco_tpu.ops.decode_kernels import (
    block_most_tiles,
    block_tile_plan,
    DecodePlan,
    decode_plan,
    eva_plans,
    eva_prefill_form,
    kda_step_form,
    mla_decode_plan,
    mla_rows_written_back,
    prefill_form,
    resolve_decode_kernel,
)
from opendiloco_tpu.serve.kvcache import pick_bucket


@functools.partial(jax.jit, static_argnums=1)
def _fresh_copy(leaves, dtype):
    # fresh buffers in the engine's dtype: the caller may pass live
    # train-state leaves that the next train_step donates (same add-zero
    # idiom as the outer plane). The rounding is ``astype``'s, the one the
    # forwards' boundary applies to a tree that has not met it yet
    return [x.astype(dtype) + jnp.zeros((), dtype) for x in leaves]


def _with_counts(tok, counts):
    """``tok`` with a routed model's FFN counts appended (``counts``: a list
    holding the int32 [3] or [4], or empty for a dense model, whose program
    then returns its tokens as they are)."""
    return jnp.concatenate([tok, *counts]) if counts else tok


# what a slot whose first token is still on the device passes the decode
# program in place of a token: the program takes the slot's entry of the
# engine's first-token vector instead (no token id is negative)
FIRST_TOKEN_ON_DEVICE = -1
# and of a slot whose newest token is the output of a decode step that has not
# been read: the program takes the slot's entry of that step's tokens
PREV_TOKEN_ON_DEVICE = -2


def serving_programs(
    cfg: LlamaConfig, *, compute_dtype, decode_kernel, chosen: bool = False, rows: bool = False,
):
    """The three functions a cold admission and a decode step run, unjitted
    -> (prefill, decode, admit_insert, carried): ``carried`` the number of
    ``decode``'s trailing arguments (rings, then per-slot state) that it
    updates and that its jit donates.

    One named scope per program: what a profiler trace calls the device work
    of a prefill and of a decode step. A routed model's programs append the
    FFN's three counts to the tokens, so that one device-to-host read fetches
    both (``ServeEngine._split_counts``). A hybrid's programs hand the
    recurrent state and the conv tail on after the K/V, CCA's its one state,
    EVA's the prompt's pooled rows and the pooling under way, which its
    ``admit_insert`` takes with the K/V of the prompt's last window
    (``ring_cache.eva_insert``: the slot's window ring, pooled ring and stats
    in one program): ``left`` is those, or nothing; with ``chosen`` each token's
    experts in each layer come last. Learned sparse attention's programs hand
    the prompt's index keys on after the K/V and its ``admit_insert`` writes the
    three rings; its decode step carries the index ring behind the K and V
    rings; with ``rows`` the rows each slot's step chose in each layer come last
    of all. Of a head of several vocabularies
    (``num_pred_heads``) the first is the one sampled; the logits go back whole.

    The first token never has to reach the host before the step that reads
    it: ``admit_insert`` writes it at ``slot`` into the ``[S]`` vector
    ``first`` beside the prompt's rows, and ``decode`` takes ``first[slot]``
    wherever ``tokens[slot]`` is ``FIRST_TOKEN_ON_DEVICE``. Nor does a step's
    token before the next step reads it: ``decode`` takes ``prev[slot]``, the
    token output of the step before as that step returned it (a routed model's
    counts still behind its ``[S]`` tokens), wherever ``tokens[slot]`` is
    ``PREV_TOKEN_ON_DEVICE``. A slot's token may come from the host, from
    ``first`` or from ``prev`` in one and the same step."""
    cd, dkn = compute_dtype, decode_kernel
    moe = bool(cfg.num_experts)
    n_state = 1 if cfg.cca or cfg.sparse else 3 if cfg.eva else 2 if cfg.hybrid else 0
    state_names = (
        ("cca_state",) if cfg.cca else ("index_cache",) if cfg.sparse
        else ("ssm_state", "conv_state")
    )
    # lightning layers beside a selection by blocks: the pooled-key ring and the
    # decaying states ride behind the caches, and the tiles the step's attention
    # held come back behind the tokens, as a routed model's counts do
    tiles = {}
    if cfg.linear and cfg.blocks:
        n_state, state_names = 2, ("pooled_cache", "lightning_state")
        tiles = {"return_block_tiles": True}
    if cfg.kda:  # the delta-rule states and the convolutions' tails, as a hybrid's two
        n_state, state_names = 2, ("kda_state", "kda_tail")

    def sample(logits):  # greedy, from the next token's head
        if cfg.num_pred_heads > 1:
            logits = logits[..., : cfg.vocab_size]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def prefill(p, ids, length):
        with jax.named_scope("odtp_serve_prefill"):
            logits, ks, vs, *rest = prefill_forward(
                p, ids, length, cfg, compute_dtype=cd, decode_kernel=dkn,
                return_moe_counts=moe, return_expert_choices=chosen,
            )
            left, counts = rest[:n_state], rest[n_state : n_state + 1]
            tok = sample(logits)
        # the one row of logits goes back as a row: a caller that wants it
        # reads it, and no second program has to cut it out after the first read
        return (_with_counts(tok, counts), logits[0], ks, vs, *left, *rest[n_state + 1 :])

    def decode(p, tokens, lens, first, ck, cv, *ssm, prev=None):
        with jax.named_scope("odtp_serve_decode"):
            if prev is not None:
                held = prev[: tokens.shape[0]]
                tokens = jnp.where(tokens == PREV_TOKEN_ON_DEVICE, held, tokens)
            tokens = jnp.where(tokens == FIRST_TOKEN_ON_DEVICE, first, tokens)
            state = {"eva_state": ssm} if cfg.eva else dict(zip(state_names, ssm))
            logits, ck, cv, *rest = decode_forward(
                p, tokens, lens, ck, cv, cfg, compute_dtype=cd,
                decode_kernel=dkn, return_moe_counts=moe,
                return_expert_choices=chosen, **state, **tiles,
                **({"return_row_choices": True} if rows else {}),
            )
            behind = int(moe) + 1 if tiles else 1  # the counts' place, the tiles' behind them
            left, counts = rest[:n_state], rest[n_state : n_state + behind]
            tok = sample(logits)
        return (_with_counts(tok, counts), logits, ck, cv, *left, *rest[n_state + behind :])

    def admit_insert(ck, cv, first, ks, vs, tok, slot):
        ck, cv = cache_insert(ck, cv, ks, vs, slot)
        return ck, cv, first.at[slot].set(tok[0])

    def eva_admit_insert(ck, cv, first, pk, pv, stats, ks, vs, pks, pvs, chunk, tok, slot):
        rings = eva_insert(ck, cv, pk, pv, stats, ks, vs, pks, pvs, chunk, slot)
        return rings[0], rings[1], first.at[slot].set(tok[0]), *rings[2:]

    def sparse_admit_insert(ck, cv, first, ci, ks, vs, iks, tok, slot):
        ck, cv = cache_insert(ck, cv, ks, vs, slot)
        return ck, cv, first.at[slot].set(tok[0]), index_insert(ci, iks, slot)

    if cfg.eva:
        admit_insert = eva_admit_insert
    if cfg.sparse:
        admit_insert = sparse_admit_insert

    return prefill, decode, admit_insert, 2 + n_state


def chunk_program(cfg: LlamaConfig, *, compute_dtype, rows: bool = False, decode_kernel: str = "xla"):
    """The one function a prompt admitted in chunks runs, unjitted:
    ``chunk(params, ids [1, C], plen, count, slot, last, first, ck, cv, ci) ->
    (the chunk's last real token's greedy successor [1] and a routed model's
    counts, its logits row, first, ck, cv, ci)``; with ``rows`` then the rows
    that token read in each layer. Everything but the ids' shape is traced: one
    compile serves every prompt and every chunk of it. ``last`` says whether
    the token is the prompt's first (then it goes into ``first[slot]``, where
    the next decode step takes it); the trailing four arguments are updated
    and a jit donates them. ``decode_kernel`` as ``serving_programs`` takes it."""
    moe = bool(cfg.num_experts)

    def chunk(p, ids, plen, count, slot, last, first, ck, cv, ci):
        with jax.named_scope("odtp_serve_prefill"):
            logits, ck, cv, ci, *rest = chunk_prefill_forward(
                p, ids, plen, count, slot, ck, cv, ci, cfg, compute_dtype=compute_dtype,
                return_moe_counts=moe, return_row_choices=rows, decode_kernel=decode_kernel,
            )
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            first = jnp.where(last, first.at[slot].set(tok[0]), first)
        return (_with_counts(tok, rest[:moe]), logits[0], first, ck, cv, ci, *rest[moe:])

    return chunk


def state_chunk_program(
    cfg: LlamaConfig, *, compute_dtype, rows: bool = False, decode_kernel: str = "xla"
):
    """``chunk_program`` for a stack of lightning layers beside attention under
    a selection by blocks: ``chunk(params, ids [1, C], plen, count, total,
    slot, last, first, ck, cv, pc, ls) -> (the chunk's last real token's greedy
    successor [1] and the ring tiles its attention visited [1], its logits row,
    first, ck, cv, pc, ls)``; with ``rows`` then the blocks that token read in
    each layer. ``total`` is the whole prompt's length (``dense_len``'s side);
    ``pc`` the pooled-key ring, ``ls`` the lightning layers' states: the chunk
    enters with ``slot``'s and leaves the next chunk's. The trailing five
    arguments are updated and a jit donates them."""

    def chunk(p, ids, plen, count, total, slot, last, first, ck, cv, pc, ls):
        with jax.named_scope("odtp_serve_prefill"):
            logits, ck, cv, _, pc, ls, tiles, *rest = chunk_prefill_forward(
                p, ids, plen, count, slot, ck, cv, None, cfg, compute_dtype=compute_dtype,
                pooled_cache=pc, lightning_state=ls, total=total, return_block_tiles=True,
                return_row_choices=rows, decode_kernel=decode_kernel,
            )
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            first = jnp.where(last, first.at[slot].set(tok[0]), first)
        return (_with_counts(tok, [tiles]), logits[0], first, ck, cv, pc, ls, *rest)

    return chunk


def kda_chunk_program(cfg: LlamaConfig, *, compute_dtype, decode_kernel: str = "xla"):
    """``chunk_program`` for a stack with kda layers: ``chunk(params, ids [1,
    C], plen, count, slot, last, first, ck, cv, ks, kt) -> (the chunk's last
    real token's greedy successor [1] and the routed FFN's counts, its logits
    row, first, ck, cv, ks, kt)``. ``ks`` the kda layers' states, ``kt`` their
    convolutions' tails: the chunk enters with ``slot``'s and leaves the next
    chunk's, or the decode step's. The trailing five arguments are updated and
    a jit donates them."""
    moe = bool(cfg.num_experts)

    def chunk(p, ids, plen, count, slot, last, first, ck, cv, ks, kt):
        with jax.named_scope("odtp_serve_prefill"):
            logits, ck, cv, _, ks, kt, *rest = chunk_prefill_forward(
                p, ids, plen, count, slot, ck, cv, None, cfg, compute_dtype=compute_dtype,
                kda_state=ks, kda_tail=kt, return_moe_counts=moe, decode_kernel=decode_kernel,
            )
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            first = jnp.where(last, first.at[slot].set(tok[0]), first)
        return (_with_counts(tok, rest[:moe]), logits[0], first, ck, cv, ks, kt)

    return chunk


class _DecodeProgram:
    """``ServeEngine._decode``: the jitted decode program under the signature
    it had before it took the first-token vector, ``(params, tokens, lens,
    cache_k, cache_v, *state)``, for whoever lowers, traces or calls it from
    outside the engine (the benchmark's drivers name a scope's instructions
    from the text of ``_decode.lower(...)``, which has to be the program that
    ran). The engine passes ``first`` and ``prev``, the token output of the
    step before (``counts`` entries longer than the slots for a routed model);
    without them no slot's token is there."""

    def __init__(self, decode, carried: int, counts: int = 0):
        self.jitted = jax.jit(decode, donate_argnums=tuple(range(4, 4 + carried)))
        self.counts = counts

    def __call__(self, p, tokens, lens, *carried, first=None, prev=None):
        slots = np.shape(tokens)[0]
        if first is None:
            first = jnp.zeros((slots,), jnp.int32)
        if prev is None:
            prev = jnp.zeros((slots + self.counts,), jnp.int32)
        return self.jitted(p, tokens, lens, first, *carried, prev=prev)

    def lower(self, p, tokens, lens, *carried):
        first = jax.ShapeDtypeStruct(tokens.shape, jnp.int32)
        prev = jax.ShapeDtypeStruct((tokens.shape[0] + self.counts,), jnp.int32)
        return self.jitted.lower(p, tokens, lens, first, *carried, prev=prev)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()


@dataclasses.dataclass(eq=False)
class Admission:
    """A cold admission whose programs are enqueued (``ServeEngine.
    admit_enqueue``) and whose first token may not have been read yet:
    ``token`` and ``t_token``, the instant it reached the host, are filled by
    ``admit_resolve`` or by the decode step that fed it on the device."""

    slot: int
    tokens: int  # the prompt's length
    tokd: jax.Array  # the first token, then a routed model's counts
    rowd: jax.Array  # the last position's logits [V], never read unasked
    state_bytes: int
    t0: float
    t_args: float
    t_dispatch: float
    token: Optional[int] = None
    t_token: Optional[float] = None
    fed: bool = False  # a decode step that takes the token on the device is enqueued
    form: str = "xla"  # the prompt's causal attention (``ServeEngine.prefill_forms``)
    # a prompt admitted in chunks (``ServeEngine.admit_begin``): its tokens, the
    # rows enqueued so far, and the newest chunk, whose counts are unread.
    # ``tokd``, ``rowd`` and the stamps are the last chunk's once it is enqueued
    prompt: Optional[np.ndarray] = None
    rows_done: int = 0
    chunk: Optional["_Chunk"] = None

    @property
    def prefilling(self) -> bool:
        """Has the prompt chunks that are not enqueued yet?"""
        return self.prompt is not None and self.rows_done < self.tokens


@dataclasses.dataclass(eq=False)
class _Chunk:
    """One enqueued chunk of a prompt admitted in chunks."""

    tokd: jax.Array  # the chunk's token, then a routed model's counts
    index: int  # which chunk of its prompt
    rows_before: int  # the slot's rows the chunk found
    count: int  # its real tokens
    t0: float
    t_args: float
    t_dispatch: float
    total: int = 0  # the whole prompt's tokens


@dataclasses.dataclass(eq=False)
class _Step:
    """A decode step whose program is enqueued and whose tokens are not read."""

    tokd: jax.Array  # its tokens, then a routed model's counts
    lens: np.ndarray  # the positions it was enqueued with: what its counters count
    fed: list  # the admissions whose first tokens it took on the device
    t0: float
    t_args: float
    t_dispatch: float


# snapshot_fn contract: () -> (epoch, blobs, codec_name) with blobs[i] =
# (payload, meta, shape) per master leaf in params-flatten order — exactly
# what DiLoCoOptimizer.master_snapshot_wire returns.
SnapshotFn = Callable[[], tuple]

_STAGES = ("prefill", "decode", "swap", "page_out", "page_in")
# where a cold prefill and a decode step change hands: until the arguments of
# the call's first program are device arrays, until its last jitted call has
# returned to Python, and from where the host starts to wait for the call's
# tokens (at once, or after the reads that come before it) until they are there
_PHASES = ("args", "dispatch", "fetch")


class ServeEngine:
    weight_format = "fp32"  # benchmark/odbench/serve_cell.py:62 prints it and nothing else reads it (ROADMAP C-b19)

    def __init__(
        self,
        cfg: LlamaConfig,
        params,
        *,
        num_slots: int = 8,
        max_context: int = 512,
        prefill_buckets: Sequence[int] = (32, 128, 512),
        compute_dtype=jnp.bfloat16,
        epoch: int = 0,
        snapshot_fn: Optional[SnapshotFn] = None,
        epoch_fn: Optional[Callable[[], int]] = None,
        max_stale_rounds: int = 0,
        decode_kernel: Optional[str] = None,
        adopt_params: bool = False,
        prefill_chunk: int = 0,
    ):
        # the chunk a prompt is admitted in is the model's where its
        # configuration names one (``q_chunk_size``); a stack with sliding
        # layers whose configuration names none takes the engine's, laid over
        # the engine's own view of the configuration, which sizes the sliding
        # rings and the chunk program by it
        if prefill_chunk and (cfg.sliding or cfg.linear or cfg.blocks or cfg.kda) and not cfg.q_chunk_size:
            cfg = dataclasses.replace(cfg, q_chunk_size=int(prefill_chunk))
        elif prefill_chunk and int(prefill_chunk) != cfg.q_chunk_size:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} is the engine's to give only where the model's "
                f"configuration admits in chunks and names none (a stack with sliding layers); "
                f"this one's q_chunk_size is {cfg.q_chunk_size}"
            )
        if (cfg.linear or cfg.blocks) and not (cfg.linear and cfg.blocks and cfg.q_chunk_size):
            raise ValueError(
                "lightning layers and attention under a selection by blocks are served together "
                "(a minicpm_sala stack) and every prompt of theirs is admitted in chunks, a chunk "
                "entering with the state the chunk before left: give the engine a prefill_chunk"
            )
        if cfg.kda and not cfg.q_chunk_size:
            raise ValueError(
                "a stack with kda layers is admitted in chunks, a chunk entering with the state "
                "and the convolution's tail the chunk before left (the one hand-over to the "
                "decode step), and its configuration names none: give the engine a prefill_chunk"
            )
        if cfg.sliding and not cfg.q_chunk_size:
            raise ValueError(
                "a stack with sliding layers is admitted in chunks (no whole prompt is inserted "
                "into a ring that wraps) and its configuration names none: give the engine a "
                "prefill_chunk"
            )
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_context = int(max_context)
        self.compute_dtype = compute_dtype
        self.prefill_buckets = sorted(
            min(int(b), self.max_context) for b in prefill_buckets
        )
        self.snapshot_fn = snapshot_fn
        self.epoch_fn = epoch_fn
        self.max_stale_rounds = int(max_stale_rounds)

        # None, which every caller but a test passes, is the kernels on a TPU
        # backend and the XLA forms elsewhere; a test asks for "pallas" by name
        # and the kernels run interpreted beside their XLA reference
        self.decode_kernel = resolve_decode_kernel(decode_kernel)
        leaves, self._treedef = jax.tree.flatten(params)
        self._shapes = [tuple(x.shape) for x in leaves]
        # bindings of a weight tree so far: 1 here, +1 a swap
        self.weight_binds = 0
        # leaves of the first tree that the engine took as they were (``adopt_params``)
        self.weights_adopted = 0
        self._bind(leaves, epoch, adopt=adopt_params)
        del leaves, params
        self.swap_seconds = 0.0
        # wall-clock per decode stage (loop-thread only, mirrored to obs
        # spans when a tracer is armed; the bench reads this directly)
        self.stage_seconds = {k: 0.0 for k in _STAGES}
        # the two stages a cell runs, cut into their phases (always on, from
        # stamps the call takes itself; spans ``serve_args``, ``serve_dispatch``
        # and ``serve_fetch`` from the same stamps while a tracer is armed).
        # ``stage_seconds`` less a stage's three is the counting after the read.
        # An admission that a decode step reads is a ``prefill`` fetch inside
        # that step's wall and outside its seconds: ``stage_seconds["prefill"]``
        # is the enqueue and that read, ``["decode"]`` the step's own three
        self.phase_seconds = {
            stage: {k: 0.0 for k in _PHASES} for stage in ("prefill", "decode")
        }
        self.phase_calls = {"prefill": 0, "decode": 0}
        # (t0, t1) of the last ``decode_step`` or ``step_ahead``, for the loop
        # to tile its own phases against
        self.decode_bounds = (0.0, 0.0)
        # what a routed FFN did in the prefills and decode steps so far, each
        # summed over layers and calls (always on; stay 0 for a dense model):
        # token-expert pairs, experts that received a token, and the busiest
        # expert's pairs
        self.moe_pairs = 0
        self.moe_experts_hit = 0
        self.moe_max_pairs = 0
        # a layer that holds a share of the experts counts its own experts'
        # pairs above, and here the pairs of all the router's experts
        self.moe_pairs_all = 0
        # what the Mamba-2 mixers did (always on; stay 0 for a model without
        # them): tokens that passed them (a prompt's tokens, a decode step's
        # live slots), and the bytes of recurrent state and conv tail the
        # calls read and wrote (a prefill writes one slot's, a decode step
        # reads and writes every slot's)
        self.ssm_tokens = 0
        self.ssm_state_bytes_moved = 0
        # what the latent attention did with its ring (always on; stay 0 for
        # a model whose cache is keys and values): the live latent rows the
        # decode steps read, over layers (each once a layer and step), and
        # the bytes of latent rows the calls moved (a prefill writes its
        # prompt's rows, a decode step reads the live rows and writes one a
        # slot)
        self.latent_rows_read = 0
        self.latent_bytes_moved = 0

        # learned sparse attention: the index-key ring beside K and V
        # (``ring_cache``); a prompt longer than every bucket goes in chunks of
        # ``q_chunk_size``, each written as one aligned block, so the ring is
        # whole chunks
        self._index: tuple = ()
        if (cfg.sparse or cfg.sliding or cfg.blocks) and self.max_context % cfg.q_chunk_size:
            raise ValueError(
                f"max_context {self.max_context} is not whole chunks of q_chunk_size "
                f"{cfg.q_chunk_size}: a prompt admitted in chunks writes each as one "
                "block of ring rows"
            )
        if cfg.sliding and cfg.latent and not cfg.sparse:
            raise ValueError(
                "a latent stack with sliding layers is served with its full layers under "
                "an indexer (index_topk > 0): its prompts go in chunks beside an index ring"
            )
        # a latent cache is the one ring, in ``cache_k``; ``cache_v`` is None, or,
        # for a latent stack with sliding layers, those layers' ring, which wraps
        if cfg.sparse:
            self._index = (
                init_index_cache(cfg, self.num_slots, self.max_context, compute_dtype),
            )
        cache = init_kv_cache(cfg, self.num_slots, self.max_context, compute_dtype)
        self.cache_k, self.cache_v = cache["k"], cache["v"]
        self.index_cache_resident_bytes = sum(x.nbytes for x in self._index)
        # what the indexer and the attention under its selection did (always
        # on; stay 0 without them), over layers, decode steps and prefills
        # alike: the index rows scored (each query's live rows), the rows
        # chosen (min(index_topk, live) a query), the bytes of index keys and
        # of K and V rows the programs read (each live row of a slot once a
        # step or a chunk: the attention reads a slot's rows in order under
        # the selection's mask), and the chunks of prompts admitted in chunks
        self.dsa_rows_scored = 0
        self.dsa_rows_selected = 0
        self.dsa_index_bytes_read = 0
        self.dsa_kv_bytes_read = 0
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        # the form a chunk's grouped-query attention over a slot's pages takes,
        # by ``decode_kernel`` and the shapes (``llama.chunk_attn_form``:
        # "tiles-pallas", the kernel that keeps its scores in VMEM, where the XLA
        # form's score tile would be written to memory, else "tiled-xla"); ""
        # where no prompt goes in chunks over K and V rows
        chunked = (cfg.sparse or cfg.sliding or cfg.blocks or cfg.kda) and not cfg.latent
        self.chunk_form = chunk_attn_form(
            cfg, cfg.q_chunk_size, self.max_context, self.decode_kernel
        ) if chunked else ""
        self.latent_cache_resident_bytes = self.cache_k.nbytes if cfg.latent else 0
        self._latent_row_bytes = (
            cfg.latent_row_dim * self.cache_k.dtype.itemsize if cfg.latent else 0
        )
        # the sliding layers' ring (0 without them): what it holds, the rows a
        # step or a chunk read of it over layers (each slot's window's rows),
        # and which form each kind of latent layer's decode step and chunk take
        # ({} without sliding layers): the decode step's by ``decode_kernel``
        # (the kernel has a tile for both rings or the engine is refused here,
        # never a step that quietly takes the XLA form), its tile and the ring
        # rows a slot's step hands back of it; a chunk's is the absorbed form a
        # tile of ring rows at a time: the full layers' from what the code sees
        # (``llama.latent_chunk_attn_form``: "absorbed-pallas", the kernel that
        # keeps its scores in VMEM, where the XLA form's score tile would be
        # written to memory), the sliding layers' over their ring that wraps
        # "absorbed-xla"
        self.swa_cache_resident_bytes = self.cache_v.nbytes if cfg.sliding and cfg.latent else 0
        self.swa_rows_read = 0
        self.swa_bytes_moved = 0
        self.latent_forms: dict = {}
        if cfg.sliding and cfg.latent:
            full, swa = self.cache_k.shape, self.cache_v.shape
            plans = {
                "full": mla_decode_plan(full[3], cfg.kv_lora_rank, full[4]),
                "sliding": mla_decode_plan(swa[3], cfg.swa_kv_lora_rank, swa[4]),
            }
            self._need_plans(
                all(plans.values()), "tile for this stack's latent rings",
                f"full {full[3:]}, sliding {swa[3:]} as (row, ring rows): {plans}",
            )
            if self.decode_kernel != "pallas":
                plans = dict.fromkeys(plans, 0)
            chunk_forms = {
                "full": latent_chunk_attn_form(
                    cfg, cfg.q_chunk_size, self.max_context, self.decode_kernel
                ),
                "sliding": "absorbed-xla",
            }
            self.latent_forms = {
                kind: {"decode": self.decode_kernel, "chunk": chunk_forms[kind], "block_t": tile,
                       "rows_written_back": mla_rows_written_back(tile)}
                for kind, tile in plans.items()
            }
        # a grouped-query stack with sliding layers: ``cache_k`` is the full
        # layers' pair of rings, ``cache_v`` the sliding layers', which wrap
        # (``ring_cache.RingPair``). What the two kinds read of them (always on;
        # stay 0 without such a stack), over layers: the rows of a decode step's
        # and a chunk's sliding layers (each slot's window's rows: at most
        # ``sliding_window_size`` a layer and query) and of their full layers
        # (each live row), the bytes of K and V rows the calls moved; and which
        # form each kind's decode step and chunk take ({} without such a stack):
        # the decode step's by ``decode_kernel`` (the kernel has a plan for both
        # rings or the engine is refused here, never a step that quietly takes
        # the XLA form), a chunk's from what the code sees (the full layers'
        # ``chunk_form``; the sliding layers' the band alone, "banded-xla", where
        # ``band_block`` cuts the ring, else every tile under the window's mask)
        self.full_rows_read = 0
        self.kinds_bytes_moved = 0
        self.kind_forms: dict = {}
        self._kinds_row_bytes = 0
        if cfg.sliding and not cfg.latent:
            self._kinds_row_bytes = 2 * cfg.kv_heads * cfg.head_dim * self.cache_k.dtype.itemsize
            size, swa_rows = self.cache_k.dtype.itemsize, self.cache_v.shape[-1]
            plans = {
                "full": decode_plan(cfg.kv_heads, cfg.head_dim, self.max_context, size),
                "sliding": decode_plan(cfg.kv_heads, cfg.head_dim, swa_rows, size),
            }
            self._need_plans(
                all(plans.values()), "plan for this stack's rings",
                f"{cfg.kv_heads} KV heads of {cfg.head_dim} over {self.max_context} and "
                f"{swa_rows} rows: {plans}",
            )
            banded = band_block(cfg.q_chunk_size, swa_rows, cfg.sliding_window_size)
            chunk_forms = {"full": self.chunk_form, "sliding": "banded-xla" if banded else "tiled-xla"}
            self.kind_forms = {
                kind: {"decode": self.decode_kernel, "chunk": chunk_forms[kind],
                       "block_t": plan.block_t if self.decode_kernel == "pallas" else 0,
                       "heads": plan.heads if self.decode_kernel == "pallas" else 0}
                for kind, plan in plans.items()
            }
            self.kind_forms["sliding"]["band_block"] = banded
        # lightning layers beside a selection by blocks: the pooled-key ring
        # beside K and V and the lightning layers' decaying states, float32
        # (``ring_cache``); empty for every other stack. What the two did
        # (always on; stay 0 without them), over layers, decode steps and
        # chunks alike: the tokens that passed the lightning mix and the bytes
        # of state the calls read and wrote (a step every slot's, there and
        # back; a chunk one slot's); the pooled keys scored (each query's
        # windows that have closed), the blocks chosen and the rows the
        # equations read of them (at most ``topk`` blocks a query and KV
        # head), the ring tiles the form moved (``block_tiles_read``: a decode
        # step's tiles that held a chosen block, a chunk's tiles that some
        # query chose) against the tiles the live rows lie in
        # (``block_tiles_live``), and the calls whose queries read every row
        # (``dense_len_calls``: a slot of a step, a chunk). ``block_forms``
        # names the form the step and the chunk take ({} without the stack):
        # the step's by ``decode_kernel`` (the kernel has a tile for the ring or
        # the engine is refused here), its tile and the most tiles a slot and
        # KV head walks; the chunk's attention's is ``chunk_form``, its
        # selection's and the lightning layers' are the XLA forms
        self._sala: tuple = ()
        self.lightning_tokens = 0
        self.lightning_state_bytes_moved = 0
        self.pooled_keys_scored = 0
        self.blocks_chosen = 0
        self.block_rows_read = 0
        self.block_tiles_read = 0
        self.block_tiles_live = 0
        self.dense_len_calls = 0
        self.block_forms: dict = {}
        if cfg.linear:
            if self.max_context <= cfg.q_chunk_size or cfg.q_chunk_size % cfg.block_sizes.kernel_stride:
                raise ValueError(
                    f"max_context {self.max_context} under chunks of {cfg.q_chunk_size}: a slot "
                    "holds more than a chunk, and a chunk is whole strides of the pooling"
                )
            tile = block_tile_plan(cfg.head_dim, self.max_context, cfg.block_sizes)
            self._need_plans(
                tile, "tile for a decode step over chosen blocks",
                f"{cfg.kv_heads} KV heads of {cfg.head_dim} over {self.max_context} rows in "
                f"blocks of {cfg.block_sizes.block_size}",
            )
            self._block_tile = tile or cfg.block_sizes.block_size
            # the chunk's attention's tile, as ``chunk_prefill_forward`` cuts the ring
            self._chunk_tile = chunk_tile(cfg, self.max_context)
            self._sala = (
                init_pooled_cache(cfg, self.num_slots, self.max_context, compute_dtype),
                init_lightning_state(cfg, self.num_slots),
            )
            pallas = self.decode_kernel == "pallas"
            self.block_forms = {
                "decode": "block-tiles-pallas" if pallas else "block-gather-xla",
                "chunk": self.chunk_form, "selection": "xla", "lightning_chunk": "chunked-xla",
                "lightning_step": "xla", "block_t": tile if pallas else 0,
                "most_tiles": block_most_tiles(self.max_context, tile, cfg.block_sizes) if pallas else 0,
            }
        self.pooled_cache_resident_bytes = self._sala[0].nbytes if self._sala else 0
        self.lightning_state_resident_bytes = self._sala[1].nbytes if self._sala else 0
        # kda layers beside grouped-query attention: the delta-rule states,
        # float32, and the tails of the convolutions on q, k and v
        # (``ring_cache.init_kda_state``); empty for every other stack. What
        # the mixer did (always on; stay 0 without it), over layers: the tokens
        # that passed its one-step form (a decode step's live slots) and its
        # chunked form (a chunk's real tokens), the blocks of the chunked form
        # that were solved (a triangular system a block and head), and the
        # bytes of state the equations move (a step every live slot's, there
        # and back; a chunk one slot's, there and back). ``kda_forms`` names the
        # form the step and the chunk take ({} without the stack): the step's is
        # ``kda_step_form``'s ("pallas": one kernel over the stacked states, a
        # live slot's visited once; "xla" off the chip and where a head's state
        # is no whole tile), the chunk's the XLA form on every platform, and the
        # attention layers' are the plain ring's (the step's by
        # ``decode_kernel``, with a plan for the ring or the engine is refused
        # here; the chunk's ``chunk_form``)
        self._kda: tuple = ()
        self.kda_step_tokens = 0
        self.kda_chunk_tokens = 0
        self.kda_blocks_solved = 0
        self.kda_state_bytes_moved = 0
        self.kda_forms: dict = {}
        if cfg.kda:
            if self.max_context < cfg.q_chunk_size:
                raise ValueError(
                    f"max_context {self.max_context} under chunks of {cfg.q_chunk_size}: a "
                    "slot's ring holds a chunk's rows as one block"
                )
            plan = decode_plan(
                cfg.kv_heads, cfg.head_dim, self.max_context, self.cache_k.dtype.itemsize,
                num_slots=1,
            )
            self._need_plans(
                plan, "plan for this stack's ring",
                f"{cfg.kv_heads} KV heads of {cfg.head_dim} over {self.max_context} rows",
            )
            state = init_kda_state(cfg, self.num_slots, compute_dtype)
            self._kda = (state["state"], state["tail"])
            self.kda_forms = {
                "step": kda_step_form(self.decode_kernel, cfg.head_dim), "chunk": "chunked-xla",
                "block": kda.BLOCK, "sub_block": kda.SUB,
                "attention_step": self.decode_kernel, "attention_chunk": self.chunk_form,
            }
        self.kda_state_resident_bytes = self._kda[0].nbytes if self._kda else 0
        self.kda_tail_resident_bytes = self._kda[1].nbytes if self._kda else 0
        # the slots' second kind of state: empty for a stack of attention layers
        self._ssm: tuple = ()
        if cfg.hybrid:
            state = init_ssm_state(cfg, self.num_slots, compute_dtype)
            self._ssm = (state["ssm"], state["conv"])
        self.ssm_state_resident_bytes = sum(x.nbytes for x in self._ssm)
        # CCA's: what each layer's projection keeps of a slot's last token
        self._cca: tuple = ()
        if cfg.cca:
            self._cca = (init_cca_state(cfg, self.num_slots, compute_dtype),)
        self.cca_state_resident_bytes = sum(x.nbytes for x in self._cca)
        # what CCA did (always on; stay 0 without it): tokens that passed its
        # projections, and the bytes of that state the calls read and wrote (a
        # prefill writes one slot's, a decode step reads and writes every slot's)
        self.cca_tokens = 0
        self.cca_state_bytes_moved = 0
        # EVA's: the pooled ring and the pooling under way, beside a
        # ``cache_k`` / ``cache_v`` of one window's rows. ``max_context``
        # bounds the positions and sizes the pooled ring; the window's ring is
        # ``window_size`` rows whatever it is
        self._eva: tuple = ()
        # which form each program's EVA attention takes ({} without it): the
        # decode step's by ``decode_kernel`` ("pallas" | "xla"; the kernel has
        # a plan for both rings or the engine is refused here, never a step
        # that quietly takes the XLA form), the prefill's by the platform and
        # the tiling ("flash" | "xla")
        self.eva_forms: dict = {}
        if cfg.eva:
            window, chunk = cfg.window_size, cfg.chunk_size
            pooled = eva_pooled_rows(cfg, self.max_context)
            self._need_plans(
                eva_plans(cfg.kv_heads, cfg.head_dim, window, chunk, pooled, self.cache_k.dtype.itemsize),
                "plan for EVA's rings at these shapes",
                f"{cfg.kv_heads} KV heads of {cfg.head_dim}, a window of {window} rows "
                f"and {pooled} pooled rows, {window // chunk} a window",
            )
            self.eva_forms = {
                "decode": self.decode_kernel,
                "prefill": eva_prefill_form(window, cfg.head_dim),
            }
            state = init_eva_state(cfg, self.num_slots, self.max_context, compute_dtype)
            self._eva = (state["pool_k"], state["pool_v"], state["stats"])
        self.eva_cache_resident_bytes = sum(x.nbytes for x in self._eva)
        # which form each bucket's whole-prompt causal attention takes, "flash"
        # (the training forward kernel: no scores in memory) | "xla", from the
        # bucket's rows, the heads and ``decode_kernel`` alone; {} where a
        # prefill runs no plain causal attention (EVA, an indexer, sliding
        # layers); and the cold admissions that took the kernel, beside all of
        # them in ``phase_calls["prefill"]``
        heads = causal_prefill_heads(cfg)
        self.prefill_forms: dict = {} if heads is None else {
            b: prefill_form(b, *heads, self.decode_kernel) for b in self.prefill_buckets
        }
        self.prefill_flash_admissions = 0
        # what EVA attention did with its two rings (always on; stay 0 without
        # it): the window's rows and the pooled rows the decode steps read,
        # over layers (a step at position p reads p % window + 1 and p //
        # window * chunks-a-window a layer); the chunks whose pooled row
        # became final (a prefill's whole chunks, a step at a chunk's last
        # position), a slot each; the slots whose position reached a window's
        # edge, where the ring restarts; the bytes of rows and stats the calls
        # moved
        self.eva_local_rows_read = 0
        self.eva_pooled_rows_read = 0
        self.eva_chunks_pooled = 0
        self.eva_window_restarts = 0
        self.eva_cache_bytes_moved = 0

        # after ``keep_expert_choices()``: each token's experts in each layer
        # of the newest prefill or decode step, on the device, [L, tokens, K]
        # int32 (the engine never reads them: a check against a reference does)
        self.expert_choices: Optional[jax.Array] = None
        self._keeps_choices = False
        # after ``keep_row_choices()``: the rows the newest call's indexer chose,
        # on the device: a decode step's [L, S, T] bool, a chunk's last real
        # token's [L, T] (a whole-prompt prefill leaves None)
        self.row_choices: Optional[jax.Array] = None
        self._keeps_rows = False

        cd = compute_dtype
        dkn = self.decode_kernel

        # the first token of each slot's newest admission, on the device: an
        # admission's insert writes it, the decode step reads it where the
        # host has not (``serving_programs``); the slots of admissions that
        # are enqueued and not yet read, in the order the chip finishes them
        self._first = jnp.zeros((self.num_slots,), jnp.int32)
        self._unread: list[Admission] = []
        # admissions whose first token a decode step took on the device,
        # beside all cold admissions in ``phase_calls["prefill"]``
        self.admissions_deferred = 0
        # the token output of the newest decode step, on the device, where the
        # next step finds the tokens of the slots that ride both; the step that
        # ``step_ahead`` enqueued and has not read; and the steps it enqueued
        # while the step before them was unread, beside all steps in
        # ``phase_calls["decode"]``
        counts = (cfg.moe_counts if cfg.num_experts else 0) + bool(self._sala)
        self._prev = jnp.zeros((self.num_slots + counts,), jnp.int32)
        self._ahead: Optional[_Step] = None
        self.steps_ahead = 0

        def programs(chosen: bool, rows: bool = False):
            prefill, decode, admit_insert, carried = serving_programs(
                cfg, compute_dtype=cd, decode_kernel=dkn, chosen=chosen, rows=rows
            )
            beside = len(self._eva) + len(self._index)
            # one compile per prompt bucket (prefill, insert); decode compiles once
            return (
                jax.jit(prefill),
                _DecodeProgram(decode, carried, counts),
                jax.jit(admit_insert, donate_argnums=tuple(range(3 + beside))),
            )

        self._programs = programs
        self._prefill, self._decode, self._admit_insert = programs(False)
        # a prompt admitted in chunks: one program, compiled once
        self._chunk = None
        if self._sala:
            self._chunk_programs = lambda rows: jax.jit(
                state_chunk_program(cfg, compute_dtype=cd, rows=rows, decode_kernel=dkn),
                donate_argnums=(7, 8, 9, 10, 11),
            )
            self._chunk = self._chunk_programs(False)
        elif self._kda:
            self._chunk = jax.jit(
                kda_chunk_program(cfg, compute_dtype=cd, decode_kernel=dkn),
                donate_argnums=(6, 7, 8, 9, 10),
            )
        elif cfg.sparse or cfg.sliding:
            self._chunk_programs = lambda rows: jax.jit(
                chunk_program(cfg, compute_dtype=cd, rows=rows, decode_kernel=dkn),
                donate_argnums=(6, 7, 8, 9),
            )
            self._chunk = self._chunk_programs(False)
        # a slot's pages coming back from the host tier (one compile per row count)
        self._insert = jax.jit(cache_insert, donate_argnums=(0, 1))
        self._state_insert = jax.jit(state_insert, donate_argnums=(0, 1))
        self._cca_insert = jax.jit(cca_state_insert, donate_argnums=(0,))

        # shared-prefix reuse jits (compiled only when the batcher asks): the
        # suffix behind a reused prefix runs the forward a chunk of a prompt
        # runs, over the slot's rows [0, plen), its own rows written in place
        def _suffix(p, tail, plen, count, slot, ck, cv):
            logits, ck, cv, _ = chunk_prefill_forward(
                p, tail, plen, count, slot, ck, cv, None, cfg, compute_dtype=cd, decode_kernel=dkn
            )
            return logits[0], ck, cv

        self._prefix_copy = jax.jit(prefix_copy, donate_argnums=(0, 1))
        self._suffix = jax.jit(_suffix, donate_argnums=(5, 6))

        # KV-tier page-out (compiled only when tiering is on): one slot's
        # ring rows gathered for D2H eviction; ``_insert`` is the way back.
        # ``rows`` is static -- padded to the prefill-bucket grid by
        # :meth:`page_rows` so the compile family stays bounded.
        self._fetch_pages = jax.jit(fetch_pages, static_argnums=(3,))

        # the prompt buckets a cold admission has run (and "chunk"), and how
        # to lower the engine's programs again (``program_texts``)
        self._ran: set = set()
        self._recipes = obs.programs.Recipes()
        obs.programs.register(self)

    def _need_plans(self, plans, what: str, shapes: str) -> None:
        """Under the kernels every ring has a plan or the engine is refused
        here: never a step that quietly takes the XLA form."""
        if self.decode_kernel == "pallas" and not plans:
            raise ValueError(
                f"decode_kernel 'pallas' has no {what} ({shapes}); decode_kernel 'xla' "
                "runs the XLA form"
            )

    def program_recipes(self):
        """How to lower again, at the engine's own shapes, the programs a cold
        admission and a decode step have run so far: ``decode``,
        ``prefill/<bucket>`` and ``insert/<bucket>`` for each bucket a prompt
        has gone through, ``chunk``, and a state's insert (``insert/state``,
        ``insert/cca``); ``obs.programs`` reads each instruction's scope and
        opcode from their texts. Not the continued prefill behind a reused
        prefix, nor the page tier's programs. What is noted holds the jitted
        functions and shapes, not the engine."""
        # shapes alone: the engine's arrays lie on one device, and lowered so
        # a program is the one its jit holds (a compile of its own otherwise)
        shaped = functools.partial(obs.programs.abstract, placed=False)
        sds, note = jax.ShapeDtypeStruct, self._recipes.note
        vec, scalar = sds((self.num_slots,), jnp.int32), sds((), jnp.int32)
        params, first = shaped(self.params), shaped(self._first)
        rings = shaped((self.cache_k, self.cache_v))
        beside = shaped((*self._eva, *self._index))
        state = shaped((*self._ssm, *self._cca, *self._sala, *self._kda))
        prefill, decode, insert, chunk = (
            self._prefill, self._decode, self._admit_insert, self._chunk
        )
        state_insert = self._cca_insert if self._cca else self._state_insert
        drop = int(self._keeps_choices)  # each token's experts come last

        def lower_insert(ids):
            # what a prompt leaves beside its K and V goes into the rings
            # beside them in the one insert, or into a state's own
            tokd, _, ks, vs, *left = jax.eval_shape(prefill, params, ids, scalar)
            left = left[: len(left) - drop] if beside else []
            return insert.lower(*rings, first, *beside, ks, vs, *left, tokd, scalar)

        def lower_state_insert(ids):
            left = jax.eval_shape(prefill, params, ids, scalar)[4:]
            return state_insert.lower(*state, *left[: len(left) - drop], scalar)

        if self.phase_calls["decode"]:
            note("decode", id(decode),
                 lambda: decode.lower(params, vec, vec, *rings, *state, *beside))
        if "chunk" in self._ran and self._sala:
            ids = sds((1, self.cfg.q_chunk_size), jnp.int32)
            note("chunk", id(chunk), lambda: chunk.lower(
                params, ids, scalar, scalar, scalar, scalar, sds((), jnp.bool_), first, *rings,
                *state))
        elif "chunk" in self._ran:
            ids = sds((1, self.cfg.q_chunk_size), jnp.int32)
            note("chunk", id(chunk), lambda: chunk.lower(
                params, ids, scalar, scalar, scalar, sds((), jnp.bool_), first, *rings,
                *(state if self._kda else beside or (None,))))
        for bucket in sorted(b for b in self._ran if b != "chunk"):
            ids = sds((1, bucket), jnp.int32)
            note(f"prefill/{bucket}", id(prefill),
                 lambda ids=ids: prefill.lower(params, ids, scalar))
            if state:
                note("insert/cca" if self._cca else "insert/state", id(state_insert),
                     lambda ids=ids: lower_state_insert(ids))
            note(f"insert/{bucket}", id(insert), lambda ids=ids: lower_insert(ids))
        return self._recipes

    def program_texts(self) -> dict:
        """{program name: compiled text} of ``program_recipes``."""
        return self.program_recipes().texts()

    def keep_expert_choices(self) -> None:
        """From here on a routed model's prefill and decode programs also hand
        back each token's experts in each layer, and the newest call's stay in
        ``expert_choices``. Programs of their own: called before the first
        request, nothing compiles twice."""
        if not self.cfg.num_experts:
            raise ValueError("keep_expert_choices needs routed experts (num_experts > 0)")
        self._keeps_choices = True
        self._prefill, self._decode, self._admit_insert = self._programs(True, self._keeps_rows)

    def keep_row_choices(self) -> None:
        """From here on the decode program and the chunk program of a
        configuration with learned sparse attention also hand back the rows
        their indexer chose, and the newest call's stay in ``row_choices``.
        Programs of their own: called before the first request, nothing
        compiles twice."""
        if not (self.cfg.sparse or self.cfg.blocks):
            raise ValueError(
                "keep_row_choices needs learned sparse attention (index_topk > 0) or a "
                "selection by blocks"
            )
        self._keeps_rows = True
        self._prefill, self._decode, self._admit_insert = self._programs(self._keeps_choices, True)
        self._chunk = self._chunk_programs(True)

    @property
    def device(self):
        """The device the engine's KV cache (and so its jits) live on."""
        return next(iter(self.cache_k.devices()))

    # -- weight residency ---------------------------------------------------

    def _bind(self, leaves, epoch: int, adopt: bool = False) -> None:
        """The one door weights come through: flat leaves (original flatten
        order; float32 masters on the device or the host) become
        ``self.params``, the tree every program of the engine reads. Every
        leaf lands in a fresh buffer in ``compute_dtype``, rounded here once,
        and the engine keeps nothing else of it.

        ``adopt`` (``ServeEngine(adopt_params=True)``: the caller gives the
        tree up, and neither donates nor changes a leaf of it afterwards): a
        leaf that arrives as a device array in ``compute_dtype`` is taken as it
        is, no copy made, so that a tree of which the chip holds one copy and
        not two is served at all; every other leaf is copied as ever."""
        leaves = list(leaves)
        copied = [
            i for i, x in enumerate(leaves)
            if not (adopt and isinstance(x, jax.Array) and x.dtype == self.compute_dtype)
        ]
        if copied:
            fresh = _fresh_copy([leaves[i] for i in copied], self.compute_dtype)
            for i, x in zip(copied, fresh):
                leaves[i] = x
        self.weights_adopted = len(leaves) - len(copied)
        self.params = jax.tree.unflatten(self._treedef, leaves)
        self.weights_epoch = int(epoch)
        self.weight_binds += 1
        self.weights_resident_bytes = sum(
            x.nbytes for x in jax.tree.leaves(self.params)
        )
        obs.count("serve_weight_binds")
        obs.gauge("serve_weights_resident_bytes", self.weights_resident_bytes)

    @property
    def swap_count(self) -> int:
        """Weight trees adopted after the one the engine was built with."""
        return self.weight_binds - 1

    # -- admission ---------------------------------------------------------

    def admit(
        self,
        slot: int,
        prompt: Sequence[int],
        *,
        prefix_src: Optional[int] = None,
        prefix_len: int = 0,
        host_prefix: Optional[tuple] = None,
    ) -> tuple[int, np.ndarray]:
        """Prefill ``prompt`` into ``slot`` and return (first greedy token,
        last-position logits [V] f32). The prompt must fit a compile
        bucket (scheduler-enforced via ``prompt_fits``).

        With ``prefix_src``/``prefix_len`` the first ``prefix_len`` tokens
        are NOT recomputed: their K/V rows are ring-copied from the live
        source slot (bitwise what a cold prefill writes — causal attention
        makes prefix K/V independent of anything after it) and only the
        suffix runs through the model. ``host_prefix=(k, v, plen)`` is the
        cold-tier variant: the prefix K/V pages come from the host prefix
        store (H2D install) instead of a live slot's ring."""
        n = len(prompt)
        from_host = host_prefix is not None and 0 < host_prefix[2] < n
        from_slot = prefix_src is not None and 0 < prefix_len < n
        if not (from_host or from_slot):
            if self.needs_chunks(n):  # the chunks back to back, then the read
                adm = self.admit_begin(slot, prompt)
                while not self.admit_chunk(adm):
                    pass
            else:
                adm = self.admit_enqueue(slot, prompt)
            logits = self._read(adm, row=True)
            return adm.token, logits
        refuse(self.cfg, "prefix_reuse")
        self._bucket_of(n)
        t0 = time.perf_counter()
        if from_host:
            hk, hv, plen = host_prefix
            self.cache_k, self.cache_v = self._insert(
                self.cache_k, self.cache_v,
                jnp.asarray(hk, self.compute_dtype),
                jnp.asarray(hv, self.compute_dtype),
                jnp.int32(slot),
            )
            tok, logits = self._run_suffix(slot, prompt, int(plen))
        else:
            tok, logits = self._admit_suffix(slot, prompt, prefix_src, prefix_len)
        # a continued prefill's routing is not counted, nor are its phases cut
        dt = time.perf_counter() - t0
        self.stage_seconds["prefill"] += dt
        tr = obs.tracer()
        if tr is not None:
            tr.add_span("serve_prefill", t0, t0 + dt, tokens=n)
        return tok, logits

    def _bucket_of(self, n: int) -> int:
        bucket = pick_bucket(n, self.prefill_buckets)
        if bucket is None:
            raise ValueError(
                f"prompt length {n} exceeds max bucket "
                f"{self.prefill_buckets[-1]}"
            )
        return bucket

    def admit_enqueue(self, slot: int, prompt: Sequence[int], positions=None) -> Admission:
        """The front half of a cold admission: the arguments made and the
        prompt's programs enqueued (prefill, the insert that also writes the
        first token into the engine's device vector, a state's insert), and
        nothing read. Until the :class:`Admission` is resolved, by
        ``admit_resolve`` or by the next ``decode_step``, that step takes the
        slot's token from the device, whatever ``tokens`` holds there.
        ``positions``: refused (token ids alone are served)."""
        self._refuse_positions(positions)
        n = len(prompt)
        bucket = self._bucket_of(n)
        self._ran.add(bucket)
        t0 = time.perf_counter()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = np.asarray(prompt, np.int32)
        idsd, nd = jnp.asarray(ids), jnp.int32(n)
        t_args = time.perf_counter()
        tokd, rowd, ks, vs, *left = self._prefill(self.params, idsd, nd)
        if self._keeps_choices:
            self.expert_choices = left.pop()
        # the slot's scalar is made here, while the device runs the
        # prompt: made with the others it holds every prefill's start back
        # by its own host time (a third of a millisecond on the chip)
        if self._eva or self._index:  # the rings beside K and V in the one insert
            self.cache_k, self.cache_v, self._first, *beside = self._admit_insert(
                self.cache_k, self.cache_v, self._first, *self._eva, *self._index, ks, vs,
                *left, tokd, jnp.int32(slot),
            )
            if self._eva:
                self._eva = tuple(beside)
            else:
                self._index = tuple(beside)
        else:
            self.cache_k, self.cache_v, self._first = self._admit_insert(
                self.cache_k, self.cache_v, self._first, ks, vs, tokd, jnp.int32(slot)
            )
        if self._cca:  # what the prompt's last token left CCA's projections
            self._cca = (self._cca_insert(*self._cca, *left, jnp.int32(slot)),)
        elif self._ssm:  # the recurrent state the prompt left, whole
            self._ssm = self._state_insert(*self._ssm, *left, jnp.int32(slot))
        adm = Admission(
            slot=int(slot), tokens=n, tokd=tokd, rowd=rowd,
            state_bytes=sum(x.nbytes for x in left),
            t0=t0, t_args=t_args, t_dispatch=time.perf_counter(),
            form=self.prefill_forms.get(bucket, "xla"),
        )
        self._unread.append(adm)
        return adm

    def _refuse_positions(self, positions) -> None:
        if positions is not None:
            raise ValueError(
                "positions are refused by the serving engine: it admits token ids, whose "
                "three position rows (temporal, height, width) are equal; an image span's "
                "rows differ, and neither the prefills nor the decode step carry them"
            )

    def needs_chunks(self, n: int) -> bool:
        """Is a prompt of ``n`` tokens admitted in chunks (learned sparse
        attention, and no bucket holds it)?"""
        if self.cfg.sliding or self._sala or self._kda:  # every prompt: no whole prompt goes
            return True  # into a ring that wraps, and a state's hand-over has the one path
        return self._chunk is not None and pick_bucket(n, self.prefill_buckets) is None

    def admit_begin(self, slot: int, prompt: Sequence[int], positions=None) -> Admission:
        """A prompt that goes in chunks is given ``slot``; nothing is enqueued.
        Each ``admit_chunk`` then enqueues the next ``q_chunk_size`` tokens."""
        self._refuse_positions(positions)
        n = len(prompt)
        if not self.needs_chunks(n) or n > self.max_context:
            raise ValueError(
                f"prompt length {n}: admitted in chunks only past every bucket and "
                f"within max_context {self.max_context}, under learned sparse attention"
            )
        now = time.perf_counter()
        return Admission(
            slot=int(slot), tokens=n, tokd=None, rowd=None, state_bytes=0,
            t0=now, t_args=now, t_dispatch=now, prompt=np.asarray(prompt, np.int32),
        )

    def admit_chunk(self, adm: Admission) -> bool:
        """Enqueue the next chunk of ``adm``'s prompt over the slot's rows so
        far; nothing of it is read -> whether that was the last. A chunk before
        the last counts its enqueue's seconds to stage ``prefill`` at once; the
        chunk before this one, finished long since (a step has been read
        meanwhile, or this chunk is queued behind it), has its counts read and
        its ``serve_prefill`` span closed here. The last chunk makes ``adm`` an
        admission like any cold one: the next decode step takes its token on
        the device, and ``admit_resolve`` or that step's read finishes it."""
        C, n, plen = self.cfg.q_chunk_size, adm.tokens, adm.rows_done
        count = min(C, n - plen)
        last = plen + count == n
        self._ran.add("chunk")
        t0 = time.perf_counter()
        ids = np.zeros((1, C), np.int32)
        ids[0, :count] = adm.prompt[plen : plen + count]
        args = (
            jnp.asarray(ids), jnp.int32(plen), jnp.int32(count), jnp.int32(adm.slot),
            jnp.asarray(last),
        )
        if self._sala:  # what the chunk's lightning layers read and write of the slot's state
            adm.state_bytes += 2 * self.lightning_state_resident_bytes // self.num_slots
        if self._kda:  # the slot's states and tails, there and back
            adm.state_bytes += 2 * (
                self.kda_state_resident_bytes + self.kda_tail_resident_bytes
            ) // self.num_slots
        t_args = time.perf_counter()
        if self._sala:  # the pooled ring and the states ride with the rings
            tokd, rowd, self._first, self.cache_k, self.cache_v, *rest = self._chunk(
                self.params, *args[:3], jnp.int32(n), *args[3:], self._first, self.cache_k,
                self.cache_v, *self._sala,
            )
            self._sala, rest = tuple(rest[:2]), [None, *rest[2:]]
        elif self._kda:  # the states and the tails ride with the rings
            tokd, rowd, self._first, self.cache_k, self.cache_v, *state = self._chunk(
                self.params, *args, self._first, self.cache_k, self.cache_v, *self._kda,
            )
            self._kda, rest = tuple(state), [None]
        else:
            tokd, rowd, self._first, self.cache_k, self.cache_v, *rest = self._chunk(
                self.params, *args, self._first, self.cache_k, self.cache_v,
                *(self._index or (None,)),
            )
        if self._index:
            self._index = (rest[0],)
        if self._keeps_rows:
            self.row_choices = rest[1]
        t_dispatch = time.perf_counter()
        before, index = adm.chunk, 0 if adm.chunk is None else adm.chunk.index + 1
        adm.chunk = _Chunk(tokd, index, plen, count, t0, t_args, t_dispatch, n)
        adm.rows_done += count
        if before is not None:
            self._close_chunk(before)
        if last:
            adm.tokd, adm.rowd = tokd, rowd
            adm.t0, adm.t_args, adm.t_dispatch = t0, t_args, t_dispatch
            self._unread.append(adm)
        else:
            self.stage_seconds["prefill"] += t_dispatch - t0
            self._count_phases("prefill", ((t0, t_args), (t_args, t_dispatch), None), obs.tracer())
        return last

    def _close_chunk(self, chunk: _Chunk, t_end: Optional[float] = None) -> dict:
        """Read what a chunk left (a routed model's counts; the token is only
        the last chunk's to use), count its work, and close its
        ``serve_prefill`` span over its enqueue (to ``t_end`` for the chunk whose
        token a blocking caller waited for) -> the span's attributes."""
        fetched = np.asarray(chunk.tokd)
        if self._sala:
            attrs = self._count_sala(
                rows_before=chunk.rows_before, count=chunk.count, total=chunk.total,
                tiles=int(fetched[-1]),
            )
            fetched = fetched[:-1]
        else:
            _, attrs = self._split_counts(fetched, 1)
        attrs.update(self._count_dsa(rows_before=chunk.rows_before, count=chunk.count))
        if self._kda:
            attrs.update(self._count_kda(chunk=chunk.count))
        if self._latent_row_bytes:  # the slot's rows so far and the chunk's own
            attrs.update(self._count_latent(
                read=chunk.rows_before + chunk.count, written=chunk.count,
                swa_read=min(chunk.rows_before + chunk.count, self.cache_v.shape[-1]),
            ))
        if self._kinds_row_bytes:
            window = self.cfg.sliding_window_size
            attrs.update(self._count_kinds(
                full=chunk.rows_before + chunk.count, written=chunk.count,
                swa=min(chunk.rows_before, window - 1) + chunk.count,
            ))
        attrs.update(chunk=chunk.index, rows_before=chunk.rows_before)
        self.prefill_chunks += 1
        self.prefill_chunk_tokens += chunk.count
        tr = obs.tracer()
        if tr is not None:
            tr.add_span(
                "serve_prefill", chunk.t0, chunk.t_dispatch if t_end is None else t_end,
                tokens=chunk.count, **attrs,
            )
        return attrs

    def admit_resolve(self, adm: Admission) -> int:
        """The back half, at once: wait for the admission's first token, count
        what its prefill did -> the token, which the caller then passes the
        decode step itself (``adm.t_token``: the instant it was on the host)."""
        self._read(adm)
        return adm.token

    def _read(self, adm: Admission, *, row: bool = False, t_from: Optional[float] = None):
        """Read ``adm``'s first token (and with ``row`` the logits it was taken
        from -> those), add the prefill's work to the counters and its seconds
        to stage ``prefill``: the enqueue, and this read with its counting.
        ``t_from``: where a decode step that fed the token on the device starts
        the wait; the ``serve_prefill`` span then ends with the enqueue, which
        is all of it that is one stretch of time."""
        self._unread.remove(adm)
        t_fetch = adm.t_dispatch if t_from is None else t_from
        fetched = np.asarray(adm.tokd)
        logits = np.asarray(adm.rowd) if row else None
        adm.t_token = time.perf_counter()
        adm.token = int(fetched[0])
        tr = obs.tracer()
        if adm.chunk is not None:  # a prompt admitted in chunks: its last chunk's span
            self._close_chunk(adm.chunk, adm.t_token if t_from is None else None)
            adm.chunk = None
        else:
            _, attrs = self._split_counts(fetched, 1)
            attrs.update(self._count_ssm(adm.tokens, adm.state_bytes))
            attrs.update(self._count_cca(adm.tokens, adm.state_bytes))
            attrs.update(self._count_latent(read=0, written=adm.tokens))
            attrs.update(self._count_eva(prompt=adm.tokens))
            if self._index:
                attrs.update(self._count_dsa(rows_before=0, count=adm.tokens, whole=True))
        t1 = time.perf_counter()
        self.stage_seconds["prefill"] += (adm.t_dispatch - adm.t0) + (t1 - t_fetch)
        self.prefill_flash_admissions += adm.form == "flash"
        if tr is not None and adm.prompt is None:
            tr.count(f"serve_prefill_{adm.form}")
            tr.add_span(
                "serve_prefill", adm.t0, t1 if t_from is None else adm.t_dispatch,
                tokens=adm.tokens, **attrs,
            )
        self._count_phases(
            "prefill",
            ((adm.t0, adm.t_args), (adm.t_args, adm.t_dispatch), (t_fetch, adm.t_token)),
            tr,
        )
        return logits

    def _count_phases(self, stage: str, bounds: tuple, tr) -> None:
        """One call's three phases, ``bounds`` each one's start and end (None:
        the call had no such phase; it counts as a call where it fetched) ->
        the engine's counters, and spans where ``tr`` is an armed tracer: they
        tile the front of a blocking call's ``serve_prefill`` /
        ``serve_decode``, and leave between dispatch and fetch whatever else
        was read meanwhile."""
        total = self.phase_seconds[stage]
        for phase, bound in zip(_PHASES, bounds):
            if bound is None:
                continue
            total[phase] += bound[1] - bound[0]
            if tr is not None:
                tr.add_span(f"serve_{phase}", *bound, stage=stage)
        self.phase_calls[stage] += bounds[-1] is not None

    def _count_ssm(self, tokens: int, state_bytes: int) -> dict:
        """Add one call's Mamba-2 work to the engine's counters -> the same
        as span attributes (nothing for a model without mixers)."""
        if not self._ssm:
            return {}
        self.ssm_tokens += tokens
        self.ssm_state_bytes_moved += state_bytes
        return {"ssm_tokens": tokens, "ssm_state_bytes": state_bytes}

    def _count_cca(self, tokens: int, state_bytes: int) -> dict:
        """Add one call's CCA work to the engine's counters -> the same as
        span attributes (nothing for a model without CCA)."""
        if not self._cca:
            return {}
        self.cca_tokens += tokens
        self.cca_state_bytes_moved += state_bytes
        return {"cca_tokens": tokens, "cca_state_bytes": state_bytes}

    def _count_eva(self, lens=None, prompt: int = 0) -> dict:
        """Add one call's traffic with EVA's two rings to the engine's
        counters -> the same as span attributes (nothing for a model without
        EVA attention): ``eva_local_rows`` and ``eva_pooled_rows`` over layers,
        ``eva_bytes`` what the call moved. A decode step over the live slots'
        positions ``lens`` reads, a layer, rows [0, p % window] of the window's
        ring (its own among them, which it wrote) and the pooled rows of the
        windows before p, writes one pooled row and reads and writes the
        slot's stats; a prefill of ``prompt`` tokens writes the rows of the
        prompt's last window, its pooled rows and one slot's stats."""
        if not self._eva:
            return {}
        cfg = self.cfg
        layers, window, chunk = cfg.num_hidden_layers, cfg.window_size, cfg.chunk_size
        row = 2 * cfg.kv_heads * cfg.head_dim * self.cache_k.dtype.itemsize  # a K and a V
        stats = self._eva[2].nbytes // self.num_slots  # a slot's, every layer's
        if lens is None:
            local, pooled = prompt % window, -(-prompt // chunk)
            self.eva_chunks_pooled += prompt // chunk
            moved = layers * (local + pooled) * row + stats
        else:
            held = np.asarray(lens)
            held = held[held > 0]
            local = int((held % window + 1).sum())
            pooled = int((held // window * cfg.eva_chunks_per_window).sum())
            self.eva_local_rows_read += layers * local
            self.eva_pooled_rows_read += layers * pooled
            self.eva_chunks_pooled += int(np.count_nonzero(held % chunk == chunk - 1))
            self.eva_window_restarts += int(np.count_nonzero(held % window == 0))
            moved = layers * (local + pooled + held.size) * row + 2 * held.size * stats
        self.eva_cache_bytes_moved += moved
        return {
            "eva_local_rows": layers * local, "eva_pooled_rows": layers * pooled, "eva_bytes": moved,
        }

    def _count_dsa(self, lens=None, rows_before: int = 0, count: int = 0, whole: bool = False) -> dict:
        """Add one call's indexing and attention under the selection to the
        engine's counters -> the same as span attributes (nothing without
        learned sparse attention): ``dsa_rows_scored`` and ``dsa_rows_selected``
        over layers. A decode step over the live slots' positions ``lens``
        scores each slot's min(p + 1, T) live rows and chooses min(index_topk,
        those); a prefill of ``count`` tokens behind ``rows_before`` rows (a
        chunk, or with ``whole`` a whole prompt in its bucket, which scores
        nothing where the bucket holds no more than ``index_topk`` rows) does
        the same for each of its queries, rows_before + i + 1 live rows for the
        i-th. The bytes are what the programs read of the rings: a slot's live
        index rows and live K and V rows once a step or a chunk."""
        if not self._index:
            return {}
        cfg = self.cfg
        layers, topk = self._index[0].shape[0], cfg.index_topk  # the layers under an indexer
        if lens is None:
            live = rows_before + 1 + np.arange(count, dtype=np.int64)
            rows_read = rows_before + count
            if whole and pick_bucket(count, self.prefill_buckets) <= topk:
                scored = 0
            else:
                scored = int(live.sum())
        else:
            held = np.asarray(lens, np.int64)
            live = np.minimum(held[held > 0] + 1, self.max_context)
            scored = rows_read = int(live.sum())
        selected = int(np.minimum(live, topk).sum())
        item = self.cache_k.dtype.itemsize
        index_bytes = layers * (rows_read if scored else 0) * cfg.index_head_dim * item
        row = cfg.latent_row_dim if cfg.latent else 2 * cfg.kv_heads * cfg.head_dim
        kv_bytes = layers * rows_read * row * item
        self.dsa_rows_scored += layers * scored
        self.dsa_rows_selected += layers * selected
        self.dsa_index_bytes_read += index_bytes
        self.dsa_kv_bytes_read += kv_bytes
        return {"dsa_rows_scored": layers * scored, "dsa_rows_selected": layers * selected}

    def _count_sala(self, lens=None, rows_before: int = 0, count: int = 0, total: int = 0,
                    tiles: int = 0) -> dict:
        """Add one call's lightning mix and its attention under the selection
        by blocks to the engine's counters -> the same as span attributes, each
        over layers (its callers ask only for such a stack). A decode step over
        the live slots' positions ``lens``: a query a slot at position p, p + 1
        rows behind it; a chunk of ``count`` tokens behind ``rows_before`` rows
        of a prompt of ``total``: a query a token. A query scores the windows
        that have closed before it, chooses min(topk, its blocks) blocks a KV
        head and reads their rows up to its own (every row up to its own under
        ``dense_len``); ``tiles`` is what the program counted."""
        cfg, sizes = self.cfg, self.cfg.block_sizes
        bs, ls, ll = sizes.block_size, cfg.num_attention_layers, cfg.num_lightning_layers
        slot_state = self.lightning_state_resident_bytes // self.num_slots
        if lens is None:
            at = rows_before + np.arange(count, dtype=np.int64)
            dense = np.full(count, total < sizes.dense_len)
            tokens, state_bytes = count, 2 * slot_state
            live_tiles = ls * -(-(rows_before + count) // self._chunk_tile)
        else:
            at = np.asarray(lens, np.int64)
            at = at[at > 0]
            dense = at + 1 < sizes.dense_len
            tokens, state_bytes = at.size, 2 * self.lightning_state_resident_bytes
            live_tiles = ls * cfg.kv_heads * int((-(-at // self._block_tile)).sum())
        seen = np.maximum((at - (sizes.kernel_size - 1)) // sizes.kernel_stride + 1, 0)
        seen = np.where(dense, 0, seen)
        blocks = at // bs + 1
        chosen = np.where(dense, blocks, np.minimum(blocks, sizes.topk))
        # the chosen blocks are whole but the query's own, which ends with it
        rows = np.where(dense, at + 1, (chosen - 1) * bs + at % bs + 1)
        # what the queries read between them: a step's slots each their own, a
        # chunk's queries the slot's one set of rows and of pooled keys
        keys, distinct = int(seen.sum()), int(rows.sum())
        if lens is None and count:
            keys, distinct = int(seen.max()), min(distinct, rows_before + count)
        attrs = {
            "lightning_tokens": ll * tokens,
            "lightning_state_bytes": state_bytes,
            "pooled_keys_scored": ls * cfg.kv_heads * int(seen.sum()),
            "pooled_keys_read": ls * cfg.kv_heads * keys,
            "block_rows_distinct": ls * cfg.kv_heads * distinct,
            "blocks_chosen": ls * cfg.kv_heads * int(chosen.sum()),
            "block_rows_read": ls * cfg.kv_heads * int(rows.sum()),
            "block_tiles_read": tiles, "block_tiles_live": live_tiles,
            "dense_len_calls": int(dense.sum()) if lens is not None else int(dense[:1].sum()),
            "block_form": self.block_forms["decode" if lens is not None else "chunk"],
        }
        self.lightning_tokens += attrs["lightning_tokens"]
        self.lightning_state_bytes_moved += state_bytes
        for name in ("pooled_keys_scored", "blocks_chosen", "block_rows_read", "block_tiles_read",
                     "block_tiles_live", "dense_len_calls"):
            setattr(self, name, getattr(self, name) + attrs[name])
        return attrs

    def _count_kda(self, step: int = 0, chunk: int = 0) -> dict:
        """Add one call's kda work to the engine's counters -> the same as
        span attributes, each over layers (its callers ask only for a stack
        with the mixer): a decode step over ``step`` live slots, or a chunk of
        ``chunk`` real tokens. The bytes are what the equations move: each
        token of a step its slot's state there and back, a chunk its slot's
        once."""
        layers = self.cfg.num_kda_layers
        slot_state = self.kda_state_resident_bytes // self.num_slots  # every kda layer's
        blocks = layers * self.cfg.num_attention_heads * -(-chunk // kda.BLOCK)
        moved = 2 * slot_state * (1 if chunk else step)
        self.kda_step_tokens += layers * step
        self.kda_chunk_tokens += layers * chunk
        self.kda_blocks_solved += blocks
        self.kda_state_bytes_moved += moved
        return {
            "kda_step_tokens": layers * step, "kda_chunk_tokens": layers * chunk,
            "kda_blocks_solved": blocks, "kda_state_bytes": moved,
            "kda_form": self.kda_forms["chunk" if chunk else "step"],
        }

    def _count_latent(self, read: int, written: int, swa_read: int = 0) -> dict:
        """Add one call's traffic with the latent ring to the engine's
        counters: ``read`` and ``written`` rows of one layer's pages, the
        same in every layer -> the same as span attributes, ``latent_rows``
        the rows the call touched (a decode step writes one of those it
        reads) and ``latent_bytes`` what it moved (nothing for a model
        without a latent cache)."""
        if not self._latent_row_bytes:
            return {}
        layers = self.cfg.num_full_layers
        moved = layers * (read + written) * self._latent_row_bytes
        self.latent_rows_read += layers * read
        self.latent_bytes_moved += moved
        attrs = {"latent_rows": layers * max(read, written), "latent_bytes": moved}
        if self.swa_cache_resident_bytes:  # the sliding layers' ring: ``swa_read`` rows of it
            swa = self.cfg.num_sliding_layers
            self.swa_rows_read += swa * swa_read
            self.swa_bytes_moved += (
                swa * (swa_read + written) * self.cfg.sliding_row_dim * self.cache_v.dtype.itemsize
            )
            attrs["swa_rows"] = swa * swa_read
        return attrs

    def _count_kinds(self, full: int, swa: int, written: int) -> dict:
        """Add one call's traffic with the rings by kind of a grouped-query
        stack with sliding layers to the engine's counters: ``full`` distinct
        rows of a full layer's pages and ``swa`` of a sliding layer's that the
        equations read (a slot's live rows; its window's rows), ``written`` the
        rows the call wrote a layer -> the same over layers as span attributes
        (``full_rows``, ``swa_rows``). Its callers ask only for such a stack."""
        lf, lw = self.cfg.num_full_layers, self.cfg.num_sliding_layers
        self.full_rows_read += lf * full
        self.swa_rows_read += lw * swa
        self.kinds_bytes_moved += (lf * (full + written) + lw * (swa + written)) * self._kinds_row_bytes
        return {"full_rows": lf * full, "swa_rows": lw * swa}

    def _split_counts(self, fetched: np.ndarray, n: int) -> tuple[np.ndarray, dict]:
        """One program's fetched token output -> (its ``n`` tokens, span
        attributes): a routed model's counts follow the tokens (three, and a
        fourth where the layer holds a share of the experts) and are added to
        the engine's counters here."""
        if fetched.size == n:
            return fetched, {}
        pairs, hit, busiest, *everywhere = (int(x) for x in fetched[n:])
        self.moe_pairs += pairs
        self.moe_experts_hit += hit
        self.moe_max_pairs += busiest
        attrs = {"moe_pairs": pairs, "moe_experts_hit": hit, "moe_max_pairs": busiest}
        # a layer that holds every expert reports three: all pairs are its own
        self.moe_pairs_all += everywhere[0] if everywhere else pairs
        if everywhere:
            attrs["moe_pairs_all"] = everywhere[0]
        return fetched[:n], attrs

    def _admit_suffix(
        self, slot: int, prompt: Sequence[int], src: int, plen: int
    ) -> tuple[int, np.ndarray]:
        self.cache_k, self.cache_v = self._prefix_copy(
            self.cache_k, self.cache_v,
            jnp.int32(src), jnp.int32(slot), jnp.int32(plen),
        )
        return self._run_suffix(slot, prompt, plen)

    def _run_suffix(
        self, slot: int, prompt: Sequence[int], plen: int
    ) -> tuple[int, np.ndarray]:
        """Continued prefill over ``slot`` whose ring already holds the
        first ``plen`` rows (live-slot copy or tier install)."""
        suffix = np.asarray(prompt[plen:], np.int32)
        ns = int(suffix.size)
        sb = pick_bucket(ns, self.prefill_buckets)
        tail = np.zeros((1, sb), np.int32)
        tail[0, :ns] = suffix
        logits, self.cache_k, self.cache_v = self._suffix(
            self.params, jnp.asarray(tail), jnp.int32(plen), jnp.int32(ns), jnp.int32(slot),
            self.cache_k, self.cache_v,
        )
        row = np.asarray(logits)
        return int(row.argmax()), row

    def prompt_fits(self, n: int) -> bool:
        if self._chunk is not None:  # past the buckets it goes in chunks
            return 0 < n <= self.max_context
        return pick_bucket(n, self.prefill_buckets) is not None

    # -- KV-tier page transfers ---------------------------------------------

    def page_rows(self, rows: int) -> int:
        """Static transfer row count for ``rows`` live ring rows: padded
        up the prefill-bucket grid (bounded compile family; padding rows
        carry a previous tenant's masked entries, which restore rewrites
        verbatim — harmless by the same lens-mask invariant, see
        ``ring_cache.ring_live_rows``)."""
        if not 0 < rows <= self.max_context:
            raise ValueError(
                f"rows {rows} outside (0, {self.max_context}]"
            )
        return pick_bucket(rows, self.prefill_buckets) or self.max_context

    def fetch_slot_pages(self, slot: int, rows: int) -> tuple:
        """Start an async D2H gather of ``slot``'s leading ``rows`` ring
        rows. Returns device arrays (the slot's pages cut to rows', rows'
        bucket-padded) with a host copy already in flight — the caller
        materializes them with ``np.asarray`` on a LATER scheduler
        iteration so the transfer overlaps the next decode step instead
        of blocking the loop. The gather is by value: the slot can be
        re-tenanted immediately."""
        refuse(self.cfg, "page_out")
        t0 = time.perf_counter()
        pk, pv = self._fetch_pages(
            self.cache_k, self.cache_v, jnp.int32(slot), self.page_rows(rows)
        )
        for a in (pk, pv):
            try:
                a.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # backend without async D2H: np.asarray still works
        self.stage_seconds["page_out"] += time.perf_counter() - t0
        return pk, pv

    def install_slot_pages(self, slot: int, k: np.ndarray, v: np.ndarray) -> None:
        """Page a slot's ring rows back H2D (tier restore): rows [0, R)
        of ``slot`` are rewritten from the host arrays. Dispatch is
        async — the next decode step queues behind it on-stream, so the
        scheduler thread never blocks on the transfer."""
        refuse(self.cfg, "page_in")
        t0 = time.perf_counter()
        self.cache_k, self.cache_v = self._insert(
            self.cache_k, self.cache_v,
            jnp.asarray(k, self.compute_dtype),
            jnp.asarray(v, self.compute_dtype),
            jnp.int32(slot),
        )
        self.stage_seconds["page_in"] += time.perf_counter() - t0

    # -- decode ------------------------------------------------------------

    def decode_step(
        self, tokens: np.ndarray, lens: np.ndarray
    ) -> tuple[np.ndarray, jax.Array]:
        """One greedy token per slot. ``tokens``/``lens`` are dense [S]
        host arrays (inactive slots pass 0s; their ring writes land in
        masked positions and are overwritten on the slot's next tenancy).
        Returns (next tokens [S] np.int32, logits [S, V] on device).

        A slot admitted by ``admit_enqueue`` and not yet resolved takes its
        token from the device, whatever ``tokens`` holds there, and its
        admission is read here, between the step's dispatch and the step's own
        read (``Admission.token``, ``t_token``): the step's ``fetch`` phase and
        ``stage_seconds["decode"]`` start again after those reads."""
        step, logits = self._enqueue_step(tokens, lens)
        return self._finish_step(step, step), logits

    def step_ahead(
        self, tokens: Optional[np.ndarray] = None, lens: Optional[np.ndarray] = None
    ) -> Optional[np.ndarray]:
        """A loop's one decode call an iteration: ``decode_step`` whose read
        lags a step. It enqueues the step over ``tokens`` / ``lens`` (None: no
        step), *then* reads the step the call before this one enqueued -> that
        step's tokens [S], or None where no step was waiting to be read. The
        chip finds the new step queued when the earlier one ends.

        ``tokens[slot]`` is ``PREV_TOKEN_ON_DEVICE`` for a slot whose token the
        unread step is computing: the program takes it from that step's output
        on the device. The admissions enqueued since the last step are fed
        theirs as in ``decode_step``; they lie, on the device, behind the step
        this call reads and before the one it enqueues, and are read by the
        *next* call, before the tokens of the step that fed them and after
        those of the step they lie behind: every read in the order the chip
        finishes, none a wait for a program enqueued after what it reads.

        The call's ``serve_decode`` span and ``stage_seconds["decode"]`` hold
        the ``args`` and ``dispatch`` of the step enqueued and the ``fetch`` of
        the step read; the span's attributes, the engine's counters and
        ``phase_calls["decode"]`` are the step's that was read (a call that
        reads none has its two phases, no span and no count)."""
        read = self._ahead
        self._ahead = None
        if tokens is None:
            return None if read is None else self._finish_step(read, None)
        # a loop reads tokens: the logits need not outlive the program
        self._ahead, _ = self._enqueue_step(tokens, lens)
        if read is not None:
            self.steps_ahead += 1
            obs.count("serve_steps_ahead")
            return self._finish_step(read, self._ahead)
        step = self._ahead
        self.stage_seconds["decode"] += step.t_dispatch - step.t0
        self.decode_bounds = (step.t0, step.t_dispatch)
        self._count_phases(
            "decode", ((step.t0, step.t_args), (step.t_args, step.t_dispatch), None),
            obs.tracer(),
        )
        return None

    def _enqueue_step(self, tokens: np.ndarray, lens: np.ndarray) -> tuple[_Step, jax.Array]:
        """Make a decode step's arguments and enqueue its program; nothing is
        read -> (the step, its logits [S, V] on the device). The slots of the
        admissions enqueued since the step before take their tokens from
        ``first``."""
        t0 = time.perf_counter()
        fed = [adm for adm in self._unread if not adm.fed]
        if fed:  # their tokens are where the program finds them: on the device
            tokens = np.array(tokens, np.int32)
            tokens[[adm.slot for adm in fed]] = FIRST_TOKEN_ON_DEVICE
        tokensd, lensd = jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32)
        t_args = time.perf_counter()
        tok, logits, self.cache_k, self.cache_v, *state = self._decode(
            self.params, tokensd, lensd, self.cache_k, self.cache_v,
            *self._ssm, *self._cca, *self._eva, *self._index, *self._sala, *self._kda,
            first=self._first, prev=self._prev,
        )
        self._prev = tok
        if self._keeps_rows:
            self.row_choices = state.pop()
        if self._keeps_choices:
            self.expert_choices = state.pop()
        if self._sala:
            self._sala = tuple(state)
        elif self._kda:
            self._kda = tuple(state)
        elif self._eva:
            self._eva = tuple(state)
        elif self._index:
            self._index = tuple(state)
        else:
            self._ssm, self._cca = tuple(state[: len(self._ssm)]), tuple(state[len(self._ssm):])
        if fed:
            for adm in fed:
                adm.fed = True
            self.admissions_deferred += len(fed)
            obs.count("serve_admissions_deferred", len(fed))
        step = _Step(
            tokd=tok, lens=np.array(lens, np.int32), fed=fed,
            t0=t0, t_args=t_args, t_dispatch=time.perf_counter(),
        )
        return step, logits

    def _finish_step(self, read: _Step, enqueued: Optional[_Step]) -> np.ndarray:
        """The back of a decode call whose front enqueued ``enqueued`` (None:
        no step): read the admissions ``read`` fed on the device, which lie
        before it there, each a wait for its own programs and no more
        (``Admission.token``, ``t_token``), then ``read``'s tokens; count what
        that step did, and close the call's ``serve_decode`` span over
        ``enqueued``'s ``args`` and ``dispatch`` and the step's own ``fetch``,
        which starts after the admissions' reads, as ``stage_seconds["decode"]``
        does -> the tokens [S]."""
        if enqueued is None:
            t0 = t_dispatch = time.perf_counter()
        else:
            t0, t_dispatch = enqueued.t0, enqueued.t_dispatch
        t_fetch = t_dispatch
        for adm in read.fed:
            if adm.token is None:  # else ``admit_resolve`` has read it
                self._read(adm, t_from=t_fetch)
                t_fetch = time.perf_counter()
        fetched = np.asarray(read.tokd)
        t_fetched = time.perf_counter()
        if self._sala:  # the tiles the step's attention held ride behind the tokens
            tok, moe = fetched[: self.num_slots], self._count_sala(read.lens, tiles=int(fetched[-1]))
        else:
            tok, moe = self._split_counts(fetched, self.num_slots)
        lens = read.lens
        held = lens[lens > 0]
        moe.update(self._count_ssm(held.size, 2 * self.ssm_state_resident_bytes))
        moe.update(self._count_cca(held.size, 2 * self.cca_state_resident_bytes))
        if self._kda:
            moe.update(self._count_kda(step=held.size))
        if self._latent_row_bytes:
            # a live slot's rows [0, lens] (the ring's T once it has wrapped),
            # the step's own among them
            moe.update(self._count_latent(
                read=int(np.minimum(held + 1, self.max_context).sum()), written=held.size,
                swa_read=int(np.minimum(held + 1, self.cfg.sliding_window_size).sum()),
            ))
        moe.update(self._count_eva(lens))
        if self._index:
            moe.update(self._count_dsa(lens))
        if self._kinds_row_bytes:
            moe.update(self._count_kinds(
                full=int(np.minimum(held + 1, self.max_context).sum()), written=held.size,
                swa=int(np.minimum(held + 1, self.cfg.sliding_window_size).sum()),
            ))
        t1 = time.perf_counter()
        # the step's own seconds: not those of the admissions read inside it
        self.stage_seconds["decode"] += (t1 - t0) - (t_fetch - t_dispatch)
        self.decode_bounds = (t0, t1)
        tr = obs.tracer()
        if tr is not None:
            tr.count(f"serve_decode_kernel_{self.decode_kernel}")
            # what the step's attention read: the cache rows of the live
            # slots (``lens`` is 0 for an empty slot)
            tr.add_span("serve_decode", t0, t1, rows=int(lens.sum()), slots=held.size, **moe)
        front = (None, None) if enqueued is None else (
            (enqueued.t0, enqueued.t_args), (enqueued.t_args, enqueued.t_dispatch)
        )
        self._count_phases("decode", (*front, (t_fetch, t_fetched)), tr)
        return tok

    def decode_plan_stats(self) -> dict:
        """Which form of ``odtp_paged_decode_attn`` the engine's shapes take
        (``decode_kernels.decode_plan``: KV heads, ring rows and slots a grid
        step; EVA's pooled ring's beside the window's) and the grid steps
        that makes a decode step over all attention layers. From shapes
        alone, so always there (``GET /stats``); zeros where
        that kernel does not run: the XLA path, a latent ring, which says its
        own kernel's tile and the rows a slot's step hands back of it
        (``decode_plan_mla_block_t``, ``decode_plan_mla_rows_written_back``; a
        stack with sliding latent layers ``..._swa_...`` too)."""
        cfg = self.cfg
        layers, S, Nkv, Dh, T = self.cache_k.shape
        size = self.cache_k.dtype.itemsize
        none = DecodePlan(0, 0, 0)
        rings = [(none, T)]
        if self.decode_kernel == "pallas" and cfg.eva:
            pooled_rows = self._eva[0].shape[-1]
            plans = eva_plans(Nkv, Dh, cfg.window_size, cfg.chunk_size, pooled_rows, size)
            rings = list(zip(plans or (none, none), (T, pooled_rows)))
        elif self._sala:
            # a decode step over chosen blocks (``odtp_block_decode_attn``): a grid
            # step a tile, ``most_tiles`` of them a slot and KV head, those
            # behind the last tile that holds a chosen block skipped
            form = self.block_forms
            return {
                "decode_plan_heads": float(bool(form["block_t"])),
                "decode_plan_block_t": float(form["block_t"]),
                "decode_plan_block_diagonal": 0.0, "decode_plan_slots": float(bool(form["block_t"])),
                "decode_plan_most_tiles": float(form["most_tiles"]),
                "decode_grid_steps": float(layers * S * Nkv * form["most_tiles"]),
                "pooled_ring_rows": float(self._sala[0].shape[-1]),
                "pooled_ring_bytes": float(self._sala[0].nbytes),
                "lightning_state_bytes": float(self._sala[1].nbytes),
            }
        elif self.decode_kernel == "pallas" and not cfg.latent:
            # under a selection (learned sparse attention) and over rings by
            # kind a step is one slot's
            one_slot = cfg.sparse or cfg.sliding or cfg.kda  # (a step that writes live slots alone)
            plan = decode_plan(Nkv, Dh, T, size, num_slots=1 if one_slot else S)
            rings = [(plan or none, T)]
        plan = rings[0][0]
        out = {
            "decode_plan_heads": float(plan.heads),
            "decode_plan_block_t": float(plan.block_t),
            "decode_plan_block_diagonal": float(plan.block_diagonal),
            "decode_plan_slots": float(plan.slots),
            "decode_grid_steps": float(layers * sum(
                math.prod(p.grid(S, Nkv, rows)) for p, rows in rings if p.heads
            )),
        }
        if self._kda:  # what the kda layers' states and their convolutions' tails hold
            out["kda_state_bytes"] = float(self.kda_state_resident_bytes)
            out["kda_tail_bytes"] = float(self.kda_tail_resident_bytes)
        if cfg.eva:
            out["eva_pooled_plan_heads"] = float(rings[-1][0].heads)
            out["eva_pooled_plan_block_t"] = float(rings[-1][0].block_t)
        if self.kind_forms:
            # each kind of grouped-query layer's pair of rings, its plan of
            # ``odtp_paged_decode_attn`` (zeros: the XLA form) and the grid steps
            # it makes a decode step: a sliding layer's grid is the tiles a
            # window can cross, whatever its ring's length
            steps = 0.0
            for kind, ring in (("full", self.cache_k), ("swa", self.cache_v)):
                form = self.kind_forms["full" if kind == "full" else "sliding"]
                out[f"decode_plan_{kind}_block_t"] = float(form["block_t"])
                out[f"decode_plan_{kind}_heads"] = float(form["heads"])
                out[f"{kind}_ring_rows"] = float(ring.shape[-1])
                out[f"{kind}_ring_bytes"] = float(ring.nbytes)
                if form["block_t"]:
                    tiles = ring.shape[-1] // form["block_t"]
                    if kind == "swa":
                        tiles = -(-(cfg.sliding_window_size - 1) // form["block_t"]) + 1
                    steps += ring.shape[0] * S * (Nkv // form["heads"]) * tiles
            out["decode_grid_steps"] = steps
            return out
        latent_rings = [("mla", self.cache_k, "full")] if cfg.latent else []
        if self.latent_forms:
            latent_rings.append(("swa", self.cache_v, "sliding"))
        for kind, ring, form in latent_rings:
            # each kind of latent layer's ring, its tile of ``odtp_mla_decode_attn``
            # (0: the XLA form), all the heads of a slot a grid step, and the
            # ring rows a slot's step hands back of the tile that holds its row
            if self.latent_forms:
                block_t = self.latent_forms[form]["block_t"]
            else:  # one kind of latent layer: a ring without a tile keeps the XLA form
                block_t = self.decode_kernel == "pallas" and mla_decode_plan(
                    ring.shape[3], cfg.kv_lora_rank, ring.shape[4]
                )
            out[f"decode_plan_{kind}_block_t"] = float(block_t)
            out[f"decode_plan_{kind}_rows_written_back"] = float(mla_rows_written_back(block_t))
            out[f"{kind}_ring_rows"] = float(ring.shape[-1])
            out[f"{kind}_ring_bytes"] = float(ring.nbytes)
            if block_t:
                out["decode_grid_steps"] += float(ring.shape[0] * S * (ring.shape[-1] // block_t))
        return out

    # -- weight hot-swap ---------------------------------------------------

    def staleness(self) -> int:
        """Outer rounds the serving weights lag the trainer's masters."""
        if self.epoch_fn is None:
            return 0
        return max(0, int(self.epoch_fn()) - self.weights_epoch)

    def maybe_swap(self) -> bool:
        """Adopt the trainer's current master snapshot when staleness
        exceeds ``max_stale_rounds``. Called between decode steps, so no
        request is ever mid-forward across a rebind; the KV cache is not
        touched (pinned by tests/test_serve.py)."""
        if self.snapshot_fn is None:
            return False
        if self.staleness() <= self.max_stale_rounds:
            return False
        t0 = time.perf_counter()
        epoch, blobs, codec_name = self.snapshot_fn()
        if epoch <= self.weights_epoch:
            return False  # raced an in-flight round; keep current weights
        self.install_wire(epoch, blobs, codec_name)
        dt = time.perf_counter() - t0
        self.swap_seconds += dt
        self.stage_seconds["swap"] += dt
        obs.count("serve_weight_swaps")
        obs.gauge("serve_last_swap_ms", dt * 1e3)
        return True

    def install_wire(self, epoch: int, blobs, codec_name: str) -> None:
        """Decode a codec-encoded master snapshot and rebind the weights."""
        codec = get_codec(codec_name)
        if len(blobs) != len(self._shapes):
            raise ValueError(
                f"snapshot has {len(blobs)} leaves, engine expects "
                f"{len(self._shapes)}"
            )
        leaves = []
        for (payload, meta, shape), want in zip(blobs, self._shapes):
            if tuple(shape) != want:
                raise ValueError(f"snapshot leaf shape {shape} != {want}")
            size = int(np.prod(shape)) if shape else 1
            leaves.append(
                np.asarray(codec.decode(payload, (size,), meta), np.float32).reshape(shape)
            )
        self._bind(leaves, epoch)

    def install_params(self, epoch: int, params) -> None:
        """Direct (uncompressed) rebind — tests and static-weight mode."""
        self._bind(jax.tree.leaves(params), epoch)
