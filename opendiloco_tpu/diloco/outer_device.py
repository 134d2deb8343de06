"""Device-resident outer plane: master + Nesterov momentum in HBM.

The reference keeps the DiLoCo master and outer-optimizer state on host
purely as a hivemind ``offload_optimizer`` artifact of GPU-memory-poor
workers (open_diloco/hivemind_diloco.py:399-400). On TPU the master fits
HBM, so ``outer_placement=device`` moves the whole outer data plane onto
the mesh:

  pseudo-gradient   pg  = master - params          one fused jit op
  outer Nesterov    buf = m*buf + g                one fused, DONATED jit
                    p  -= lr*(g + m*buf)           op at HBM bandwidth

Donation replaces the host path's clone-then-rebind double copies (the
old buffers are handed to XLA for reuse instead of being copied for the
serve thread), and the master/momentum never cross the host boundary.
The D2H boundary transfer shrinks to wire width: for the plain ``fp16``
codec the pseudo-gradient is cast to float16 INSIDE jit (f16 round-trip
is idempotent, so the bytes that later ride the wire are unchanged — see
``compression.device_wire_dtype``) and the host fetch moves half-width
bytes. The H2D return carries only the averaged pseudo-gradient; the
apply runs on device.

A blocking boundary crosses the host in *pieces* (``cut_pieces``: runs of
whole leaves, large ones first): the fetch hands each piece on as its last
shard lands (``pseudo_grad(deliver=...)``), the optimizer all-reduces it, and
``PutBack`` has its average back on the devices while later pieces are still
arriving. The two device programs -- one pseudo-gradient jit before, one
donated apply after -- see all leaves at once, as they always did.

Thread contract: every mutating entry point takes ``self.lock`` (an
RLock) around the donating jit call AND the rebind, and the serve
thread's lazy host snapshot (``host_state``) holds the same lock while
it fetches — a donated buffer is deleted at call time, so a fetch racing
a donation would read freed memory. The DiLoCoOptimizer wraps its
(plane mutation, epoch advance, pending publish) sequences in this lock
too, so a snapshot is always epoch-consistent.
"""

from __future__ import annotations

import concurrent.futures
import functools
import queue
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from opendiloco_tpu import obs
from opendiloco_tpu.diloco.compression import device_wire_dtype
from opendiloco_tpu.diloco.hostpool import OutputPool


def _sqsum(leaves):
    total = jnp.zeros((), jnp.float32)
    for g in leaves:
        total = total + jnp.sum(jnp.square(g.astype(jnp.float32)))
    return total


def _nesterov_step(masters, bufs, grads, lr, momentum, nesterov, has_mom):
    """The load-bearing SGD rule (torch.optim.SGD parity — the same update
    OuterSGD.step_indices runs on host):
      buf = momentum*buf + g;  d = g + momentum*buf (nesterov) | buf;
      p -= lr*d.  Returns (new_masters, new_bufs, d)."""
    if not has_mom:
        d = grads
        return [m - lr * g for m, g in zip(masters, grads)], [], d
    if not bufs:  # first armed step: momentum starts at zero
        bufs = [jnp.zeros_like(m) for m in masters]
    new_b = [momentum * b + g for b, g in zip(bufs, grads)]
    if nesterov:
        d = [g + momentum * b for g, b in zip(grads, new_b)]
    else:
        d = new_b
    new_m = [m - lr * dd for m, dd in zip(masters, d)]
    return new_m, new_b, d


# -- jitted entry points -----------------------------------------------------
# Lists of leaves are pytree args, so the jit cache is keyed by fragment
# length + avals: the fragment partition is fixed at construction, giving a
# small bounded set of executables that never recompiles across rounds.


# The squared norm of the pseudo-gradient rides every one of these jits (one
# reduction over an array the jit already holds, one scalar more to fetch),
# whether a tracer is armed or not: arming the tracer in a running process
# must not pick another program, or the first traced boundary compiles.


@jax.jit
def _pg_f32(masters, params):
    pg = [m - p for m, p in zip(masters, params)]
    return pg, _sqsum(pg)


@jax.jit
def _pg_f32_ef(masters, params, res):
    """Error-feedback pseudo-gradient: the residual add is fused into the
    same dispatch (pg = master - params + residual). Error feedback forces
    full-width D2H (see __init__), so no wire-cast variant exists — the
    host must see the exact f32 values it will encode to measure the true
    roundtrip error."""
    pg = [m - p + r for m, p, r in zip(masters, params, res)]
    return pg, _sqsum(pg)


@functools.partial(jax.jit, static_argnames=("wire_dtype", "keep32"))
def _pg_wire(masters, params, wire_dtype, keep32):
    """Pseudo-gradient with the wire cast fused in: the D2H fetch of
    ``wire`` moves wire-width (half for f16) bytes. ``keep32`` retains the
    f32 pseudo-gradient on device for the overlap landing math."""
    pg = [m - p for m, p in zip(masters, params)]
    wire = [g.astype(wire_dtype) for g in pg]
    return (pg if keep32 else []), wire, _sqsum(pg)


@functools.partial(
    jax.jit, static_argnames=("nesterov", "has_mom"), donate_argnums=(0, 1, 2)
)
def _apply_fused(masters, bufs, avg, lr, momentum, *, nesterov, has_mom):
    """Blocking apply: donated masters/momentum stepped in one fused op.
    ``avg`` is dead after the step, so it is donated too — its hot pages
    become XLA scratch instead of a fresh (page-faulting) allocation."""
    new_m, new_b, _ = _nesterov_step(
        masters, bufs, avg, lr, momentum, nesterov, has_mom
    )
    return new_m, new_b


@functools.partial(
    jax.jit, static_argnames=("nesterov", "has_mom"),
    donate_argnums=(0, 1, 2, 3),
)
def _apply_sync_fused(
    masters, bufs, avg, params, lr, momentum, *, nesterov, has_mom
):
    """Blocking apply + params <- master in ONE dispatch: the new master
    is written to both outputs while hot instead of re-read by a separate
    ``_overwrite_fused`` launch — one fewer full-model pass per boundary.
    The old param buffers are donated (the caller is replacing them); the
    add-zero keeps the fresh params from aliasing the live masters (see
    ``_overwrite_fused`` for why that aliasing would be fatal)."""
    new_m, new_b, _ = _nesterov_step(
        masters, bufs, avg, lr, momentum, nesterov, has_mom
    )
    new_p = [m + jnp.zeros((), m.dtype) for m in new_m]
    return new_m, new_b, new_p


@functools.partial(
    jax.jit, static_argnames=("nesterov", "has_mom"), donate_argnums=(2, 3)
)
def _estimate_fused(
    masters, bufs, pg, boundary, lr, momentum, *, nesterov, has_mom
):
    """Eager-overlap launch: the update estimated from the LOCAL
    pseudo-gradient. Masters/bufs are NOT donated (the pre-round arrays
    stay live for the correction on landing); pg and the boundary copy
    are consumed. delta = est_m - boundary matches the host path's
    associativity exactly — computing it as pg - lr*d instead rounds at
    the pseudo-gradient's scale and drifts ~1e3 ulps over a few rounds."""
    est_m, est_b, _ = _nesterov_step(
        masters, bufs, pg, lr, momentum, nesterov, has_mom
    )
    delta = [e - b for e, b in zip(est_m, boundary)]
    return est_m, est_b, delta


@functools.partial(
    jax.jit, static_argnames=("nesterov", "has_mom"),
    donate_argnums=(0, 1, 2, 3),
)
def _land_delayed_fused(
    masters, bufs, boundary, avg, lr, momentum, *, nesterov, has_mom
):
    """Delayed-overlap landing: true outer step from the pre-round
    masters + the deferred boundary rewrite as a delta,
    delta = new_m - boundary (same associativity as the host path; the
    boundary copy is donated — last use)."""
    new_m, new_b, _ = _nesterov_step(
        masters, bufs, avg, lr, momentum, nesterov, has_mom
    )
    delta = [m - b for m, b in zip(new_m, boundary)]
    return new_m, new_b, delta


@functools.partial(
    jax.jit, static_argnames=("nesterov", "has_mom"),
    donate_argnums=(0, 1, 2, 3),
)
def _land_eager_fused(masters, bufs, est_m, avg, lr, momentum, *, nesterov, has_mom):
    """Eager-overlap landing: true step from the pre-round masters/bufs
    (donated) corrected against the estimated masters (donated — the live
    plane rebinds to the returned true arrays)."""
    new_m, new_b, _ = _nesterov_step(
        masters, bufs, avg, lr, momentum, nesterov, has_mom
    )
    delta = [t - e for t, e in zip(new_m, est_m)]
    return new_m, new_b, delta


@functools.partial(
    jax.jit, static_argnames=("wire_dtype", "nesterov", "has_mom", "eager")
)
def _stream_launch_fused(
    masters, bufs, params, lr, momentum, *, wire_dtype, nesterov, has_mom, eager
):
    """Streaming fragment launch: pseudo-gradient + wire cast + (eager)
    locally-estimated step in ONE dispatch with NOTHING donated — the live
    fragment masters/bufs/params stay bound. Unlike ``_estimate_fused``,
    the estimate never rebinds the live plane: the plane stays pre-round
    until the fragment's all-reduce lands (``stream_land``), which is what
    lets N fragment rounds be in flight at once without tearing the
    served master. Every output is freshly computed (no input
    pass-through), so the comm thread can fetch the wire arrays
    lock-free while train steps keep donating the live params.

    eager:   returns (wire, delta, est_m) — delta = est_m - params is the
             immediately-applied first-step estimate (arxiv 2502.12996),
             est_m is retained for the landing reconciliation.
    delayed: returns (wire, boundary, []) — an independent f32 boundary
             copy for the landing rewrite."""
    pg = [m - p for m, p in zip(masters, params)]
    wire = [g.astype(wire_dtype) for g in pg] if wire_dtype is not None else pg
    if not eager:
        boundary = [
            p.astype(jnp.float32) + jnp.zeros((), jnp.float32) for p in params
        ]
        return wire, boundary, []
    est_m, _, _ = _nesterov_step(
        masters, bufs, pg, lr, momentum, nesterov, has_mom
    )
    delta = [e - p for e, p in zip(est_m, params)]
    return wire, delta, est_m


@functools.partial(jax.jit, static_argnames=("nesterov", "has_mom", "eager"))
def _stream_launch_fused_ef(
    masters, bufs, params, res, lr, momentum, *, nesterov, has_mom, eager
):
    """``_stream_launch_fused`` with the error-feedback residual add fused
    in (pg = master - params + residual) and no wire cast (error feedback
    forces full-width D2H). Same contract: nothing donated, the live plane
    is NOT rebound, every output is freshly computed."""
    pg = [m - p + r for m, p, r in zip(masters, params, res)]
    wire = pg
    if not eager:
        boundary = [
            p.astype(jnp.float32) + jnp.zeros((), jnp.float32) for p in params
        ]
        return wire, boundary, []
    est_m, _, _ = _nesterov_step(
        masters, bufs, pg, lr, momentum, nesterov, has_mom
    )
    delta = [e - p for e, p in zip(est_m, params)]
    return wire, delta, est_m


@functools.partial(jax.jit, donate_argnums=(1,))
def _overwrite_fused(masters, params):
    # params <- master. The add-zero is load-bearing: a bare passthrough
    # would let jax forward the master arrays themselves as outputs, and
    # the caller binds these as train-state leaves that the next
    # train_step DONATES — which would delete the live masters.
    return [m + jnp.zeros((), m.dtype) for m in masters]


@functools.partial(jax.jit, donate_argnums=(1,))
def _sub_fused(new, base):
    # delta = new - base over a fragment (gossip landing under streaming);
    # the retained base copies are dead after this, so they donate
    return [a - b for a, b in zip(new, base)]


@functools.partial(jax.jit, static_argnames=("dtype",))
def _cast_fused(leaves, dtype):
    # wire-width pre-cast for masters-only host fetches (serve snapshots):
    # fresh buffers by construction (astype materializes), nothing donates
    # them, so the fetched host views can stay zero-copy
    return [x.astype(dtype) for x in leaves]


@jax.jit
def _copy_fused(leaves):
    # fresh buffers (see _overwrite_fused for why the add-zero matters)
    return [x.astype(jnp.float32) + jnp.zeros((), jnp.float32) for x in leaves]


def _own(x: np.ndarray) -> np.ndarray:
    """Force a host array to own its memory. On the CPU backend
    ``device_get`` returns zero-copy views of the device buffer; a later
    donation deletes that buffer under the view."""
    if x.dtype != np.float32:
        return x.astype(np.float32)
    if x.base is not None or not x.flags.c_contiguous:
        return np.array(x, np.float32)
    return x


def _host_f32(x: np.ndarray) -> np.ndarray:
    """Widen a fetched wire array to f32 WITHOUT forcing ownership: a
    ``device_get`` view's base keeps its device buffer alive, so the copy
    is only needed when that buffer is later donated (see pseudo_grad for
    the one aliasing case that must use ``_own``). At model scale the
    skipped copy is a full extra memory pass per boundary."""
    return x if x.dtype == np.float32 else x.astype(np.float32)


# shards in flight during a sharded fetch (transfer, then copy): on a
# four-chip host two read 3.3 GB/s, four 3.7 and eight no more (PERF.md, PR 27)
_FETCH_THREADS = 4


def _is_assembled(x: jax.Array) -> bool:
    """Whether ``x``'s host value has to be put together from shards: not a
    fully replicated array, nor one that lives on a single device."""
    return not x.is_fully_replicated and len(x.addressable_shards) > 1


def _distinct_shards(x: jax.Array) -> list:
    """``x``'s addressable shards, one for each distinct index: a mesh axis
    that replicates repeats a shard on several devices, and one copy of it is
    enough (the de-duplication ``jax.Array``'s own host value makes)."""
    seen, out = set(), []
    for shard in x.addressable_shards:
        key = tuple((sl.start, sl.stop) for sl in shard.index)
        if key not in seen:
            seen.add(key)
            out.append(shard)
    return out


def cut_pieces(nbytes: Sequence[int]) -> list[list[int]]:
    """A round's leaves, as positions into the round's own list, cut into the
    pieces of the boundary's pipeline, from their bytes and nothing else.

    Large leaves first, each a piece of its own; a leaf of less than a
    ``_RIDES_BELOW``-th of the round's bytes (the norms) rides with the piece
    before it. The last piece's all-reduce and way back are the tail nothing
    hides, so the small leaves come last. Every peer of a round cuts the same
    model alike, which is what lets a piece be a round of its own on the wire."""
    order = sorted(range(len(nbytes)), key=lambda j: -nbytes[j])
    total = sum(nbytes)
    pieces: list[list[int]] = []
    for j in order:
        if pieces and nbytes[j] * _RIDES_BELOW < total:
            pieces[-1].append(j)
        else:
            pieces.append([j])
    return pieces


_RIDES_BELOW = 64


class _Landing:
    """Counts a fetch's pieces down and hands each on, in the pieces' order,
    from whichever thread lands the last part of the next one due."""

    def __init__(self, leaves: list, out: list, pieces, units, deliver):
        self._leaves, self._out, self._pieces = leaves, out, pieces
        self._left = list(units)  # parts still under way, piece by piece
        self._deliver = deliver
        self._due = 0
        self._lock = threading.Lock()

    def landed(self, k: int, parts: int = 1) -> None:
        with self._lock:
            self._left[k] -= parts
            while self._due < len(self._pieces) and self._left[self._due] == 0:
                piece = self._pieces[self._due]
                for j in piece:
                    # the piece is on the host: let go of its device arrays
                    # (a view that device_get made keeps its own buffer)
                    self._leaves[j] = None
                self._deliver(self._due, [self._out[j] for j in piece])
                self._due += 1


def _copy_shard(whole: np.ndarray, shard, landing=None, k: int = 0) -> None:
    """One shard's transfer (awaited here), then its copy into its slice."""
    np.copyto(whole[shard.index], np.asarray(shard.data))
    if landing is not None:
        landing.landed(k)


def _fetch_sharded(
    leaves: Sequence[jax.Array],
    keys: Sequence[int],
    pool: OutputPool,
    lock,
    pieces: Optional[Sequence[Sequence[int]]] = None,
    deliver=None,
) -> tuple[list[np.ndarray], dict]:
    """Host copies of jit outputs, bit for bit ``jax.device_get(leaves)``'s,
    with the sharded ones assembled into arrays from ``pool`` (position
    ``keys[j]`` for ``leaves[j]``) -> (arrays, what the fetch did).

    ``device_get`` starts every shard's transfer at once and then assembles
    each array in the calling thread, shard after shard, into an ``np.empty``
    of its own. On a four-chip TPU host both cost: 32 transfers of 50-400 MB
    in flight together arrive at 1.3 GB/s and four at a time at 3.7, and the
    assembly writes a model's size of new pages in every round (PERF.md,
    PR 27). Here a few threads each take one shard at a time -- its transfer,
    then its copy into its slice of an array the pool has kept since an
    earlier round, in the shard's own memory order (numpy lets go of the GIL
    in both) -- so a few transfers are in flight and the copies hide behind
    them. A leaf that is fully replicated or lives on one device has nothing
    to assemble and goes through ``device_get`` as before. ``lock`` guards
    the pool: held while an array is taken, not while shards arrive.

    With ``deliver``, the leaves are fetched in the order of ``pieces``
    (positions into ``leaves``) and ``deliver(k, arrays of piece k)`` is
    called as piece ``k``'s last shard lands, piece after piece in order,
    from the thread that landed it; ``leaves`` (a list) is emptied piece by
    piece, so that a piece's device arrays are let go once it is on the host."""
    n = len(leaves)
    if pieces is None:
        pieces = [range(n)]
    parts = [_distinct_shards(x) if _is_assembled(x) else None for x in leaves]
    out: list = [None] * n
    landing = None
    if deliver is not None:
        units = [
            sum(1 if parts[j] is None else len(parts[j]) for j in piece)
            for piece in pieces
        ]
        landing = _Landing(leaves, out, pieces, units, deliver)
    for piece in pieces:
        for j in piece:
            if parts[j] is None:
                leaves[j].copy_to_host_async()
    copied = n_shards = new = 0
    with concurrent.futures.ThreadPoolExecutor(_FETCH_THREADS) as workers:
        copies = []
        for k, piece in enumerate(pieces):
            rest = []
            for j in piece:
                x, shards = leaves[j], parts[j]
                if shards is None:
                    rest.append(j)
                    continue
                # the first shard's host array says which memory order the
                # device hands over; the whole gets the same, so that every
                # shard's copy runs along both arrays' memory
                first = np.asarray(shards[0].data)
                with lock:
                    before = pool.new_bytes
                    out[j] = whole = pool.take(keys[j], first, x.shape, x.dtype)
                    new += pool.new_bytes - before
                copied += whole.nbytes
                n_shards += len(shards)
                copies += [
                    workers.submit(_copy_shard, whole, s, landing, k) for s in shards
                ]
                parts[j] = None  # the shards are the work items' now
            if rest:
                got = jax.device_get([leaves[j] for j in rest])
                for j, a in zip(rest, got):
                    out[j] = a
                if landing is not None:
                    landing.landed(k, len(rest))
        for c in copies:
            c.result()
    return out, {"bytes": copied, "shards": n_shards, "new_bytes": new}


def _put_leaves(host_arrays, shardings) -> list[jax.Array]:
    """``device_put`` leaf after leaf, each resident on its devices before the
    next is put. Put all at once, as a list comprehension puts them, three
    and more sharded leaves are under way together and the four chips' way
    back runs at 3-4 GB/s; one at a time it runs at 24 (PERF.md section 6,
    PR 47: 1.7-2.3 s against 0.30 s for the 1.7B model's 6.8 GB). No caller
    has anything to do before the last leaf is there."""
    out = []
    for a, s in zip(host_arrays, shardings):
        out.append(jax.device_put(a, s))
        out[-1].block_until_ready()
    return out


@functools.lru_cache(maxsize=None)
def _device_put_copies() -> bool:
    """Whether ``device_put`` copies host numpy memory on this backend.
    When it does (every current backend), ``_h2d`` can skip its defensive
    pre-copy of pooled-buffer views — the put itself already yields an
    independent device buffer; when a CPU jax zero-copy ALIASES instead,
    the pre-copy is load-bearing (see ``_h2d``). Probed once at first
    boundary, not assumed from version strings."""
    a = np.zeros(8, np.float32)
    d = jax.device_put(a)
    jax.block_until_ready(d)
    a[0] = 1.0
    return float(d[0]) == 0.0


class DeviceOuterPlane:
    """Sharded device master + momentum and the fused outer-boundary ops."""

    def __init__(
        self,
        trainer,
        param_leaves: Sequence[jax.Array],
        *,
        lr: float,
        momentum: float,
        nesterov: bool,
        compression: str = "none",
        error_feedback: bool = False,
    ):
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.nesterov = bool(nesterov)
        self.error_feedback = bool(error_feedback)
        wire = device_wire_dtype(compression)
        if self.error_feedback:
            # full-width D2H: the host measures the codec roundtrip error
            # against the exact f32 pseudo-gradient; a device wire cast
            # (fp16) would hide the cast error from the residual
            wire = None
        self._wire_dtype = jnp.dtype(wire) if wire is not None else None
        # per-leaf error-feedback residuals in HBM (zeros-initialized
        # lazily at the first EF pseudo-gradient; None when EF is off)
        self.ef_res: Optional[list[jax.Array]] = None
        self.shardings = jax.tree.leaves(trainer.state_shardings["params"])
        if len(self.shardings) != len(list(param_leaves)):
            raise ValueError("param leaves / shardings mismatch")
        self.lock = threading.RLock()
        # fresh f32 device copies — the master never aliases live params
        self.masters: list[jax.Array] = _copy_fused(list(param_leaves))
        self.bufs: Optional[list[jax.Array]] = None
        # the host arrays sharded pseudo-gradients are assembled into, kept
        # from round to round (taken under self.lock); two a leaf, because
        # an overlapped round's all-reduce still reads one at the next boundary
        self._fetched = OutputPool(keep=2)
        # what the last pseudo_grad's fetch did (``_fetch_sharded``'s stats);
        # empty when no leaf was sharded and ``device_get`` did it all
        self.last_fetch: dict = {}
        # how to lower the pseudo-gradient and the apply again at the shapes
        # they ran at (``DiLoCoOptimizer.program_recipes``)
        self.recipes = obs.programs.Recipes()

    # -- helpers -----------------------------------------------------------

    def _sel(self, leaves, frag: Optional[list[int]]):
        if leaves is None:
            return []
        return list(leaves) if frag is None else [leaves[i] for i in frag]

    def _put_back(self, attr: str, frag: Optional[list[int]], new: list) -> None:
        cur = getattr(self, attr)
        if frag is None:
            setattr(self, attr, list(new))
            return
        merged = list(cur)
        for j, i in enumerate(frag):
            merged[i] = new[j]
        setattr(self, attr, merged)

    def _ensure_bufs(self) -> None:
        if self.momentum != 0.0 and self.bufs is None:
            # zeros for ALL leaves at the first armed step (OuterSGD
            # semantics: untouched fragments keep their momentum frozen)
            self.bufs = _put_leaves(
                (np.zeros(m.shape, np.float32) for m in self.masters),
                self.shardings,
            )

    def _ensure_ef(self) -> None:
        if self.error_feedback and self.ef_res is None:
            self.ef_res = _put_leaves(
                (np.zeros(m.shape, np.float32) for m in self.masters),
                self.shardings,
            )

    def _h2d(
        self,
        host_leaves,
        frag: Optional[list[int]],
        piece: Optional[int] = None,
    ) -> list[jax.Array]:
        """Averaged pseudo-gradient H2D. all_reduce results are views into
        pooled backend buffers the next call reclaims, so a zero-copy CPU
        device_put (which would ALIAS them) needs a pre-copy; a copying
        device_put already yields independent device memory and the
        pre-copy would just double the H2D cost — probed, not assumed.

        Leaf after leaf (``_put_leaves``), so it returns with the arrays
        resident and the ``outer/h2d`` span is the whole way back, with
        ``bytes`` (and ``piece``: the boundary's pipeline puts a piece a
        call). Takes no lock (the shardings never change), so it can run
        beside a fetch."""
        sh = self._sel(self.shardings, frag)
        tr = obs.tracer()
        t0 = time.perf_counter() if tr is not None else 0.0
        own = np.asarray if _device_put_copies() else np.array
        out = _put_leaves((own(a, dtype=np.float32) for a in host_leaves), sh)
        if tr is not None:
            tr.add_span(
                "outer/h2d", t0, time.perf_counter(), leaves=len(out),
                bytes=sum(a.nbytes for a in out),
                **({} if piece is None else {"piece": piece}),
            )
        return out

    def _scalars(self):
        return np.float32(self.lr), np.float32(self.momentum)

    def _ran(self, name: str, frag: Optional[list[int]], fn, *args, **static) -> None:
        """Remember how to lower ``fn`` at ``args``' shapes, under ``name``
        (a fragment's under its first leaf and count); the first call of a
        name does the work."""
        if frag:
            name = f"{name}/{frag[0]}+{len(frag)}"
        if name not in self.recipes:
            shapes = obs.programs.abstract(args)
            self.recipes.note(name, None, lambda: fn.lower(*shapes, **static))

    @property
    def _has_mom(self) -> bool:
        return self.momentum != 0.0

    # -- boundary ops ------------------------------------------------------

    def fetch(
        self,
        leaves: Sequence[jax.Array],
        frag: Optional[list[int]] = None,
        *,
        pieces: Optional[Sequence[Sequence[int]]] = None,
        deliver=None,
    ) -> tuple[list[np.ndarray], dict]:
        """Host copies of jit outputs over this plane's leaves (``frag``'s,
        or all), bit for bit ``jax.device_get``'s -> (arrays, what the fetch
        did: ``_fetch_sharded``'s stats, empty when no leaf was sharded and
        ``device_get`` did it all, to the letter). An assembled array is the
        caller's for as long as it keeps it; dropped, it is written again by
        a later fetch of the same leaf.

        With ``deliver``, ``deliver(k, arrays of pieces[k])`` is called piece
        after piece as each is whole on the host, and ``leaves`` (a list) is
        emptied as they go (``_fetch_sharded``)."""
        if any(_is_assembled(x) for x in leaves):
            keys = frag if frag is not None else range(len(leaves))
            return _fetch_sharded(
                leaves, keys, self._fetched, self.lock, pieces, deliver
            )
        # nothing to assemble: device_get's, to the letter; the pieces are
        # all in hand when it returns and go on one after another
        out = jax.device_get(leaves)
        if deliver is not None:
            for k, piece in enumerate(pieces or [range(len(leaves))]):
                for j in piece:
                    leaves[j] = None
                deliver(k, [out[j] for j in piece])
        return out, {}

    def pseudo_grad(
        self,
        param_leaves: Sequence[jax.Array],
        frag: Optional[list[int]] = None,
        *,
        keep_device: bool = False,
        pieces: Optional[Sequence[Sequence[int]]] = None,
        deliver=None,
    ) -> tuple[list[np.ndarray], float, Optional[list[jax.Array]]]:
        """(host f32 pseudo-gradient, ||pg||, device f32 pg or None).

        The D2H fetch moves wire-width bytes when the codec has a device
        pre-cast (fp16); the host widens back to f32 for the backend. The
        norm rides the same jit in every run (one extra HBM reduction)
        instead of a serial per-leaf host dot.

        With ``deliver`` (the blocking boundary's stage 1) the one jit runs
        over all leaves as ever, and ``deliver(k, host f32 arrays of
        pieces[k])`` is called piece after piece as each lands, from the
        thread that landed it; a piece's device pseudo-gradient is let go
        there. Not with ``keep_device``."""
        hand_on = None
        if deliver is not None:
            if keep_device:
                raise ValueError("a piece's device arrays are let go as it lands")

            def hand_on(k, arrays):
                deliver(k, [_host_f32(x) for x in arrays])

        with self.lock:
            m = self._sel(self.masters, frag)
            p = list(param_leaves)
            if self._wire_dtype is not None:
                static = dict(wire_dtype=self._wire_dtype, keep32=keep_device)
                self._ran("outer/pseudo_grad", frag, _pg_wire, m, p, **static)
                pg32, wire, sq = _pg_wire(m, p, **static)
            elif self.error_feedback:
                self._ensure_ef()
                r = self._sel(self.ef_res, frag)
                self._ran("outer/pseudo_grad", frag, _pg_f32_ef, m, p, r)
                pg32, sq = _pg_f32_ef(m, p, r)
                wire = pg32
            else:
                self._ran("outer/pseudo_grad", frag, _pg_f32, m, p)
                pg32, sq = _pg_f32(m, p)
                wire = pg32
            assembled = [_is_assembled(x) for x in wire]
            fetched, self.last_fetch = self.fetch(
                wire, frag, pieces=pieces, deliver=hand_on
            )
        # the fetched views keep their device buffers alive, so no copy —
        # EXCEPT the eager f32 case, where ``wire`` IS the kept-on-device
        # pseudo-gradient that ``_estimate_fused`` will DONATE while the
        # all-reduce thread is still reading the host views. An assembled
        # array is the pool's and owns its memory in either case.
        aliased = keep_device and self._wire_dtype is None
        host = [
            _own(x) if aliased and not pooled else _host_f32(x)
            for x, pooled in zip(fetched, assembled)
        ]
        norm = float(np.sqrt(float(sq)))
        return host, norm, (pg32 if keep_device else None)

    def apply_average(
        self,
        averaged: Sequence[np.ndarray],
        frag: Optional[list[int]] = None,
        sync: Optional[Sequence[jax.Array]] = None,
    ) -> Optional[list[jax.Array]]:
        """Blocking apply: the fused, donated Nesterov step over the averaged
        pseudo-gradient; masters/momentum rebind in place under the lock.
        ``averaged`` is the round's leaves in their own order: device arrays
        the boundary's pipeline has put back already (``PutBack``), or host
        arrays, which are put here first. With ``sync`` (the live param
        leaves), the params <- master overwrite rides the SAME jit — the
        synced leaves' old buffers are donated — and the merged fresh leaves
        are returned, saving ``sync_params``'s extra full-model pass."""
        with self.lock:
            self._ensure_bufs()
            avg = list(averaged)
            if avg and not isinstance(avg[0], jax.Array):
                avg = self._h2d(avg, frag)
            m = self._sel(self.masters, frag)
            b = self._sel(self.bufs, frag)
            lr, mom = self._scalars()
            static = dict(nesterov=self.nesterov, has_mom=self._has_mom)
            if sync is None:
                self._ran("outer/apply", frag, _apply_fused, m, b, avg, lr, mom, **static)
                new_m, new_b = _apply_fused(m, b, avg, lr, mom, **static)
                new_p = None
            else:
                p = self._sel(list(sync), frag)
                self._ran("outer/apply", frag, _apply_sync_fused, m, b, avg, p, lr, mom, **static)
                new_m, new_b, new_p = _apply_sync_fused(m, b, avg, p, lr, mom, **static)
            self._put_back("masters", frag, new_m)
            if self._has_mom:
                self._put_back("bufs", frag, new_b)
        if sync is None:
            return None
        if frag is None:
            return list(new_p)
        merged = list(sync)
        for j, i in enumerate(frag):
            merged[i] = new_p[j]
        return merged

    def copy_leaves(self, leaves: Sequence[jax.Array]) -> list[jax.Array]:
        """Fresh f32 device copies (the overlap paths' boundary snapshot:
        the live param buffers get donated by the next train_step)."""
        return _copy_fused(list(leaves))

    def estimate(
        self, pg_dev: list[jax.Array], boundary: list[jax.Array]
    ) -> list[jax.Array]:
        """Eager-overlap launch: rebind the live masters/momentum to the
        locally-estimated step (pre-round arrays stay untouched for the
        landing correction) and return the device delta for the params.
        Donates pg_dev and the boundary copy."""
        with self.lock:
            # no _ensure_bufs: the first armed round's pre-round bufs stay
            # None (the jit zero-initializes), matching the host opt_snap
            lr, mom = self._scalars()
            est_m, est_b, delta = _estimate_fused(
                self.masters, self.bufs or [], pg_dev, boundary, lr, mom,
                nesterov=self.nesterov, has_mom=self._has_mom,
            )
            self.masters = est_m
            if self._has_mom:
                self.bufs = est_b
            return delta

    def land_delayed(
        self,
        pre_masters: list[jax.Array],
        pre_bufs: Optional[list[jax.Array]],
        boundary: list[jax.Array],
        averaged: Sequence[np.ndarray],
    ) -> list[jax.Array]:
        """Delayed-overlap landing: fused true step + deferred boundary
        rewrite. Donates the pre-round arrays and the boundary copy."""
        with self.lock:
            avg = self._h2d(averaged, None)
            lr, mom = self._scalars()
            new_m, new_b, delta = _land_delayed_fused(
                pre_masters, pre_bufs or [], boundary, avg, lr, mom,
                nesterov=self.nesterov, has_mom=self._has_mom,
            )
            self.masters = new_m
            if self._has_mom:
                self.bufs = new_b
            return delta

    def land_eager(
        self,
        pre_masters: list[jax.Array],
        pre_bufs: Optional[list[jax.Array]],
        averaged: Sequence[np.ndarray],
    ) -> list[jax.Array]:
        """Eager-overlap landing: true step from the pre-round arrays,
        corrected against the live (estimated) masters. Donates both."""
        with self.lock:
            avg = self._h2d(averaged, None)
            lr, mom = self._scalars()
            new_m, new_b, delta = _land_eager_fused(
                pre_masters, pre_bufs or [], self.masters, avg, lr, mom,
                nesterov=self.nesterov, has_mom=self._has_mom,
            )
            self.masters = new_m
            if self._has_mom:
                self.bufs = new_b
            return delta

    def stream_launch(
        self,
        param_leaves: Sequence[jax.Array],
        frag: list[int],
        *,
        eager: bool,
    ) -> tuple[list[jax.Array], Optional[list[jax.Array]], list[jax.Array]]:
        """Streaming fragment launch: one fused dispatch computes the
        fragment pseudo-gradient (wire-cast for the D2H fetch), plus the
        eager first-step estimate when ``eager``. NOTHING is donated and
        the live plane is NOT rebound — the plane stays pre-round for this
        fragment until ``stream_land``, so N fragment rounds can be in
        flight at once without tearing the served master.

        Returns ``(wire, delta, retained)``:
          wire     — fresh device arrays for the comm thread to
                     ``device_get`` lock-free (no one ever donates them)
          delta    — eager only: device delta to apply to the fragment's
                     param leaves right now (None when delayed)
          retained — eager: est_m for the landing correction;
                     delayed: the independent f32 boundary copy
        """
        with self.lock:
            m = self._sel(self.masters, frag)
            b = self._sel(self.bufs, frag)
            p = [param_leaves[i] for i in frag]
            lr, mom = self._scalars()
            if self.error_feedback:
                self._ensure_ef()
                r = self._sel(self.ef_res, frag)
                wire, aux, est_m = _stream_launch_fused_ef(
                    m, b, p, r, lr, mom,
                    nesterov=self.nesterov, has_mom=self._has_mom,
                    eager=eager,
                )
            else:
                wire, aux, est_m = _stream_launch_fused(
                    m, b, p, lr, mom,
                    wire_dtype=self._wire_dtype, nesterov=self.nesterov,
                    has_mom=self._has_mom, eager=eager,
                )
        if eager:
            return wire, aux, est_m
        return wire, None, aux

    def stream_land(
        self,
        frag: list[int],
        averaged: Sequence[np.ndarray],
        *,
        est_m: Optional[list[jax.Array]] = None,
        boundary: Optional[list[jax.Array]] = None,
    ) -> list[jax.Array]:
        """Streaming fragment landing: true outer step for the fragment
        from the LIVE plane arrays (still pre-round for this fragment —
        ``stream_launch`` never rebinds), reconciled against the retained
        eager estimate (delta = true - est, telescoping with the launch's
        est - boundary to exactly true - boundary) or the retained
        boundary copy (delayed). Donates the fragment's live masters/bufs
        and the retained arrays, rebinds the fragment entries, and returns
        the device delta for the fragment's param leaves."""
        with self.lock:
            if self._has_mom:
                # full-length zeros if momentum is armed but no round has
                # landed yet: frag-selected zeros == the implied pre-round
                # momentum the launch-time estimate zero-initialized
                self._ensure_bufs()
            avg = self._h2d(averaged, frag)
            pre_m = self._sel(self.masters, frag)
            pre_b = self._sel(self.bufs, frag)
            lr, mom = self._scalars()
            if est_m is not None:
                new_m, new_b, delta = _land_eager_fused(
                    pre_m, pre_b, est_m, avg, lr, mom,
                    nesterov=self.nesterov, has_mom=self._has_mom,
                )
            else:
                new_m, new_b, delta = _land_delayed_fused(
                    pre_m, pre_b, boundary, avg, lr, mom,
                    nesterov=self.nesterov, has_mom=self._has_mom,
                )
            self._put_back("masters", frag, new_m)
            if self._has_mom:
                self._put_back("bufs", frag, new_b)
            return delta

    def sync_params(
        self,
        param_leaves: Sequence[jax.Array],
        frag: Optional[list[int]] = None,
    ) -> list[jax.Array]:
        """params <- master for the synced leaves (old param buffers are
        donated); unsynced fragment leaves pass through live."""
        with self.lock:
            m = self._sel(self.masters, frag)
            p = self._sel(list(param_leaves), frag)
            fresh = _overwrite_fused(m, p)
        if frag is None:
            return list(fresh)
        merged = list(param_leaves)
        for j, i in enumerate(frag):
            merged[i] = fresh[j]
        return merged

    def host_frag(
        self, frag: Optional[list[int]]
    ) -> tuple[list[np.ndarray], Optional[list[np.ndarray]]]:
        """Host f32 copies of one fragment's (masters, bufs) — the gossip
        pair wire is encoded host-side, so a pair round D2H-fetches only
        its fragment. Lock held across the fetch (donation-race rule of
        host_state); bufs is None until momentum arms."""
        with self.lock:
            m = jax.device_get(self._sel(self.masters, frag))
            b = (
                jax.device_get(self._sel(self.bufs, frag))
                if self.bufs is not None else None
            )
        return (
            [_own(x) for x in m],
            None if b is None else [_own(x) for x in b],
        )

    def gossip_land(
        self,
        frag: Optional[list[int]],
        masters_np: Sequence[np.ndarray],
        bufs_np: Optional[Sequence[np.ndarray]],
        *,
        sync: Optional[Sequence[jax.Array]] = None,
        base: Optional[list[jax.Array]] = None,
    ):
        """Adopt a NoLoCo-stepped fragment (host numpy from noloco_step):
        H2D the new masters/momentum and rebind the fragment entries.

        Blocking path passes ``sync`` (the live param leaves) and gets the
        merged post-sync leaves back — the fragment's params reset to the
        new master via the donating overwrite, unsynced leaves pass
        through live. Streaming passes ``base`` (the retained pre-round
        master copies) and gets the device delta (new - base) for
        _apply_frag_delta; the base copies are donated. Caller holds
        self.lock when it needs the rebind atomic with a params update.

        Round cadence is not this plane's concern: lockstep pair rounds,
        async bounded-staleness matches, and async self-rounds all land
        through the same two shapes above (the staleness-weighted mix
        happened host-side in gossip.py before noloco_step)."""
        with self.lock:
            sh = self._sel(self.shardings, frag)
            new_m = _put_leaves((np.asarray(m, np.float32) for m in masters_np), sh)
            self._put_back("masters", frag, new_m)
            if self._has_mom and bufs_np is not None:
                self._ensure_bufs()
                new_b = _put_leaves((np.asarray(b, np.float32) for b in bufs_np), sh)
                self._put_back("bufs", frag, new_b)
            if sync is not None:
                p = self._sel(list(sync), frag)
                fresh = _overwrite_fused(new_m, p)
                if frag is None:
                    return list(fresh)
                merged = list(sync)
                for j, i in enumerate(frag):
                    merged[i] = fresh[j]
                return merged
            if base is not None:
                return _sub_fused(new_m, base)
            return None

    def set_ef_residuals(
        self, idxs: Sequence[int], host_errs: list[np.ndarray]
    ) -> None:
        """Commit hook for the ErrorFeedback ledger: adopt the round's
        roundtrip errors as the live device residuals for ``idxs``."""
        with self.lock:
            self._ensure_ef()
            merged = list(self.ef_res)
            put = _put_leaves(
                (np.asarray(e, np.float32) for e in host_errs),
                [self.shardings[i] for i in idxs],
            )
            for i, d in zip(idxs, put):
                merged[i] = d
            self.ef_res = merged

    # -- host boundary (serve / checkpoint / state averaging) --------------

    def ef_host_state(self) -> Optional[list[np.ndarray]]:
        """Host snapshot of the error-feedback residuals (None before any
        committed round). Same donation-race discipline as host_state —
        though nothing ever donates ef_res leaves, the lock keeps the
        fetch consistent with a concurrent commit."""
        with self.lock:
            if self.ef_res is None:
                return None
            fetched = jax.device_get(self.ef_res)
        return [_own(x) for x in fetched]

    def load_ef(self, residuals_np: Optional[Sequence]) -> None:
        """Adopt checkpointed residuals; None entries (host-placement
        checkpoints with partially-committed leaves) load as zeros."""
        with self.lock:
            if residuals_np is None:
                self.ef_res = None
                return
            self.ef_res = _put_leaves(
                (
                    np.zeros(m.shape, np.float32)
                    if r is None
                    else np.asarray(r, np.float32)
                    for r, m in zip(residuals_np, self.masters)
                ),
                self.shardings,
            )

    def host_state(
        self, refs: Optional[tuple] = None
    ) -> tuple[list[np.ndarray], Optional[list[np.ndarray]]]:
        """Lazily fetched host snapshot (f32 copies that own their memory).
        Holding the lock for the whole fetch is the point: a donation
        racing the device_get would read freed buffers. Pass an explicit
        ``(masters, bufs)`` tuple to snapshot a pending round's pre-round
        arrays (``bufs`` may be None there even when the live plane has
        momentum — the round started before the first armed step)."""
        with self.lock:
            masters, bufs = refs if refs is not None else (self.masters, self.bufs)
            m = jax.device_get(masters)
            b = jax.device_get(bufs) if bufs else None
        return [_own(x) for x in m], (None if b is None else [_own(x) for x in b])

    def host_masters(
        self,
        refs: Optional[list] = None,
        wire_dtype: Optional[str] = None,
    ) -> list[np.ndarray]:
        """Masters-only host fetch for the serve plane's weight hot-swap.

        With ``wire_dtype`` (``"float16"`` when the state codec is plain
        fp16 — see ``compression.device_wire_dtype`` for why only the
        idempotent cast qualifies) the narrowing runs INSIDE jit, so the
        D2H boundary copy moves half-width bytes and the returned host
        arrays are f16 for the codec to pass through. Without it this is
        ``host_state`` minus the momentum fetch. Lock held across the
        whole fetch for the same donation-race reason as host_state."""
        with self.lock:
            masters = list(refs) if refs is not None else self.masters
            if wire_dtype is not None:
                masters = _cast_fused(masters, jnp.dtype(wire_dtype))
                # the cast outputs are private buffers nothing ever
                # donates; a zero-copy device_get view is safe to hand out
                return [np.asarray(x) for x in jax.device_get(masters)]
            fetched = jax.device_get(masters)
        return [_own(x) for x in fetched]

    def load(
        self,
        masters_np: Sequence[np.ndarray],
        bufs_np: Optional[Sequence[np.ndarray]],
        *,
        lr: Optional[float] = None,
        momentum: Optional[float] = None,
        nesterov: Optional[bool] = None,
    ) -> None:
        """Adopt a host master/momentum state (checkpoint restore or peer
        onboarding); optionally adopt the serialized optimizer scalars."""
        with self.lock:
            if lr is not None:
                self.lr = float(lr)
            if momentum is not None:
                self.momentum = float(momentum)
            if nesterov is not None:
                self.nesterov = bool(nesterov)
            self.masters = _put_leaves(
                (np.array(m, dtype=np.float32) for m in masters_np), self.shardings
            )
            if bufs_np is None or self.momentum == 0.0:
                self.bufs = None
            else:
                self.bufs = _put_leaves(
                    (np.array(b, dtype=np.float32) for b in bufs_np), self.shardings
                )

    def load_masters(self, masters_np: Sequence[np.ndarray]) -> None:
        """Adopt averaged full-state masters (average_state_every leg);
        momentum is untouched, matching the host path."""
        with self.lock:
            self.masters = _put_leaves(
                (np.array(m, dtype=np.float32) for m in masters_np), self.shardings
            )


class PutBack(threading.Thread):
    """Stage 3 of the blocking boundary's pipeline: a piece's average goes
    back to the devices as soon as the all-reduce has returned it, while
    later pieces are still arriving on the host.

    ``submit(k, averaged)`` from the all-reduce's thread, piece after piece;
    ``wait()`` -> the round's averaged leaves on the devices, in the leaves'
    own order (``apply_average``'s argument), once the last piece is
    resident there; ``drop()`` after a failed round: joined, the puts let
    go. ``seconds``: the first put's start to the last piece resident."""

    def __init__(
        self,
        plane: DeviceOuterPlane,
        frag: Optional[list[int]],
        pieces: Sequence[Sequence[int]],
    ):
        super().__init__(name="outer-h2d")
        idxs = range(len(plane.masters)) if frag is None else frag
        self._plane, self._pieces = plane, pieces
        # the plane's leaves each piece holds (``pieces`` counts within the round)
        self._idxs = [[idxs[j] for j in piece] for piece in pieces]
        self._todo: queue.SimpleQueue = queue.SimpleQueue()
        self._put: list = [None] * len(idxs)
        self._error: Optional[BaseException] = None
        self._t0: Optional[float] = None
        self.seconds = 0.0

    def submit(self, k: int, averaged: Sequence[np.ndarray]) -> None:
        self._todo.put((k, averaged))

    def _put_piece(self, k: int, averaged) -> None:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        for j, d in zip(self._pieces[k], self._plane._h2d(averaged, self._idxs[k], k)):
            self._put[j] = d
        self.seconds = time.perf_counter() - self._t0

    def run(self) -> None:
        try:
            # the queue's item is let go before the next is waited for: a
            # piece's host average goes back to its pool once it is resident
            while (item := self._todo.get()) is not None:
                self._put_piece(*item)
                item = None
        except BaseException as e:  # re-raised by wait(), in the caller
            self._error = e

    def wait(self) -> list[jax.Array]:
        self._todo.put(None)
        self.join()
        if self._error is not None:
            raise self._error
        return self._put

    def drop(self) -> None:
        self._todo.put(None)
        self.join()
        self._put = []
