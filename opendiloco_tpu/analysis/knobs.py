"""The declarative registry of every ``ODTP_*`` environment knob.

This table is the single authority: the knob_check pass fails the build
when code reads a knob missing here (undeclared), when a registered knob
is never read anywhere (dead), or when a read site's literal default
disagrees with the registered default (mismatch). The README knob table
is generated from this registry (``scripts/odtp_lint.py --write-knob-table``),
so docs cannot drift from code either.

``default`` is the exact fallback the code uses when the variable is
unset; ``""`` means unset-is-off/derived (the ``doc_default`` column says
what that behaves like). Keep entries sorted by (subsystem, name).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str  # bool | int | float | str | spec | path
    default: str  # canonical code default ("" = unset)
    subsystem: str  # transport | diloco | chaos | obs | serve | fleet | model | bench | analysis
    doc: str  # one line, lands verbatim in the README table
    doc_default: str = ""  # display override when default="" reads poorly


KNOBS: tuple[Knob, ...] = (
    # -- analysis -------------------------------------------------------------
    Knob("ODTP_LOCKCHECK", "bool", "", "analysis",
         "`1` wraps `threading` locks created by this package in the runtime "
         "lock-order witness: per-thread acquisition order is recorded and any "
         "cycle in the global order graph raises immediately instead of "
         "deadlocking. Zero-cost when unset.", doc_default="off"),
    # -- bench ----------------------------------------------------------------
    Knob("ODTP_ASYNC_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --async` "
         "(default `ASYNC_BENCH.json` in the repo root).",
         doc_default="repo artifact"),
    Knob("ODTP_AUTOSCALE_BENCH_OUT", "path", "", "bench",
         "Output path override for `scripts/fleet_autoscale_bench.py` "
         "(default `AUTOSCALE_BENCH.json` in the repo root).",
         doc_default="repo artifact"),
    Knob("ODTP_BOUNDARY_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --boundary` "
         "(default `BOUNDARY_BENCH.json` in the repo root).",
         doc_default="repo artifact"),
    Knob("ODTP_COMPRESS_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --compress`.",
         doc_default="repo artifact"),
    Knob("ODTP_CONV_STEPS", "int", "300", "bench",
         "Inner steps per arm in `scripts/convergence_evidence.py`."),
    Knob("ODTP_DECODE_BENCH_OUT", "path", "", "bench",
         "Output path override for `scripts/serve_bench.py --decode`.",
         doc_default="repo artifact"),
    Knob("ODTP_GOSSIP_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --gossip`.",
         doc_default="repo artifact"),
    Knob("ODTP_HETERO_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --hetero`.",
         doc_default="repo artifact"),
    Knob("ODTP_HIER_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --hier`.",
         doc_default="repo artifact"),
    Knob("ODTP_LIVE_TRAIN_STEPS", "int", "1500", "bench",
         "Step budget for `scripts/live_train.py`."),
    Knob("ODTP_OUTER_BENCH_OUT", "path", "", "bench",
         "Output path override for the `bench_outer.py` all-reduce sweep.",
         doc_default="repo artifact"),
    Knob("ODTP_SERVE_BENCH_OUT", "path", "", "bench",
         "Output path override for `scripts/serve_bench.py`.",
         doc_default="repo artifact"),
    Knob("ODTP_SERVE_FLEET_BENCH_OUT", "path", "", "bench",
         "Output path override for `scripts/serve_fleet_bench.py`.",
         doc_default="repo artifact"),
    Knob("ODTP_STREAM_BENCH_OUT", "path", "", "bench",
         "Output path override for `bench_outer.py --stream`.",
         doc_default="repo artifact"),
    # -- chaos ----------------------------------------------------------------
    Knob("ODTP_CHAOS", "spec", "", "chaos",
         "Seedable fault-injection spec, e.g. "
         "`seed=7;drop_conn=0.05;delay_ms=20..200;kill_worker=r3:w5`. "
         "Unset = plane off, zero cost.", doc_default="off"),
    Knob("ODTP_RETRY_BASE_S", "float", "0.5", "chaos",
         "Base of the bounded exponential backoff between outer-round retries."),
    Knob("ODTP_RETRY_CAP_S", "float", "15", "chaos",
         "Cap of the outer-round retry backoff, seconds."),
    Knob("ODTP_ROUND_RETRIES", "int", "3", "chaos",
         "How many times a failed outer round re-forms before the step "
         "raises (callers may pass a different programmatic default)."),
    # -- diloco ---------------------------------------------------------------
    Knob("ODTP_ASYNC_DECAY", "float", "0.5", "diloco",
         "Geometric discount on an async gossip partner's mixing weight "
         "per epoch of staleness distance (weight = 0.5 * decay^d — "
         "exactly the pair average at distance 0)."),
    Knob("ODTP_ASYNC_PATIENCE_S", "float", "2.0", "diloco",
         "How long an async-gossip worker waits for ANY in-window partner "
         "before stepping alone (self-round policy) — bounds what a fast "
         "worker can lose to a slow galaxy per round."),
    Knob("ODTP_ASYNC_STALENESS", "int", "0", "diloco",
         "Bounded-staleness window (outer epochs) for fully asynchronous "
         "gossip rounds: workers free-run their inner loops and mix with "
         "any partner within this epoch distance. `0` keeps the lockstep "
         "per-(epoch, fragment) pairing."),
    Knob("ODTP_GOSSIP_LINK_BIAS", "float", "1.0", "diloco",
         "Exponent on the normalized pair capacity when gossip draws "
         "partners (linkstate-aware pairing); `0` disables link awareness, "
         "higher prefers fast pairs harder."),
    Knob("ODTP_GOSSIP_LINK_FLOOR", "float", "0.25", "diloco",
         "Minimum relative draw weight for the slowest gossip pair — keeps "
         "every pair reachable under any bias (never starved; NoLoCo "
         "mixing needs connectivity)."),
    Knob("ODTP_GOSSIP_SEED", "int", "0", "diloco",
         "Shared pairing-PRNG seed for gossip outer rounds; must match "
         "galaxy-wide (every worker derives the same pairing locally)."),
    Knob("ODTP_GOSSIP_SELF_ROUND", "str", "nesterov", "diloco",
         "Odd-galaxy self-pair policy: `nesterov` steps on own state "
         "(plain DiLoCo step, no wire), `hold` skips the round entirely."),
    Knob("ODTP_STATE_CODEC", "str", "", "diloco",
         "Codec override for onboarding/serve state payloads (`none` "
         "restores raw fp32; default: configured codec when fp16-family, "
         "else fp16).", doc_default="derived"),
    Knob("ODTP_TOPK_DENSITY", "float", "0.03125", "diloco",
         "Fraction of largest-|x| elements the `topk` codec keeps (1/32 "
         "default ~= 0.25 B/elem on the wire)."),
    # -- fleet ----------------------------------------------------------------
    Knob("ODTP_FLEET_CODEC", "str", "", "fleet",
         "Delta-push codec override for the serving fleet "
         "(`blockwise4bit` or `topk`); keyframes always ride the "
         "onboarding state codec.", doc_default="config"),
    Knob("ODTP_FLEET_KEYFRAME_EVERY", "int", "", "fleet",
         "Full-snapshot keyframe cadence override (outer epochs) for the "
         "fleet delta publisher; keyframes re-pin replica bit-exactness "
         "and onboard (re)joining replicas.", doc_default="config"),
    Knob("ODTP_PREFIX_DIRECTORY", "bool", "", "fleet",
         "`1` arms the fleet prefix-cache directory: replicas advertise "
         "host-tier prefix hashes on health frames and the router routes "
         "matching prompts to a holder (shared system prompt prefilled "
         "once fleet-wide). Arms each replica's KV tier.",
         doc_default="config"),
    Knob("ODTP_FLEET_PUSH_INTERVAL_S", "float", "", "fleet",
         "Seconds between fleet pusher wake-ups per replica (each wake-up "
         "ships pending delta/keyframe frames or a staleness ping).",
         doc_default="config"),
    Knob("ODTP_FLEET_SCALE_COOLDOWN_S", "float", "", "fleet",
         "Minimum seconds between autoscaler scaling actions (replacement "
         "of dead replicas and spare replenishment are never "
         "cooldown-gated).", doc_default="config"),
    Knob("ODTP_FLEET_SLO_P99_MS", "float", "", "fleet",
         "Serving latency SLO for the fleet autoscaler: worst-replica "
         "decode p99 above this (or queue depth above "
         "`fleet.slo_queue_depth`) is a breach that scales the fleet up. "
         "0 disables the latency term.", doc_default="config"),
    Knob("ODTP_FLEET_WARM_SPARES", "int", "", "fleet",
         "Warm-spare pool size: replicas kept pre-keyframed on the push "
         "channel but unregistered with the router, so scale-up is a "
         "promotion (mailbox adoption), not a cold boot.",
         doc_default="config"),
    # -- model ----------------------------------------------------------------
    Knob("ODTP_SCAN_UNROLL", "int", "", "model",
         "Overrides the scan-over-layers unroll factor (experiments and "
         "`scripts/aot_roofline.py`; cost analysis needs the stack unrolled).",
         doc_default="config"),
    # -- obs ------------------------------------------------------------------
    Knob("ODTP_OBS", "bool", "", "obs",
         "`1` arms the tracing/metrics plane (and with it the flight "
         "recorder, galaxy overseer and anomaly watchdogs). Unset = "
         "zero-cost no-op.", doc_default="off"),
    Knob("ODTP_OBS_BLACKBOX_CAP", "int", "512", "obs",
         "Flight-recorder event-ring length (recent spans/instants kept "
         "for the black-box dump)."),
    Knob("ODTP_OBS_BLACKBOX_FLUSH_S", "float", "5.0", "obs",
         "Min seconds between rate-limited black-box autodumps (per round "
         "and per chaos fault); `0` dumps on every trigger. Watchdog trips "
         "always dump immediately."),
    Knob("ODTP_OBS_DIR", "path", "", "obs",
         "Flush a `trace-w<rank>-<pid>.jsonl` event file here at exit, and "
         "`blackbox-<worker>-<pid>.json` flight-recorder dumps on trouble.",
         doc_default="no flush"),
    Knob("ODTP_OBS_EVENTS_CAP", "int", "65536", "obs",
         "Event ring limit; overflow increments a `dropped` counter."),
    Knob("ODTP_OBS_PROM_PORT", "int", "", "obs",
         "Serve Prometheus 0.0.4 text at `:PORT/metrics`.",
         doc_default="no endpoint"),
    Knob("ODTP_REQTRACE_CAP", "int", "256", "obs",
         "Completed request traces kept per process in the reqtrace ring "
         "(oldest evicted); inflight traces are unbounded by this."),
    Knob("ODTP_REQTRACE_EXPORT", "path", "", "obs",
         "Write the reqtrace ring (report + full traces) here at exit; "
         "unset falls back to `ODTP_OBS_DIR/reqtrace-<worker>-<pid>.json` "
         "when a dir is set.", doc_default="no export"),
    Knob("ODTP_REQTRACE_SAMPLE", "float", "1.0", "obs",
         "Fraction of requests traced at the minting edge (deterministic "
         "1-in-N thinning); adopted upstream contexts are always "
         "honored."),
    Knob("ODTP_ROOFLINE", "path", "", "obs",
         "Path override for the banked roofline JSON backing MFU gauges.",
         doc_default="auto-discover"),
    Knob("ODTP_WATCHDOG_DIVERGE_Z", "float", "6.0", "obs",
         "Divergence watchdog: trip when own pseudo-grad norm or loss is "
         "this many sigma from the galaxy's (needs >= 4 reporting workers); "
         "`0` disables."),
    Knob("ODTP_WATCHDOG_STALL_S", "float", "0.0", "obs",
         "Stall watchdog deadline: no outer-round progress for this many "
         "seconds trips `anomaly_stall` + a black-box dump (never kills "
         "the run).", doc_default="off"),
    Knob("ODTP_WATCHDOG_STRAGGLER_X", "float", "3.0", "obs",
         "Straggler watchdog factor: trip on a worker whose round time "
         "exceeds X times the galaxy median, or whose inner tokens/s falls "
         "below 1/X of it; `0` disables."),
    # -- serve ----------------------------------------------------------------
    Knob("ODTP_DECODE_BLOCK_T", "int", "", "serve",
         "Ring-page tile size for the Pallas decode kernels (must divide "
         "the slot context); unset = the shared block heuristic.",
         doc_default="auto"),
    Knob("ODTP_KV_HOST_SLOTS", "int", "", "serve",
         "Host KV-tier budget: paused slot pages + prefix-store entries it "
         "may hold at once (page-outs beyond it are declined and the slot "
         "stays resident).", doc_default="config"),
    Knob("ODTP_KV_TIER", "bool", "", "serve",
         "`1` arms the host-memory cold KV tier: the scheduler pages "
         "evicted slot rings D2H between decode steps and time-slices more "
         "live sequences than the device ring holds. Off = all-resident, "
         "bit-identical.", doc_default="config"),
    Knob("ODTP_KV_TIER_CODEC", "str", "", "serve",
         "Cold-page codec: `none` stores f32 (evict+restore bit-exact), "
         "`blockwise4bit` stores pages 8x smaller with a bounded, "
         "test-pinned restore error.", doc_default="config"),
    # -- transport ------------------------------------------------------------
    Knob("ODTP_BULK_BANDWIDTH_BPS", "float", "0", "transport",
         "Per-process egress cap in bytes/s (token bucket) emulating a "
         "constrained WAN link; 0 = unlimited."),
    Knob("ODTP_BULK_STREAMS", "int", "4", "transport",
         "Parallel TCP streams a large bulk frame stripes over."),
    Knob("ODTP_BULK_STRIPE_MIN", "int", "67108864", "transport",
         "Payload bytes above which a bulk frame stripes (64 MiB)."),
    Knob("ODTP_BULK_STRIPE_WAIT_S", "float", "300", "transport",
         "How long a receiver waits for a stripe's session before failing "
         "the round to the retry path."),
    Knob("ODTP_BULK_THRESHOLD", "int", "1048576", "transport",
         "Payload bytes above which a frame rides the threaded bulk plane "
         "instead of the asyncio RPC path (1 MiB)."),
    Knob("ODTP_EXPECT_PEERS", "int", "0", "transport",
         "Rendezvous group-complete fast path: close matchmaking as soon "
         "as this many peers joined; 0 = wait out the window."),
    Knob("ODTP_HIER", "bool", "", "transport",
         "`1` arms the two-level hierarchical outer round: the planner "
         "clusters peers into sites, elects one aggregator per site, and "
         "only aggregators touch the WAN. Off = flat butterfly.",
         doc_default="off"),
    Knob("ODTP_HIER_AGG", "spec", "", "transport",
         "`|`-separated fnmatch globs over peer ids naming PREFERRED "
         "aggregators (e.g. the site-uplink hosts); sites with no live "
         "match fall back to capacity/peer-id election.",
         doc_default="elected"),
    Knob("ODTP_LINK_ADAPT", "bool", "", "transport",
         "`1` arms bandwidth-aware transport: proportional reduce-scatter "
         "partitioning, BDP-derived striping, straggler hedging. Off = "
         "bit-identical uniform path.", doc_default="off"),
    Knob("ODTP_LINK_ALPHA", "float", "0.4", "transport",
         "EWMA weight of the per-peer link estimator."),
    Knob("ODTP_LINK_HEDGE_FACTOR", "float", "3.0", "transport",
         "A stripe lagging this multiple of its link-derived deadline is "
         "re-dispatched over an idle connection; 0 disables hedging."),
    Knob("ODTP_LINK_HYST", "float", "0.25", "transport",
         "Relative drift before a peer's published link estimate tracks "
         "the live EWMA (plan anti-flap)."),
    Knob("ODTP_LINK_MIN_SHARE", "float", "0.25", "transport",
         "Floor on a worker's reduce-scatter part, as a fraction of the "
         "uniform 1/n share."),
    Knob("ODTP_LINK_PROBE_BYTES", "int", "262144", "transport",
         "Micro-probe payload seeding the link estimator on fresh peers; "
         "0 disables probing."),
    Knob("ODTP_PIPELINE", "bool", "1", "transport",
         "`1` (default) chunk-pipelines the outer all-reduce (codec work "
         "overlaps the socket); `0` restores the serial path."),
    Knob("ODTP_PIPELINE_CHUNK_ELEMS", "int", "", "transport",
         "Pipeline chunk size in raw elements; overrides "
         "`ODTP_PIPELINE_CHUNK_MB`.", doc_default="derived"),
    Knob("ODTP_PIPELINE_CHUNK_MB", "float", "8", "transport",
         "Pipeline chunk size in MB of fp32 elements."),
    Knob("ODTP_RDV_FAILBACK_S", "float", "60.0", "transport",
         "How long a worker keeps trying the native rendezvous daemon "
         "before failing back to worker-hosted rendezvous."),
    Knob("ODTP_SITE_RATIO", "float", "4.0", "transport",
         "Auto-clustering threshold: peers whose pairwise link capacity is "
         "within this factor of the group's fattest link share a site."),
    Knob("ODTP_SITES", "spec", "", "transport",
         "Explicit site assignment: `;`-separated sites, each a "
         "`|`-separated list of fnmatch globs over peer ids (e.g. "
         "`rack-a-*;rack-b-*`). Unset = cluster from the gossiped link "
         "matrix.", doc_default="auto-cluster"),
    Knob("ODTP_WORKER_RENDEZVOUS", "bool", "1", "transport",
         "`0` disables the in-process fallback rendezvous server (require "
         "the external daemon)."),
)

REGISTRY: dict[str, Knob] = {k.name: k for k in KNOBS}

TABLE_BEGIN = "<!-- odtp-knobs:begin (generated by scripts/odtp_lint.py --write-knob-table; do not edit by hand) -->"
TABLE_END = "<!-- odtp-knobs:end -->"


def render_table() -> str:
    """The README knob table, grouped by subsystem, markdown."""
    out = [
        TABLE_BEGIN,
        "",
        "| Knob | Type | Default | Subsystem | What it does |",
        "|---|---|---|---|---|",
    ]
    for k in sorted(KNOBS, key=lambda k: (k.subsystem, k.name)):
        default = k.doc_default or k.default or "unset"
        out.append(
            f"| `{k.name}` | {k.type} | `{default}` | {k.subsystem} | {k.doc} |"
        )
    out += ["", TABLE_END]
    return "\n".join(out)
