"""Realization tracing: per-generation span timelines across the planes.

ROADMAP item 3 holds the control plane to "end-to-end realization p99
< 1s at 10k agents" — but the only realization signal used to be ONE
histogram (`antrea_tpu_dissemination_latency_seconds`) collapsing wire,
queue-wait, compile, canary, swap and settle into a single number.  This
module is the Dapper-shaped answer: one SPAN per policy realization,
keyed by a correlation id (policy uid x spec generation x bundle commit
seq), stamped at every stage boundary as the realization flows
controller -> wire -> agent queue -> commit plane -> live traffic:

  controller   WatchEvent.ts — the commit instant, stamped by
               RamStore.apply when the event enters the dissemination
               plane (the span's origin; unstamped events — resync
               replays — are EXCLUDED and metered, never guessed);
  wire         receipt at the agent's watch callback;
  queue_wait   receipt -> the commit transaction the event rode starts
               (dirty-flag latency + install backoff — retries extend
               it, which is the honest realization latency);
  compile      the engine built + swapped the candidate tensors;
  canary       fresh-probe certification against the scalar oracle;
  swap         acceptance of the certified candidate;
  settle       durability (snapshot rotation, LKG retention);
  first_hit    the first LIVE packet batch classified under the new
               bundle generation — a cheap per-generation latch in the
               engines' step() metadata (host-side only: the compiled
               step HLO is bit-identical with tracing on or off).

Stamps are clamped monotonic at record time, so every stage duration is
>= 0 and the stage durations TELESCOPE — they sum exactly to the
end-to-end latency (first_hit - controller).  Both engines share this
tracer (the commit plane stamps are plane-level), so the span STRUCTURE
is oracle-parity by construction.

Surfaces: `antrea_tpu_policy_realization_seconds{stage}` histograms, a
bounded drop-oldest span table served at `GET /realization?uid=`
(agent/apiserver.py), `antctl realization --uid <policy>`,
`realization.json` in the support bundle, and a `realization` event in
the flight recorder per closed span.  Bookkeeping cost is budgeted by
the maintenance scheduler's `observability` task.

The HOT path has its own tracer here too (`StepTracer`): one record per
`Datapath.step` call — the `step` span, its seven host phases
(STEP_PHASES) and the transfer counters — kept in a bounded in-memory
ring and mirrored into the JAX profiler's trace as `tpuflow.step[.phase]`
annotations; STEP_SCOPES (declared in the leaf module ops/scopes.py,
re-exported here) names the scopes the device program's ops
carry, so a device trace files every op under the same cut.

What a step or an install COMPILED is the build ledger's (`BuildLedger`,
one per process): every executable the process traces, lowers and
compiles (or loads from the persistent cache) is a row, filed under the
span that was open — a step by its `seq`, a commit stage or sub-span
(COMMIT_SUBSPANS), the engine constructor's `construct` span, or
`other`.  Spans read its two running totals at their edges, so a step
record and a commit sub-span carry the builds they caused.  All stamps
are time.perf_counter_ns (the step ring's clock); every span is also a
`jax.profiler.TraceAnnotation`.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict, deque
from typing import Optional

import numpy as np

from ..ops.scopes import STEP_SCOPES, device_scope  # noqa: F401
from .metrics import Histogram

# Stage DURATIONS of one realization span, in causal order; each is the
# gap to the previous stage's stamp (origin: the controller commit).
# tools/check_events.py asserts this tuple, the README span-stage table
# and the antrea_tpu_policy_realization_seconds registration agree.
REALIZATION_STAGES = (
    "wire", "queue_wait", "compile", "canary", "swap", "settle",
    "first_hit",
)

# Histogram label values: the stage durations plus the end-to-end total.
_HIST_STAGES = REALIZATION_STAGES + ("total",)

# Commit-plane stamp names in transaction order (tracked per commit, then
# grafted onto every span the commit realized).
_COMMIT_STAMPS = ("start", "compile", "canary", "swap", "settle")

# Sub-spans of a commit: (name, the stage it lies inside).  As with a
# step's STEP_SUBSPANS below, a sub-span is NOT a stage — it is not added
# to the telescoping sum — and those of one stage are disjoint, so they
# sum to at most the stage.  `rules`: the host rule compile
# (`compile_policy_set`, the tenant rung padding, `rule_split`); `tables`:
# the host table build (`ops/match.to_host`, `_pad_tables`); `upload`:
# the engine placing host tables on the device and waiting for them
# (rule, isolation and Service tables), beside it the counter
# `table_bytes`, the bytes placed; `oracle`: the scalar Oracle built over
# the bundle and the probes' wanted verdicts; `walk`: the candidate's
# fresh walk of the probes (`_canary_classify`).  `digest` (stage None)
# is the audit plane's golden digests after the settle stamp: a span of
# the transaction's own, in no stage.  A commit that does none of it
# (the oracle engine places nothing, a delta that appends compiles no
# rule) records zeros.  Outside a transaction (the constructor's boot
# tables, the watchdog's canary) nothing is recorded.
COMMIT_SUBSPANS = (
    ("rules", "compile"), ("tables", "compile"), ("upload", "compile"),
    ("oracle", "canary"), ("walk", "canary"), ("digest", None),
)
_SUB_STAGE = dict(COMMIT_SUBSPANS)

# Host phases of ONE `Datapath.step` call, in order; contiguous children
# of the parent span `step` (phase k ends where phase k+1 begins).  The
# ONE place the phase names are spelled: engines stamp by index
# (StepTracer.phase), readers take the names from here.
STEP_PHASES = (
    "stage", "upload", "dispatch", "wait", "fetch", "account", "attribute",
)

# Boundary indices for StepTracer.phase: SP_<PHASE> is where that phase
# begins, SP_DONE closes the last one.
(SP_STAGE, SP_UPLOAD, SP_DISPATCH, SP_WAIT, SP_FETCH, SP_ACCOUNT,
 SP_ATTRIBUTE, SP_DONE) = range(len(STEP_PHASES) + 1)

# Sub-spans: (name, the phase it lies inside).  A sub-span is NOT a
# phase — it is not added to the telescoping sum — and only the engine
# that has the work opens it (parallel/meshpath.MeshDatapath: `route` is
# the shard hash, the failover mask, `_shard_placement` and the
# permutation of the columns into replica order; `retry` is the whole of
# `_spill_retry`).  An engine that never opens one records zeros.
STEP_SUBSPANS = (("route", "stage"), ("retry", "account"))
SS_ROUTE, SS_RETRY = range(len(STEP_SUBSPANS))

# Steps the in-memory ring keeps (drop-oldest, drops metered).
STEP_RING_SLOTS = 4096

# One ring row.  `t_*` are time.perf_counter_ns() readings: the `step`
# span runs t_start..t_end, phase p runs from t_<p> to the next field
# (t_done closes the last phase), so the ten stamps are monotonic and
# the phases telescope to t_done - t_stage exactly.  The four counters
# are taken where the transfer is issued: one per host->device upload
# and its nbytes, one per fetched output and its nbytes.  Sub-span s
# runs <s>_t0..<s>_t1 on the same clock, inside its phase (both 0 where
# it never opened); `spill_lanes` are the lanes the mesh placed off their
# home replica, `retry_lanes` those of them re-served from home inside
# the same call (0 on one chip).  `round_lanes` is the step program's own
# count of the lanes its slow-path rounds were run at, padding included
# (models/pipeline.round_ladder; the mesh sums its replicas and its spill
# retry): n_miss over it is how full the rounds were.  `v6_lanes` are the
# lanes of the batch whose family mask `is6` is set, counted from the host
# batch in `stage` (0 on a narrow engine, which refuses such a batch).
# `xla_builds` / `xla_build_ns` are the executables the build ledger saw
# built inside the call and their trace + lower + backend ns: 0 on a step
# that ran programs already in memory, so a slow step that built one is
# told from a step that was stopped.
STEP_RECORD = np.dtype(
    [("seq", "<i8"), ("lanes", "<i8"), ("n_miss", "<i8"),
     ("round_lanes", "<i8"), ("t_start", "<i8")]
    + [(f"t_{p}", "<i8") for p in STEP_PHASES]
    + [("t_done", "<i8"), ("t_end", "<i8"), ("h2d_transfers", "<i8"),
       ("h2d_bytes", "<i8"), ("d2h_transfers", "<i8"), ("d2h_bytes", "<i8")]
    + [(f"{s}_{e}", "<i8") for s, _ in STEP_SUBSPANS for e in ("t0", "t1")]
    + [("spill_lanes", "<i8"), ("retry_lanes", "<i8"), ("v6_lanes", "<i8"),
       ("xla_builds", "<i8"), ("xla_build_ns", "<i8")]
)
_N_STAMPS = len(STEP_PHASES) + 3  # start, one per phase, done, end

# -- the XLA build ledger --------------------------------------------------

# JAX's monitoring events of one build (jax 0.9: `jax/_src/dispatch.py`,
# `compiler.py`, `compilation_cache.py`).  A jitted function's first call
# at a shape fires trace (the jaxpr; the functions and primitives it
# traces inside fire their own, nested in time), lower (the MLIR module)
# and backend (compile, or load from the persistent cache, with a cache
# hit or miss event inside it); an in-memory hit fires none.
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
BUILD_CACHE = ("none", "hit", "miss")  # BUILD_RECORD.cache indexes this
_EV_CACHE = {"/jax/compilation_cache/cache_hits": 1,
             "/jax/compilation_cache/cache_misses": 2}

# Builds the ring keeps (drop-oldest, drops metered): a cold install with
# its eager canary builds ~1–2 thousand.
BUILD_RING_SLOTS = 8192

# One build row: `seq` counts the process's builds from 1, `t_end` is the
# perf_counter_ns at which the backend compile ended; trace / lower /
# backend ns; `cache` indexes BUILD_CACHE (a miss is a compile the cache
# then stored; `none`, a compile it did not); `span` names the span open
# then (`step`, `construct`, `commit.<stage>`, `commit.<stage>.<sub-span>`,
# `commit.digest` or `other`) and `step_seq` the open step's `seq` (0
# outside a step); `fun_name` is the executable's (`jit(f)`).
BUILD_RECORD = np.dtype(
    [("seq", "<i8"), ("t_end", "<i8"), ("trace_ns", "<i8"),
     ("lower_ns", "<i8"), ("backend_ns", "<i8"), ("cache", "i1"),
     ("step_seq", "<i8"), ("span", "U40"), ("fun_name", "U96")])


class BuildLedger:
    """Every executable the process builds, and the span that caused it.

    One per process (`build_ledger()`): JAX's listeners cannot be
    unregistered, so they are registered once, behind the module guard.
    Listeners run on the compiling thread, inside the call that built;
    `cause` is the process's, so a build on another thread (an API
    handler's) is filed under the span the engine's thread has open.
    `builds` and `build_ns` are running totals that a span reads at its
    start and end; `cause` is the span open now, swapped in and out by the
    spans themselves.  A build's trace and lower time is the time of the
    outermost trace(s) and the lowering since the previous build; a
    lowering that no backend compile followed (`jit(f).lower(x)`) is
    dropped at the next one, so the totals count executables only.
    """

    def __init__(self, slots: int = BUILD_RING_SLOTS):
        self.slots = int(slots)
        self._clock = time.perf_counter_ns
        self._rows: deque = deque(maxlen=self.slots)
        self.builds = 0
        self.build_ns = 0
        self.cause = ("other", 0)  # (span name, step seq)
        self._traces: list = []  # (t_end, ns) of traces not yet lowered
        self._lowered = None  # (trace ns, lower ns) awaiting the backend
        self._cache = 0

    def on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == _EV_TRACE:
            t, ns = self._clock(), int(duration_secs * 1e9)
            # An enclosing trace ends last: it replaces what it traced.
            self._traces = [x for x in self._traces if x[0] < t - ns]
            self._traces.append((t, ns))
        elif event == _EV_LOWER:
            self._lowered = (sum(ns for _, ns in self._traces),
                             int(duration_secs * 1e9))
            self._traces = []
        elif event == _EV_BACKEND:
            trace_ns, lower_ns = self._lowered or (0, 0)
            backend_ns = int(duration_secs * 1e9)
            self._lowered = None
            self.builds += 1
            self.build_ns += trace_ns + lower_ns + backend_ns
            span, step_seq = self.cause
            self._rows.append((self.builds, self._clock(), trace_ns,
                               lower_ns, backend_ns, self._cache, step_seq,
                               span, str(kw.get("fun_name", ""))[:96]))
            self._cache = 0

    def on_event(self, event: str, **kw) -> None:
        hit = _EV_CACHE.get(event)
        if hit:
            self._cache = hit

    @property
    def dropped(self) -> int:
        return self.builds - len(self._rows)

    def trace(self, since: int = 0) -> dict:
        """{"records": the rows of builds after build `since`, oldest
        first (BUILD_RECORD), "dropped": those of them the ring lost}."""
        rows = [r for r in list(self._rows) if r[0] > since]
        return {"records": np.array(rows, BUILD_RECORD),
                "dropped": max(0, self.dropped - since)}


_LEDGER: Optional[BuildLedger] = None


def build_ledger() -> BuildLedger:
    """The process's build ledger; the first call registers its listeners
    with jax.monitoring (once: they cannot be unregistered)."""
    global _LEDGER
    if _LEDGER is None:
        import jax.monitoring

        led = BuildLedger()
        jax.monitoring.register_event_duration_secs_listener(led.on_duration)
        jax.monitoring.register_event_listener(led.on_event)
        _LEDGER = led
    return _LEDGER


class Span:
    """A span outside the step ring: a TraceAnnotation, the ledger's
    `cause` while it is open, and on close `ns`, `builds` and `build_ns`
    (what the ledger's totals moved by).  `nbytes` is the caller's to set
    (the `upload` sub-span's `table_bytes`)."""

    __slots__ = ("_ann", "_cause", "_prev", "_t0", "_b0", "_n0", "ns",
                 "builds", "build_ns", "nbytes")

    def __init__(self, annotation: str, cause: str):
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(annotation)
        self._cause = cause
        self.ns = self.builds = self.build_ns = self.nbytes = 0

    def __enter__(self):
        led = build_ledger()
        self._ann.__enter__()
        self._prev, led.cause = led.cause, (self._cause, 0)
        self._t0, self._b0, self._n0 = (time.perf_counter_ns(), led.builds,
                                        led.build_ns)
        return self

    def __exit__(self, *exc):
        led = build_ledger()
        self.ns = time.perf_counter_ns() - self._t0
        self.builds = led.builds - self._b0
        self.build_ns = led.build_ns - self._n0
        led.cause = self._prev
        self._ann.__exit__(*exc)
        return False


def construct_span(init):
    """Decorator of an engine's `__init__`: the constructor's own span
    `construct` (`tpuflow.construct`).  It marks where the engine's
    `build_trace()` begins and hands the span to the engine's realization
    tracer (`last_commit()["construct_s"]`).  A subclass's `__init__` and
    its base's both carry it: the outermost opens the span."""

    @functools.wraps(init)
    def wrapped(self, *args, **kwargs):
        if self._builds_from is not None:  # inside a subclass's span
            return init(self, *args, **kwargs)
        self._builds_from = build_ledger().builds
        with Span("tpuflow.construct", "construct") as span:
            init(self, *args, **kwargs)
        tr = getattr(self, "_realization", None)
        if tr is not None:
            tr.note_construct(span)

    return wrapped


class CommitSpan(Span):
    """COMMIT_SUBSPANS' `name` of the tracer's open commit transaction
    (`tpuflow.commit.<stage>.<name>`); with no tracer or no open commit
    it records nothing and costs nothing."""

    __slots__ = ("_tracer", "_name")

    def __init__(self, tracer, name: str):
        stage = _SUB_STAGE[name]
        where = f"{stage}.{name}" if stage else name
        super().__init__(f"tpuflow.commit.{where}", f"commit.{where}")
        self._tracer = tracer
        self._name = name

    def __enter__(self):
        if self._tracer is None or self._tracer._open_commit is None:
            self._tracer = None
            return self
        return super().__enter__()

    def __exit__(self, *exc):
        if self._tracer is not None:
            super().__exit__(*exc)
            self._tracer._commit_sub(self._name, self)
        return False


class StepTracer:
    """Phase spans + transfer counters of every `step` call, always on.

    Owned by the engine (one per datapath, shared by its tenant worlds);
    single-threaded like `step` itself.  `begin` opens the `step` span,
    `phase(k)` is the boundary where STEP_PHASES[k] begins (k ==
    len(STEP_PHASES) closes the last), `end` closes the span, writes the
    ring row and returns the span's seconds — the ONE clock pair
    `step_hist` and the telemetry fold are fed from.  A `_step` that
    raised leaves its unreached boundaries on the end stamp (zero-width
    phases), never an open record.  `sub(k)` opens STEP_SUBSPANS[k]
    inside the running phase and `sub_end()` closes it (one open at a
    time; `end` closes one a raise left open).  Every span is also a
    `jax.profiler.TraceAnnotation` (`tpuflow.step`, `tpuflow.step.<phase>`,
    `tpuflow.step.<phase>.<sub-span>`) — free while no profiler session
    runs, and in a traced run an event on the device ops' timeline.
    While the span is open the build ledger files builds under its `seq`;
    `begin` and `end` read the ledger's two totals for `xla_builds` and
    `xla_build_ns`.
    """

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self._ledger = build_ledger()
        self._built = (0, 0)
        self._cause = None
        self._names = tuple(f"tpuflow.step.{p}" for p in STEP_PHASES)
        self._sub_names = tuple(f"tpuflow.step.{p}.{s}"
                                for s, p in STEP_SUBSPANS)
        self.slots = STEP_RING_SLOTS
        self._clock = time.perf_counter_ns  # the benchmark client's clock
        self._ring = np.zeros(self.slots, STEP_RECORD)
        self._rows = self._ring.view(np.int64).reshape(self.slots, -1)
        self.steps_total = 0
        self.dropped = 0
        self._stamps: list = []
        self._span = self._child = self._sub = None
        self._sub_at = 0
        self._subs = [0] * (2 * len(STEP_SUBSPANS))
        self._lanes = 0
        self.n_miss = self.round_lanes = 0
        self.h2d_transfers = self.h2d_bytes = 0
        self.d2h_transfers = self.d2h_bytes = 0
        self.spill_lanes = self.retry_lanes = self.v6_lanes = 0

    def begin(self, lanes: int) -> None:
        self._lanes = int(lanes)
        self.n_miss = self.round_lanes = 0
        self.h2d_transfers = self.h2d_bytes = 0
        self.d2h_transfers = self.d2h_bytes = 0
        self.spill_lanes = self.retry_lanes = self.v6_lanes = 0
        self._subs = [0] * (2 * len(STEP_SUBSPANS))
        seq = self.steps_total + 1
        self._span = self._annotate("tpuflow.step", seq=seq)
        self._span.__enter__()
        led = self._ledger
        self._built = (led.builds, led.build_ns)
        self._cause, led.cause = led.cause, ("step", seq)
        self._stamps = [self._clock()]

    def phase(self, k: int) -> None:
        t = self._clock()
        if self._child is not None:
            self._child.__exit__(None, None, None)
            self._child = None
        self._stamps.append(t)
        if k < len(self._names):
            self._child = self._annotate(self._names[k])
            self._child.__enter__()

    def sub(self, k: int) -> None:
        """Open STEP_SUBSPANS[k] inside the phase that is running."""
        self._sub = self._annotate(self._sub_names[k])
        self._sub.__enter__()
        self._sub_at = 2 * k
        self._subs[2 * k] = self._clock()

    def sub_end(self) -> None:
        self._subs[self._sub_at + 1] = self._clock()
        self._sub.__exit__(None, None, None)
        self._sub = None

    def since(self, k: int) -> float:
        """Seconds from where phase k began to the latest boundary."""
        return (self._stamps[-1] - self._stamps[k + 1]) * 1e-9

    def uploaded(self, x):
        """Count one host->device transfer where it is issued; -> x."""
        self.h2d_transfers += 1
        self.h2d_bytes += x.nbytes
        return x

    def fetched(self, x):
        """Count one device->host copy where it lands; -> x."""
        self.d2h_transfers += 1
        self.d2h_bytes += x.nbytes
        return x

    def end(self) -> float:
        if self._sub is not None:  # a raise inside it
            self.sub_end()
        t = self._clock()
        if self._child is not None:
            self._child.__exit__(None, None, None)
            self._child = None
        self._span.__exit__(None, None, None)
        led = self._ledger
        led.cause = self._cause
        builds, build_ns = self._built
        ts = self._stamps
        ts.extend([t] * (_N_STAMPS - len(ts)))
        seq = self.steps_total = self.steps_total + 1
        if seq > self.slots:
            self.dropped += 1  # the oldest row, overwritten here
        self._rows[(seq - 1) % self.slots] = (
            seq, self._lanes, self.n_miss, self.round_lanes, *ts,
            self.h2d_transfers, self.h2d_bytes, self.d2h_transfers,
            self.d2h_bytes, *self._subs, self.spill_lanes, self.retry_lanes,
            self.v6_lanes, led.builds - builds, led.build_ns - build_ns)
        return (t - ts[0]) * 1e-9

    def records(self) -> np.ndarray:
        """The closed records the ring still holds, oldest first (a
        copy: the ring is overwritten in place)."""
        if self.steps_total <= self.slots:
            return self._ring[:self.steps_total].copy()
        return np.roll(self._ring, -(self.steps_total % self.slots))


class RealizationTracer:
    """Span table + stage histograms for ONE node's realization path.

    Owned by the datapath (both engines construct one; the agent
    controller, commit plane and step latch all stamp through it).
    Single-threaded like its callers.  All tables are bounded and
    drop-oldest; drops are metered, never silent.
    """

    def __init__(self, *, span_slots: int = 256, pending_slots: int = 1024,
                 clock=time.monotonic, recorder=None):
        if span_slots <= 0 or pending_slots <= 0:
            raise ValueError(
                f"realization tracer tables must be positive, got "
                f"span_slots={span_slots} pending_slots={pending_slots}")
        self.span_slots = int(span_slots)
        self.pending_slots = int(pending_slots)
        self._clock = clock
        self._recorder = recorder
        # (uid, gen) -> span dict; three lifecycle tables, all bounded:
        # pending (stamped controller/wire, awaiting a commit), awaiting
        # (bound to a commit, awaiting first live hit), closed (the span
        # table the API serves).  OrderedDict -> drop-OLDEST on overflow.
        self._pending: OrderedDict = OrderedDict()
        self._awaiting: OrderedDict = OrderedDict()
        self._closed: OrderedDict = OrderedDict()
        self.spans_dropped_total = 0
        self.spans_closed_total = 0
        # Events that arrived without a controller stamp (resync replays):
        # excluded from the histograms, metered not guessed.
        self.unstamped_total = 0
        # The in-flight and last-completed commit transactions.
        self._open_commit: Optional[dict] = None
        self._last_commit: Optional[tuple[int, dict]] = None  # (gen, stamps)
        # The open / last commit's sub-spans (COMMIT_SUBSPANS): name ->
        # [ns, builds, build ns, bytes]; and the builds of each stage.
        self._open_subs: dict = {}
        self._last_subs: dict = {}
        self._stage_builds: dict = {}
        self._last_builds: dict = {}
        self._built = (0, 0)  # the ledger's totals at the last stamp
        self._cause = None  # the ledger's cause before the commit began
        # The engine constructor's span (construct_span): (seconds,
        # builds, build seconds), or None.
        self._construct = None
        # First-hit latch: highest bundle generation live traffic has
        # stepped under, and when.  One int compare on the hot step.
        self._hit_gen = -1
        self._hit_at = 0.0
        # Stamp-op counter: the maintenance `observability` task reads
        # the delta as this plane's accounted cost.
        self._stamps_total = 0
        self._stamps_taken = 0
        self.hist = {s: Histogram() for s in _HIST_STAGES}
        # The most recent elastic-mesh resize span (parallel/reshard.py):
        # migrate/certify/cutover stage durations telescoping to total,
        # the realization-span shape.  One slot — resizes are rare
        # operator/autoscaler events, not a table workload.
        self.last_resize = None

    def now(self) -> float:
        return float(self._clock())

    # -- agent-side stamps ---------------------------------------------------

    def note_unstamped(self) -> None:
        """An event with no controller stamp (ts=0: resync replay /
        filestore reload) left pending work: its realization latency is
        unknowable, so it is counted out of the histograms, not guessed
        into them."""
        self.unstamped_total += 1

    def policy_event(self, uid: str, gen: int, ts: float) -> None:
        """A stamped NetworkPolicy watch event arrived at the agent:
        open (or extend) the span for (uid, spec generation).  The
        EARLIEST controller stamp wins — re-deliveries and retries must
        lengthen the span, never shorten it."""
        self._stamps_total += 1
        key = (uid, int(gen))
        t_wire = max(float(ts), self.now())
        sp = self._pending.get(key)
        if sp is None:
            old = self._awaiting.get(key)
            if old is not None:
                if float(ts) <= old["commit"]["settle"]:
                    return  # re-delivery of the realization in flight;
                    # it adds nothing
                # Stamp POSTDATES the commit that realized the old
                # lifetime: uid reuse (delete/re-add) while the old span
                # still awaits its first hit.  Retire it metered — its
                # first-hit attribution would belong to the new lifetime.
                del self._awaiting[key]
                self.spans_dropped_total += 1
            old = self._closed.get(key)
            if old is not None:
                if float(ts) <= old["closed_at"]:
                    return  # re-delivery of a realization already closed
                # Controller stamp POSTDATES the close: the controller
                # restarts spec generations at 1 after a delete/re-add,
                # so this is a NEW lifetime of the uid reusing the key.
                # Retire the old span and trace the new realization.
                del self._closed[key]
            sp = {"uid": uid, "generation": int(gen),
                  "controller_ts": float(ts), "wire_ts": t_wire}
            self._pending[key] = sp
            while len(self._pending) > self.pending_slots:
                self._pending.popitem(last=False)
                self.spans_dropped_total += 1
        else:
            sp["controller_ts"] = min(sp["controller_ts"], float(ts))

    def realized(self) -> None:
        """The agent's sync() successfully applied state: every pending
        span rode the datapath's most recent commit transaction — bind
        them to its stage stamps and start waiting for the first live
        hit on that bundle generation."""
        if not self._pending:
            return
        self._stamps_total += 1
        if self._last_commit is None:
            # No commit recorded (tracer attached mid-flight): the spans
            # cannot be stage-attributed honestly; meter them out.
            self.spans_dropped_total += len(self._pending)
            self._pending.clear()
            return
        gen, stamps = self._last_commit
        for key, sp in self._pending.items():
            sp["bundle_generation"] = int(gen)
            sp["commit"] = dict(stamps)
            self._awaiting[key] = sp
            while len(self._awaiting) > self.pending_slots:
                self._awaiting.popitem(last=False)
                self.spans_dropped_total += 1
        self._pending.clear()
        if self._hit_gen >= gen:
            # Live traffic already stepped under this (or a newer)
            # bundle: the realization is visible now.
            self._close_up_to(self._hit_gen, self._hit_at)

    # -- commit-plane stamps (datapath/commit.py) ----------------------------

    def commit_begin(self) -> None:
        """A commit transaction entered its compile stage.  queue_wait
        ends here for every span this commit realizes."""
        self._stamps_total += 1
        self._open_commit = {"start": self.now()}
        self._open_subs = {name: [0, 0, 0, 0] for name, _ in COMMIT_SUBSPANS}
        self._stage_builds = {}
        led = build_ledger()
        self._built = (led.builds, led.build_ns)
        if self._cause is None:
            self._cause = led.cause
        led.cause = (f"commit.{_COMMIT_STAMPS[1]}", 0)

    def _commit_sub(self, name: str, span: "Span") -> None:
        """A CommitSpan closed inside the open commit (a sub-span may open
        more than once: rules, then Services, are both uploads)."""
        self._stamps_total += 1
        acc = self._open_subs[name]
        acc[0] += span.ns
        acc[1] += span.builds
        acc[2] += span.build_ns
        acc[3] += int(span.nbytes)

    def commit_stage(self, stage: str) -> None:
        """Stamp a completed commit stage (compile/canary/swap/settle),
        clamped monotonic against the previous stamp."""
        if self._open_commit is None:
            return
        self._stamps_total += 1
        prev = max(self._open_commit.values())
        self._open_commit[stage] = max(self.now(), prev)
        led = build_ledger()
        builds, build_ns = self._built
        self._stage_builds[stage] = (led.builds - builds,
                                     led.build_ns - build_ns)
        self._built = (led.builds, led.build_ns)
        k = min(_COMMIT_STAMPS.index(stage) + 1, len(_COMMIT_STAMPS) - 1)
        led.cause = (f"commit.{_COMMIT_STAMPS[k]}", 0)  # the next stage

    def note_construct(self, span: "Span") -> None:
        """The engine constructor's span closed (construct_span)."""
        self._construct = (span.ns * 1e-9, span.builds,
                           span.build_ns * 1e-9)

    def commit_done(self, gen: int) -> None:
        """The transaction settled at bundle generation `gen`: its stamps
        become the binding target for the next realized() batch."""
        oc = self._open_commit
        self._open_commit = None
        if oc is None:
            return
        self._stamps_total += 1
        # Backfill any stage a path legitimately skipped (a no-op delta
        # never swaps) so the telescoping invariant holds span-wide.
        t = oc["start"]
        for s in _COMMIT_STAMPS:
            t = oc[s] = max(oc.get(s, t), t)
        # A stage's sub-spans lie inside it whatever the two clocks did:
        # each is clamped to what the earlier ones left of the stage.
        left = {s: oc[s] - oc[p]
                for p, s in zip(_COMMIT_STAMPS, _COMMIT_STAMPS[1:])}
        subs = {}
        for name, stage in COMMIT_SUBSPANS:
            ns, builds, build_ns, nbytes = self._open_subs[name]
            sec = ns * 1e-9
            if stage is not None:
                sec = min(sec, left[stage])
                left[stage] -= sec
            subs[name] = (sec, builds, build_ns * 1e-9, nbytes)
        self._last_subs = subs
        self._last_builds = self._stage_builds  # commit_begin makes anew
        self._last_commit = (int(gen), oc)
        self._end_cause()

    def commit_abort(self) -> None:
        """The transaction rolled back: nothing realized, drop the
        stamps (the retry's own transaction re-stamps from compile)."""
        self._open_commit = None
        self._end_cause()

    def _end_cause(self) -> None:
        if self._cause is not None:
            build_ledger().cause = self._cause
            self._cause = None

    def last_commit(self) -> Optional[dict]:
        """Stage seconds of the last settled commit transaction, readable
        without a realization span (a direct `install_bundle` opens
        none): {"generation", "compile_s", "canary_s", "swap_s",
        "settle_s"}, telescoping to settle - start; beside them, in no
        sum, the sub-spans "<name>_s" of COMMIT_SUBSPANS (`rules`,
        `tables`, `upload` of compile, `oracle`, `walk` of canary,
        `digest` after settle), the counter "table_bytes", the engine
        constructor's "construct_s" (0.0 where no span was handed over),
        and "builds": span -> (executables built, their trace + lower +
        backend seconds) for `construct`, each stage and each sub-span.
        `compile` runs from commit_begin to the stamp after the engine
        built and uploaded the candidate (snapshot + host rule compile +
        upload); `canary` is the fresh-probe gate.  None before the first
        commit."""
        if self._last_commit is None:
            return None
        gen, stamps = self._last_commit
        out = {"generation": gen}
        for prev, stage in zip(_COMMIT_STAMPS, _COMMIT_STAMPS[1:]):
            out[f"{stage}_s"] = stamps[stage] - stamps[prev]
        construct = self._construct or (0.0, 0, 0.0)
        builds = {"construct": construct[1:]}
        for stage in _COMMIT_STAMPS[1:]:
            n, ns = self._last_builds.get(stage, (0, 0))
            builds[stage] = (n, ns * 1e-9)
        for name, (sec, n, build_s, nbytes) in self._last_subs.items():
            out[f"{name}_s"] = sec
            builds[name] = (n, build_s)
        out["table_bytes"] = self._last_subs["upload"][3]
        out["construct_s"] = construct[0]
        out["builds"] = builds
        return out

    # -- the first-hit latch (engines' step()) -------------------------------

    def first_hit(self, gen: int, batch_size: int = 0) -> None:
        """Hot-step latch: the caller is about to classify live traffic
        under bundle generation `gen`.  First call per generation stamps
        the latch and closes every span awaiting a generation <= gen;
        every later call is ONE int compare.  Pure host code — zero
        device ops, so step HLO is bit-identical with tracing disabled."""
        if gen <= self._hit_gen or batch_size <= 0:
            return
        self._stamps_total += 1
        t = self.now()
        self._hit_gen = int(gen)
        self._hit_at = t
        if self._awaiting:
            self._close_up_to(int(gen), t)

    def _close_up_to(self, gen: int, t_hit: float) -> None:
        done = [k for k, sp in self._awaiting.items()
                if sp["bundle_generation"] <= gen]
        for key in done:
            self._finish(self._awaiting.pop(key), t_hit)

    def _finish(self, sp: dict, t_hit: float) -> None:
        c = sp.pop("commit")
        # Telescoping stamp chain, clamped monotonic end to end: every
        # stage >= 0 and the stages sum EXACTLY to total.
        t0 = sp["controller_ts"]
        chain = [
            ("wire", max(sp["wire_ts"], t0)),
            ("queue_wait", c["start"]),
            ("compile", c["compile"]),
            ("canary", c["canary"]),
            ("swap", c["swap"]),
            ("settle", c["settle"]),
            ("first_hit", t_hit),
        ]
        stages, prev = {}, t0
        for name, t in chain:
            t = max(t, prev)
            stages[name] = t - prev
            prev = t
        sp["stages_s"] = stages
        sp["total_s"] = prev - t0
        sp["closed_at"] = prev
        for name, dt in stages.items():
            self.hist[name].observe(dt)
        self.hist["total"].observe(sp["total_s"])
        self.spans_closed_total += 1
        key = (sp["uid"], sp["generation"])
        self._closed[key] = sp
        self._closed.move_to_end(key)
        while len(self._closed) > self.span_slots:
            self._closed.popitem(last=False)  # drop-oldest CLOSED span:
            # served telemetry aging out of the bounded table, not loss
        if self._recorder is not None:
            self._recorder.emit(
                kind="realization", uid=sp["uid"], gen=sp["generation"],
                bundle_gen=sp["bundle_generation"],
                total_s=round(sp["total_s"], 6))

    # -- elastic-mesh resize spans (parallel/reshard.py) ---------------------

    def note_resize_span(self, span: dict) -> None:
        """Record a completed data-axis resize span so resize latency is
        measurable beside policy-realization latency (served in stats()
        as `last_resize`; the flight recorder's reshard-cutover event
        carries the same total on the journal clock)."""
        self._stamps_total += 1
        self.last_resize = dict(span)

    # -- maintenance accounting ----------------------------------------------

    def take_cost(self) -> int:
        """Stamp ops since the last take — the accounted cost the
        maintenance scheduler's `observability` task budgets."""
        d = self._stamps_total - self._stamps_taken
        self._stamps_taken = self._stamps_total
        return d

    # -- observability -------------------------------------------------------

    def spans(self, uid: Optional[str] = None) -> list[dict]:
        """Span-table rows, oldest first: closed spans plus the still
        in-flight ones (marked by state) so an operator mid-outage sees
        where a realization is STUCK, not just the ones that finished.

        Called from API handler threads while the engine thread stamps:
        a table iteration racing an insert/pop raises RuntimeError, so
        the read retries on a fresh view instead of locking the hot
        stamp path (best-effort empty after repeated conflicts)."""
        for _ in range(8):
            try:
                return self._spans_once(uid)
            except RuntimeError:
                continue
        return []

    def _spans_once(self, uid: Optional[str]) -> list[dict]:
        out = []
        for state, table in (("pending", self._pending),
                             ("awaiting_first_hit", self._awaiting),
                             ("closed", self._closed)):
            for sp in table.values():
                row = dict(sp)
                row.pop("commit", None)
                row["state"] = state
                out.append(row)
        if uid is not None:
            out = [r for r in out if r["uid"] == uid]
        return out

    def stats(self) -> dict:
        return {
            "stages": list(REALIZATION_STAGES),
            "pending": len(self._pending),
            "awaiting_first_hit": len(self._awaiting),
            "closed": len(self._closed),
            "span_slots": self.span_slots,
            "spans_closed_total": int(self.spans_closed_total),
            "spans_dropped_total": int(self.spans_dropped_total),
            "unstamped_total": int(self.unstamped_total),
            "first_hit_generation": int(self._hit_gen),
            "p99_s": (self.hist["total"].quantile(0.99)
                      if self.hist["total"].count else None),
            "last_resize": self.last_resize,
        }
