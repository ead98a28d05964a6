"""Unified client resilience layer: deadlines, retries, health, hedging.

The tail-at-scale toolkit for every client datapath (reference analogs:
XceiverClientGrpc's per-request deadlines, the OM failover provider's
jittered retry policy, and the hedged-read pattern of Dean & Barroso's
"The Tail at Scale"). Four cooperating pieces, all consulted by
`ec_reader`, `ec_writer`, `replicated`, `ratis_client`, `native_dn`,
`re_encode` and `storage/reconstruction`:

- ``Deadline``: one wall-clock budget minted at the OPERATION boundary
  (key read/write, reconstruction job) and propagated ambiently —
  every hop below derives its socket/RPC timeout from the remaining
  budget via :func:`op_timeout` instead of hardcoding one. Nested
  boundaries inherit the outer deadline; a hop that finds the budget
  spent fails fast with ``DEADLINE_EXCEEDED`` instead of queueing more
  work behind a doomed call.

- ``RetryPolicy``: capped exponential backoff with FULL jitter
  (AWS-style ``sleep = uniform(0, min(cap, base * 2**attempt))``), so
  a fleet of clients retrying into a fresh Raft leader or a recovering
  datanode cannot thundering-herd it on synchronized ticks.

- ``PeerHealth`` / ``HealthRegistry``: per-datanode EWMA latency (+
  mean absolute deviation, giving a cheap P95 proxy), EWMA error rate,
  and a circuit breaker (CLOSED -> OPEN after N consecutive failures
  -> HALF_OPEN single probe after a cooldown -> CLOSED on probe
  success). Selection points — the EC reader's survivor choice, the
  EC writer's reallocation exclude list, reconstruction source order —
  consult it so known-bad peers are routed around WITHOUT burning a
  retry attempt, while a half-open probe keeps rediscovering recovered
  peers.

- ``HedgeGroup``: first-result-wins racing of a primary fetch against
  late-fired hedges. The hedge fires only after the primary has
  exceeded the peer's P95 EWMA (or the ``OZONE_TPU_HEDGE_MS`` floor),
  so steady-state traffic costs nothing extra; the loser's result is
  discarded exactly once (its transport hygiene — pooled-connection
  checkin or close — is the callable's own, already-tested contract).

Chaos parity: nothing here sleeps or times out through side channels —
stragglers injected by net/partition.py delay rules or the LD_PRELOAD
fault injector are seen exactly like real slow peers.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
import os
import random
import threading
import time
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor
from concurrent.futures import wait as _fwait
from dataclasses import dataclass
from enum import Enum
from typing import Callable, Iterable, Optional, Sequence

from ozone_tpu.storage.ids import StorageError
from ozone_tpu.utils.metrics import MetricsRegistry, registry
from ozone_tpu.utils.tracing import Tracer

#: StorageError code for a spent operation budget; transport-shaped
#: (like UNAVAILABLE) so failover/exclude machinery treats it as
#: "stop waiting", never as a data error
DEADLINE_EXCEEDED = "DEADLINE_EXCEEDED"

#: StorageError code for server admission pushback (bounded queue full,
#: tenant bucket drained, SLO shed — see ozone_tpu/admission). A
#: DELIBERATE answer from a healthy peer: retryable-with-server-hint,
#: never a transport fault (must not trip breakers or failover), and
#: counted apart from deadline_exceeded below.
SERVER_BUSY = "SERVER_BUSY"

#: every resilience signal lands in ONE registry so prometheus_text()
#: exposes the whole straggler story side by side
METRICS: MetricsRegistry = registry("client.resilience")


def server_pushback_floor(e: BaseException,
                          verb: str = "") -> Optional[float]:
    """Classify + account one server pushback. For a SERVER_BUSY
    StorageError: increments the ``server_busy`` counters (separate
    from ``deadline_exceeded`` — pushback is load, not a spent budget)
    and returns the server's Retry-After hint in seconds (0.0 when the
    message carries none) to use as the backoff FLOOR. Returns None for
    anything that is not server pushback."""
    if not (isinstance(e, StorageError) and e.code == SERVER_BUSY):
        return None
    from ozone_tpu.admission import retry_after_hint

    METRICS.counter("server_busy").inc()
    if verb:
        METRICS.counter(f"server_busy_{verb}").inc()
    return retry_after_hint(getattr(e, "msg", str(e))) or 0.0


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


# --------------------------------------------------------------- deadline
class Deadline:
    """Absolute wall-clock budget for one logical operation."""

    __slots__ = ("t_end", "op")

    def __init__(self, seconds: Optional[float], op: str = "op"):
        self.t_end = (math.inf if seconds is None or seconds <= 0
                      else time.monotonic() + seconds)
        self.op = op

    def remaining(self) -> float:
        return self.t_end - time.monotonic()

    def expired(self) -> bool:
        return self.remaining() <= 0

    def check(self, verb: str = "") -> None:
        """Fail fast when the budget is spent (counted per verb)."""
        if self.expired():
            METRICS.counter("deadline_exceeded").inc()
            if verb:
                METRICS.counter(f"deadline_exceeded_{verb}").inc()
            Tracer.instance().event("deadline_exceeded", op=self.op,
                                    verb=verb)
            raise StorageError(
                DEADLINE_EXCEEDED,
                f"operation {self.op} deadline exceeded"
                + (f" before {verb}" if verb else ""))

    def timeout(self, default: Optional[float],
                verb: str = "") -> Optional[float]:
        """Effective timeout for the next hop: the smaller of the hop's
        default and the remaining budget. Raises when already spent —
        a zero timeout would surface as a confusing transport error."""
        self.check(verb)
        left = self.remaining()
        if default is None:
            return None if math.isinf(left) else left
        return min(default, left)


_current: contextvars.ContextVar[Optional[Deadline]] = \
    contextvars.ContextVar("ozone_tpu_deadline", default=None)


def current() -> Optional[Deadline]:
    """The ambient deadline of this thread's operation, if any."""
    return _current.get()


@contextlib.contextmanager
def start(op: str, seconds: Optional[float] = None):
    """Operation-boundary scope: mint a Deadline and make it ambient.

    Created ONCE per operation — a nested boundary (a key read inside a
    reconstruction job) inherits the outer deadline instead of minting
    a fresh budget. ``seconds=None`` reads ``OZONE_TPU_OP_DEADLINE_S``
    (unset/0 = unbounded, the default: deadlines are an operator
    opt-in until tuned for the deployment)."""
    outer = _current.get()
    if outer is not None:
        yield outer
        return
    if seconds is None:
        seconds = _env_f("OZONE_TPU_OP_DEADLINE_S", 0.0)
    if seconds is None or seconds <= 0:
        # unbounded: install NO deadline (hops use their defaults)
        yield None
        return
    d = Deadline(seconds, op)
    tok = _current.set(d)
    try:
        yield d
    finally:
        _current.reset(tok)


@contextlib.contextmanager
def activate(deadline: Optional[Deadline]):
    """Re-establish a captured deadline on a WORKER thread (contextvars
    do not cross ThreadPoolExecutor boundaries): readers/writers capture
    `current()` at the operation edge and wrap their pool tasks."""
    if deadline is None:
        yield None
        return
    tok = _current.set(deadline)
    try:
        yield deadline
    finally:
        _current.reset(tok)


def op_timeout(default: Optional[float],
               verb: str = "") -> Optional[float]:
    """Deadline-derived timeout for one hop: `default` when no operation
    deadline is ambient, min(default, remaining) otherwise. The ONE
    sanctioned way to pick a socket/RPC timeout in the client layers —
    the resilience lint fails hardcoded literals elsewhere."""
    d = _current.get()
    if d is None:
        return default
    return d.timeout(default, verb)


def check_deadline(verb: str = "") -> None:
    d = _current.get()
    if d is not None:
        d.check(verb)


# ----------------------------------------------------------------- retry
@dataclass(frozen=True)
class RetryPolicy:
    """Capped exponential backoff with full jitter.

    ``backoff_s(attempt)`` draws uniform(0, min(cap, base * 2**attempt))
    — the AWS "full jitter" shape: the expected sleep still doubles per
    attempt, but two clients that failed together never sleep the same
    interval, so a recovered leader sees a trickle instead of a wave."""

    base_s: float = 0.25
    cap_s: float = 5.0
    max_attempts: int = 8
    #: 0.0 = FULL jitter (default). Raise to guarantee a fraction of
    #: the exponential ladder: 0.5 is AWS "equal jitter" — sleep =
    #: hi/2 + uniform(0, hi/2). Leader-failover loops use it so the
    #: retry window provably outlives an election (a full-jitter
    #: ladder can draw near-zero sleeps across EVERY attempt and burn
    #: the whole attempt budget mid-election), while retries still
    #: decorrelate across clients.
    floor_fraction: float = 0.0

    def backoff_s(self, attempt: int,
                  rng: Optional[random.Random] = None) -> float:
        hi = min(self.cap_s, self.base_s * (2.0 ** max(0, attempt)))
        lo = hi * min(1.0, max(0.0, self.floor_fraction))
        r = rng.uniform(lo, hi) if rng is not None \
            else random.uniform(lo, hi)
        return r

    def sleep(self, attempt: int,
              deadline: Optional[Deadline] = None,
              rng: Optional[random.Random] = None,
              floor_s: Optional[float] = None) -> bool:
        """Sleep the jittered backoff, clipped to the deadline. Returns
        False (without sleeping the full interval) when the policy's
        attempt cap is reached or the budget cannot cover another
        attempt — either way the caller stops retrying.

        ``floor_s`` is a server-supplied backoff floor (the Retry-After
        hint on a SERVER_BUSY pushback): the jittered draw is raised to
        at least the hint, because the server KNOWS when capacity will
        exist and retrying sooner is guaranteed wasted work."""
        if attempt >= self.max_attempts - 1:
            return False
        d = self.backoff_s(attempt, rng)
        if floor_s is not None and floor_s > 0:
            d = max(d, floor_s)
        if deadline is None:
            deadline = _current.get()
        if deadline is not None:
            left = deadline.remaining()
            if left <= 0:
                return False
            d = min(d, left)
        METRICS.counter("retries_slept").inc()
        Tracer.instance().event("retry", attempt=attempt + 1,
                                backoff_ms=round(d * 1e3, 1))
        time.sleep(d)
        return not (deadline is not None and deadline.expired())


def failover_retry_policy(attempts: int) -> RetryPolicy:
    """The ONE tuning for leader-failover loops (OM and SCM clients):
    equal-jitter capped exponential — jitter decorrelates clients that
    failed together, while the 0.5 floor keeps the summed window long
    enough to provably outlive an election on a slow rig (full jitter
    can draw near-zero sleeps across every attempt and burn the whole
    attempt budget mid-election; soak seed 31337 reproduced exactly
    that as total writer starvation)."""
    return RetryPolicy(base_s=0.2, cap_s=0.6, max_attempts=attempts,
                       floor_fraction=0.5)


# ---------------------------------------------------------------- health
#: StorageError codes that mean "the PEER (or the path to it) is
#: unwell" — only these feed the circuit breaker. Application-level
#: outcomes (NO_SUCH_BLOCK on a degraded group, CONTAINER_NOT_FOUND,
#: quota/token refusals, checksum mismatches) are answers from a
#: healthy peer and must never trip it. SERVER_BUSY is deliberately
#: absent too: admission pushback comes from a peer healthy enough to
#: refuse — tripping breakers (or rotating failover) on it would turn
#: graceful shedding into a cascading brownout.
TRANSPORT_FAULT_CODES = frozenset({"UNAVAILABLE", "TIMEOUT",
                                   "IO_EXCEPTION"})


def is_transport_fault(e: BaseException) -> bool:
    """Whether an exception should count against a peer's breaker:
    socket/lookup failures always; StorageError only for transport-
    shaped codes (DEADLINE_EXCEEDED is the OPERATION's state, not the
    peer's, and does not count). A verb-unsupported refusal travels as
    an IO_EXCEPTION-coded UNIMPLEMENTED (dn_client.batch_unsupported's
    downgrade signal) but is a healthy peer's answer, not a fault."""
    if isinstance(e, StorageError):
        if e.code == "IO_EXCEPTION" and "UNIMPLEMENTED" in e.msg:
            return False
        return e.code in TRANSPORT_FAULT_CODES
    return isinstance(e, (OSError, ConnectionError, KeyError))


class BreakerState(Enum):
    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"


#: EWMA smoothing for latency/error signals: ~last 10 samples dominate
_ALPHA = 0.2


class PeerHealth:
    """One peer's rolling health: EWMA latency + deviation (a cheap P95
    proxy: mean + 4 * mean-abs-deviation), EWMA error rate, and the
    circuit breaker. Thread-safe; writers are the datapath's own
    success/failure edges, readers the selection points."""

    def __init__(self, peer: str, open_after: int, reset_s: float):
        self.peer = peer
        self._open_after = max(1, int(open_after))
        self._reset_s = reset_s
        self._lock = threading.Lock()
        self.ewma_s: Optional[float] = None
        self.ewma_dev_s: float = 0.0
        self.error_rate: float = 0.0
        self.consecutive_failures = 0
        self.samples = 0
        self._state = BreakerState.CLOSED
        self._opened_at = 0.0
        self._probe_claimed = False
        self._probe_at = 0.0

    # ------------------------------------------------------- observations
    def record_success(self, latency_s: float) -> None:
        with self._lock:
            if self.ewma_s is None:
                self.ewma_s = latency_s
            else:
                dev = abs(latency_s - self.ewma_s)
                self.ewma_dev_s += _ALPHA * (dev - self.ewma_dev_s)
                self.ewma_s += _ALPHA * (latency_s - self.ewma_s)
            self.error_rate += _ALPHA * (0.0 - self.error_rate)
            self.samples += 1
            self.consecutive_failures = 0
            if self._state is not BreakerState.CLOSED:
                # half-open probe succeeded (or an in-flight call from
                # before the trip landed): the peer is back
                self._state = BreakerState.CLOSED
                self._probe_claimed = False
                METRICS.counter("breaker_closed").inc()
                Tracer.instance().event("breaker_closed", peer=self.peer)

    def record_failure(self) -> None:
        with self._lock:
            self.error_rate += _ALPHA * (1.0 - self.error_rate)
            self.samples += 1
            self.consecutive_failures += 1
            if self._state is BreakerState.HALF_OPEN:
                # the single probe failed: back to OPEN, fresh cooldown
                self._state = BreakerState.OPEN
                self._opened_at = time.monotonic()
                self._probe_claimed = False
                METRICS.counter("breaker_reopened").inc()
                Tracer.instance().event("breaker_reopened",
                                        peer=self.peer)
            elif (self._state is BreakerState.CLOSED
                  and self.consecutive_failures >= self._open_after):
                self._state = BreakerState.OPEN
                self._opened_at = time.monotonic()
                METRICS.counter("breaker_opened").inc()
                Tracer.instance().event("breaker_opened", peer=self.peer)

    # ---------------------------------------------------------- decisions
    @property
    def state(self) -> BreakerState:
        with self._lock:
            self._maybe_half_open()
            return self._state

    def _maybe_half_open(self) -> None:
        if (self._state is BreakerState.OPEN
                and time.monotonic() - self._opened_at >= self._reset_s):
            self._state = BreakerState.HALF_OPEN
            self._probe_claimed = False
            METRICS.counter("breaker_half_open").inc()

    def allow(self) -> bool:
        """May this peer be SELECTED for traffic right now? CLOSED:
        yes. OPEN: no until the cooldown. HALF_OPEN: exactly one caller
        gets the probe; everyone else keeps routing around until the
        probe's outcome lands."""
        with self._lock:
            self._maybe_half_open()
            if self._state is BreakerState.CLOSED:
                return True
            if self._state is BreakerState.HALF_OPEN:
                now = time.monotonic()
                # one probe per reset window: a claimed probe whose
                # outcome never landed (claimer chose another peer, or
                # the call is still in flight past the window) expires,
                # so the peer can never be wedged half-open forever
                if not self._probe_claimed \
                        or now - self._probe_at >= self._reset_s:
                    self._probe_claimed = True
                    self._probe_at = now
                    return True
            return False

    def p95_s(self) -> Optional[float]:
        """EWMA-derived tail estimate; None until a sample lands."""
        with self._lock:
            if self.ewma_s is None:
                return None
            return self.ewma_s + 4.0 * self.ewma_dev_s


class HealthRegistry:
    """peer id -> PeerHealth, shared per client factory (and process-
    default for components constructed without one)."""

    def __init__(self, open_after: Optional[int] = None,
                 reset_s: Optional[float] = None,
                 hedge_floor_s: Optional[float] = None):
        self.open_after = int(open_after if open_after is not None
                              else _env_f("OZONE_TPU_BREAKER_FAILURES", 5))
        self.reset_s = (reset_s if reset_s is not None
                        else _env_f("OZONE_TPU_BREAKER_RESET_S", 10.0))
        #: hedge-delay floor; OZONE_TPU_HEDGE_MS overrides (milliseconds)
        self.hedge_floor_s = (
            hedge_floor_s if hedge_floor_s is not None
            else _env_f("OZONE_TPU_HEDGE_MS", 50.0) / 1000.0)
        self._peers: dict[str, PeerHealth] = {}
        self._lock = threading.Lock()

    def get(self, peer: str) -> PeerHealth:
        with self._lock:
            h = self._peers.get(peer)
            if h is None:
                h = self._peers[peer] = PeerHealth(
                    peer, self.open_after, self.reset_s)
            return h

    # convenience edges -------------------------------------------------
    def success(self, peer: str, latency_s: float) -> None:
        self.get(peer).record_success(latency_s)

    def failure(self, peer: str) -> None:
        self.get(peer).record_failure()

    def observe(self, peer: str, fn: Callable, *a, **kw):
        """Run fn(*a, **kw) and fold its outcome into the peer's health.
        Only transport-shaped failures (is_transport_fault) count
        against the breaker; an application-level error still records a
        SUCCESS sample (the peer answered) before propagating."""
        t0 = time.monotonic()
        try:
            out = fn(*a, **kw)
        except BaseException as e:  # noqa: BLE001 - classify + re-raise
            d = _current.get()
            if d is not None and d.expired():
                # the hop's timeout was shrunk by a (now-)spent
                # operation budget: the peer never had a fair chance —
                # record NOTHING, or deadline starvation would open
                # breakers on healthy peers cluster-wide
                pass
            elif is_transport_fault(e):
                self.failure(peer)
            else:
                self.success(peer, time.monotonic() - t0)
            raise
        self.success(peer, time.monotonic() - t0)
        return out

    def allow(self, peer: str) -> bool:
        return self.get(peer).allow()

    def usable(self, peer: str) -> bool:
        """Non-claiming breaker check for SELECTION contexts (ordering,
        spare counting): anything not currently OPEN is usable. Unlike
        allow() this never consumes the half-open probe, so a peer can
        never be starved of its recovery probe by callers that were
        only comparing candidates."""
        ok = self.get(peer).state is not BreakerState.OPEN
        if not ok:
            METRICS.counter("breaker_skips").inc()
            Tracer.instance().event("breaker_skip", peer=peer)
        return ok

    def is_open(self, peer: str) -> bool:
        with self._lock:
            h = self._peers.get(peer)
        return h is not None and h.state is BreakerState.OPEN

    def open_peers(self) -> list[str]:
        """Peers whose breaker refuses traffic RIGHT NOW (OPEN and still
        cooling down) — the EC writer folds these into its allocation
        exclude list so a reallocation never lands on a tripped peer."""
        with self._lock:
            peers = list(self._peers.values())
        return [h.peer for h in peers if h.state is BreakerState.OPEN]

    def preferred(self, peers: Sequence[str]) -> list[str]:
        """Selection order: breaker-usable peers first (stable-sorted
        fastest EWMA first, unknowns keeping their position), tripped
        peers last as the only-remaining-choice fallback. Uses the
        non-claiming check — ordering candidates must not consume
        half-open probes."""
        def key(i_p):
            i, p = i_p
            h = self.get(p)
            lat = h.ewma_s if h.ewma_s is not None else 0.0
            return (h.state is BreakerState.OPEN, lat, i)

        return [p for _, p in sorted(enumerate(peers), key=key)]

    def hedge_delay_s(self, peer: str) -> float:
        """How long a fetch from `peer` may run before a hedge fires:
        its P95 EWMA, floored by OZONE_TPU_HEDGE_MS (cold peers have no
        EWMA yet and get the floor)."""
        p95 = self.get(peer).p95_s()
        return max(self.hedge_floor_s, p95 or 0.0)


_default_registry: Optional[HealthRegistry] = None
_default_lock = threading.Lock()


def default_registry() -> HealthRegistry:
    """Process-wide registry for components built without a factory."""
    global _default_registry
    with _default_lock:
        if _default_registry is None:
            _default_registry = HealthRegistry()
        return _default_registry


def reset_for_tests() -> None:
    """Drop the process-default registry (fresh breakers per test)."""
    global _default_registry
    with _default_lock:
        _default_registry = None


# --------------------------------------------------------------- hedging
#: shared hedge executor. NOTE it carries PRIMARIES too, not just the
#: rare hedges (a racer needs its primary interruptible-by-abandonment,
#: which blocking socket IO is not) — so it must be sized for the
#: process's expected read concurrency, not the hedge rate.
#: OZONE_TPU_HEDGE_THREADS overrides; daemon threads so a straggling
#: loser can never hold process exit.
_hedge_pool: Optional[ThreadPoolExecutor] = None
_hedge_pool_lock = threading.Lock()


def _hedge_executor() -> ThreadPoolExecutor:
    global _hedge_pool
    with _hedge_pool_lock:
        if _hedge_pool is None:
            _hedge_pool = ThreadPoolExecutor(
                max_workers=max(4, int(_env_f("OZONE_TPU_HEDGE_THREADS",
                                              32.0))),
                thread_name_prefix="hedge")
        return _hedge_pool


class HedgeWinner:
    """Outcome of a hedged race: the single consumed result."""

    __slots__ = ("value", "index", "hedged")

    def __init__(self, value, index: int, hedged: bool):
        self.value = value
        self.index = index  # 0 = primary, 1.. = hedge rank
        self.hedged = hedged  # True when a hedge was FIRED (won or not)


class HedgeGroup:
    """Race a primary callable against hedges, first success wins.

    The primary runs immediately; each hedge fires only after
    ``delay_s`` without a primary result. EXACTLY ONE result is
    consumed; completed losers' return values are discarded (their
    transport hygiene — returning a pooled connection or closing an
    errored one — is the callable's own contract, which is why both
    the winner's and the loser's connections stay clean). Pending
    losers are left to finish on the daemon hedge pool and their
    results dropped on arrival."""

    def __init__(self, metrics: MetricsRegistry = METRICS,
                 executor: Optional[ThreadPoolExecutor] = None):
        self.metrics = metrics
        self._executor = executor

    def run(self, primary: Callable[[], object],
            hedges: Iterable[Callable[[], object]] = (),
            delay_s: float = 0.05,
            deadline: Optional[Deadline] = None) -> HedgeWinner:
        if deadline is None:
            deadline = _current.get()
        ex = self._executor or _hedge_executor()
        todo = list(hedges)
        futs: dict[Future, int] = {}
        fired = 0
        errors: list[BaseException] = []

        def fire(fn: Callable[[], object], idx: int) -> None:
            if idx > 0:
                self.metrics.counter("hedges_fired").inc()
                Tracer.instance().event("hedge_fired", idx=idx)
            # the context is made as the branch is handed over: its
            # hand-off's wait then leaves the hedge delay out
            futs[ex.submit(self._wrap(
                fn, deadline, Tracer.instance().handoff()))] = idx

        fire(primary, 0)
        while True:
            if not futs:
                if not todo:
                    raise errors[-1]  # every branch failed: surface last
                fired += 1
                fire(todo.pop(0), fired)
                continue
            budget = delay_s if todo else None
            if deadline is not None:
                deadline.check("hedge")
                left = deadline.remaining()
                if not math.isinf(left):
                    budget = left if budget is None \
                        else min(budget, left)
            done, _pending = _fwait(list(futs), timeout=budget,
                                    return_when=FIRST_COMPLETED)
            failed_this_round = False
            for f in done:
                idx = futs.pop(f)
                err = f.exception()
                if err is None:
                    # first success wins; pending losers are abandoned
                    # on the daemon pool, their results discarded
                    if idx > 0:
                        self.metrics.counter("hedges_won").inc()
                        Tracer.instance().event("hedge_won", idx=idx)
                    return HedgeWinner(f.result(), idx, fired > 0)
                errors.append(err)
                failed_this_round = True
            if todo and (failed_this_round or not done):
                # primary past its grace window, or a branch failed
                # outright: bring the next hedge into the race
                fired += 1
                fire(todo.pop(0), fired)

    @staticmethod
    def _wrap(fn: Callable[[], object], deadline: Optional[Deadline],
              trace_ctx: str = ""):
        def run():
            # hedge branches run on the shared daemon pool: both the
            # deadline and the trace context must travel explicitly
            with activate(deadline), Tracer.instance().activate(trace_ctx):
                return fn()

        return run


def hedged_call(primary: Callable[[], object],
                hedges: Iterable[Callable[[], object]],
                delay_s: float) -> HedgeWinner:
    """One-shot convenience over a shared HedgeGroup."""
    return HedgeGroup().run(primary, hedges, delay_s)
