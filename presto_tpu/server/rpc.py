"""Unified RPC plane for every coordinator<->worker<->client HTTP call.

Reference parity: presto routes all intra-cluster traffic through one
airlift HttpClient with per-client config-driven timeouts, and treats
node failure detection / recoverable execution as coordinator duties
(SURVEY.md §2.5, §5.3). Here the single helper replaces the ad-hoc
``urllib.request.urlopen`` call sites (``tools/check_rpc_calls.py``
enforces that) and adds what raw urlopen lacks:

- per-call, config-driven timeouts on a **monotonic** clock,
- bounded retries with exponential backoff + **full jitter** for
  connection-level failures on idempotent calls (POSTs are never
  retried here — task creation is made idempotent one level up, where
  the coordinator mints a fresh task id per attempt),
- fault-plane hooks (:mod:`presto_tpu.utils.faults`) before every
  attempt, so chaos tests inject at the one choke point,
- ``rpc.*`` metrics (requests / failures / retries / time).

The module also owns :class:`CircuitBreaker` — per-peer health memory
(CLOSED -> OPEN after N consecutive failures -> one HALF_OPEN probe ->
CLOSED) that the coordinator keys by worker node id and folds into
scheduling next to the discovery TTL.
"""

from __future__ import annotations

import dataclasses
import json
import random
import threading
import time
import urllib.error
import urllib.request
from typing import List, Optional

from presto_tpu.utils import faults, tracing
from presto_tpu.utils.metrics import REGISTRY

#: connection-level failures eligible for retry. ``TimeoutError`` and
#: ``socket.timeout`` are OSErrors; ``HTTPError`` is excluded by
#: :func:`is_retryable` — the server answered, so re-sending cannot
#: change the outcome.
RETRYABLE_EXCS = (urllib.error.URLError, ConnectionError, OSError)

#: backoff jitter source when no seeded fault plane is active
_RNG = random.Random()


def backoff_rng() -> random.Random:
    """Full-jitter RNG: the fault plane's dedicated backoff stream
    when chaos is configured (deterministic schedules for seeded,
    single-threaded draws — concurrent threads still interleave),
    else the module default."""
    plane = faults.active()
    return plane.backoff_rng if plane is not None else _RNG


def is_retryable(exc: BaseException) -> bool:
    """Connection-level failure (dead socket, refused, timed out) —
    NOT an HTTP error response, which is an answer, not a failure."""
    return isinstance(exc, RETRYABLE_EXCS) and not isinstance(
        exc, urllib.error.HTTPError
    )


def is_task_recoverable(exc: BaseException) -> bool:
    """A failure that means the PEER cannot own the task rather than
    the task itself failing: any connection-level failure, a 404 on a
    task endpoint (the worker crashed + restarted under the same URI
    and lost the task), or a 503 (the worker is DRAINING and rejects
    new tasks). Recoverable by rescheduling on another worker; every
    other HTTP error is an execution failure that would fail anywhere."""
    if is_retryable(exc):
        return True
    return isinstance(exc, urllib.error.HTTPError) and exc.code in (
        404,
        503,
    )


@dataclasses.dataclass(frozen=True)
class RpcPolicy:
    """Per-call knobs, config-driven (reference: airlift HttpClient
    config keys)."""

    timeout_s: float = 30.0
    retries: int = 2
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    #: token-acked page-pull requests kept in flight per pull loop
    #: (``rpc.pull-depth``): 1 = strict request->ack->request; 2+
    #: overlaps the next page's network round trip with this page's
    #: deserialization (see :func:`pull_pages`)
    pull_depth: int = 2

    @staticmethod
    def from_config(config) -> "RpcPolicy":
        """Policy from NodeConfig ``rpc.*`` keys (defaults preserve the
        previously hardcoded 30 s request timeout)."""
        if config is None:
            return RpcPolicy()
        return RpcPolicy(
            timeout_s=float(config.get("rpc.request-timeout-s", 30.0)),
            retries=int(config.get("rpc.retries", 2)),
            backoff_base_s=float(config.get("rpc.backoff-base-s", 0.05)),
            backoff_max_s=float(config.get("rpc.backoff-max-s", 2.0)),
            pull_depth=int(config.get("rpc.pull-depth", 2)),
        )


DEFAULT_POLICY = RpcPolicy()

#: shared executor for pipelined page pulls: one process-wide pool
#: instead of a fresh ThreadPoolExecutor per pull (no thread churn per
#: task stream). Speculative fetches are plain bounded-timeout GETs
#: answered at once (only a pull's head request is held by the
#: producer, and that one runs on the pull's own thread) — no
#: inter-future dependencies, so a shared pool cannot deadlock or
#: starve; abandoned fetches finish within the rpc timeout and their
#: results are dropped.
_PULL_POOL = None
_PULL_POOL_LOCK = threading.Lock()
_PULL_POOL_WORKERS = 32

#: results long-poll: the header a puller sends with the request for
#: its head token (milliseconds), and the longest a producer holds a
#: request — well under ``RpcPolicy.timeout_s``, so a held request is
#: never taken for a dead peer
MAX_WAIT_HEADER = "X-Max-Wait"
PULL_MAX_WAIT_S = 1.0


def _pull_executor():
    global _PULL_POOL
    with _PULL_POOL_LOCK:
        if _PULL_POOL is None:
            from concurrent.futures import ThreadPoolExecutor

            _PULL_POOL = ThreadPoolExecutor(
                max_workers=_PULL_POOL_WORKERS,
                thread_name_prefix="page-pull",
            )
        return _PULL_POOL


def compute_backoff(
    attempt: int,
    policy: RpcPolicy = DEFAULT_POLICY,
    rng: Optional[random.Random] = None,
) -> float:
    """Exponential backoff with full jitter: uniform(0, min(cap,
    base * 2^attempt)). Full jitter (vs equal or none) de-correlates
    retry storms from many callers hitting one recovering peer."""
    cap = min(
        policy.backoff_max_s, policy.backoff_base_s * (2.0 ** attempt)
    )
    return (rng or backoff_rng()).uniform(0.0, cap)


@dataclasses.dataclass
class RpcResponse:
    """One successful HTTP exchange (2xx, including bodyless 204)."""

    status: int
    headers: object  # email.message.Message: case-insensitive .get
    body: bytes

    def json(self) -> dict:
        return json.loads(self.body) if self.body else {}


def call(
    method: str,
    url: str,
    body: Optional[bytes] = None,
    *,
    policy: RpcPolicy = DEFAULT_POLICY,
    timeout_s: Optional[float] = None,
    headers=None,
    traceparent: str = "",
    idempotent: Optional[bool] = None,
    wait_site: str = "",
) -> RpcResponse:
    """One RPC with bounded retries.

    Retries apply only to idempotent calls (default: every method but
    POST) and only for connection-level failures — an HTTP error
    status or an application exception propagates immediately. Sleeps
    between attempts follow :func:`compute_backoff`.

    ``wait_site``: callers on a statement's path name the round trip
    (a ``wait`` of utils/tracing.py, backoff sleeps included);
    heartbeats and announcers leave it empty and are not timed.
    """
    if wait_site:
        with tracing.wait(wait_site):
            return call(
                method, url, body, policy=policy, timeout_s=timeout_s,
                headers=headers, traceparent=traceparent,
                idempotent=idempotent,
            )
    if idempotent is None:
        idempotent = method != "POST"
    hdrs = dict(headers or ())
    if traceparent:
        hdrs["traceparent"] = traceparent
    timeout = policy.timeout_s if timeout_s is None else timeout_s
    attempts = (policy.retries if idempotent else 0) + 1
    last: Optional[BaseException] = None
    for attempt in range(attempts):
        if attempt:
            REGISTRY.counter("rpc.retries").update()
            time.sleep(compute_backoff(attempt - 1, policy))
        try:
            faults.maybe_inject_rpc(method, url)
            req = urllib.request.Request(
                url, data=body, method=method, headers=hdrs
            )
            with REGISTRY.timer("rpc.time").time():
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    out = RpcResponse(r.status, r.headers, r.read())
            REGISTRY.counter("rpc.requests").update()
            return out
        except Exception as e:
            REGISTRY.counter("rpc.failures").update()
            last = e
            if not (idempotent and is_retryable(e)):
                raise
    assert last is not None
    raise last


def call_json(method: str, url: str, obj=None, **kw) -> dict:
    """JSON-in/JSON-out convenience over :func:`call`."""
    hdrs = dict(kw.pop("headers", None) or ())
    hdrs.setdefault("Content-Type", "application/json")
    body = json.dumps(obj).encode() if obj is not None else None
    return call(method, url, body, headers=hdrs, **kw).json()


def pull_pages(
    uri: str,
    task_id: str,
    buffer: int,
    *,
    policy: RpcPolicy = DEFAULT_POLICY,
    deadline_s: float = 3600.0,
    traceparent: str = "",
    stall=None,
    timeout_msg: str = "",
    depth: Optional[int] = None,
    site: str = "rpc",
) -> list:
    """The token-acked exchange pull loop (one implementation for the
    coordinator's gather and the worker's shuffle read): GET
    ``/v1/task/{id}/results/{buffer}/{token}`` until ``X-Complete``,
    one token a page. Returns the deserialized pages.

    Long-poll: the request for the HEAD token (the next page the
    caller consumes) carries ``X-Max-Wait`` (:data:`MAX_WAIT_HEADER`,
    milliseconds), and the producer holds it until the page exists,
    the task is terminal, or the wait ran out — a page reaches the
    puller when it exists, not at the next tick of a poll. The head
    request runs on the calling thread (which blocks on its result
    anyway), so a held request never occupies the shared pool.

    Pipelining (``depth``, default ``policy.pull_depth``): tokens
    ``head+1 .. head+depth-1`` are requested speculatively on the pool,
    WITHOUT a max-wait (answered at once), so page N+1's round trip
    overlaps page N's decompress/deserialize when pages are buffered
    ahead. Every request carries an ``X-Ack`` header with the CONSUMED
    floor — the producer frees only pages the puller has actually
    received, so a speculative in-flight request can never free an
    unconsumed page (with depth 1 the floor equals the requested
    token, the historical ack-via-URL behavior).

    Stale-204 rule: a speculative answer was requested before the head
    reached its token. A page (200) or the end of the stream (204 with
    ``X-Complete``, final once true) is taken; its "no page yet" (204,
    not complete) is STALE by the time it is read — it is dropped and
    the token re-requested as the head, never stalled on.

    ``stall()`` runs on a FRESH non-complete 204 of the head request —
    the max-wait ran out; callers use it to poll task status and
    surface failures. A peer that answered well before the max-wait
    (it does not honour the header, or it is draining) is polled
    instead: a 20 ms sleep between requests. The deadline is
    monotonic. ``site`` ("coordinator", "worker") names the loop's
    waits: ``<site>.pull_get`` each GET (the held head request
    included), ``<site>.pull_result`` the caller blocked on a
    speculative GET, ``<site>.pull_idle`` the fallback sleep."""
    from presto_tpu.server import pages_wire

    depth = max(1, policy.pull_depth if depth is None else int(depth))
    out: list = []
    deadline = time.monotonic() + deadline_s
    # the held request must answer well inside the request timeout
    max_wait_s = min(PULL_MAX_WAIT_S, policy.timeout_s / 2.0)

    def fetch(t: int, ack: int, wait_ms: int = 0) -> RpcResponse:
        hdrs = {"X-Ack": str(ack)}
        if wait_ms > 0:
            hdrs[MAX_WAIT_HEADER] = str(wait_ms)
        return call(
            "GET",
            f"{uri}/v1/task/{task_id}/results/{buffer}/{t}",
            policy=policy,
            traceparent=traceparent,
            headers=hdrs,
            wait_site=site + ".pull_get",
        )

    token = 0
    inflight: dict = {}
    executor = _pull_executor() if depth > 1 else None
    try:
        while True:
            left_s = deadline - time.monotonic()
            if left_s < 0:
                raise TimeoutError(
                    timeout_msg
                    or f"pull of {task_id}[{buffer}] timed out"
                )
            # keep the speculative window full: (head, head+depth)
            for t in range(token + 1, token + depth):
                if t not in inflight:
                    inflight[t] = executor.submit(fetch, t, token)
            resp = None
            spec = inflight.pop(token, None)
            if spec is not None:
                with tracing.wait(site + ".pull_result"):
                    resp = spec.result()
                if (
                    resp.status != 200
                    and resp.headers.get("X-Complete") != "true"
                ):
                    resp = None  # stale "no page yet": ask again
            if resp is None:
                wait_s = min(max_wait_s, left_s)
                t_asked = time.monotonic()
                resp = fetch(token, token, int(wait_s * 1000))
                held_s = time.monotonic() - t_asked
            got_page = resp.status == 200
            if got_page:
                out.append(pages_wire.deserialize_page(resp.body))
                token += 1
            if resp.headers.get("X-Complete") == "true":
                return out
            if got_page:
                continue
            # a fresh 204, not complete: the max-wait ran out
            if stall is not None:
                stall()
            if held_s < wait_s / 2.0:
                with tracing.wait(site + ".pull_idle"):
                    time.sleep(0.02)
    finally:
        for f in inflight.values():
            f.cancel()


class CircuitBreaker:
    """Per-peer health memory (consecutive-failure scoring).

    CLOSED counts consecutive connection-level failures; at
    ``threshold`` the circuit OPENs and :meth:`allow` excludes the peer
    for ``open_s`` seconds (monotonic clock — wall jumps cannot reopen
    or pin it). After that, HALF_OPEN admits ONE probe: probe success
    re-CLOSEs, probe failure re-OPENs. A granted probe that never
    resolves (its query died elsewhere) re-arms after another
    ``open_s``, so a lost probe cannot wedge the breaker.

    ``transitions`` records every state change in order — the
    OPEN -> HALF_OPEN -> CLOSED cycle asserted by the chaos suite.
    """

    def __init__(self, threshold: int = 3, open_s: float = 5.0):
        self.threshold = threshold
        self.open_s = open_s
        self.state = "CLOSED"
        self.transitions: List[str] = []
        self._fails = 0
        self._opened = 0.0
        self._probe_at = 0.0
        self._lock = threading.Lock()

    def _to(self, state: str) -> bool:
        if state == self.state:
            return False
        self.state = state
        self.transitions.append(state)
        return True

    def peek(self) -> str:
        """Current state, without consuming a probe slot."""
        with self._lock:
            return self.state

    def allow(self) -> bool:
        """May this peer be scheduled to right now? OPEN -> HALF_OPEN
        promotion and probe-slot accounting happen here."""
        with self._lock:
            if self.state == "CLOSED":
                return True
            now = time.monotonic()
            if (
                self.state == "OPEN"
                and now - self._opened >= self.open_s
            ):
                self._to("HALF_OPEN")
                self._probe_at = 0.0
            if self.state == "HALF_OPEN" and (
                self._probe_at == 0.0
                or now - self._probe_at >= self.open_s
            ):
                self._probe_at = now
                return True
            return False

    def record_success(self) -> bool:
        """True when this success CLOSEd a half-open circuit."""
        with self._lock:
            self._fails = 0
            self._probe_at = 0.0
            return self._to("CLOSED")

    def record_failure(self) -> bool:
        """True when this failure OPENed the circuit."""
        with self._lock:
            self._fails += 1
            if self.state == "HALF_OPEN" or (
                self.state == "CLOSED" and self._fails >= self.threshold
            ):
                self._opened = time.monotonic()
                self._probe_at = 0.0
                return self._to("OPEN")
            return False
