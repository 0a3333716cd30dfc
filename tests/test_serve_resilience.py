"""Serve resilience plane — pure units, no cluster (ISSUE 8).

Covers the state machines the request path composes: deadline budget
accounting across retries, breaker trip/half-open/close transitions,
admission shed-oldest ordering, and breaker-aware replica selection
(drain-marked replicas never reach the routing table — the controller
removes them — so exclusion here is tried-replica + breaker-state)."""

import random
import threading
import time

import pytest

from ray_tpu.core.errors import (ActorDiedError, NodeDiedError,
                                 ObjectLostError, TaskError,
                                 WorkerCrashedError, make_task_error)
from ray_tpu.serve.resilience import (AdmissionGate, BreakerBoard,
                                      CircuitBreaker, Deadline,
                                      RequestShedError,
                                      RequestTimeoutError,
                                      StreamInterruptedError,
                                      is_system_fault, select_replica)


# ------------------------------------------------------------ deadline
def test_deadline_budget_accounting_across_retries():
    """One budget spans every failover retry: each attempt sees only
    what the previous attempts left over."""
    t = [100.0]
    d = Deadline(10.0, clock=lambda: t[0])
    assert d.bounded and not d.expired
    assert d.remaining() == pytest.approx(10.0)
    t[0] += 4.0   # attempt 1 burned 4s
    assert d.remaining() == pytest.approx(6.0)
    t[0] += 5.0   # attempt 2 burned 5s more
    assert d.remaining() == pytest.approx(1.0)
    assert not d.expired
    t[0] += 1.5
    assert d.expired
    assert d.remaining() == 0.0  # never negative


def test_deadline_unbounded_and_cap():
    d = Deadline(0.0, clock=lambda: 0.0)
    assert not d.bounded and not d.expired
    assert d.remaining(cap=120.0) == 120.0
    b = Deadline(500.0, clock=lambda: 0.0)
    assert b.remaining(cap=60.0) == 60.0  # clamped per-attempt


# ------------------------------------------------------------- breaker
def _breaker(clock, threshold=3, reset_s=2.0):
    br = CircuitBreaker(failure_threshold=threshold, reset_s=reset_s,
                        clock=clock, rng=random.Random(0))
    br._backoff.jitter = 0.0  # deterministic windows for the test
    return br


def test_breaker_trips_after_consecutive_failures_only():
    t = [0.0]
    br = _breaker(lambda: t[0])
    assert not br.record_failure()
    assert not br.record_failure()
    br.record_success()           # success resets the streak
    assert not br.record_failure()
    assert not br.record_failure()
    assert br.record_failure()    # third CONSECUTIVE -> trip
    assert br.state == "open"
    assert not br.allow()


def test_breaker_half_open_probe_and_close():
    t = [0.0]
    br = _breaker(lambda: t[0], reset_s=2.0)
    for _ in range(3):
        br.record_failure()
    assert br.state == "open"
    t[0] = 1.9
    assert not br.allow()         # window not elapsed
    t[0] = 2.1
    assert br.allow()             # exactly one half-open probe
    assert br.state == "half_open"
    assert not br.allow()         # second request still blocked
    assert br.record_success()    # probe succeeded -> closed
    assert br.state == "closed"
    assert br.allow()


def test_breaker_reopen_backs_off_exponentially():
    t = [0.0]
    br = _breaker(lambda: t[0], reset_s=2.0)
    for _ in range(3):
        br.record_failure()
    first_window = br._open_for
    t[0] = first_window + 0.1
    assert br.allow()             # half-open probe
    assert br.record_failure()    # probe FAILED -> reopen, longer
    assert br.state == "open"
    assert br._open_for > first_window
    # close resets the schedule
    t[0] += br._open_for + 0.1
    assert br.allow()
    br.record_success()
    for _ in range(3):
        br.record_failure()
    assert br._open_for == pytest.approx(first_window)


def test_breaker_board_transitions_and_prune():
    events = []
    board = BreakerBoard(failure_threshold=2, reset_s=60.0,
                         on_transition=lambda k, s: events.append(
                             (k, s)))
    assert board.allow("a")
    board.record_failure("a")
    board.record_failure("a")
    assert board.state("a") == "open"
    assert events == [("a", "open")]
    assert not board.allow("a")
    # Pruning a replaced replica key drops its failure history.
    board.record_failure("b")
    board.prune(["b"])
    assert board.state("a") == "closed"  # fresh breaker if re-seen
    assert board.snapshot().keys() == {"b"}


# ------------------------------------------------------ admission gate
def test_admission_gate_shed_oldest_ordering():
    """When the queue is full the OLDEST waiter is shed, newest kept:
    under overload the stalest request (most likely already timed out
    client-side) is the one rejected."""
    gate = AdmissionGate(max_queued=2, capacity=lambda: 1)
    holder = gate.admit()                 # occupies the only slot
    results = {}

    def waiter(name):
        try:
            with gate.admit(Deadline(10.0), "dep"):
                results[name] = "served"
        except RequestShedError:
            results[name] = "shed"

    threads = []
    for name in ("oldest", "middle"):
        th = threading.Thread(target=waiter, args=(name,))
        th.start()
        threads.append(th)
        deadline = time.time() + 5
        while gate.depth() < len(threads) and time.time() < deadline:
            time.sleep(0.01)
    assert gate.depth() == 2
    th = threading.Thread(target=waiter, args=("newest",))
    th.start()
    threads.append(th)
    deadline = time.time() + 5
    while "oldest" not in results and time.time() < deadline:
        time.sleep(0.01)
    assert results.get("oldest") == "shed"
    holder.release()                      # slots free -> FIFO serve
    for th in threads:
        th.join(10)
    assert results == {"oldest": "shed", "middle": "served",
                       "newest": "served"}
    assert gate.depth() == 0 and gate.active() == 0


def test_admission_gate_deadline_expiry_while_queued():
    gate = AdmissionGate(max_queued=4, capacity=lambda: 1)
    holder = gate.admit()
    t0 = time.time()
    with pytest.raises(RequestTimeoutError):
        gate.admit(Deadline(0.3), "dep")
    assert time.time() - t0 < 5.0
    assert gate.depth() == 0              # expired ticket removed
    holder.release()


def test_admission_gate_uses_grown_capacity():
    """Replica scale-up must drain the queue immediately: waiters
    re-attempt promotion against the CURRENT capacity instead of
    staying pinned at the concurrency the queue formed under."""
    cap = [1]
    gate = AdmissionGate(max_queued=8, capacity=lambda: cap[0])
    holder = gate.admit()
    admitted = []

    def waiter(i):
        with gate.admit(Deadline(10.0), "dep"):
            admitted.append(i)
            time.sleep(0.3)

    threads = [threading.Thread(target=waiter, args=(i,))
               for i in range(4)]
    for th in threads:
        th.start()
    deadline = time.time() + 5
    while gate.depth() < 4 and time.time() < deadline:
        time.sleep(0.01)
    assert gate.depth() == 4 and not admitted
    cap[0] = 5            # scale-up: capacity grows with NO release
    deadline = time.time() + 5
    while len(admitted) < 4 and time.time() < deadline:
        time.sleep(0.05)
    assert sorted(admitted) == [0, 1, 2, 3], admitted
    for th in threads:
        th.join(10)
    holder.release()
    assert gate.active() == 0 and gate.depth() == 0


def test_admission_gate_disabled_and_unbounded_capacity():
    # max_queued=0 disables the gate entirely.
    gate = AdmissionGate(max_queued=0, capacity=lambda: 1)
    tickets = [gate.admit() for _ in range(10)]
    for tk in tickets:
        tk.release()
    # capacity 0 = unbounded: no queueing either.
    gate2 = AdmissionGate(max_queued=2, capacity=lambda: 0)
    with gate2.admit(Deadline(1.0)):
        with gate2.admit(Deadline(1.0)):
            assert gate2.depth() == 0


# ----------------------------------------------------- fault classes
def test_system_faults_vs_user_exceptions():
    assert is_system_fault(ActorDiedError("abc", "died"))
    assert is_system_fault(WorkerCrashedError("crashed"))
    assert is_system_fault(ObjectLostError("deadbeef"))
    assert is_system_fault(NodeDiedError("node gone"))
    # User exceptions — including their TaskError duals — are NEVER
    # system faults: they must surface exactly once, not retry.
    assert not is_system_fault(ValueError("user bug"))
    dual = make_task_error("ValueError('user bug')", "tb",
                           ValueError("user bug"))
    assert isinstance(dual, TaskError)
    assert not is_system_fault(dual)
    assert not is_system_fault(TimeoutError("slow"))


def test_typed_errors_pickle_roundtrip():
    import pickle

    for e in (RequestShedError("dep", 5),
              RequestTimeoutError("dep", 1.5),
              StreamInterruptedError("dep", "ActorDiedError(...)", 7)):
        e2 = pickle.loads(pickle.dumps(e))
        assert type(e2) is type(e)
        assert str(e2) == str(e)


# ----------------------------------------------------- replica select
class _Rep:
    def __init__(self, key):
        self._key = key
        self.actor_id = self

    def hex(self):
        return self._key


def test_select_replica_prefers_low_inflight_and_skips_excluded():
    board = BreakerBoard(failure_threshold=3, reset_s=60.0)
    reps = [_Rep("a"), _Rep("b")]
    rng = random.Random(0)
    sel = select_replica(reps, board, {"a": 5, "b": 0}, rng=rng)
    assert sel is not None and sel[1] == "b"
    # The replica a failover already tried is excluded...
    sel = select_replica(reps, board, {}, exclude={"b"}, rng=rng)
    assert sel[1] == "a"
    # ...and excluding everything yields None (caller widens).
    assert select_replica(reps, board, {}, exclude={"a", "b"},
                          rng=rng) is None


def test_select_replica_walks_past_open_breakers():
    """An OPEN breaker black-holes its replica: selection falls
    through to the next candidate, and a fully-open board selects
    nothing (the router surfaces 503/UNAVAILABLE)."""
    board = BreakerBoard(failure_threshold=1, reset_s=60.0)
    reps = [_Rep("a"), _Rep("b"), _Rep("c")]
    board.record_failure("a")            # trip a
    rng = random.Random(1)
    for _ in range(16):
        sel = select_replica(reps, board, {}, rng=rng)
        assert sel[1] in ("b", "c")      # a is never chosen
    board.record_failure("b")
    board.record_failure("c")
    assert select_replica(reps, board, {}, rng=rng) is None


def test_select_replica_consumes_probe_only_for_chosen():
    """A half-open breaker's single probe slot must not be burned on
    a candidate the router then discards."""
    t = [0.0]
    board = BreakerBoard(failure_threshold=1, reset_s=1.0,
                         clock=lambda: t[0])
    reps = [_Rep("a")]
    board.record_failure("a")
    t[0] = 10.0                          # open window elapsed
    sel = select_replica(reps, board, {}, rng=random.Random(0))
    assert sel[1] == "a"                 # admitted as the probe
    # The probe slot is consumed: a second concurrent request is NOT
    # routed to the half-open replica.
    assert select_replica(reps, board, {},
                          rng=random.Random(0)) is None
    board.record_success("a")            # probe succeeded
    assert select_replica(reps, board, {},
                          rng=random.Random(0))[1] == "a"


def test_drain_marked_replica_excluded_from_routing_table():
    """Replica bleed-off on drain: the serve controller REMOVES a
    draining node's replica from the routable set it pushes to
    handles — routing exclusion is the absence from the table, so no
    selection over the post-bleed table can ever pick it."""
    board = BreakerBoard(failure_threshold=3, reset_s=60.0)
    table = [_Rep("live1"), _Rep("drainme"), _Rep("live2")]
    bled_table = [r for r in table if r.actor_id.hex() != "drainme"]
    rng = random.Random(2)
    picked = {select_replica(bled_table, board, {}, rng=rng)[1]
              for _ in range(32)}
    assert picked == {"live1", "live2"}
