"""Admission control: per-tenant token buckets, weighted fair queueing,
bounded queues + queue-wait timeouts, and the degradation ladder
(core/admission.py).  Everything runs against a fake clock, so rate and
timeout behaviour is deterministic.

Covers the overload-protection contract: every rejection is a *typed*
429/503 with a Retry-After hint, queued work expires instead of hanging,
release order tracks tenant weights (Jain-fair), and the ``/stats``
snapshot stays consistent while handler threads hammer submit/poll.
"""
import threading

import pytest

from repro.core.admission import (LEVEL_DRAINING, LEVEL_NORMAL,
                                  LEVEL_SHED_ALL, LEVEL_SHED_BULK,
                                  AdmissionController, Overloaded,
                                  RateLimited, TenantConfig, TokenBucket,
                                  jain_index)
from repro.core.request import Request, SamplingParams


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _req(tenant="default", prompt_len=8, interactive=False):
    return Request(prompt_tokens=list(range(prompt_len)),
                   sampling=SamplingParams(max_tokens=4),
                   tenant=tenant,
                   priority=5 if interactive else 0,
                   deadline_ms=500.0 if interactive else None)


def _ctl(clock, **kw):
    kw.setdefault("max_queue_depth", 64)
    kw.setdefault("queue_timeout_s", 10.0)
    return AdmissionController(clock=clock, **kw)


# --------------------------------------------------------------------------- #
# token buckets
# --------------------------------------------------------------------------- #
def test_token_bucket_refills_at_rate():
    b = TokenBucket(rate=2.0, burst=4.0)
    assert b.try_take(4.0, now=0.0)         # burst drained
    assert not b.try_take(1.0, now=0.0)
    assert b.time_until(1.0, now=0.0) == pytest.approx(0.5)
    assert b.try_take(1.0, now=0.5)         # 0.5s * 2/s = 1 token back
    assert TokenBucket(rate=0.0, burst=0.0).try_take(1e9, now=0.0)  # disabled


def test_rps_limit_rejects_with_retry_after():
    clock = FakeClock()
    ctl = _ctl(clock, tenants={
        "t": TenantConfig(rps=1.0, burst_requests=2.0)})
    ctl.submit(_req("t"))
    ctl.submit(_req("t"))
    with pytest.raises(RateLimited) as ei:
        ctl.submit(_req("t"))
    assert ei.value.status == 429
    assert ei.value.code == "rate_limited"
    assert 0 < ei.value.retry_after <= 1.0
    clock.advance(1.0)                      # bucket refills one request
    ctl.submit(_req("t"))


def test_tps_limit_counts_prompt_tokens():
    clock = FakeClock()
    ctl = _ctl(clock, tenants={
        "t": TenantConfig(tps=10.0, burst_tokens=10.0)})
    ctl.submit(_req("t", prompt_len=8))
    with pytest.raises(RateLimited) as ei:
        ctl.submit(_req("t", prompt_len=8))
    assert "tokens/s" in str(ei.value)
    # a rejected request must not have burned the budget it was denied
    clock.advance(0.7)                      # 7 tokens back -> 9 available
    ctl.submit(_req("t", prompt_len=8))


def test_rate_limits_are_per_tenant():
    clock = FakeClock()
    ctl = _ctl(clock, tenants={
        "limited": TenantConfig(rps=1.0, burst_requests=1.0)})
    ctl.submit(_req("limited"))
    with pytest.raises(RateLimited):
        ctl.submit(_req("limited"))
    ctl.submit(_req("free"))                # other tenants unaffected


# --------------------------------------------------------------------------- #
# weighted fair queueing
# --------------------------------------------------------------------------- #
def test_release_order_tracks_weights():
    clock = FakeClock()
    ctl = _ctl(clock, tenants={"a": TenantConfig(weight=2.0),
                               "b": TenantConfig(weight=1.0)})
    for _ in range(12):
        ctl.submit(_req("a"))
        ctl.submit(_req("b"))
    ready, expired = ctl.poll(capacity=9)
    assert not expired
    by = {"a": 0, "b": 0}
    for r in ready:
        by[r.tenant] += 1
    assert by == {"a": 6, "b": 3}           # exactly the 2:1 weight split
    shares = [by["a"] / 2.0, by["b"] / 1.0]
    assert jain_index(shares) == pytest.approx(1.0)


def test_idle_tenant_joins_at_current_vtime_not_zero():
    clock = FakeClock()
    ctl = _ctl(clock)
    for _ in range(16):
        ctl.submit(_req("bulk", prompt_len=32))
    ctl.poll(capacity=8)                    # bulk's vtime is far along
    ctl.submit(_req("newcomer", prompt_len=8))
    ready, _ = ctl.poll(capacity=2)
    # SFQ join rule: the newcomer starts at the backlogged minimum, so its
    # first request releases immediately instead of waiting out the
    # virtual-time lead bulk built up — but it gets no retroactive credit
    # that would let it monopolise the next several rounds
    assert "newcomer" in {r.tenant for r in ready}


def test_fair_share_under_flood_vs_trickle():
    clock = FakeClock()
    ctl = _ctl(clock, max_queue_depth=512)
    for _ in range(100):
        ctl.submit(_req("flood"))
    for _ in range(10):
        ctl.submit(_req("trickle"))
    ready, _ = ctl.poll(capacity=20)
    by = {"flood": 0, "trickle": 0}
    for r in ready:
        by[r.tenant] += 1
    # equal weights: the flood tenant cannot crowd out the trickle tenant
    assert by["trickle"] == 10
    assert by["flood"] == 10


# --------------------------------------------------------------------------- #
# bounded queue + timeouts
# --------------------------------------------------------------------------- #
def test_queue_timeout_expires_instead_of_hanging():
    clock = FakeClock()
    ctl = _ctl(clock, queue_timeout_s=5.0)
    stale = _req("t")
    ctl.submit(stale)
    clock.advance(6.0)
    fresh = _req("t")
    ctl.submit(fresh)
    ready, expired = ctl.poll(capacity=4)
    assert [r.request_id for r in expired] == [stale.request_id]
    assert [r.request_id for r in ready] == [fresh.request_id]
    assert ctl.queue_depth == 0
    snap = ctl.snapshot()
    assert snap["timeouts"] == 1
    assert snap["tenants"]["t"]["timeouts"] == 1


def test_global_depth_bound_sheds_everything():
    clock = FakeClock()
    ctl = _ctl(clock, max_queue_depth=4, shed_queue_depth=4)
    for _ in range(4):
        ctl.submit(_req("t", interactive=True))
    assert ctl.level == LEVEL_SHED_ALL
    for interactive in (False, True):       # hard bound ignores class
        with pytest.raises(Overloaded) as ei:
            ctl.submit(_req("t", interactive=interactive))
        assert ei.value.status == 503
        assert ei.value.retry_after >= 1.0


def test_per_tenant_queue_bound():
    clock = FakeClock()
    ctl = _ctl(clock, tenants={"small": TenantConfig(max_queue=2)})
    ctl.submit(_req("small"))
    ctl.submit(_req("small"))
    with pytest.raises(Overloaded):
        ctl.submit(_req("small"))
    ctl.submit(_req("other"))               # global queue still open


# --------------------------------------------------------------------------- #
# degradation ladder
# --------------------------------------------------------------------------- #
def test_shed_bulk_keeps_interactive_traffic():
    clock = FakeClock()
    ctl = _ctl(clock, max_queue_depth=16, shed_queue_depth=4)
    for _ in range(4):
        ctl.submit(_req("t", interactive=True))
    assert ctl.level == LEVEL_SHED_BULK
    with pytest.raises(Overloaded) as ei:
        ctl.submit(_req("t"))               # batch-class: shed
    assert ei.value.status == 503
    ctl.submit(_req("t", interactive=True))  # interactive: still admitted


def test_saturated_headroom_escalates_soft_shed():
    clock = FakeClock()
    ctl = _ctl(clock, max_queue_depth=16, shed_queue_depth=2,
               headroom_fn=lambda: 0.0)
    ctl.submit(_req("t", interactive=True))
    assert ctl.level == LEVEL_NORMAL        # below the soft threshold
    ctl.submit(_req("t", interactive=True))
    assert ctl.level == LEVEL_SHED_ALL      # soft shed + no headroom
    with pytest.raises(Overloaded):
        ctl.submit(_req("t", interactive=True))


@pytest.mark.parametrize("served,idle_s", [(1, 0.0), (2, 60.0)])
def test_burst_after_cold_request_is_not_shed(served, idle_s):
    """The release rate counts backlogged time only: requests served
    promptly, then (after an idle gap) a burst, must all be admitted —
    neither a single release nor the idle gap between requests may read
    as a stalled server."""
    clock = FakeClock()
    ctl = _ctl(clock, shed_wait_s=10.0)
    for _ in range(served):
        clock.advance(idle_s)
        ctl.submit(_req("t"))
        clock.advance(0.01)
        assert len(ctl.poll(8)[0]) == 1
    for _ in range(6):
        ctl.submit(_req("t"))
    assert ctl.level == LEVEL_NORMAL


def test_drain_is_terminal_and_finishes_queued_work():
    clock = FakeClock()
    ctl = _ctl(clock)
    queued = _req("t")
    ctl.submit(queued)
    ctl.start_drain()
    assert ctl.level == LEVEL_DRAINING
    with pytest.raises(Overloaded) as ei:
        ctl.submit(_req("t"))
    assert ei.value.code == "draining"
    ready, _ = ctl.poll(capacity=4)         # in-queue work still releases
    assert [r.request_id for r in ready] == [queued.request_id]


def test_drop_removes_queued_request():
    clock = FakeClock()
    ctl = _ctl(clock)
    a, b = _req("t"), _req("t")
    ctl.submit(a)
    ctl.submit(b)
    assert ctl.drop(a.request_id) is a
    assert ctl.drop(a.request_id) is None   # already gone
    ready, _ = ctl.poll(capacity=4)
    assert [r.request_id for r in ready] == [b.request_id]


# --------------------------------------------------------------------------- #
# jain_index
# --------------------------------------------------------------------------- #
def test_jain_index_bounds():
    assert jain_index([1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_index([1.0, 0.0, 0.0]) == pytest.approx(1 / 3)
    assert jain_index([]) == 1.0
    assert jain_index([0.0, 0.0]) == 1.0    # no service at all is "fair"


# --------------------------------------------------------------------------- #
# /stats counters under concurrent mutation
# --------------------------------------------------------------------------- #
def test_snapshot_consistent_under_concurrent_mutation():
    """Handler threads submit while the loop thread polls and another
    thread snapshots: no exception, no lost request — every submit is
    accounted as released, shed, expired, or still queued."""
    ctl = AdmissionController(
        max_queue_depth=32, queue_timeout_s=30.0,
        tenants={"a": TenantConfig(weight=2.0),
                 "b": TenantConfig(rps=200.0, burst_requests=4.0)})
    n_per_thread = 200
    outcomes = {"admitted": 0, "rejected": 0}
    outcome_lock = threading.Lock()
    stop = threading.Event()
    snaps = []

    def submitter(tenant):
        for i in range(n_per_thread):
            try:
                ctl.submit(_req(tenant, interactive=(i % 2 == 0)))
                with outcome_lock:
                    outcomes["admitted"] += 1
            except (RateLimited, Overloaded):
                with outcome_lock:
                    outcomes["rejected"] += 1

    released = []

    def poller():
        while not stop.is_set():
            ready, expired = ctl.poll(capacity=4)
            released.extend(ready)
            assert not expired              # 30s timeout never trips here

    def snapshotter():
        while not stop.is_set():
            snap = ctl.snapshot()
            snaps.append(snap)
            # internal consistency of one snapshot: global counters are
            # the sums of the per-tenant ones
            for key in ("shed_rate_limited", "shed_overload", "timeouts"):
                assert snap[key] == sum(t[key]
                                        for t in snap["tenants"].values())
            assert snap["queue_depth"] >= 0

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in ("a", "b", "c")]
    aux = [threading.Thread(target=poller), threading.Thread(target=snapshotter)]
    for t in aux + threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in aux:
        t.join()
    ready, _ = ctl.poll(capacity=10_000)    # drain what's left
    released.extend(ready)

    assert outcomes["admitted"] + outcomes["rejected"] == 3 * n_per_thread
    assert len(released) == outcomes["admitted"]
    assert len({r.request_id for r in released}) == len(released)
    final = ctl.snapshot()
    assert final["queue_depth"] == 0
    assert final["released"] == outcomes["admitted"]
    assert (final["shed_rate_limited"] + final["shed_overload"]
            == outcomes["rejected"])
    assert snaps, "snapshotter never ran"
    # counters only ever grow
    for a, b in zip(snaps, snaps[1:]):
        assert b["released"] >= a["released"]
        assert b["shed_overload"] >= a["shed_overload"]
        assert b["shed_rate_limited"] >= a["shed_rate_limited"]
