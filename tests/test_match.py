"""Message-matching engine + counter subsystem (paper method 2):
matching semantics (wildcards, FIFO, non-overtaking), counter drain under
concurrent producers, defect detection regression, comm-layer routing."""
import random
import textwrap
import threading

from repro.core import analyses, timeline
from repro.core.counters import (CounterRegistry, CounterStat, counter_stats,
                                 _pow2_bin)
from repro.match import ANY_SOURCE, ANY_TAG, MODES, Fabric, MatchEngine

DEFECT_KINDS = ("long_traversal", "umq_flood")


def make_engine(mode="binned"):
    return MatchEngine(mode=mode, registry=CounterRegistry())


# ---------------------------------------------------------------- semantics

def test_specific_match_and_unexpected_path():
    for mode in MODES:
        eng = make_engine(mode)
        r = eng.post_recv(src=2, tag=5)
        assert not r.completed
        eng.arrive(src=2, tag=5, nbytes=64)
        assert r.completed and r.message.nbytes == 64
        # unexpected: arrival first, then the recv pulls it from the UMQ
        eng.arrive(src=1, tag=9)
        r2 = eng.post_recv(src=1, tag=9)
        assert r2.completed
        assert eng.outstanding() == (0, 0), mode


def test_wildcards_match_any_envelope():
    for mode in MODES:
        eng = make_engine(mode)
        r_any = eng.post_recv(src=ANY_SOURCE, tag=ANY_TAG)
        eng.arrive(src=7, tag=3)
        assert r_any.completed and r_any.message.src == 7
        r_src = eng.post_recv(src=ANY_SOURCE, tag=4)
        eng.arrive(src=2, tag=4)
        assert r_src.completed
        r_tag = eng.post_recv(src=6, tag=ANY_TAG)
        eng.arrive(src=6, tag=99)
        assert r_tag.completed, mode


def test_earliest_posted_recv_wins():
    """MPI ordering: among matching posted receives, post order decides —
    even when a wildcard posted earlier competes with an exact match."""
    for mode in MODES:
        eng = make_engine(mode)
        r_wild = eng.post_recv(src=ANY_SOURCE, tag=ANY_TAG)
        r_spec = eng.post_recv(src=3, tag=7)
        eng.arrive(src=3, tag=7)
        assert r_wild.completed and not r_spec.completed, mode
        eng.arrive(src=3, tag=7)
        assert r_spec.completed, mode


def test_fifo_per_envelope():
    """Non-overtaking: same-envelope receives complete in post order with
    same-envelope messages in arrival order."""
    for mode in MODES:
        eng = make_engine(mode)
        recvs = [eng.post_recv(src=1, tag=2) for _ in range(4)]
        for _ in range(4):
            eng.arrive(src=1, tag=2)
        seqs = [r.message.seq for r in recvs]
        assert all(r.completed for r in recvs), mode
        assert seqs == sorted(seqs), mode


def test_earliest_arrival_wins_on_umq():
    for mode in MODES:
        eng = make_engine(mode)
        eng.arrive(src=4, tag=1, nbytes=111)
        eng.arrive(src=4, tag=1, nbytes=222)
        r = eng.post_recv(src=ANY_SOURCE, tag=1)
        assert r.completed and r.message.nbytes == 111, mode


def test_modes_are_semantically_equivalent():
    """The seeded defects change *cost*, never *matching*: a random legal
    workload (wildcards, two communicators) must produce identical
    (recv, message) pairings in all three modes."""
    rng = random.Random(1234)
    ops = []
    balance = 0
    for _ in range(600):
        comm = rng.randrange(2)
        if balance > 0 and rng.random() < 0.5:
            ops.append(("arrive", rng.randrange(4), rng.randrange(6), comm))
            balance -= 1
        else:
            src = ANY_SOURCE if rng.random() < 0.3 else rng.randrange(4)
            tag = ANY_TAG if rng.random() < 0.3 else rng.randrange(6)
            ops.append(("post", src, tag, comm))
            balance += 1

    def run(mode):
        eng = make_engine(mode)
        recvs = []
        for op, a, b, c in ops:
            if op == "post":
                recvs.append(eng.post_recv(src=a, tag=b, comm=c))
            else:
                eng.arrive(src=a, tag=b, comm=c)
        return [(r.seq, r.message.seq) for r in recvs if r.completed]

    ref = run("binned")
    assert len(ref) > 100
    for mode in ("linear", "leaky_umq"):
        assert run(mode) == ref, mode


def test_any_any_recvs_are_binned_per_comm():
    """A wildcard recv on another communicator must not shadow a deeper
    same-comm wildcard recv (regression: any-any bucket keyed by comm)."""
    for mode in MODES:
        eng = make_engine(mode)
        eng.post_recv(src=ANY_SOURCE, tag=ANY_TAG, comm=1)
        r = eng.post_recv(src=ANY_SOURCE, tag=ANY_TAG, comm=0)
        eng.arrive(src=5, tag=5, comm=0)
        assert r.completed, mode


def test_linear_traversal_grows_binned_does_not():
    depths = {}
    for mode in ("linear", "binned"):
        reg = CounterRegistry()
        eng = MatchEngine(mode=mode, registry=reg)
        k = 1024
        for t in range(k):
            eng.post_recv(src=0, tag=t)
        for t in reversed(range(k)):
            eng.arrive(src=0, tag=t)
        depths[mode] = reg.drain()["match.prq.traversal_depth"].mean
    assert depths["binned"] <= 0.25 * depths["linear"]
    assert depths["binned"] <= 4


def test_leaky_umq_accumulates_binned_drains():
    lengths = {}
    for mode in ("binned", "leaky_umq"):
        reg = CounterRegistry()
        fab = Fabric(mode=mode, registry=reg)
        for _ in range(40):
            fab.all_reduce(8, nbytes=1024)
        stats = reg.drain()
        lengths[mode] = stats["match.umq.length"].vmax
        prq, umq = fab.outstanding()
        assert prq == 0
        if mode == "binned":
            assert umq == 0         # fully reclaimed
        else:
            assert umq > 0          # tombstones left behind
            assert stats["match.umq.leaked"].total > 0
    assert lengths["leaky_umq"] > 10 * max(lengths["binned"], 1)


# ---------------------------------------------------------------- counters

def test_pow2_binning():
    assert _pow2_bin(0) == 0
    assert _pow2_bin(1) == 1
    assert _pow2_bin(3) == 2
    assert _pow2_bin(4) == 4
    assert _pow2_bin(1023) == 512


def test_counter_drain_concurrent_producers():
    """No lost updates: totals across drain-while-producing equal the sum
    every producer thread contributed."""
    reg = CounterRegistry()
    n_threads, n_iter = 8, 2000
    stop = threading.Event()

    def produce():
        for i in range(n_iter):
            reg.count("conc.count", 2)
            reg.observe("conc.hist", i % 32)

    drained_mid = []

    def consume():
        while not stop.is_set():
            drained_mid.append(reg.drain().get("conc.count"))

    threads = [threading.Thread(target=produce) for _ in range(n_threads)]
    consumer = threading.Thread(target=consume)
    consumer.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    consumer.join()
    stats = reg.drain()
    assert stats["conc.count"].total == 2 * n_threads * n_iter
    assert stats["conc.count"].count == n_threads * n_iter
    hist = stats["conc.hist"]
    assert hist.count == n_threads * n_iter
    assert hist.vmin == 0 and hist.vmax == 31
    assert sum(hist.bins.values()) == hist.count


def test_snapshot_events_round_trip():
    reg = CounterRegistry(pid=3)
    for i in range(10):
        reg.observe("rt.depth", i)
    reg.count("rt.n", 5)
    events = reg.snapshot_events(t_ns=123)
    assert all(e.category == "counter" and e.pid == 3 and e.duration == 0
               for e in events)
    stats = counter_stats(events)
    assert stats["rt.depth"].count == 10 and stats["rt.depth"].vmax == 9
    assert stats["rt.n"].total == 5
    # counter events survive the chrome-trace serialization unchanged
    back = timeline.from_chrome_trace(timeline.to_chrome_trace(events))
    stats2 = counter_stats(back)
    assert stats2["rt.depth"].bins == stats["rt.depth"].bins
    # merging two snapshots accumulates
    merged = counter_stats(list(events) + list(back))
    assert merged["rt.depth"].count == 20


def test_periodic_snapshots_are_deltas():
    """snapshot_events is snapshot-and-clear: merging periodic snapshots
    of one registry must not double-count (regression)."""
    reg = CounterRegistry()
    events = []
    for _ in range(4):
        for v in range(10):
            reg.observe("p.depth", v)
        events += reg.snapshot_events()
    assert reg.snapshot_events() == []        # nothing new since last
    stats = counter_stats(events)
    assert stats["p.depth"].count == 40
    assert stats["p.depth"].total == 4 * sum(range(10))


def test_counter_stat_merge():
    a, b = CounterStat("x"), CounterStat("x")
    for v in (1, 2, 3):
        a.add(v, True)
    for v in (10, 20):
        b.add(v, True)
    a.merge(b)
    assert a.count == 5 and a.total == 36
    assert a.vmin == 1 and a.vmax == 20


# ---------------------------------------------------------------- detectors

def _workload(mode, rounds=20):
    reg = CounterRegistry()
    fab = Fabric(mode=mode, registry=reg)
    for _ in range(rounds):
        fab.all_reduce(16, nbytes=1 << 16)
        eng = fab.engine(0)
        for t in range(256):
            eng.post_recv(src=1, tag=10_000 + t)
        for t in reversed(range(256)):
            eng.arrive(src=1, tag=10_000 + t)
    return reg.snapshot_events()


def test_analyze_all_flags_linear_not_binned():
    """The regression the ISSUE names: the seeded linear-search defect is
    flagged from counters alone; the binned engine is clean."""
    flagged = [f.kind for f in analyses.analyze_all(_workload("linear"))
               if f.kind in DEFECT_KINDS]
    assert "long_traversal" in flagged
    clean = [f.kind for f in analyses.analyze_all(_workload("binned"))
             if f.kind in DEFECT_KINDS]
    assert clean == []


def test_analyze_all_flags_leaky_umq():
    flagged = [f.kind for f in analyses.analyze_all(_workload("leaky_umq"))
               if f.kind in DEFECT_KINDS]
    assert "umq_flood" in flagged


# ---------------------------------------------------------------- comm layer

def test_comm_layer_routes_through_fabric(subproc):
    out = subproc(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.comm import collectives, ring
        from repro.core.compat import make_mesh
        from repro.core.counters import CounterRegistry
        from repro.match import Fabric

        reg = CounterRegistry()
        collectives.configure_matching(Fabric(mode="binned", registry=reg))
        mesh = make_mesh((8,), ("r",))
        x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
        jax.jit(jax.shard_map(lambda s: ring.ring_all_gather(s, "r"),
                          mesh=mesh, in_specs=P("r", None),
                          out_specs=P("r", None)))(x)
        jax.jit(jax.shard_map(lambda s: collectives.psum(s, "r"),
                          mesh=mesh, in_specs=P("r", None),
                          out_specs=P(None, None)))(x)
        collectives.configure_matching(None)
        stats = reg.drain()
        total = stats["match.expected"].total + stats["match.unexpected"].total
        # ring_all_gather: 7 ppermute steps x 8 ranks; psum decomposes to a
        # ring all-reduce: 2 * 7 steps x 8 ranks
        assert total == 7 * 8 + 14 * 8, total
        assert stats["match.prq.traversal_depth"].vmax <= 4
        print("ROUTED", int(total))
    """), devices=8)
    assert "ROUTED 168" in out
