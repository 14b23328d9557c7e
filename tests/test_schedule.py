"""Property tests of the topology-aware bucket schedule
(``repro.utils.schedule``) — the single stage plan consumed by the scan and
ring order drivers — and of the two-level ``("pod", "ring")`` hop plan
(``make_hier_plan``) the hierarchical messaging ring executes.

Invariants checked over a grid of (p, min_bucket, ring[, pods]) shapes:
coverage (every stage buffer holds all its live rows), the stage-size law
(the top stage p rounded up to the bucket floor, never above next_pow2(p);
every later stage a power of two; every stage a multiple of pod*ring), iteration counts summing to
p - 1, scan == ring at R = 1, plan invariance across (P, R) factorizations
of one shard count, and the degenerate wide-ring plan. For the hop plan:
exactly-once coverage of every unordered block pair across both levels,
P = 1 reproducing the flat ``process_pair`` schedule, and the analytic
``hop_counts`` wire model (P=1 == the flat body's hop count; cross-pod
sequential rounds at hier topologies strictly below the flat ring's
sequential rounds at equal total shards). Violations must be
construction-time ``ValueError``s, never silent wrong orders.
"""

import itertools
import math

import pytest

from repro.utils.schedule import (
    HOP_CROSS_OVL,
    HOP_CROSS_SEQ,
    HOP_INTRA_OVL,
    HOP_INTRA_SEQ,
    Schedule,
    make_hier_plan,
    make_schedule,
    whole_chunk,
)
from repro.utils.shapes import next_pow2

PS = (2, 3, 5, 8, 16, 17, 31, 64, 85, 100, 129)


def _assert_stage_sizes(sched, p, min_bucket, shards):
    """The stage-size law: the top stage is ``min(next_pow2(p),
    round_up(p, floor))`` with ``floor = next_pow2(max(min_bucket,
    shards))`` (a ring wider than the padded problem takes one stage of
    ``shards`` rows), every later stage a power of two, every stage a
    multiple of the shard count."""
    floor = next_pow2(max(min_bucket, shards, 1))
    top = (shards if shards > next_pow2(p)
           else min(next_pow2(p), -(-p // floor) * floor))
    sizes = [m for m, _ in sched.stages]
    assert sizes[0] == top, f"top stage {sizes[0]}, want {top}"
    for m in sizes[1:]:
        assert m & (m - 1) == 0, "stages below the top are powers of two"
    for m in sizes:
        assert m % shards == 0, "stage size must divide evenly over the ring"
MIN_BUCKETS = (1, 4, 8, 32)
RINGS = (1, 2, 4, 8)
PODS = (1, 2, 4, 8)


@pytest.mark.parametrize(
    "p,min_bucket,ring", itertools.product(PS, MIN_BUCKETS, RINGS)
)
def test_schedule_invariants(p, min_bucket, ring):
    sched = make_schedule(p, min_bucket, ring=ring)
    assert sched.total_iterations == p - 1
    _assert_stage_sizes(sched, p, min_bucket, ring)
    r = p
    for m, cnt, pos in sched.walk():
        assert sched.block(m) * ring == m
        assert sched.block(m) >= 1
        assert sched.live_at(pos) == r
        # coverage: the buffer holds every live row at every iteration it
        # spans (live rows only shrink within a stage)
        assert m >= min(r, p), f"stage m={m} cannot hold r={r} live rows"
        if ring <= next_pow2(p):
            assert m <= next_pow2(p)
        r -= cnt
    assert r == 1
    # stage sizes strictly decrease (compactions only shrink buffers)
    sizes = [m for m, _ in sched.stages]
    assert sizes == sorted(sizes, reverse=True)
    assert sched.num_compactions <= max(p.bit_length(), 1)


@pytest.mark.parametrize("p,min_bucket", itertools.product(PS, MIN_BUCKETS))
def test_ring1_is_the_scan_plan(p, min_bucket):
    """R=1 must reproduce the scan driver's plan exactly — the law
    m(r) = clamp(next_pow2(r), floor, top) with the top stage
    top = min(next_pow2(p), round_up(p, floor))."""
    sched = make_schedule(p, min_bucket, ring=1)
    floor = next_pow2(max(min_bucket, 1))
    cap = min(next_pow2(p), -(-p // floor) * floor)
    expect = [min(cap, max(floor, next_pow2(r))) for r in range(p, 1, -1)]
    got = [m for m, cnt, _ in sched.walk() for _ in range(cnt)]
    assert got == expect


@pytest.mark.parametrize("p,ring,stages", [
    # iJR904 (Table 1): 770 rows fill 800, not 1024
    (770, 1, ((800, 258), (512, 256), (256, 128), (128, 64), (64, 32),
              (32, 31))),
    # e_coli_core (Table 1): 85 rows fill 96, not 128
    (85, 1, ((96, 21), (64, 32), (32, 31))),
    # Fig. 4's p=1000 ring: 1024 under both laws, the plan is unchanged
    (1000, 4, ((1024, 488), (512, 256), (256, 128), (128, 64), (64, 32),
               (32, 31))),
])
def test_top_stage_sized_to_the_data(p, ring, stages):
    """The paper's shapes under the default floor of 32."""
    assert make_schedule(p, 32, ring=ring).stages == stages


@pytest.mark.parametrize("p,min_bucket,ring,pods,chunk,top", [
    # the scan's threshold fits at the default floor and at the benches' 8
    (770, 32, 1, 1, 16, 800),
    (85, 32, 1, 1, 16, 96),
    (770, 8, 1, 1, 16, 784),
    (770, 16, 1, 1, 16, 784),
    # the four-chip ring: 200-row blocks would cut the chunk to 10
    (770, 32, 4, 1, 16, 832),
    (1000, 32, 4, 1, 16, 1024),
    (1000, 8, 4, 1, 16, 1024),
    # the hierarchical (2, 4) ring of bench_table2, chunk 32
    (770, 32, 4, 2, 32, 1024),
])
def test_top_stage_keeps_the_chunk(p, min_bucket, ring, pods, chunk, top):
    """The shipped threshold configurations keep their full chunk in the
    top stage: every shard's block there divides into whole chunks."""
    sched = make_schedule(p, min_bucket, ring=ring, pods=pods, chunk=chunk)
    assert sched.stages[0][0] == top
    assert whole_chunk(chunk, sched.block(top)) == chunk


@pytest.mark.parametrize("p,ring", itertools.product(PS + (770,), RINGS))
@pytest.mark.parametrize("chunk", (16, 10))
def test_chunk_never_below_the_pow2_plan(p, ring, chunk):
    """No stage of a threshold plan gives a shard a smaller chunk than the
    historical next_pow2 plan did; the dense plan (chunk 1) is never
    larger than the threshold plan."""
    min_bucket = 8
    sched = make_schedule(p, min_bucket, ring=ring, chunk=chunk)
    dense = make_schedule(p, min_bucket, ring=ring)
    assert dense.stages[0][0] <= sched.stages[0][0]
    floor = next_pow2(max(min_bucket, ring))
    old = [ring] * (p - 1) if ring > next_pow2(p) else [
        min(next_pow2(p), max(floor, next_pow2(r))) for r in range(p, 1, -1)]
    new = [m for m, cnt, _ in sched.walk() for _ in range(cnt)]
    assert len(new) == len(old) == p - 1
    for m_new, m_old in zip(new, old):
        assert m_new <= m_old
        assert (whole_chunk(chunk, m_new // ring)
                >= whole_chunk(chunk, m_old // ring)), (m_new, m_old)


@pytest.mark.parametrize("p,min_bucket,want", [
    (770, 32, (173_401_104, 120_723_696, 0.56, 0.37)),
    (85, 32, (250_576, 175_648, 0.59, 0.42)),
])
def test_pair_slots_and_padded_share(p, min_bucket, want):
    """``pair_slots`` under the historical next_pow2 top stage and under
    the sized one, and the padded share ``1 - C(p+1, 3) / pair_slots`` of
    a fit (iJR904: 0.56 -> 0.37)."""
    floor = next_pow2(max(min_bucket, 1))
    old = [min(next_pow2(p), max(floor, next_pow2(r)))
           for r in range(p, 1, -1)]
    old_slots = sum(m * (m - 1) // 2 for m in old)
    new_slots = make_schedule(p, min_bucket).pair_slots
    live = math.comb(p + 1, 3)
    assert live == sum(r * (r - 1) // 2 for r in range(2, p + 1))
    assert (old_slots, new_slots) == want[:2]
    assert round(1 - live / old_slots, 2) == want[2]
    assert round(1 - live / new_slots, 2) == want[3]


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("ring", (2, 4, 8))
def test_ring_floor_clamps_stage_sizes(p, ring):
    """The ring floor: no stage may be smaller than the ring (every shard
    keeps a non-empty block), even when min_bucket asks for less."""
    sched = make_schedule(p, 1, ring=ring)
    assert all(m >= ring for m, _ in sched.stages)


def test_wide_ring_degenerates_to_single_stage():
    """ring wider than the padded problem: one stage of size ring — one row
    (or zero) per shard, no compactions."""
    sched = make_schedule(5, 4, ring=16)
    assert sched.stages == ((16, 4),)
    assert sched.num_compactions == 0


def test_trivial_problems_have_empty_plans():
    assert make_schedule(1, 8).stages == ()
    assert make_schedule(0, 8).stages == ()
    assert make_schedule(1, 8).total_iterations == 0


def test_schedule_is_hashable_and_cacheable():
    a = make_schedule(64, 8, ring=4, sample_shards=2)
    b = make_schedule(64, 8, ring=4, sample_shards=2)
    assert a == b and hash(a) == hash(b)
    assert a != make_schedule(64, 8, ring=2, sample_shards=2)


def test_invalid_ring_sizes_rejected():
    with pytest.raises(ValueError, match="power of two"):
        make_schedule(16, 8, ring=3)
    with pytest.raises(ValueError, match="power of two"):
        Schedule(p=4, min_bucket=2, ring=0, stages=((4, 3),))


def test_invariant_violations_rejected_at_construction():
    with pytest.raises(ValueError, match="power of two"):
        Schedule(p=4, min_bucket=2, stages=((3, 3),))
    with pytest.raises(ValueError, match="multiple of ring"):
        Schedule(p=8, min_bucket=2, ring=4, stages=((2, 7),))
    with pytest.raises(ValueError, match="cover"):
        Schedule(p=8, min_bucket=2, stages=((4, 7),))
    with pytest.raises(ValueError, match="sum to"):
        Schedule(p=8, min_bucket=2, stages=((8, 3),))


# ---------------------------------------------------------------------------
# the pod level of the bucket schedule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "p,pods,ring", itertools.product(PS, (2, 4), (1, 2, 4))
)
def test_schedule_pod_invariants(p, pods, ring):
    """With pods > 1 every stage size follows the stage-size law AND is a
    multiple of the total shard count P*R (every shard of both levels keeps an equal
    non-empty block)."""
    sched = make_schedule(p, 4, ring=ring, pods=pods)
    shards = pods * ring
    assert sched.shards == shards
    assert sched.total_iterations == p - 1
    _assert_stage_sizes(sched, p, 4, shards)
    for m, _cnt, _pos in sched.walk():
        assert sched.block(m) * shards == m
        assert sched.block(m) >= 1


@pytest.mark.parametrize("p,min_bucket", itertools.product(PS, MIN_BUCKETS))
@pytest.mark.parametrize("shards", (2, 4, 8, 16))
def test_schedule_depends_only_on_shard_product(p, min_bucket, shards):
    """Every (P, R) factorization of one shard count shares ONE stage plan —
    the hierarchical and flat rings of equal width compact at the same
    iterations, which is what makes their orders comparable bit-for-bit."""
    plans = {
        make_schedule(p, min_bucket, ring=shards // pods, pods=pods).stages
        for pods in (1, 2, 4, 8, 16)
        if pods <= shards and shards % pods == 0
    }
    assert len(plans) == 1


def test_schedule_pod_rejections():
    with pytest.raises(ValueError, match="power of two"):
        make_schedule(16, 8, ring=2, pods=3)
    with pytest.raises(ValueError, match="multiple of ring"):
        Schedule(p=16, min_bucket=2, ring=2, pods=4, stages=((4, 15),))


# ---------------------------------------------------------------------------
# the two-level hop plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pods,ring", itertools.product(PODS, RINGS))
def test_hier_plan_exactly_once_pair_coverage(pods, ring):
    """Simulate the walk on every device: each unordered block pair must be
    processed exactly once per iteration, across BOTH ring levels."""
    plan = make_hier_plan(pods, ring)
    s = pods * ring
    seen = {}
    for q in range(pods):
        for i in range(ring):
            d = q * ring + i
            for e, t, dedup in plan.processed_offsets():
                src = plan.src(e, t, q, i)
                if plan.keep(dedup, d, src):
                    assert src != d, "a processed offset may never be (0, 0)"
                    key = (min(d, src), max(d, src))
                    seen[key] = seen.get(key, 0) + 1
    want = {(a, b) for a in range(s) for b in range(a + 1, s)}
    assert set(seen) == want
    assert all(v == 1 for v in seen.values())


@pytest.mark.parametrize("ring", (1, 2, 4, 8, 16))
def test_hier_plan_p1_is_the_flat_ring_schedule(ring):
    """P=1 must reproduce ``dist.ring.process_pair`` exactly: one epoch,
    hops t = 1..R/2, the antipodal dedup only at t = R/2 (even R)."""
    from repro.dist.ring import process_pair, ring_steps

    plan = make_hier_plan(1, ring)
    assert plan.exchange_cadence == ring
    assert len(plan.epochs) == 1
    offsets = plan.processed_offsets()
    assert [t for _e, t, _dd in offsets] == list(range(1, ring_steps(ring) + 1))
    for _e, t, dedup in offsets:
        # dedup hops are exactly those where process_pair tie-breaks on the
        # device index (the higher-indexed endpoint drops the pair)
        assert dedup == (not process_pair(ring, t, 1, 0))


@pytest.mark.parametrize("pods,ring", itertools.product(PODS, RINGS))
def test_hier_plan_dedup_offsets_are_self_conjugate(pods, ring):
    for e, t, dedup in make_hier_plan(pods, ring).processed_offsets():
        conj = ((pods - e) % pods, (ring - t) % ring)
        assert dedup == ((e, t) == conj)


@pytest.mark.parametrize("ring", (2, 4, 8, 16))
def test_hop_counts_flat_matches_the_flat_body(ring):
    """P=1 wire model == the flat ``_ring_body``: R/2 overlapped packet
    rounds (1 pre-shift + R/2 - 1 prefetches), R/2 sequential rider rounds
    (R/2 - 1 catch-ups + 1 ride home), nothing cross-pod."""
    hc = make_hier_plan(1, ring).hop_counts()
    assert hc["intra_ovl"] == ring // 2
    assert hc["intra_seq"] == ring // 2
    assert hc["cross_ovl"] == hc["cross_seq"] == 0
    assert hc["overlap_frac"] == 0.5


@pytest.mark.parametrize("pods,ring", ((2, 4), (4, 2), (4, 4), (2, 8), (8, 2)))
def test_hop_counts_hier_beats_flat_sequential_cross_hops(pods, ring):
    """The tentpole's wire win: at equal total shards S = P*R, a flat ring
    spanning the pods pays cross-pod latency on ALL S/2 sequential rider
    rounds; the two-level plan pays it on strictly fewer (the riders cross
    pods only at epoch transitions + the ride home), with every block
    packet round overlapped behind compute."""
    hc = make_hier_plan(pods, ring).hop_counts()
    flat_seq_cross = (pods * ring) // 2  # flat ring: every rider round may
    #   cross a pod boundary when the S shards span the pods
    assert hc["cross_seq"] < flat_seq_cross
    assert hc["overlap_frac"] > 0
    # totals are conserved: the plan still moves every packet R/2-equivalent
    # times — only *where* the hops land (overlapped vs sequential, intra vs
    # cross) changes
    assert hc["total"] == (hc["intra_ovl"] + hc["intra_seq"]
                           + hc["cross_ovl"] + hc["cross_seq"])


def test_hop_counts_indices_cover_the_vector():
    assert sorted((HOP_INTRA_OVL, HOP_INTRA_SEQ,
                   HOP_CROSS_OVL, HOP_CROSS_SEQ)) == [0, 1, 2, 3]


def test_hier_plan_rejects_non_pow2():
    with pytest.raises(ValueError, match="power of two"):
        make_hier_plan(3, 4)
    with pytest.raises(ValueError, match="power of two"):
        make_hier_plan(2, 5)
