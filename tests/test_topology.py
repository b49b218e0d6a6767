"""GossipGraD §4.3–4.5 schedule properties."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (build_schedule, diffusion_steps, dissemination_partner,
                        hypercube_partner, log2_steps, reachability,
                        ring_partner)


@given(st.integers(2, 64), st.integers(0, 7))
@settings(max_examples=40, deadline=None)
def test_dissemination_is_permutation(p, k):
    """Balanced communication (§4.3 property 2): every step is a permutation."""
    send = dissemination_partner(p, k)
    assert sorted(send) == list(range(p))


@given(st.sampled_from([2, 4, 8, 16, 32, 64]), st.integers(0, 7))
@settings(max_examples=30, deadline=None)
def test_hypercube_is_involutive_permutation(p, k):
    send = hypercube_partner(p, k)
    assert sorted(send) == list(range(p))
    # hypercube exchange is pairwise: partner of partner is self
    assert np.array_equal(send[send], np.arange(p))


def test_hypercube_rejects_non_power_of_two():
    with pytest.raises(ValueError):
        hypercube_partner(6, 0)


@given(st.integers(2, 64), st.integers(1, 4), st.integers(0, 99))
@settings(max_examples=40, deadline=None)
def test_schedule_rows_are_permutations(p, rotations, seed):
    s = build_schedule(p, num_rotations=rotations, seed=seed)
    for row in s.perms:
        assert sorted(row) == list(range(p))


@given(st.integers(2, 64), st.sampled_from(["dissemination", "hypercube"]),
       st.integers(1, 4), st.integers(0, 99))
@settings(max_examples=60, deadline=None)
def test_rotated_schedule_steps_are_bijective(p, topology, rotations, seed):
    """Balanced communication survives rotation (§4.5.1): at EVERY step of a
    rotated schedule, send_to is a true bijection — recv_from inverts it
    exactly (recv_from[send_to[i]] == i), for both base topologies,
    including non-power-of-two p for dissemination."""
    if topology == "hypercube":
        p = 1 << max(1, p.bit_length() - 1)  # nearest power of two <= p
    s = build_schedule(p, topology=topology, num_rotations=rotations,
                       seed=seed)
    for t in range(s.period):
        send = s.send_to(t)
        recv = s.recv_from(t)
        assert sorted(send) == list(range(p))          # surjective + injective
        assert np.array_equal(recv[send], np.arange(p))  # true inverse
        assert np.array_equal(send[recv], np.arange(p))


@given(st.sampled_from([2, 4, 8, 16, 32, 64]), st.integers(1, 4),
       st.integers(0, 99))
@settings(max_examples=40, deadline=None)
def test_rotated_hypercube_stays_involutive(p, rotations, seed):
    """Relabeling by sigma preserves the pairwise-exchange property: every
    rotated hypercube step is still its own inverse."""
    s = build_schedule(p, topology="hypercube", num_rotations=rotations,
                       seed=seed)
    for t in range(s.period):
        send = s.send_to(t)
        assert np.array_equal(send[send], np.arange(p))


@given(st.integers(2, 96), st.integers(1, 4), st.integers(0, 99))
@settings(max_examples=40, deadline=None)
def test_every_rotation_round_diffuses_in_log_p(p, rotations, seed):
    """§4.4 under rotation: EACH round of a rotated dissemination schedule
    (a relabeled copy of the base topology) completes diffusion in exactly
    ceil(log2 p) substeps — including non-power-of-two p."""
    s = build_schedule(p, num_rotations=rotations, seed=seed)
    assert s.substeps == log2_steps(p)
    for r in range(rotations):
        reach = np.eye(p, dtype=bool)
        for k in range(s.substeps):
            recv = s.recv_from(r * s.substeps + k)
            reach = reach | reach[recv]
            if k < s.substeps - 1 and p > 2:
                # sub-linear diffusion is tight: not complete a step early
                assert not reach.all() or p == 2
        assert reach.all()


@given(st.integers(2, 128))
@settings(max_examples=40, deadline=None)
def test_dissemination_diffuses_in_log_p(p):
    """§4.4 claim: all ranks have indirectly mixed after ceil(log2 p) steps."""
    s = build_schedule(p, num_rotations=1)
    assert diffusion_steps(s) == log2_steps(p) == max(1, math.ceil(math.log2(p)))


@given(st.sampled_from([4, 8, 16, 32, 64]))
@settings(max_examples=10, deadline=None)
def test_hypercube_diffuses_in_log_p(p):
    s = build_schedule(p, topology="hypercube", num_rotations=1)
    assert diffusion_steps(s) == log2_steps(p)


def test_reachability_monotone():
    s = build_schedule(16, num_rotations=2, seed=3)
    prev = 16  # diag
    for t in range(1, 5):
        r = reachability(s, t)
        assert r.sum() >= prev
        prev = r.sum()
    assert reachability(s, 4).all()


def test_rotation_changes_partners():
    """§4.5.1: after log p steps the topology is re-drawn — direct partners
    differ between rounds (with overwhelming probability for p=32)."""
    s = build_schedule(32, num_rotations=3, seed=0)
    first_round = s.perms[: s.substeps]
    second_round = s.perms[s.substeps: 2 * s.substeps]
    assert not all(np.array_equal(a, b)
                   for a, b in zip(first_round, second_round))


def test_no_rotation_repeats_partners():
    s = build_schedule(32, num_rotations=1)
    assert np.array_equal(s.send_to(0), s.send_to(s.substeps))


def test_ring_partner():
    send = ring_partner(5)
    assert list(send) == [1, 2, 3, 4, 0]


def test_direct_partner_fraction_with_rotation():
    """Without rotation each rank only ever directly meets log(p) of p ranks
    (§4.5.1's motivation); rotation strictly increases the set."""
    p = 64
    norot = build_schedule(p, num_rotations=1)
    rot = build_schedule(p, num_rotations=4, seed=1)

    def distinct_partners(s, steps):
        seen = set()
        for t in range(steps):
            seen.update((i, int(s.send_to(t)[i])) for i in range(p))
        return len(seen)

    steps = 4 * norot.substeps
    assert distinct_partners(rot, steps) > distinct_partners(norot, steps)
