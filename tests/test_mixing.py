"""Mixing-matrix theory (GossipGraD §6) made executable."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (build_schedule, consensus_contraction,
                        is_doubly_stochastic, mixing_matrix, round_matrix,
                        spectral_gap)


@given(st.integers(2, 64), st.integers(0, 20))
@settings(max_examples=30, deadline=None)
def test_step_matrix_doubly_stochastic(p, t):
    s = build_schedule(p, num_rotations=2, seed=7)
    m = mixing_matrix(s.recv_from(t))
    assert is_doubly_stochastic(m)


@given(st.integers(2, 64))
@settings(max_examples=30, deadline=None)
def test_mean_preserved(p):
    """Pairwise averaging conserves the global mean exactly — the invariant
    behind Corollary 6.3 (all nodes converge to the SAME minimum)."""
    s = build_schedule(p, num_rotations=2, seed=1)
    rng = np.random.default_rng(0)
    w = rng.normal(size=(p, 3))
    mean0 = w.mean(0)
    for t in range(17):
        w = mixing_matrix(s.recv_from(t)) @ w
    assert np.allclose(w.mean(0), mean0, atol=1e-12)


@given(st.sampled_from([2, 4, 8, 16, 32, 64, 128]))
@settings(max_examples=10, deadline=None)
def test_dissemination_round_is_exact_average(p):
    """For power-of-two p, one dissemination round (log2 p gossip steps) IS an
    exact all-reduce average: the disagreement contraction is 0. This is the
    strongest form of the paper's diffusion claim."""
    s = build_schedule(p, num_rotations=1)
    m = round_matrix(s)
    assert consensus_contraction(m) < 1e-10
    # and the round matrix is exactly the averaging projector
    assert np.allclose(m, np.ones((p, p)) / p, atol=1e-12)


@given(st.integers(3, 63).filter(lambda p: p & (p - 1)))
@settings(max_examples=20, deadline=None)
def test_non_power_two_round_still_contracts(p):
    s = build_schedule(p, num_rotations=1)
    c = consensus_contraction(round_matrix(s))
    assert c < 1.0  # strict contraction every round


def test_single_step_contracts_weakly():
    s = build_schedule(16, num_rotations=1)
    c = consensus_contraction(mixing_matrix(s.recv_from(0)))
    assert 0.0 < c <= 1.0
    assert spectral_gap(mixing_matrix(s.recv_from(0))) > 0.0


def test_consensus_convergence_simulation():
    """Repeated gossip drives disagreement to zero at the round rate."""
    p = 24
    s = build_schedule(p, num_rotations=2, seed=5)
    rng = np.random.default_rng(1)
    w = rng.normal(size=(p, 8))
    target = w.mean(0)
    dev = [np.abs(w - target).max()]
    for t in range(6 * s.substeps):
        w = mixing_matrix(s.recv_from(t)) @ w
        dev.append(np.abs(w - target).max())
    assert dev[-1] < 1e-6 * dev[0]
