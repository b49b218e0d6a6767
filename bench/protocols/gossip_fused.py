"""Reference of one synchronous GossipGraD step with the fused mix+SGD
update (``--protocol gossip --packed``; the fused engine is that path's
default).

Replica d at step t, with gradient g_d taken at its incoming parameters p_d:

    partner  = p_s, where s is the replica that sends to d at step t
    mixed    = store((1 - alpha) * p_d + alpha * partner)   (alpha = 0: p_d)
    m_d     <- momentum * m_d + g_d                       (kept in store dtype)
    p_d     <- store(mixed - lr * m_d)

all in float32 before ``store`` rounds to the parameters' dtype. The
partner term is one update stale (GoSGD-style), as in the paper's
asynchronous variant (section 5).

Schedule (paper section 4.4.2 and 4.5.1): dissemination, replica i sends to
(i + 2^k) mod p at substep k of a round of ceil(log2 p) substeps; round r of
``num_rotations`` relabels the replicas through a permutation sigma_r
(identity for r = 0, then successive draws of
``numpy.random.default_rng(0).permutation(p)``), i -> sigma^-1(base(sigma(i))).
The schedule cycles with period ``num_rotations * ceil(log2 p)``.

Momentum and alpha are not options of the launcher: it trains with SGD at
the paper's (Caffe's) momentum 0.9, and the gossip protocol mixes at alpha
0.5 (the average of the pair).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MOMENTUM = 0.9
ALPHA = 0.5


def send_to(p: int, step: int, topology: str, num_rotations: int,
            seed: int = 0) -> np.ndarray:
    if topology != "dissemination":
        raise ValueError(f"reference covers dissemination, not {topology!r}")
    sub = max(1, math.ceil(math.log2(p))) if p > 1 else 1
    rng = np.random.default_rng(seed)
    rows = []
    for r in range(num_rotations):
        sigma = np.arange(p) if r == 0 else rng.permutation(p)
        inv = np.empty_like(sigma)
        inv[sigma] = np.arange(p)
        for k in range(sub):
            base = (np.arange(p) + 2 ** k) % p
            rows.append(inv[base[sigma]])
    return rows[step % len(rows)]


def _update(p, partner, m, g, lr, momentum, alpha):
    def leaf(p, b, m, g):
        p32 = p.astype(jnp.float32)
        if b is not None:
            mixed = p32 * (1.0 - alpha) + b.astype(jnp.float32) * alpha
            p32 = mixed.astype(p.dtype).astype(jnp.float32)
        m32 = momentum * m.astype(jnp.float32) + g.astype(jnp.float32)
        return (p32 - lr * m32).astype(p.dtype), m32.astype(m.dtype)

    if partner is None:
        out = jax.tree.map(lambda p, m, g: leaf(p, None, m, g), p, m, g)
    else:
        out = jax.tree.map(leaf, p, partner, m, g)
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda x: x[0], out, is_leaf=is_pair),
            jax.tree.map(lambda x: x[1], out, is_leaf=is_pair))


_update_jit = jax.jit(_update, static_argnames=("momentum", "alpha"),
                      donate_argnums=(0, 2))


def step_update(step: int, params: list, moms: list, grads: list, *,
                hp: dict, devices: list):
    """New (params, moms), one tree per replica, each on its own device."""
    p = len(params)
    alpha = float(hp["alpha"]) if p > 1 else 0.0
    partners = [None] * p
    if alpha != 0.0:
        dst = send_to(p, step, hp["topology"], hp["num_rotations"])
        for src, d in enumerate(dst):
            partners[d] = jax.device_put(params[src], devices[d])
    out = [_update_jit(params[d], partners[d], moms[d], grads[d],
                       jnp.float32(hp["lr"]), momentum=float(hp["momentum"]),
                       alpha=alpha)
           for d in range(p)]
    return [o[0] for o in out], [o[1] for o in out]
