"""Plain float32 reference of a dense decoder-only LM (Qwen3, OLMo).

Written from the published descriptions, in straightforward ``jax.numpy``
with every matrix product at ``Precision.HIGHEST``; it imports nothing of the
system under test. The parameter tree has the layout the trainer keeps (one
segment of layers stacked on a leading axis), so the benchmark can hand the
same seeded weights to both.

Departures from the published models, shared with the trained program and
stated in the configuration files: rotary embedding rotates interleaved
feature pairs ``(2i, 2i+1)``; with random weights this is the published
half-split rotation under a fixed permutation of each head's features.

``quant`` names the precision of every matrix product's operands: ``None``
(float32) for the reference, or a lower one for the control:
``"bfloat16"``, or ``"float8_e4m3fn"`` with one amax scale per operand. The
backward pass reuses the rounded forward operands (straight-through).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def dims(config: dict) -> dict:
    c = config["config"]
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return {"d": d, "layers": c["num_hidden_layers"], "heads": h,
            "kv_heads": c.get("num_key_value_heads", h),
            "head_dim": c.get("head_dim") or d // h,
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "rope_theta": float(c.get("rope_theta", 10000.0)),
            "tied": bool(c.get("tie_word_embeddings", False))}


def arch(config: dict) -> dict:
    """Architecture switches the reference needs beyond the sizes."""
    r = config["reference"]
    return {"norm": r["norm"], "norm_eps": float(r["norm_eps"]),
            "qk_norm": bool(r.get("qk_norm", False)),
            "dtype": jnp.dtype(config["config"]["torch_dtype"])}


# ------------------------------------------------------------------ weights
def leaf_specs(config: dict) -> dict:
    """Tree of (shape, init) in the trainer's layout; init is "ones" or the
    standard deviation of a normal draw."""
    m, a = dims(config), arch(config)
    d, n, h, k, hd, ff = (m["d"], m["layers"], m["heads"], m["kv_heads"],
                          m["head_dim"], m["ff"])
    scaled = a["norm"] == "rms"
    norm = (lambda *s: {"scale": (s + (d,), "ones")}) if scaled else (
        lambda *s: {})
    mixer = {"wq": ((n, d, h, hd), d ** -0.5),
             "wk": ((n, d, k, hd), d ** -0.5),
             "wv": ((n, d, k, hd), d ** -0.5),
             "wo": ((n, h, hd, d), (h * hd) ** -0.5)}
    if a["qk_norm"]:
        mixer["q_norm"] = ((n, hd), "ones")
        mixer["k_norm"] = ((n, hd), "ones")
    block = {"norm1": norm(n), "mixer": mixer, "norm2": norm(n),
             "ff": {"w_gate": ((n, d, ff), d ** -0.5),
                    "w_in": ((n, d, ff), d ** -0.5),
                    "w_out": ((n, ff, d), ff ** -0.5)}}
    if not m["tied"]:
        raise ValueError("the dense reference covers tied embeddings only")
    init_std = float(config["config"].get("initializer_range", 0.02))
    return {"embed": ((m["vocab"], d), init_std), "layers": [[block]],
            "final_norm": norm()}


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(config: dict, key_data) -> dict:
    """Seeded weights in the stated storage dtype. ``key_data`` is a
    (2,) uint32 array (threefry key words); leaf ``i`` draws from
    ``fold_in(key, i)``."""
    dtype = arch(config)["dtype"]
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    specs, treedef = jax.tree.flatten(leaf_specs(config), is_leaf=_is_spec)
    leaves = []
    for i, (shape, init) in enumerate(specs):
        if init == "ones":
            leaves.append(jnp.ones(shape, dtype))
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * init
            leaves.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def key_words(seed: int) -> np.ndarray:
    """Two uint32 key words from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


# ------------------------------------------------------------------ forward
def _quantize(x, quant):
    if quant is None:
        return x
    if quant == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "float8_e4m3fn":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, F8_MAX / amax, 1.0)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        raise ValueError(f"unknown operand precision {quant!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _quantize(a, quant), _quantize(b, quant),
                      precision=HIGHEST)


def _norm(x, scale, kind, eps):
    if kind == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale
    if kind == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    raise ValueError(f"unknown norm {kind!r}")


def _rope(x, theta):
    """Rotate interleaved pairs of x (B, S, H, hd) by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, hd/2)
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c], -1).reshape(x.shape)


def _layer(h, w, m, a, quant):
    kind, eps = a["norm"], a["norm_eps"]
    x = _norm(h, w["norm1"].get("scale"), kind, eps)
    mx = w["mixer"]
    q = _mm("bsd,dhk->bshk", x, mx["wq"], quant)
    k = _mm("bsd,dhk->bshk", x, mx["wk"], quant)
    v = _mm("bsd,dhk->bshk", x, mx["wv"], quant)
    if a["qk_norm"]:
        q = _norm(q, mx["q_norm"], "rms", eps)
        k = _norm(k, mx["k_norm"], "rms", eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    group = m["heads"] // m["kv_heads"]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = q.shape[1]
    scores = _mm("bshd,bthd->bhst", q, k, quant) / math.sqrt(m["head_dim"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhst,bthd->bshd", probs, v, quant)
    h = h + _mm("bshk,hkd->bsd", o, mx["wo"], quant)
    x = _norm(h, w["norm2"].get("scale"), kind, eps)
    ff = w["ff"]
    gate = _mm("bsd,df->bsf", x, ff["w_gate"], quant)
    up = _mm("bsd,df->bsf", x, ff["w_in"], quant)
    return h + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, ff["w_out"], quant)


def loss_fn(params, tokens, config, quant=None, head_rows: int = 1024):
    """Mean next-token cross entropy on ``tokens`` (B, S+1), computed in
    float32 from ``params`` in any dtype: each layer's weights are widened
    inside its own step. Layers are rematerialised one at a time and the
    output head runs in blocks of ``head_rows`` tokens, so the reference
    fits on one chip beside its own parameters and gradients."""
    m, a = dims(config), arch(config)
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    embed = params["embed"].astype(jnp.float32)
    h = embed[inputs]

    @jax.checkpoint
    def body(h, w):
        return _layer(h, f32(w), m, a, quant), None

    h, _ = jax.lax.scan(body, h, params["layers"][0][0])
    h = _norm(h, f32(params["final_norm"]).get("scale"), a["norm"],
              a["norm_eps"])
    h = h.reshape(-1, m["d"])
    y = labels.reshape(-1)
    rows = min(head_rows, h.shape[0])
    assert h.shape[0] % rows == 0, (h.shape, rows)

    @jax.checkpoint
    def head(total, blk):
        hb, yb = blk
        logits = _mm("td,vd->tv", hb, embed, quant)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(
        head, jnp.zeros((), jnp.float32),
        (h.reshape(-1, rows, m["d"]), y.reshape(-1, rows)))
    return total / y.shape[0]
