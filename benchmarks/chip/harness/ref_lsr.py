"""Plain reference of the sim cell: the paper's federated least squares
(arXiv:2006.14591, Algorithm 1 with PP2) for a grid of variants, step sizes
and seeds, one round at a time.  It imports nothing of the program.

Per round ``k`` of a cell with key ``key``: ``kk = fold_in(key, k)`` split
in three (sample, participation, algorithm); each worker draws ``batch``
sample indices ``randint(., (N, batch), 0, n)`` and takes the gradient of
``0.5 mean((x w - y)^2)``; a worker takes part when its uniform is under
``p``; the algorithm key ``fold_in(k_alg, k)`` splits into an uplink key
(split again over workers) and a downlink key.  A worker sends ``g - h``
(``+ e`` with error feedback); the uplink and downlink round a vector to
``s`` levels of its norm with ``uniform(key, (d,))``; the server forms
``hbar + sum / (p N)``, updates ``hbar += alpha sum / N`` and each worker
``h += alpha dhat``; ``w -= gamma omega``.  The loss ``0.5 mean over all
points of (x w - y)^2`` is read after every ``eval_every``-th round.

Every product is an elementwise multiply and a sum in float32, so the
reference needs no matmul precision; ``mm="high"`` or ``"bf16"`` rounds the
products as the TPU's three-pass or one-pass bfloat16 matmul would (the
control), the same on any backend.

A rounding decision whose uniform lies within ``margin`` of its threshold
can go either way under float32 rounding alone; the reference records the
first round at which one occurs, and only eval points before it compare.
Nor do eval points of a run that diverges (its loss risen to ``rise``
times its first eval loss).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(x):
    """Round to bfloat16's 8 mantissa bits, for certain (a convert to
    bfloat16 and back may be elided by XLA's excess-precision rule)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _prod(a, b, mm: str):
    """Elementwise product at a matmul precision: float32 exactly
    (``f32``), as the TPU's three-pass bfloat16 (``high``: each operand
    split into a bfloat16 head and tail, the tail-by-tail term dropped), or
    as its one-pass bfloat16 (``bf16``)."""
    if mm == "f32":
        return a * b
    if mm == "bf16":
        return _bf16(a) * _bf16(b)
    if mm == "high":
        ah, bh = _bf16(a), _bf16(b)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return ah * bh + (ah * bl + al * bh)
    raise ValueError(mm)


def make_problem(key_seed: int, n_workers: int, n_per: int, d: int,
                 noise: float):
    """X [N, n, d], Y [N, n]: y = <w*, x> + noise, x ~ N(0, I) (App. C.1),
    drawn as ``split(PRNGKey(key_seed), 4)`` -> w*, X, noise."""
    k1, k2, k3, _ = jax.random.split(jax.random.PRNGKey(key_seed), 4)
    w_true = jax.random.normal(k1, (d,))
    X = jax.random.normal(k2, (n_workers, n_per, d))
    E = noise * jax.random.normal(k3, (n_workers, n_per))
    Y = jnp.sum(X * w_true, axis=-1) + E
    return X, Y


def squant_omega(d: int, s: int) -> float:
    return min(d / s ** 2, math.sqrt(d) / s)


def gamma_max(X: np.ndarray, d: int, n: int, s: int, p: float) -> float:
    """Table 3's step-size bound for Artemis (both ways compressed, with
    memory), with L the largest eigenvalue of X_i^T X_i / n over workers."""
    X = np.asarray(X, np.float64)
    L = max(np.linalg.eigvalsh(x.T @ x / x.shape[0])[-1] for x in X)
    wu = wd = squant_omega(d, s)
    N = n
    g1 = 1.0 / ((wd + 1) * (1 + 2.0 / (N * p)) * L)
    g2 = 3.0 / ((wd + 1) * (3 + 8 * (wu + 1) * (N + 2) / (N * p)) * L)
    g3 = N / ((wd + 1) * (N + 4 * (wu + 1) / p - 2) * L)
    return float(min(g1, g2, g3))


def _squant(key, x, s):
    """(decoded, smallest decision margin) of one vector."""
    norm = jnp.sqrt(jnp.sum(x * x))
    r = jnp.where(norm > 0, jnp.abs(x) / norm * s, 0.0)
    low = jnp.floor(r)
    u = jax.random.uniform(key, x.shape)
    q = jnp.sign(x) * (low + (u < r - low).astype(x.dtype))
    live = (r - low) > 0
    margin = jnp.min(jnp.where(live, jnp.abs(u - (r - low)), jnp.inf))
    return q * norm / s, margin


def _cell(X, Y, variant: Dict, gamma, key, *, iters: int, eval_every: int,
          batch: int, s: int, p: float, margin: float, mm: str):
    n, n_per, d = X.shape
    up, dwn, ef = variant["up"], variant["dwn"], variant["ef"]
    alpha = variant["alpha"]

    def predict(x, w):
        return jnp.sum(_prod(x, w, mm), axis=-1)

    def grad_of(xb, res):
        return jnp.sum(_prod(xb, res[..., None], mm), axis=1) / xb.shape[1]

    def round_(carry, k):
        w, h, hbar, e, fragile = carry
        kk = jax.random.fold_in(key, k)
        k_idx, k_act, k_alg = jax.random.split(kk, 3)
        idx = jax.random.randint(k_idx, (n, batch), 0, n_per)
        xb = jnp.take_along_axis(X, idx[..., None], axis=1)       # [n, b, d]
        yb = jnp.take_along_axis(Y, idx, axis=1)                   # [n, b]
        g = grad_of(xb, predict(xb, w) - yb)
        active = (jax.random.uniform(k_act, (n,)) < p).astype(jnp.float32)
        active = active[:, None]
        up_key, dwn_key = jax.random.split(jax.random.fold_in(k_alg, k))
        up_keys = jax.random.split(up_key, n)
        delta = g - h + (e if ef else 0.0)
        m = jnp.inf
        if up:
            dhat, mu = jax.vmap(lambda kk_, x: _squant(kk_, x, s))(up_keys,
                                                                  delta)
            m = jnp.minimum(m, jnp.min(mu))
        else:
            dhat = delta
        if ef:
            e_new = e + (g - h) - dhat
            e = active * e_new + (1 - active) * e
        dhat = active * dhat
        h = h + alpha * dhat
        tot = jnp.sum(dhat, axis=0)
        ghat = hbar + tot / (p * n)
        hbar = hbar + alpha * tot / n
        if dwn:
            omega, md = _squant(dwn_key, ghat, s)
            m = jnp.minimum(m, md)
        else:
            omega = ghat
        w = w - gamma * omega
        fragile = jnp.where((fragile < 0) & (m < margin), k, fragile)
        return (w, h, hbar, e, fragile), w

    def loss(w):
        res = predict(X, w) - Y
        return jnp.mean(0.5 * res * res)

    z = jnp.zeros((n, d), jnp.float32)
    carry = (jnp.zeros((d,), jnp.float32), z, jnp.zeros((d,), jnp.float32),
             z, jnp.int32(-1))
    (_, _, _, _, fragile), ws = jax.lax.scan(round_, carry, jnp.arange(iters))
    w_eval = ws[eval_every - 1::eval_every]
    return jax.vmap(loss)(w_eval), fragile


@functools.lru_cache(maxsize=None)
def _grid_fn(variant_items, iters, eval_every, batch, s, p, margin, mm):
    variant = dict(variant_items)
    f = functools.partial(_cell, variant=variant, iters=iters,
                          eval_every=eval_every, batch=batch, s=s, p=p,
                          margin=margin, mm=mm)
    return jax.jit(jax.vmap(lambda X, Y, g, k: f(X, Y, gamma=g, key=k),
                            in_axes=(None, None, 0, 0)))


def variant_table(name: str, d: int, s: int) -> Dict:
    """The paper's variants: what each compresses, and its memory rate."""
    table = {
        "sgd": (False, False, False, 0.0),
        "qsgd": (True, False, False, 0.0),
        "diana": (True, False, False, None),
        "biqsgd": (True, True, False, 0.0),
        "artemis": (True, True, False, None),
        "dore": (True, True, True, None),
    }
    up, dwn, ef, alpha = table[name]
    if alpha is None:
        alpha = 1.0 / (2.0 * (squant_omega(d, s) + 1.0))
    return {"up": up, "dwn": dwn, "ef": ef, "alpha": alpha}


def run_grid(X, Y, variants: Sequence[str], gammas, keys, *, iters: int,
             eval_every: int, batch: int, s: int, p: float, margin: float,
             mm: str = "f32"):
    """losses [V, G, S, E] and the first fragile round [V, G, S] (-1: none)
    of the grid, variant-major as the program lays it out."""
    d = X.shape[-1]
    gammas = np.asarray(gammas, np.float32)
    keys = np.asarray(keys, np.uint32)
    G, S = len(gammas), keys.shape[0]
    gg = jnp.asarray(np.repeat(gammas, S))
    kk = jnp.asarray(np.tile(keys, (G, 1)))
    losses, frag = [], []
    for v in variants:
        var = variant_table(v, d, s)
        fn = _grid_fn(tuple(sorted(var.items())), iters, eval_every, batch,
                      s, p, margin, mm)
        lo, fr = fn(X, Y, gg, kk)
        losses.append(np.asarray(lo).reshape(G, S, -1))
        frag.append(np.asarray(fr).reshape(G, S))
    return np.stack(losses), np.stack(frag)


def gap_points(prog: np.ndarray, ref: np.ndarray, fragile: np.ndarray,
               eval_every: int, rise: float) -> np.ndarray:
    """Relative gap of the program's eval losses from the reference's at
    every eval point [V, G, S, E], NaN where not compared: at or after the
    cell's first fragile round, or where the reference's loss is not finite
    or has risen to ``rise`` times its first eval loss (a run that diverges
    multiplies every rounding difference each round)."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    E = ref.shape[-1]
    rounds = (np.arange(E) + 1) * eval_every - 1            # round of each
    cut = np.where(fragile < 0, np.iinfo(np.int32).max, fragile)
    with np.errstate(invalid="ignore", over="ignore"):
        ok = (rounds[None, None, None, :] < cut[..., None]) \
            & np.isfinite(ref) & (ref < rise * ref[..., :1])
        gap = np.abs(prog - ref) / np.maximum(np.abs(ref), 1e-30)
    gap = np.where(np.isfinite(prog), gap, np.inf)
    return np.where(ok, gap, np.nan)


def grid_gap(prog: np.ndarray, ref: np.ndarray, fragile: np.ndarray,
             eval_every: int, rise: float) -> float:
    """Widest gap over every compared eval point of the grid (0 when none
    compares)."""
    g = gap_points(prog, ref, fragile, eval_every, rise)
    return float(np.nanmax(g)) if not np.isnan(g).all() else 0.0
