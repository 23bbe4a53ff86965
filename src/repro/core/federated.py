"""Federated/distributed simulator reproducing the paper's experiments (§5).

N workers with heterogeneous local datasets, a central server, partial
participation, bidirectional compression, and full uplink/downlink/catch-up
bit metering (Remark 3: a returning worker downloads the missed compressed
updates, or the whole model if it has been away > floor(M1/M2) rounds).

The whole optimization runs under one ``lax.scan`` so hundreds of iterations
for all 5+ algorithm variants finish in seconds on CPU.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import artemis as art
from repro.core import compression as comp


# ---------------------------------------------------------------------------
# Problems: least-squares regression & logistic regression (paper §C.1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Problem:
    """N-worker problem with stacked data X: [N, n, d], Y: [N, n]."""
    X: jax.Array
    Y: jax.Array
    kind: str                   # 'lsr' | 'logistic'
    reg: float = 0.0            # l2 regularization (strong convexity floor)

    @property
    def n_workers(self) -> int:
        return self.X.shape[0]

    @property
    def dim(self) -> int:
        return self.X.shape[-1]

    def local_loss(self, w: jax.Array, x: jax.Array, y: jax.Array) -> jax.Array:
        pred = x @ w
        if self.kind == "lsr":
            per = 0.5 * (pred - y) ** 2
        elif self.kind == "logistic":
            per = jnp.logaddexp(0.0, -y * pred)
        else:
            raise ValueError(self.kind)
        return jnp.mean(per) + 0.5 * self.reg * jnp.sum(w**2)

    def global_loss(self, w: jax.Array) -> jax.Array:
        losses = jax.vmap(lambda x, y: self.local_loss(w, x, y))(self.X, self.Y)
        return jnp.mean(losses)

    def worker_grad(self, w: jax.Array, idx: jax.Array) -> jax.Array:
        """Stacked minibatch gradients [N, d]; idx: [N, b] sample indices."""
        def one(x, y, ix):
            xb, yb = x[ix], y[ix]
            return jax.grad(self.local_loss)(w, xb, yb)
        return jax.vmap(one)(self.X, self.Y, idx)

    def full_grad(self, w: jax.Array) -> jax.Array:
        def one(x, y):
            return jax.grad(self.local_loss)(w, x, y)
        return jax.vmap(one)(self.X, self.Y)

    def smoothness(self) -> float:
        """L estimate: max_i largest eigenvalue of (1/4 for logistic) X_i^T X_i / n."""
        def one(x):
            cov = x.T @ x / x.shape[0]
            return jnp.linalg.eigvalsh(cov)[-1]
        lam = jax.vmap(one)(self.X)
        scale = 1.0 if self.kind == "lsr" else 0.25
        return float(jnp.max(lam)) * scale + self.reg

    def solve_opt(self, iters: int = 3000) -> jax.Array:
        """w* by full-batch GD (closed-form for LSR)."""
        if self.kind == "lsr" and self.reg == 0.0:
            X = self.X.reshape(-1, self.dim)
            Y = self.Y.reshape(-1)
            return jnp.linalg.lstsq(X, Y)[0]
        L = self.smoothness()
        w = jnp.zeros((self.dim,))
        def body(w, _):
            g = jnp.mean(self.full_grad(w), axis=0)
            return w - (1.0 / L) * g, None
        w, _ = jax.lax.scan(body, w, None, length=iters)
        return w


# ---------------------------------------------------------------------------
# Synthetic datasets (paper §C.1)
# ---------------------------------------------------------------------------

def make_lsr_problem(key, n_workers=20, n_per=200, d=20, noise=0.4,
                     iid=True) -> Tuple[Problem, jax.Array]:
    """LSR: y = <w*, x> + e, e ~ N(0, noise^2). noise=0 => sigma_* = 0."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w_true = jax.random.normal(k1, (d,))
    if iid:
        X = jax.random.normal(k2, (n_workers, n_per, d))
    else:
        # per-worker anisotropic covariances -> heterogeneous distributions
        scales = 0.5 + jax.random.uniform(k4, (n_workers, 1, d)) * 2.0
        X = jax.random.normal(k2, (n_workers, n_per, d)) * scales
    E = noise * jax.random.normal(k3, (n_workers, n_per))
    Y = jnp.einsum("nbd,d->nb", X, w_true) + E
    return Problem(X=X, Y=Y, kind="lsr"), w_true


def make_logistic_problem(key, n_workers=20, n_per=200, d=2,
                          ) -> Problem:
    """Non-i.i.d. logistic: half the workers use model w1=(10,10,..),
    the other half w2=(10,-10,..), with distinct input covariances (§C.1)."""
    k1, k2, k3 = jax.random.split(key, 3)
    w1 = jnp.full((d,), 10.0).at[1:].set(10.0)
    w2 = jnp.full((d,), 10.0).at[1:].set(-10.0)
    # the two worker populations deliberately share ONE uniform draw so their
    # covariances are exact mirrors (cov1 + cov2 == 3); a fresh key here
    # would decouple them and shift the golden logistic problems
    cov1 = 1.0 + 0.5 * jax.random.uniform(k3, (d,))
    cov2 = 2.0 - 0.5 * jax.random.uniform(k3, (d,))  # repro-lint: allow=prng-key-reuse
    Xs, Ys = [], []
    keys = jax.random.split(k1, n_workers)
    for i in range(n_workers):
        cov = cov1 if i % 2 == 0 else cov2
        wm = w1 if i % 2 == 0 else w2
        x = jax.random.normal(keys[i], (n_per, d)) * cov
        pz = jax.nn.sigmoid(x @ wm)
        y = 2.0 * jax.random.bernoulli(jax.random.fold_in(k2, i), pz).astype(jnp.float32) - 1.0
        Xs.append(x)
        Ys.append(y)
    return Problem(X=jnp.stack(Xs), Y=jnp.stack(Ys), kind="logistic", reg=1e-3)


def make_clustered_problem(key, n_workers=20, n_per=400, d=40, noise=0.2) -> Problem:
    """Stand-in for the TSNE-clustered real datasets: each worker's inputs come
    from a distinct Gaussian cluster (non-i.i.d., unbalanced scales)."""
    kc, kx, kw, ke = jax.random.split(key, 4)
    centers = 3.0 * jax.random.normal(kc, (n_workers, d))
    X = centers[:, None, :] + jax.random.normal(kx, (n_workers, n_per, d))
    w_true = jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    Y = jnp.einsum("nbd,d->nb", X, w_true) + noise * jax.random.normal(ke, (n_workers, n_per))
    return Problem(X=X, Y=Y, kind="lsr", reg=1e-3)


# ---------------------------------------------------------------------------
# The simulator
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    losses: np.ndarray          # [iters] F(w_k)
    bits: np.ndarray            # [iters] cumulative communicated bits
    w_final: np.ndarray
    w_avg: np.ndarray           # Polyak-Ruppert average (all iterates)
    w_tail_avg: np.ndarray      # average over the last half (variance readout)
    dist_to_opt: Optional[np.ndarray] = None


def run(problem: Problem, cfg: art.ArtemisConfig, gamma: float, iters: int,
        key: jax.Array, batch: int = 1, w0: Optional[jax.Array] = None,
        full_batch: bool = False, w_star: Optional[jax.Array] = None,
        gamma_decay: bool = False, eval_every: int = 1,
        backend: Optional[str] = None) -> RunResult:
    """Run Artemis (any variant) on ``problem`` for ``iters`` rounds.

    Thin wrapper over the batched sweep engine (``core.sweep.run_sweep``)
    with a single-cell grid: repeated calls that differ only in ``gamma`` or
    ``key`` hit the compiled-program cache and re-trace zero times.  The
    original one-trace-per-call loop is kept as ``run_percell`` (legacy
    reference).

    Bit metering (unified rule, DESIGN.md §4): per round, every ACTIVE worker
    pays its uplink message plus the downlink catch-up — one compressed
    update per round missed since its last participation (>= 1: a worker
    active every round pays exactly this round's broadcast), capped at one
    full model once it has been away longer than floor(M1/M2) rounds
    (Remark 3).  Inactive workers communicate nothing.
    """
    from repro.core import sweep as _sweep   # lazy: sweep imports this module
    res = _sweep.run_sweep(
        problem, [cfg], [gamma], jnp.asarray(key)[None], iters, batch=batch,
        eval_every=eval_every, full_batch=full_batch, w0=w0, w_star=w_star,
        gamma_decay=gamma_decay, backend=backend)
    return RunResult(
        losses=res.losses[0, 0, 0],
        bits=res.bits[0, 0, 0],
        w_final=res.w_final[0, 0, 0],
        w_avg=res.w_avg[0, 0, 0],
        w_tail_avg=res.w_tail_avg[0, 0, 0],
        dist_to_opt=res.dists[0, 0, 0] if w_star is not None else None,
    )


def run_percell(problem: Problem, cfg: art.ArtemisConfig, gamma: float,
                iters: int, key: jax.Array, batch: int = 1,
                w0: Optional[jax.Array] = None, full_batch: bool = False,
                w_star: Optional[jax.Array] = None,
                gamma_decay: bool = False) -> RunResult:
    """Legacy single-cell loop: traces a fresh ``lax.scan`` per call and
    evaluates the full-batch loss every iteration.  Kept as the reference
    implementation the sweep engine is benchmarked and cross-checked against
    (``benchmarks/dist_bench.sweep_engine_suite``, tests/test_sweep.py)."""
    n, d = problem.n_workers, problem.dim
    n_per = problem.X.shape[1]
    c_up, c_dwn = cfg.compressors()
    m1 = comp.FP_BITS * d                        # full-model message
    m2 = max(c_dwn.bits(d), 1.0)                 # compressed-update message
    catchup_window = max(int(m1 // m2), 1)

    w0 = jnp.zeros((d,)) if w0 is None else w0
    state0 = art.init_state(cfg)
    last_part0 = -jnp.ones((n,), jnp.int32)      # k_i, last participation

    def step(carry, k):
        w, st, wsum, wtail, last_part = carry
        kk = jax.random.fold_in(key, k)
        k_idx, k_act, k_art = jax.random.split(kk, 3)
        if full_batch:
            grads = problem.full_grad(w)
        else:
            idx = jax.random.randint(k_idx, (n, batch), 0, n_per)
            grads = problem.worker_grad(w, idx)
        active = (jax.random.uniform(k_act, (n,)) < cfg.p).astype(jnp.float32)
        omega, st, stats = art.artemis_round(cfg, st, grads, k_art, active)
        g = gamma / jnp.sqrt(k + 1.0) if gamma_decay else gamma
        w = w - g * omega
        # --- catch-up bit metering (Remark 3) ------------------------------
        missed = k - last_part              # rounds since last download (>= 1)
        catch_bits = jnp.where(missed > catchup_window,
                               float(m1), missed.astype(jnp.float32) * m2)
        catch_bits = jnp.sum(active * catch_bits)
        last_part = jnp.where(active > 0, k, last_part).astype(jnp.int32)
        bits = stats["uplink_bits"] + catch_bits    # dwnlink counted in catch-up
        loss = problem.global_loss(w)
        wtail = wtail + jnp.where(k >= iters // 2, 1.0, 0.0) * w
        return (w, st, wsum + w, wtail, last_part), (loss, bits,
                                                     jnp.linalg.norm(w - (w_star if w_star is not None else 0.0)))

    (w, _, wsum, wtail, _), (losses, bits, dists) = jax.lax.scan(
        step, (w0, state0, jnp.zeros_like(w0), jnp.zeros_like(w0), last_part0),
        jnp.arange(iters))
    return RunResult(
        losses=np.asarray(losses),
        bits=np.asarray(jnp.cumsum(bits)),
        w_final=np.asarray(w),
        w_avg=np.asarray(wsum / iters),
        w_tail_avg=np.asarray(wtail / max(iters - iters // 2, 1)),
        dist_to_opt=np.asarray(dists) if w_star is not None else None,
    )


def gamma_max(problem: Problem, cfg: art.ArtemisConfig) -> float:
    """Step-size upper bound from Table 3 / Theorems S5-S6."""
    c_up, c_dwn = cfg.compressors()
    L = problem.smoothness()
    N, p = cfg.n_workers, cfg.p
    wu, wd = c_up.omega, c_dwn.omega
    if cfg.resolved_alpha() == 0.0:   # Thm S5
        return p * N / (L * (wd + 1) * (p * N + 2 * (wu + 1)))
    # Thm S6 (minimum of the three constraints)
    g1 = 1.0 / ((wd + 1) * (1 + 2.0 / (N * p)) * L)
    g2 = 3.0 / ((wd + 1) * (3 + 8 * (wu + 1) * (N + 2) / (N * p)) * L)
    g3 = N / ((wd + 1) * (N + 4 * (wu + 1) / p - 2) * L)
    return min(g1, g2, g3)
