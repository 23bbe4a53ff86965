"""Pallas kernel microbenchmarks (CPU interpret timings + HBM traffic model).

Wall-times on CPU interpret mode are NOT TPU predictions; the derived column
carries the *memory-traffic model* (bytes moved per element), which is what
the fused kernel improves and what the TPU memory roofline sees.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels import squant as sq
from repro.kernels import fused_memory as fm

KEY = jax.random.PRNGKey(0)

FAST = False      # set by benchmarks/run.py --fast: small shapes, 1 rep


def _bench(fn, *args, reps=5):
    if FAST:
        reps = 1
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def kernel_suite():
    m, n = (256, 256) if FAST else (1024, 1024)
    x = jax.random.normal(KEY, (m, n))
    u = jax.random.uniform(jax.random.PRNGKey(1), (m, n))
    h = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (m, n))
    block = (256, 256)
    rows = []

    us = _bench(lambda: sq.squant_encode(x, u, s=1, block=block, interpret=True))
    rows.append(("kernel/squant_encode", us, "bytes_per_elem=4r+1w+0.0002s"))

    us_ref = _bench(lambda: ref.squant_encode_ref(x, u, 1, *block))
    rows.append(("kernel/squant_encode_ref", us_ref, "oracle"))

    def tiles(a):                      # [M, N] -> [T, 256, 256] tile stack
        return ref._blockify(a, *block).reshape((-1,) + block)
    xt, ht, ut = tiles(x), tiles(h), tiles(u)
    us = _bench(lambda: fm.fused_memory_update(xt, ht, ut, 0.25, s=1,
                                               interpret=True))
    # unfused: delta=g-h (2r 1w), encode (2r 1w), decode+h update (3r 1w)
    # fused: g,h,u read once; q, h_new written once
    rows.append(("kernel/fused_memory", us,
                 "hbm_passes fused=3r2w vs unfused=7r3w (1.67x less traffic)"))

    def unfused(g, hh, uu):
        q, s_ = sq.squant_encode(g - hh, uu, s=1, block=block, interpret=True)
        dh = sq.squant_decode(q, s_, block=block, interpret=True)
        return q, s_, hh + 0.25 * dh
    us = _bench(lambda: unfused(x, h, u))
    rows.append(("kernel/unfused_memory", us, "reference pipeline"))

    q, s_ = sq.squant_encode(x, u, s=1, block=block, interpret=True)
    us = _bench(lambda: sq.dequant_apply(h, q, s_, 0.01, block=block,
                                         interpret=True))
    rows.append(("kernel/dequant_apply", us, "fused optimizer apply"))

    # server-side ring accumulation (fused dequant-accumulate of N payloads)
    from repro.kernels import ring_sum as rs
    nq = jax.random.randint(jax.random.PRNGKey(4), (4, m, n), -2, 3,
                            dtype=jnp.int8)
    ns = jax.random.uniform(jax.random.PRNGKey(5), (4, m, 1))
    us = _bench(lambda: rs.ring_sum(nq, ns, interpret=True))
    rows.append(("kernel/ring_sum", us,
                 "fused N-payload dequant-accumulate, 1 f32 write"))
    us = _bench(lambda: rs.ring_sum_ref(nq, ns))
    rows.append(("kernel/ring_sum_ref", us, "oracle"))

    # wire-format compression ratio
    c, shape = ops.encode(KEY, x, s=1)
    ratio = (x.size * 4) / c.wire_bytes
    rows.append(("kernel/wire_ratio", 0.0, f"fp32_bytes/wire_bytes={ratio:.2f}"))
    return rows


ALL = [kernel_suite]
