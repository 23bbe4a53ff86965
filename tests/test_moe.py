"""MoE routing correctness (softmax routers, all experts held): forward and
gradients of the dropless grouped-matmul layer vs a naive dense reference.
The sigmoid router, held-expert shares and the shared expert are tested
against the float32 reference in test_kanana.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe

KEY = jax.random.PRNGKey(0)
G_, T_, D_, F_, E_, K_ = 2, 16, 8, 12, 4, 2


def _naive_moe(p, x, top_k, activation="silu"):
    """Every token reaches its top-k experts (computed densely per expert
    with masks — no sort, no grouped matmul, no scatter)."""
    b, s, d = x.shape
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, top_k)
    gate = (gate / jnp.sum(gate, -1, keepdims=True)).astype(x.dtype)
    out = jnp.zeros_like(x)
    n_experts = p["router"].shape[1]
    for e in range(n_experts):
        if activation == "silu":
            h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        else:
            h = jax.nn.gelu(x @ p["w_up"][e])
        y = h @ p["w_down"][e]
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        out = out + y * w[..., None]
    return out


@pytest.fixture(scope="module")
def setup():
    p = moe.init_moe(KEY, D_, F_, E_, E_, "silu")
    x = jax.random.normal(jax.random.PRNGKey(1), (G_, T_, D_))
    return p, x


def test_forward_matches_naive(setup):
    p, x = setup
    out, _ = moe.moe_apply(p, x, top_k=K_)
    ref = _naive_moe(p, x, K_)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_grads_match_naive(setup):
    """Autodiff of the sort / grouped matmul / scatter-add path must agree
    with autodiff of the naive path."""
    p, x = setup

    def loss_fast(p, x):
        out, _ = moe.moe_apply(p, x, top_k=K_)
        return jnp.sum(out * jnp.cos(jnp.arange(D_)))

    def loss_ref(p, x):
        return jnp.sum(_naive_moe(p, x, K_) * jnp.cos(jnp.arange(D_)))

    g1 = jax.grad(loss_fast, argnums=(0, 1))(p, x)
    g2 = jax.grad(loss_ref, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_capacity_drops_tokens(setup):
    """The layer has no capacity: with adversarially unbalanced routing
    (expert 0's load past what a 1.25 capacity factor would keep) no
    token is dropped, and the output is the naive one."""
    p, x = setup
    # bias router hard toward expert 0
    p2 = dict(p, router=p["router"].at[:, 0].add(100.0))
    out, stats = moe.moe_apply(p2, x, top_k=K_)
    assert float(stats["moe_max_load"]) > 1.25 * G_ * T_ * K_ / E_
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_naive_moe(p2, x, K_)),
                               rtol=1e-4, atol=1e-5)


def test_aux_loss_balanced_lower(setup):
    p, x = setup
    _, bal = moe.moe_apply(p, x, top_k=K_)
    p2 = dict(p, router=p["router"].at[:, 0].add(100.0))
    _, skew = moe.moe_apply(p2, x, top_k=K_)
    assert float(skew["aux"]) > float(bal["aux"])


def test_grad_through_capacity_drop(setup):
    """Gradients stay finite under routing that would overflow a 1.25
    capacity factor (biased hard toward expert 0)."""
    p, x = setup
    p = dict(p, router=p["router"].at[:, 0].add(100.0))

    def loss(p, x):
        out, stats = moe.moe_apply(p, x, top_k=K_)
        return jnp.mean(out ** 2) + 0.01 * stats["aux"]

    g = jax.grad(loss)(p, x)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
