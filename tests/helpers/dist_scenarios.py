"""Multi-device test scenarios (run in a subprocess with 8 fake CPU devices).

Invoked by tests/test_dist.py as:
    python tests/helpers/dist_scenarios.py <scenario>
Exits non-zero on assertion failure.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import dist
from repro.launch import mesh as M
from repro.models.model import build_model
from repro.optim import sgd


def _setup(variant="artemis", worker_axes=("pod",), s=3, p=1.0):
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = configs.get_config("starcoder2-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = None if variant == "none" else dist.DistConfig(
        worker_axes=worker_axes, variant=variant, s=s, p_participation=p)
    init_state, step_fn = dist.make_train_step(model, sgd(0.05), dcfg, mesh)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 128), 0,
                                          cfg.vocab)}
    return mesh, model, params, init_state, step_fn, batch


def scenario_convergence():
    mesh, model, params, init_state, step_fn, batch = _setup("artemis")
    with jax.set_mesh(mesh):
        state = init_state(params)
        jstep = jax.jit(step_fn)
        losses = []
        for _ in range(12):
            state, (loss, _) = jstep(state, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses
    assert all(np.isfinite(l) for l in losses)
    # memory engaged: h moved away from zero
    hn = sum(float(jnp.sum(jnp.square(l))) for l in jax.tree.leaves(state.artemis.h))
    assert hn > 0


def scenario_sgd_variant_matches_baseline():
    """variant='sgd' over pod (explicit psum) == dcfg=None baseline (XLA)."""
    out = {}
    for tag, variant in [("explicit", "sgd"), ("baseline", "none")]:
        mesh, model, params, init_state, step_fn, batch = _setup(variant)
        with jax.set_mesh(mesh):
            state = init_state(params)
            jstep = jax.jit(step_fn)
            for _ in range(3):
                state, (loss, _) = jstep(state, batch)
            out[tag] = float(loss)
    assert abs(out["explicit"] - out["baseline"]) < 5e-3, out


def scenario_baseline_state_tree():
    """dcfg=None (the plain data-parallel baseline) keeps its per-leaf
    Artemis state, all replicated, through a step: h, e and acc a [1] f32
    zero stub per parameter leaf, hbar a scalar zero per leaf — the tree a
    ``--dist none`` checkpoint holds."""
    mesh, model, params, init_state, step_fn, batch = _setup("none")
    ptree = jax.tree.structure(params)
    with jax.set_mesh(mesh):
        state = init_state(params)
        new, (loss, _) = jax.jit(step_fn)(state, batch)
    assert np.isfinite(float(loss))
    for st in (state, new):
        art = st.artemis
        for name, shape in (("h", (1,)), ("e", (1,)), ("acc", (1,)),
                            ("hbar", ())):
            tree = getattr(art, name)
            assert jax.tree.structure(tree) == ptree, name
            for leaf in jax.tree.leaves(tree):
                assert leaf.shape == shape and leaf.dtype == jnp.float32, name
                assert not np.any(np.asarray(leaf)), name
        assert art.prev_active.shape == (1,) and art.step.shape == ()
        for leaf in jax.tree.leaves(art):
            assert leaf.sharding.is_fully_replicated, leaf.sharding
    assert int(state.artemis.step) == 0 and int(new.artemis.step) == 1
    shard = dist.state_shardings(mesh, state, M.params_shardings(mesh, params),
                                 None)
    for sh in jax.tree.leaves(shard.artemis):
        assert sh.spec == P(), sh


def scenario_all_variants_lower():
    for variant in ["qsgd", "diana", "biqsgd", "artemis"]:
        mesh, model, params, init_state, step_fn, batch = _setup(variant)
        with jax.set_mesh(mesh):
            state = init_state(params)
            state, (loss, _) = jax.jit(step_fn)(state, batch)
            assert np.isfinite(float(loss)), variant


def scenario_partial_participation():
    mesh, model, params, init_state, step_fn, batch = _setup("artemis", p=0.5)
    with jax.set_mesh(mesh):
        state = init_state(params)
        jstep = jax.jit(step_fn)
        losses = [float(jstep(state, batch)[1][0])]
        for _ in range(15):
            state, (loss, _) = jstep(state, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def scenario_int8_ring_in_hlo():
    """The compiled HLO must move int8 (not f32) across the worker axis."""
    import re
    mesh, model, params, init_state, step_fn, batch = _setup("artemis")
    with jax.set_mesh(mesh):
        state = init_state(params)
        hlo = jax.jit(step_fn).lower(state, batch).compile().as_text()
    perms = re.findall(r"= (\w+)\[[0-9,]*\][^ ]* collective-permute", hlo)
    assert any(d == "s8" for d in perms), perms


def scenario_mesh_policy():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    # big matrices: 2-D sharded
    assert M.param_spec(mesh, "layers/mlp/w_up", (4, 256, 512)) == \
        P(None, "data", "model")
    # embed: vocab unsharded
    assert M.param_spec(mesh, "embed", (1000, 256)) == P(None, "model")
    # moe experts over model when divisible
    assert M.param_spec(mesh, "layers/moe/w_up", (4, 8, 256, 512)) == \
        P(None, "model", "data", None)
    # non-divisible expert count falls back to 2-D weight sharding
    assert M.param_spec(mesh, "layers/moe/w_up", (4, 3, 256, 512)) == \
        P(None, None, "data", "model")
    # non-divisible dims left unsharded
    assert M.param_spec(mesh, "layers/mlp/w_up", (4, 255, 513)) == P(None, None, None)
    # strip_axes removes manual axes
    assert M.strip_axes(P("pod", "data"), ("pod",)) == P(None, "data")
    assert M.strip_axes(P(("pod", "data"), None), ("pod",)) == P(("data",), None)


def scenario_pipeline_sharding():
    from repro.data.pipeline import ShardedBatches
    from repro.data.synthetic import TokenStream, TokenStreamConfig
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    stream = TokenStream(TokenStreamConfig(vocab=64, seq_len=16, batch=8))
    sb = ShardedBatches(stream, mesh)
    b = sb.batch_at(0)
    assert b["tokens"].shape == (8, 16)
    assert b["tokens"].sharding.spec == P(("pod", "data"))
    # determinism
    b2 = sb.batch_at(0)
    assert jnp.array_equal(b["tokens"], b2["tokens"])


def scenario_dore_and_local_steps():
    """Beyond-paper variants: Dore-style EF and local-step accumulation both
    converge; the local (non-communicating) step's HLO has NO collectives."""
    import re
    from repro.core.dist import make_local_step
    mesh, model, params, init_state, step_fn, batch = _setup("dore")
    with jax.set_mesh(mesh):
        state = init_state(params)
        jstep = jax.jit(step_fn)
        l0 = float(jstep(state, batch)[1][0])
        for _ in range(8):
            state, (loss, _) = jstep(state, batch)
        assert float(loss) < l0
        en = sum(float(jnp.sum(jnp.square(l)))
                 for l in jax.tree.leaves(state.artemis.e))
        assert en > 0, "EF buffer never engaged"

    mesh, model, params, init_state, step_fn, batch = _setup("artemis")
    dcfg = dist.DistConfig(worker_axes=("pod",), variant="artemis", s=3,
                           local_steps=4)
    init_state, step_fn = dist.make_train_step(model, sgd(0.05), dcfg, mesh)
    local_fn = make_local_step(model, dcfg, mesh)
    with jax.set_mesh(mesh):
        state = init_state(params)
        hlo = jax.jit(local_fn).lower(state, batch).compile().as_text()
        colls = re.findall(r"(all-reduce|all-gather|collective-permute|"
                           r"reduce-scatter|all-to-all)\(", hlo)
        assert not colls, f"local step must not communicate: {colls[:5]}"


def scenario_train_main_compiles_once():
    """``launch/train.py:main`` with the Artemis workers on ``data``, which
    the param policy also splits (FSDP): whisper-tiny (reduced) trains on
    its device-drawn frames, and the step compiles exactly once, so the
    steady window times no compile."""
    import logging
    from repro.launch import train
    from repro.obs import spans as obs_spans

    compiles = []

    class Count(logging.Handler):
        def emit(self, rec):
            if "Compiling jit(step_fn)" in rec.getMessage():
                compiles.append(rec)

    logging.getLogger("jax").addHandler(Count())
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_log_compiles", True)
    logs = train.main(["--arch", "whisper-tiny", "--reduced", "--dist",
                       "artemis", "--mesh", "8x1", "--workers", "data",
                       "--steps", "3", "--batch", "8", "--seq", "32",
                       "--log-every", "1"])
    assert [r["step"] for r in logs] == [1, 2, 3], logs
    assert all(np.isfinite(r["loss"]) for r in logs), logs
    assert len(compiles) == 1, len(compiles)
    assert obs_spans.total("train/steady_steps") > 0


if __name__ == "__main__":
    name = sys.argv[1]
    globals()[f"scenario_{name}"]()
    print(f"scenario {name}: OK")
