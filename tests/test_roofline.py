"""Roofline derivation unit tests: HLO collective parsing + flops accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import roofline as R

HLO = """
  %ag = f32[2,64,128]{2,1,0} all-gather(%x), replica_groups={{0,1}}
  %ar.1 = bf16[1024]{0} all-reduce(%y), to_apply=%sum
  %cp = s8[32,16]{1,0} collective-permute(%q), source_target_pairs={{0,1}}
  %rs = f32[512]{0} reduce-scatter(%z), dimensions={0}
  %a2a = f32[8,8]{1,0} all-to-all(%w), dimensions={0}
  %ags = s8[4,4]{1,0} all-gather-start(%v)
  %agd = s8[4,4]{1,0} all-gather-done(%ags)
"""


def test_collective_bytes_parsing():
    out = R.collective_bytes(HLO)
    assert out["all-gather"] == 2 * 64 * 128 * 4 + 4 * 4      # incl. -start
    assert out["all-reduce"] == 1024 * 2
    assert out["collective-permute"] == 32 * 16
    assert out["reduce-scatter"] == 512 * 4
    assert out["all-to-all"] == 64 * 4


def test_shape_bytes_tuple():
    assert R._shape_bytes("(f32[4,4], s8[8])") == 64 + 8


def test_collective_dtype_bytes_parsing():
    out = R.collective_dtype_bytes(HLO)
    assert out[("collective-permute", "s8")] == 32 * 16
    assert out[("all-gather", "f32")] == 2 * 64 * 128 * 4
    assert out[("all-gather", "s8")] == 4 * 4          # -start only, not -done
    assert out[("all-reduce", "bf16")] == 1024 * 2
    assert ("collective-permute", "f32") not in out


def test_bucketed_wire_model_accounting():
    m = R.bucketed_wire_model(n_workers=4, n_buckets=8, rows=33, row=256)
    assert m["hlo_s8_bytes"] == 8 * 33 * 256
    assert m["hlo_scale_bytes"] == 4 * 8 * 33
    assert m["wire_bytes_per_step"] == 3 * m["payload_bytes"]
    # pipelining can only help, and the exposed time is what overlap leaves
    assert m["step_comm_pipelined_s"] <= m["step_comm_serial_s"]
    assert m["exposed_comm_s"] <= m["comm_s"]
    # compute-bound regime: wire so fast the dequant fully hides it
    fast = R.bucketed_wire_model(n_workers=4, n_buckets=8, rows=33, row=256,
                                 ici_bw=1e18, coll_lat=0.0)
    assert fast["exposed_comm_s"] == 0.0


def test_wire_bytes_match_guard():
    """The bucketed model's levels and row scales against a ring hop's two
    collective-permutes."""
    hop = HLO + "  %cs = f32[32,1]{1,0} collective-permute(%s)\n"
    m = R.bucketed_wire_model(n_workers=4, n_buckets=1, rows=32, row=16)
    ok = R.wire_bytes_match(hop, m)
    assert ok["ok"] and ok["rel_err"] == 0.0
    assert ok["measured_s8"] == 32 * 16 and ok["measured_scale_f32"] == 4 * 32
    bad = R.wire_bytes_match(hop, R.bucketed_wire_model(
        n_workers=4, n_buckets=2, rows=32, row=16))
    assert not bad["ok"] and bad["rel_err"] == pytest.approx(0.5)
    none = R.wire_bytes_match("", m)
    assert not none["ok"]                  # zero measured bytes never pass


def test_bucketed_wire_model_from_codec():
    """Passing a core/codec.py codec derives the byte split from its
    wire_bytes — identical to the legacy analytic split for row_squant, and
    a genuinely different wire (s32 indices + f32 values) for sparsify."""
    from repro.core import codec as wire
    rq = wire.make_codec("row_squant", 256, s=3)
    m_codec = R.bucketed_wire_model(n_workers=4, n_buckets=8, rows=33,
                                    row=256, codec=rq)
    m_legacy = R.bucketed_wire_model(n_workers=4, n_buckets=8, rows=33,
                                     row=256)
    for k in ("payload_bytes", "hlo_s8_bytes", "hlo_scale_bytes",
              "wire_bytes_per_step", "comm_s", "exposed_comm_s"):
        assert m_codec[k] == m_legacy[k], k
    assert m_codec["hlo_bytes_by_dtype"] == {"s8": 8 * 33 * 256.0,
                                             "f32": 8 * 4 * 33.0}

    sp = wire.make_codec("sparsify", 256, q=0.5)
    m_sp = R.bucketed_wire_model(n_workers=4, n_buckets=8, rows=33, row=256,
                                 codec=sp)
    n = 33 * 256
    assert m_sp["hlo_bytes_by_dtype"] == {"s32": 8 * 4.0 * n,
                                          "f32": 8 * 4.0 * n}
    assert m_sp["hlo_s8_bytes"] == 0.0


def test_wire_bytes_match_per_dtype():
    """Codec-derived models check EVERY payload dtype, not just s8."""
    m = {"hlo_bytes_by_dtype": {"s8": 32 * 16.0}}
    ok = R.wire_bytes_match(HLO, m)
    assert ok["ok"] and ok["by_dtype"]["s8"]["rel_err"] == 0.0
    # a dtype the HLO does not carry fails the guard
    m2 = {"hlo_bytes_by_dtype": {"s8": 32 * 16.0, "s32": 1024.0}}
    assert not R.wire_bytes_match(HLO, m2)["ok"]
    # byte mismatch on a present dtype fails too
    m3 = {"hlo_bytes_by_dtype": {"s8": 32 * 16 * 2.0}}
    bad = R.wire_bytes_match(HLO, m3)
    assert not bad["ok"] and bad["rel_err"] == pytest.approx(0.5)


def test_roofline_terms_and_dominant():
    rl = R.Roofline(arch="a", shape="s", mesh="pod", chips=256, kind="train",
                    hlo_flops=197e12, hlo_bytes=819e9 * 2,
                    coll_bytes={"all-reduce": int(50e9 * 0.5)},
                    model_flops=100e12).finalize()
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(2.0)
    assert rl.collective_s == pytest.approx(0.5)
    assert rl.dominant == "memory"


def test_active_params_moe():
    cfg = configs.get_config("olmoe-1b-7b", reduced=True)
    from repro.models.model import build_model
    params = jax.eval_shape(lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    total = R.count_params(params)
    active = R.active_params(cfg, params)
    assert active < total                     # top-2 of 4 experts
    # expert fraction scales by top_k/n_experts = 1/2
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    expert = sum(int(np.prod(l.shape)) for p, l in flat
                 if "moe" in "/".join(str(getattr(q, 'key', q)) for q in p)
                 and "router" not in "/".join(str(getattr(q, 'key', q)) for q in p))
    assert active == pytest.approx(total - expert + expert * 0.5)


def test_model_flops_conventions():
    cfg = configs.get_config("starcoder2-7b", reduced=True)
    from repro.models.model import build_model
    params = jax.eval_shape(lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    n = R.active_params(cfg, params)
    assert R.model_flops(cfg, params, "train", 2, 8) == 6 * n * 16
    assert R.model_flops(cfg, params, "prefill", 2, 8) == 2 * n * 16
    assert R.model_flops(cfg, params, "decode", 2, 8) == 2 * n * 2


def test_input_specs_shapes():
    for arch in configs.ARCHS:
        cfg = configs.get_config(arch)
        for sname, shape in configs.SHAPES.items():
            if configs.applicable(cfg, shape):
                continue
            specs = configs.input_specs(cfg, shape)
            if shape.kind in ("train", "prefill"):
                if cfg.family == "vlm":
                    assert specs["tokens"].shape == (shape.batch,
                                                     shape.seq - cfg.n_patches)
                    assert specs["embeds"].shape[1] == cfg.n_patches
                else:
                    assert specs["tokens"].shape == (shape.batch, shape.seq)
            else:
                assert specs["token"].shape == (shape.batch,)
                assert len(jax.tree.leaves(specs["cache"])) > 0
