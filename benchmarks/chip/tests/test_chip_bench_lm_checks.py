"""The kanana cell's comparison, driven end to end on the CPU at a test
size with the cell's own limits: a sound run is correct and reports the
per-layer metrics' inputs; the reference in float8 put in the program's
place, a step that returns its state unchanged, and half of each row's
tokens left out are not."""

import pytest

import chip_cells as cells
from harness import compare, flops_lm, spec

DRIVER = spec.load_driver("train_lm")
WORKLOAD = "train.kanana-2-30b-a3b.s4k"

# At the test size bfloat16 moves the comparison more than at the cell's
# (a width of 64, and routing flips among few tokens): sound runs read loss
# gaps up to 1.7e-4, gradient gaps up to 0.11 and change gaps up to 0.040
# on six seeds (CPU), the float8 control 2.8e-4 and 0.050 (change) or
# more, half the tokens left out 1.4e-3, 0.35 and 0.15.  The tests hold the
# loss to 2.2e-4, the gradient to 0.3 and the change to 0.045 and keep the
# cell's other limits.
TEST_LIMITS = {"loss_gap": 2.2e-4, "grad_gap": 0.3, "change_gap": 0.045}


@pytest.fixture(scope="module")
def cell():
    """The cell at a test size: the dense layer and 2 MoE layers of width
    64, 4 of 16 experts held (4 per token), one row of 256 tokens."""
    cfg = cells._load("configs", "kanana-2-30b-a3b.json")
    small = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=32,
                 moe_intermediate_size=32, intermediate_size=96,
                 n_routed_experts=4, n_routed_experts_published=16,
                 num_experts_per_tok=4, num_hidden_layers=3, vocab_size=512)
    cfg.update(small)
    cfg["run_as"]["fields"].update(
        n_layers=3, d_model=64, n_heads=4, n_kv=4, d_ff=32, vocab=512,
        head_dim=16, kv_lora_rank=32, qk_rope_dim=8, v_head_dim=8,
        n_experts=16, top_k=4, expert_shards=4, dense_ff=96, q_chunk=32,
        xent_chunk=32)
    tr = cells._load("traffic", "train.lm.s4k.json")
    tr.update(seq=256)
    tr["wire"] = dict(tr["wire"], bucket_bytes=4096)
    return spec.Cell(name=WORKLOAD, chips=1, config_name="kanana-2-30b-a3b",
                     config=cfg, traffic_name="test", traffic=tr,
                     driver="train_lm", end_to_end=[], per_layer=[],
                     limits={**cells._load("limits", f"{WORKLOAD}.json"),
                             **TEST_LIMITS})


def test_sound_run_is_correct(cell):
    res = DRIVER.run(cells.ctx(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    rec = res["rec"]
    # 256 tokens x 4 per token x 2 MoE layers, about a quarter held
    assert 0 < rec["moe_held_assignments"] < 256 * 4 * 2
    assert rec["moe_max_load"] <= rec["moe_held_assignments"]
    assert rec["train_flops_per_step"] == flops_lm.lm_train_flops(
        cell.config, 1, 256)


def test_control_in_float8_is_not_correct(cell):
    (r,) = list(DRIVER.readings(cells.ctx(cell), [2**31 + 11], control="fp8"))
    assert not compare.judge(r, cell.limits)["correct"], r


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_not_correct(cell, fault):
    res = DRIVER.run(cells.ctx(cell, fault=fault))
    assert not res["correct"], res["checks"]


def test_flops_of_the_cell():
    """The cell's step: about 11.3 TFLOP, attention's square 46% of it,
    the held experts 1.5%."""
    cfg = cells._load("configs", "kanana-2-30b-a3b.json")
    parts = flops_lm.lm_forward_flops(cfg, 1, 4096)
    total = flops_lm.lm_train_flops(cfg, 1, 4096)
    assert total == pytest.approx(11.26e12, rel=2e-3)
    assert 3 * parts["attn_square"] / total == pytest.approx(0.458, abs=2e-3)
    assert 3 * parts["experts"] / total == pytest.approx(0.0155, abs=1e-3)


def test_readers_match_wrapped_scopes_and_ragged_dots():
    """The new readers on a made-up trace of two steps: scopes plain and
    wrapped by transforms, XLA's ragged-dot calls counted as the held
    experts', and the roofline share from the step's counter."""
    from harness import trace as TR

    def op(t0, t1, scope):
        return TR.Op(t0 * 1e-3, t1 * 1e-3, "fusion", scope)

    ops = [op(0, 4, "jit(step_fn)/model/jvp(mla)/dot_general"),
           op(4, 6, "jit(step_fn)/model/transpose(jvp())/while/body/mla/mul"),
           op(6, 7, "jit(step_fn)/model/jvp()/while/body/moe/route/top_k"),
           op(7, 8, "jit(step_fn)/model/jvp()/while/body/moe/experts/mul"),
           op(8, 10, "ragged-dot-none"),
           op(10, 11, "jit(step_fn)/model/jvp()/while/body/moe/shared/dot"),
           op(11, 12, "jit(step_fn)/model/jvp()/while/body/xmla/dot"),
           op(12, 13, "jit(step_fn)/wire/memory/add")]
    red = TR.Reduced(window=(0.0, 0.02), devices=[ops], spans=[])
    rec = {"trace": red, "steps": 2, "device_kind": "TPU v5 lite",
           "moe_held_assignments": 1000.0,
           "expert_flops_per_assignment": 3 * 2 * 2048 * 768}
    read = {m: spec.load_reader(m)
            for m in ("mla_ms.train", "moe_ms.train", "moe_expert_roofline")}
    assert read["mla_ms.train"](rec) == pytest.approx(3.0)
    assert read["moe_ms.train"](rec) == pytest.approx(2.5)
    work = 3 * 1000.0 * 3 * 2 * 2048 * 768
    assert read["moe_expert_roofline"](rec) == pytest.approx(
        100 * work / (1.5e-3 * 197e12))
    assert read["moe_expert_roofline"]({**rec, "trace": None}) is None
    del rec["moe_held_assignments"]
    assert read["moe_expert_roofline"](rec) is None
