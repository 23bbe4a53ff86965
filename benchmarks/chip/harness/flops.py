"""Operations and bytes computed from shapes, for MFU and roofline shares.

``encdec_train_flops`` counts the matrix products that one training step of
the encoder-decoder needs, forward and backward, with no recomputation
(remat is the program's choice, not the model's need).  A product of an
[m, k] by a [k, n] matrix is 2 m k n operations forward and twice that
backward (one product for the input's gradient, one for the weight's);
the front-end projection takes stubbed frames that need no gradient, so its
backward is one product.  Attention counts the full score and value
products over every query-key pair (no halving for the causal mask), as
PaLM's accounting does.  Norms, softmax and elementwise work are left out.
"""
from __future__ import annotations


def encdec_forward_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Forward matmul FLOPs per step by part, for ``batch`` rows of ``seq``
    decoder tokens and ``cfg["max_source_positions"]`` encoder frames."""
    d = cfg["d_model"]
    f = cfg["max_source_positions"]
    le, ld = cfg["encoder_layers"], cfg["decoder_layers"]
    ffe, ffd = cfg["encoder_ffn_dim"], cfg["decoder_ffn_dim"]
    v = cfg["vocab_size"]
    s = seq
    front = 2 * f * d * d
    enc_layer = (4 * 2 * f * d * d          # q, k, v, o projections
                 + 2 * 2 * f * f * d        # scores and values
                 + 2 * 2 * f * d * ffe)     # up and down
    dec_layer = (4 * 2 * s * d * d          # self q, k, v, o
                 + 2 * 2 * s * s * d        # self scores and values
                 + 2 * 2 * s * d * d        # cross q, o
                 + 2 * 2 * f * d * d        # cross k, v over the encoder
                 + 2 * 2 * s * f * d        # cross scores and values
                 + 2 * 2 * s * d * ffd)     # up and down
    head = 2 * s * d * v
    return {"front": batch * front, "encoder": batch * le * enc_layer,
            "decoder": batch * ld * dec_layer, "head": batch * head}


def encdec_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward model FLOPs of one step over ``batch`` rows."""
    fw = encdec_forward_flops(cfg, batch, seq)
    total = 0.0
    for part, x in fw.items():
        total += x * (2 if part == "front" else 3)
    return float(total)


def bucket_acc_bytes(n_buckets: int, rows: int, row: int) -> int:
    """HBM bytes one ``bucket_acc`` fold must move: read the f32
    accumulator, the int8 levels and the f32 row scales, write the f32
    accumulator."""
    elems = n_buckets * rows * row
    return elems * 4 + elems * 1 + n_buckets * rows * 4 + elems * 4


def bucket_acc_flops(n_buckets: int, rows: int, row: int) -> int:
    """One multiply and one add per element."""
    return 2 * n_buckets * rows * row


def bucket_layout(total: int, bucket_bytes: int = 1 << 16,
                  max_buckets: int = 16, row: int = 256):
    """(buckets, rows, row) of the wire's equal-size buckets for ``total``
    f32 elements: buckets of ``bucket_bytes`` rounded up to whole rows,
    grown so that at most ``max_buckets`` cover the whole."""
    def up(x, m):
        return -(-x // m) * m
    elems = up(max(bucket_bytes // 4, row), row)
    elems = min(elems, up(total, row))
    n = -(-total // elems)
    if n > max_buckets:
        elems = up(-(-total // max_buckets), row)
        n = -(-total // elems)
    return n, elems // row, row
