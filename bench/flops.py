"""Work a cell needs, counted from its shapes: the yardstick of the
utilization and roofline metrics.  Nothing here reads the program."""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that enter a matmul per token: every layer's projections
    and the (tied) output head.  The embedding gather is not a matmul."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return m["num_hidden_layers"] * per_layer + m["vocab_size"] * d


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (forward and backward):
    6 x matmul params x tokens, plus the causal attention matmuls (QK^T
    and PV over the lower triangle) x 3.  Recomputation does not count."""
    base = 6.0 * matmul_params(m) * batch * seq
    ctx_sum = seq * (seq + 1) / 2
    attn = (4.0 * batch * ctx_sum * m["num_attention_heads"]
            * m["head_dim"] * m["num_hidden_layers"])
    return base + 3.0 * attn


def round_bytes(comm_floats: float, rows_per_worker: int) -> float:
    """Least HBM traffic of RANL rounds on a dense design matrix: one pass
    over the columns each worker keeps (comm_floats counts kept
    coordinates over workers and rounds), 4 bytes each."""
    return 4.0 * comm_floats * rows_per_worker


def init_flops(num_workers: int, rows: int, dim: int) -> float:
    """FLOPs of the init phase's mean Hessian, X^T diag(s) X per worker."""
    return 2.0 * num_workers * rows * dim * dim
