"""The work counts behind the utilization and roofline metrics."""

import json
import os

import numpy as np

from bench import flops
from bench.reference import convex_ranl

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def model(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_phi4mini_l2v8_model_flops_hand_count():
    m = model("phi4-mini-3.8b.l2v8")
    # per layer: wq 3072x3072, wk/wv 3072x1024, wo 3072x3072,
    # gate/up 3072x8192, down 8192x3072; tied head 25008x3072
    per_layer = (3072 * 3072 + 2 * 3072 * 1024 + 3072 * 3072
                 + 3 * 3072 * 8192)
    assert per_layer == 100_663_296
    params = 2 * per_layer + 25008 * 3072
    assert flops.matmul_params(m) == params == 278_151_168
    tokens = 4 * 2048
    # causal QK^T and PV: 4 x B x S(S+1)/2 x heads x head_dim x layers,
    # forward and backward (x3)
    attn = 4 * 4 * (2048 * 2049 // 2) * 24 * 128 * 2 * 3
    assert flops.train_step_flops(m, 4, 2048) == 6 * params * tokens + attn
    assert abs(flops.train_step_flops(m, 4, 2048) - 1.4291e13) < 1e9


def test_round_bytes_counts_kept_columns_of_a_known_mask():
    # 3 workers, 10 coordinates in 5 regions of 2; worker 0 keeps regions
    # {0, 1}, worker 1 keeps {4}, worker 2 none: 4 + 2 + 0 kept coords
    ids = np.asarray(convex_ranl.region_ids(10, 5))
    M = np.array([[1, 1, 0, 0, 0], [0, 0, 0, 0, 1], [0, 0, 0, 0, 0]], bool)
    kept = int(M[:, ids].sum())
    assert kept == 6
    rows = 25000
    assert flops.round_bytes(kept, rows) == 6 * rows * 4


def test_init_flops():
    assert flops.init_flops(16, 25000, 2000) == 2 * 16 * 25000 * 2000 ** 2
