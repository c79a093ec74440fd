"""The MoE train face at a small size on a data 2 x model 2 mesh of
virtual CPU devices, with the chip check skipped: sound, it is correct
and drops no row; with each planted fault (among them the expert outputs'
exchange between model shards left out), or with the control (the
reference one precision lower) in the program's place, it is not.  The
limits are the cell's own.  The runs take a fresh interpreter, whose
device count is set before JAX starts.  Then the work counts behind
``step_mfu.moe`` and ``expert_roofline.moe``, by hand."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from bench import moe, run
from bench.faces import train_moe

CELL = "train.moonlight-l5e8.2x2.s8192"
TINY = {"hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "vocab_size": 101, "router_experts": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 3,
        "mesh": {"data": 2, "model": 2}}
SEED = 2 ** 33 + 4242
KINDS = ("program", "control") + train_moe.FAULTS
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def drive(kind):
    spec, cell, config, traffic = run.resolve_cell(CELL)
    config = dict(config, **TINY)
    traffic = dict(traffic, batch=2, seq=32)
    devices = jax.devices()[:4]
    face = train_moe.Face(config, traffic, devices, SEED, cell["name"])
    args = types.SimpleNamespace(seed=SEED, seconds=0.5, trace=0)
    peaks = {"devices": {devices[0].device_kind: {}}}
    if kind == "control":
        face.setup = lambda split: None
        face.window = lambda s: {"attempted": 1, "failed": 0}
        face.release = face.control
        face.end_to_end = lambda counts: {}
    elif kind != "program":
        face.fault = kind
    return run.run_cell(args, spec=spec, cell=cell, config=config,
                        traffic=traffic, devices=devices, peaks=peaks,
                        face=face)


def drive_all():
    """Every kind's result, keyed by kind (run on four devices)."""
    return {kind: drive(kind) for kind in KINDS}


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    code = ("import json; from bench.tests.test_train_moe import drive_all;"
            " print(json.dumps(drive_all()))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(results):
    out = results["program"]
    assert out["correct"], out["checks"]
    assert out["checks"]["dropped_rows"]["value"] == 0


@pytest.mark.parametrize("kind", ("control",) + train_moe.FAULTS)
def test_fault_or_control_is_not_correct(results, kind):
    out = results[kind]
    assert not out["correct"], out["checks"]


M = {"hidden_size": 8, "vocab_size": 50, "first_k_dense_replace": 1,
     "num_hidden_layers": 3, "intermediate_size": 12,
     "moe_intermediate_size": 4, "n_routed_experts": 4, "router_experts": 16,
     "n_shared_experts": 2, "num_attention_heads": 2, "kv_lora_rank": 6,
     "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
     "num_experts_per_tok": 3}


def test_moe_step_flops_hand_count():
    # per layer, latent attention: wq 8x(2x5), wkv_a 8x(6+2), wkv_b
    # 6x(2x6), wo (2x3)x8; the dense FFN 3x8x12; per MoE layer the router
    # 8x16 and the shared experts 3x8x(2x4); the head 50x8
    mla = 8 * 10 + 8 * 8 + 6 * 12 + 6 * 8
    assert mla == 264 and moe.mla_params(M) == mla
    dense = 3 * mla + 3 * 8 * 12 + 2 * (8 * 16 + 3 * 8 * 8) + 50 * 8
    assert moe.dense_matmul_params(M) == dense == 2120
    assert moe.expert_row_params(M) == 3 * 8 * 4
    # causal QK^T (dim 5) and PV (dim 3): 2 x B x S(S+1)/2 x heads x 8
    # x layers, forward
    attn = 2 * 2 * (10 * 11 // 2) * 2 * 8 * 3
    assert moe.attention_flops(M, 2, 10) == attn
    held_rows = 37
    want = 6 * (2 * 10 * dense + held_rows * 96) + 3 * attn
    assert moe.train_step_flops(M, 2, 10, held_rows) == want


def test_expert_least_time_hand_count():
    peak = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e4}
    # 40 rows over 4 chips: 10 rows x 96 params x 6 = 5,760 FLOPs -> 5.76 s;
    # bytes: 2 of 4 experts' 96 f32 params, 10 rows of 8 in and out:
    # 4 x (192 + 160) = 1,408 -> 0.1408 s; compute bound
    assert moe.expert_least_s(M, 40, 4, 2, peak) == pytest.approx(5.76)
    slow_hbm = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 100.0}
    assert moe.expert_least_s(M, 40, 4, 2, slow_hbm) == pytest.approx(14.08)
