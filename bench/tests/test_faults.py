"""A run of each cell's path at a small size on the CPU, with the chip
check skipped: sound, it is correct; with each planted fault the cell can
have, or with the control in the program's place, it is not.  The limits
are the cells' own."""

import types

import jax
import jax.numpy as jnp
import pytest

from bench import faults, run

TINY_MODEL = {"program_arch": "phi4-mini-3.8b", "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 97,
              "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
              "rms_norm_eps": 1e-6, "tie_word_embeddings": True}
TINY_TRAFFIC = {"batch": 4, "seq": 32, "noise": 0.1, "affine": [31, 17]}
TINY_PROBLEM = {"num_workers": 4, "rows_per_worker": 256, "dim": 64,
                "lam": 1e-3, "mu": 1e-3, "heterogeneity": 0.5,
                "logit_scale": 3.0, "newton_iters": 8}
SEED = 2 ** 33 + 12345


def tiny(workload):
    spec, cell, config, traffic = run.resolve_cell(workload)
    config = dict(config)
    if config["face"] == "train":
        config["model"] = TINY_MODEL
        traffic = dict(traffic, **TINY_TRAFFIC)
    else:
        config["problem"] = TINY_PROBLEM
        traffic = dict(traffic, num_rounds=20)
    return spec, cell, config, traffic


def drive(workload, kind):
    spec, cell, config, traffic = tiny(workload)
    devices = jax.devices()[:1]
    face = run.make_face(config, traffic, devices, SEED, cell["name"])
    args = types.SimpleNamespace(seed=SEED, seconds=0.5, trace=0)
    peaks = {"devices": {devices[0].device_kind: {}}}
    with faults.planted(kind, face):
        if kind == "control":
            # the reference one precision lower, in the program's place
            face.setup = lambda split: None
            face.window = lambda s: {"attempted": 1, "failed": 0}
            face.release = face.control
            face.end_to_end = lambda counts: {}
        return run.run_cell(args, spec=spec, cell=cell, config=config,
                            traffic=traffic, devices=devices, peaks=peaks,
                            face=face)


CELLS = ["train.phi4mini-l2.s2048", "convex.epsilon.dense"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = drive(workload, "program")
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload,kind", [
    (w, k) for w in CELLS
    for k in ("control",) + faults.for_config(run.resolve_cell(w)[2])])
def test_fault_or_control_is_not_correct(workload, kind):
    out = drive(workload, kind)
    assert not out["correct"], out["checks"]
