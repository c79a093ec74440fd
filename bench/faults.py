"""Planted faults and the control, for setting and testing the limits of
``correct``.

Each fault breaks the timed path underneath the benchmark, in the running
process only:

* ``state_unchanged``: the step or round aggregate returns zeros, so the
  parameters or iterate never move;
* ``half_batch``: each worker's loss or gradient sees only the first half
  of its rows (of its positions, where it holds one row), the mean taken
  over them;
* ``answer_altered``: the largest coordinate of a job's last iterate is
  set to zero where the result is produced.

``control`` puts the reference, computed one precision lower, in the
program's place.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = {"train": ("state_unchanged", "half_batch"),
          "convex": ("state_unchanged", "half_batch", "answer_altered")}


def for_config(config: dict):
    """The faults a cell of ``config`` can have."""
    return FAULTS[config["face"]]


def _half_loss(loss_fn):
    """Each worker's loss over the first half of its rows, or of its
    positions where it holds one row."""
    def half(params, batch):
        rows, seq = batch["tokens"].shape[:2]
        if rows >= 2:
            cut = lambda a: a[:rows // 2]
        else:
            cut = lambda a: a[:, :seq // 2]
        return loss_fn(params, jax.tree.map(cut, batch))
    return half


def _zero_masked_aggregate(orig):
    def agg(G, mask, C):
        g, c = orig(G, mask, C)
        return jnp.zeros_like(g), c
    return agg


def _zero_server_aggregate(orig):
    def agg(G, Mx, C, **kw):
        g, c = orig(G, Mx, C, **kw)
        return jnp.zeros_like(g), c
    return agg


def _half_worker_grad(self, i, x, key):
    Xi, yi = self.X[i], self.y[i]
    rows = Xi.shape[0] // 2
    Xi, yi = Xi[:rows], yi[:rows]
    z = (Xi @ x) * yi
    s = jax.nn.sigmoid(-z)
    return -(Xi.T @ (s * yi)) / rows + self.lam * x


def _alter_answer(res):
    import dataclasses
    xs = res.xs
    j = jnp.argmax(jnp.abs(xs[-1]))
    return dataclasses.replace(res, xs=xs.at[-1, j].set(0.0))


@contextlib.contextmanager
def planted(kind: str, face):
    """Plant ``kind`` for the duration of the block ("program" and
    "control" plant nothing)."""
    from bench.faces import train as train_face
    patches = []
    if kind == "half_batch" and face.config["face"] == "train":
        face.loss_wrapper = _half_loss
    elif kind == "state_unchanged" and face.config["face"] == "train":
        import repro.optim.ranl_llm as mod
        patches.append((mod, "masked_aggregate",
                        _zero_masked_aggregate(mod.masked_aggregate)))
    elif kind == "state_unchanged":
        import repro.core.ranl as mod
        patches.append((mod, "server_aggregate",
                        _zero_server_aggregate(mod.server_aggregate)))
    elif kind == "half_batch":
        from repro.core.convex import Logistic
        patches.append((Logistic, "worker_grad", _half_worker_grad))
    elif kind == "answer_altered":
        face.result_hook = _alter_answer
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    for obj, name, new in patches:
        setattr(obj, name, new)
    if patches:
        jax.clear_caches()
        train_face._PROGRAMS.clear()
    try:
        yield
    finally:
        for obj, name, old in saved:
            setattr(obj, name, old)
        if patches:
            jax.clear_caches()
            train_face._PROGRAMS.clear()


def readings(face, kind: str, seconds: float, ref=None) -> dict:
    """The compared numbers of one run of ``kind`` against the reference.
    A train cell's reference is the same for every kind of one seed and is
    reused through ``ref``.  Returns the gaps and ``_ref``."""
    from bench.faces import convex as convex_face
    from bench.faces import train as train_face
    split = {}
    if kind == "control":
        face.control()
    else:
        face.setup(split)
        if face.config["face"] == "convex":
            face.window(seconds)
        face.release()
    if face.config["face"] == "train":
        ref = ref or face.reference()
        got = train_face.gaps(face.checked, ref)
    else:
        ref = face.reference(face.checked)
        got = convex_face.gaps(face.checked, ref)
    got["_ref"] = ref
    got["split"] = {k: float(np.round(v, 3)) for k, v in split.items()}
    return got
