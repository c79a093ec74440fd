"""Pre-compile auditor: walk a closed jaxpr, inventory what matters.

Complements the post-compile HLO view (``hlo_audit``): the jaxpr is
available before XLA ever runs, carries exact ``lax.scan`` trip counts
(where HLO needs while-condition parsing), and still shows structure the
compiler later fuses away.  The walker recurses through every sub-jaxpr
(jit / scan / while / cond / shard_map / custom_* calls) and reports:

- **collectives** — ``psum`` / ``all_gather`` / ``ppermute`` / ... with
  their axis names, per-shard payload aval and loop multiplier (product
  of enclosing scan lengths),
- **PRNG key reuse** — the same key consumed by two bit-generating
  random primitives.  Keys are tracked per-variable with aliases
  transported through ``random_wrap``/``random_unwrap`` and across call
  boundaries; ``fold_in``/``split`` DERIVE fresh keys (not reuse), and a
  key closed over a scan body (a scan const) is charged once per
  iteration — drawing from the loop key itself instead of
  ``fold_in(k, t)`` is exactly the bug class this catches,
- **f64 / weak-type promotion leaks** — any float64/complex128 aval, and
  widening ``convert_element_type`` ops fed by weak-typed operands,
- **host-sync hazards** — callback/infeed/outfeed primitives that force
  a device-host round trip inside compiled code,
- **max aval bytes** — the largest intermediate the trace ever names.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.extend import core as jcore

COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "pgather", "pbroadcast",
}
REDUCE_PRIMS = {"psum", "pmax", "pmin"}
DRAW_PRIMS = {"random_bits", "random_gamma", "threefry2x32"}
KEY_TRANSPORT_PRIMS = {"random_wrap", "random_unwrap", "copy",
                       "convert_element_type"}
HOST_SYNC_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "debug_print", "infeed", "outfeed"}
# primitives that call one sub-jaxpr with their operands (jax.jit,
# closed_call, jax.checkpoint, custom derivatives, shard_map)
CALL_PRIMS = {"jit", "closed_call", "remat2", "custom_jvp_call",
              "custom_vjp_call", "shard_map"}


@dataclass(frozen=True)
class JaxprCollective:
    prim: str
    axes: tuple[str, ...]
    dtype: str
    shape: tuple[int, ...]
    multiplier: int
    count: int = 1

    @property
    def payload_bytes(self) -> int:
        n = 1
        for s in self.shape:
            n *= int(s)
        return n * np.dtype(self.dtype).itemsize

    @property
    def signature(self) -> str:
        shape = ",".join(str(s) for s in self.shape)
        return (f"{self.prim}|{'+'.join(self.axes) or 'none'}"
                f"|{self.dtype}[{shape}]|x{self.multiplier}")


@dataclass
class JaxprAuditReport:
    collectives: list[JaxprCollective] = field(default_factory=list)
    key_reuse: list[str] = field(default_factory=list)
    f64_leaks: list[str] = field(default_factory=list)
    weak_widenings: list[str] = field(default_factory=list)
    host_syncs: list[str] = field(default_factory=list)
    max_aval_bytes: int = 0

    @property
    def ok(self) -> bool:
        return not (self.key_reuse or self.f64_leaks or self.host_syncs)

    def signature(self) -> dict[str, int]:
        """Stable collective inventory map — what ``CONTRACTS.json``
        commits per config (counts come from repo code structure, not
        XLA's optimizer, so they survive compiler upgrades)."""
        sig: Counter[str] = Counter()
        for c in self.collectives:
            sig[c.signature] += c.count
        return dict(sorted(sig.items()))

    def reduce_count(self, *, in_loop: bool | None = None) -> int:
        return sum(c.count for c in self.collectives
                   if c.prim in REDUCE_PRIMS
                   and (in_loop is None
                        or (c.multiplier > 1) == in_loop))

    def to_json(self) -> dict:
        return {
            "ok": self.ok,
            "collectives": self.signature(),
            "key_reuse": self.key_reuse,
            "f64_leaks": self.f64_leaks,
            "weak_widenings": self.weak_widenings,
            "host_syncs": self.host_syncs,
            "max_aval_bytes": self.max_aval_bytes,
        }


def _aval_bytes(aval) -> int:
    try:
        n = 1
        for s in aval.shape:
            n *= int(s)
        return n * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _axes_param(params) -> tuple[str, ...]:
    axes = params.get("axes", params.get("axis_name", ()))
    if axes is None:
        return ()
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _sub_jaxprs(eqn):
    """(name, Jaxpr, consts) of every sub-jaxpr a primitive carries."""
    out = []
    for pname, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                out.append((pname, v.jaxpr, v.consts))
            elif isinstance(v, jcore.Jaxpr):
                out.append((pname, v, ()))
    return out


class _Walker:
    """Single pass over the jaxpr forest, shared mutable state.

    Key tracking: every Var gets a root id on first sight
    (``_root``); transport primitives and call-boundary alignment alias
    vars onto existing roots; draw primitives charge their operand's
    root ``weight`` consumptions, where ``weight`` is the product of
    enclosing scan lengths for roots born OUTSIDE the loop (a root born
    inside the body is per-iteration, so its birth weight divides out).
    A root charged at least twice its birth weight was drawn from twice
    with identical bits — reported as reuse.
    """

    def __init__(self):
        self.report = JaxprAuditReport()
        self._roots: dict = {}          # id(Var) -> root id
        self._born: dict[int, int] = {}  # root id -> birth weight
        self._drawn: Counter[int] = Counter()
        self._desc: dict[int, str] = {}
        self._next = 0

    def _root(self, var, weight: int):
        if isinstance(var, jcore.Literal):
            return None
        key = id(var)
        if key not in self._roots:
            self._roots[key] = self._next
            self._born[self._next] = weight
            self._desc[self._next] = str(var.aval)
            self._next += 1
        return self._roots[key]

    def _alias(self, var, root):
        if root is not None and not isinstance(var, jcore.Literal):
            self._roots[id(var)] = root

    def walk(self, jaxpr: jcore.Jaxpr, weight: int = 1):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            for ov in eqn.outvars:
                b = _aval_bytes(ov.aval)
                if b > self.report.max_aval_bytes:
                    self.report.max_aval_bytes = b
                dt = getattr(ov.aval, "dtype", None)
                if dt is not None and str(dt) in ("float64", "complex128"):
                    self.report.f64_leaks.append(
                        f"{name} -> {ov.aval} (x{weight})")
            if name == "convert_element_type":
                iv = eqn.invars[0]
                src = getattr(iv.aval, "dtype", None)
                dst = eqn.params.get("new_dtype")
                weak = getattr(iv.aval, "weak_type", False)
                if (weak and src is not None and dst is not None
                        and np.dtype(dst).itemsize > np.dtype(src).itemsize):
                    self.report.weak_widenings.append(
                        f"weak {src} -> {dst}")
            if name in HOST_SYNC_PRIMS:
                self.report.host_syncs.append(f"{name} (x{weight})")
            if name in COLLECTIVE_PRIMS:
                for iv in eqn.invars:
                    aval = iv.aval
                    if not hasattr(aval, "dtype"):
                        continue
                    self.report.collectives.append(JaxprCollective(
                        prim=name, axes=_axes_param(eqn.params),
                        dtype=str(aval.dtype),
                        shape=tuple(int(s) for s in aval.shape),
                        multiplier=weight))
            if name in DRAW_PRIMS:
                root = self._root(eqn.invars[0], weight)
                if root is not None:
                    self._drawn[root] += weight
            elif name in KEY_TRANSPORT_PRIMS and len(eqn.outvars) == 1:
                self._alias(eqn.outvars[0],
                            self._root(eqn.invars[0], weight))
            self._descend(eqn, weight)

    def _descend(self, eqn, weight: int):
        subs = _sub_jaxprs(eqn)
        if not subs:
            return
        name = eqn.primitive.name
        if name in CALL_PRIMS:
            for _, sub, _consts in subs[:1]:
                for outer, inner in zip(eqn.invars, sub.invars):
                    self._alias(inner, self._root(outer, weight))
                self.walk(sub, weight)
                for inner, outer in zip(sub.outvars, eqn.outvars):
                    self._alias(outer, self._root(inner, weight))
        elif name == "scan":
            _, sub, _consts = subs[0]
            length = max(int(eqn.params.get("length", 1)), 1)
            nconsts = int(eqn.params.get("num_consts", 0))
            # consts keep their outer roots (a key closed over the body
            # is THE cross-iteration reuse hazard); carry/xs slots are
            # per-iteration values -> fresh roots at the inner weight
            for outer, inner in zip(eqn.invars[:nconsts],
                                    sub.invars[:nconsts]):
                self._alias(inner, self._root(outer, weight))
            self.walk(sub, weight * length)
        elif name == "while":
            cn = int(eqn.params.get("cond_nconsts", 0))
            bn = int(eqn.params.get("body_nconsts", 0))
            cond, body = None, None
            for pname, sub, _consts in subs:
                if pname == "cond_jaxpr":
                    cond = sub
                elif pname == "body_jaxpr":
                    body = sub
            if cond is not None:
                for outer, inner in zip(eqn.invars[:cn], cond.invars[:cn]):
                    self._alias(inner, self._root(outer, weight))
                self.walk(cond, weight)
            if body is not None:
                for outer, inner in zip(eqn.invars[cn:cn + bn],
                                        body.invars[:bn]):
                    self._alias(inner, self._root(outer, weight))
                # trip count is dynamic: charge body consts as if the
                # loop ran twice (drawing from a loop-invariant key in a
                # multi-trip while IS reuse; a 1-trip while false-flags,
                # which the repo has none of)
                self.walk(body, weight * 2)
        else:
            for _, sub, _consts in subs:
                self.walk(sub, weight)

    def finish(self) -> JaxprAuditReport:
        for root, drawn in sorted(self._drawn.items()):
            born = self._born.get(root, 1)
            if drawn >= 2 * born:
                self.report.key_reuse.append(
                    f"key {self._desc.get(root, '?')} drawn from "
                    f"{drawn} time(s) (birth weight {born}) — derive "
                    f"fresh keys with fold_in/split instead")
        return self.report


def audit_jaxpr(closed_jaxpr) -> JaxprAuditReport:
    """Audit a ``ClosedJaxpr`` (or raw ``Jaxpr``)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    w = _Walker()
    w.walk(jaxpr)
    return w.finish()


def audit_fn(fn, *args, **kwargs) -> JaxprAuditReport:
    """Trace ``fn(*args, **kwargs)`` with ``jax.make_jaxpr`` and audit."""
    return audit_jaxpr(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))
