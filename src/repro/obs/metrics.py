"""The runtime contract-drift alarm.

``check_byte_drift`` compares the observed ``comm_bytes``/``pod_bytes``
of every recorded round against the per-round ceilings
:func:`repro.analysis.contracts.round_byte_budget` derives for the same
options, and returns structured ``kind="drift"`` journal records where
they diverge — the runtime form of the CI-only static contract audit.

Import-light by design (jax never): the report CLI loads this without
the engine stack.
"""

from __future__ import annotations

__all__ = ["check_byte_drift", "byte_budget_for"]

#: Relative headroom on the byte ceilings before the alarm fires: the
#: budgets are exact worst-case wire-model sums, so anything past float
#: round-off is genuine drift.
DRIFT_RTOL = 1e-6


def byte_budget_for(engine: str, options, *, dim: int,
                    num_workers: int) -> dict:
    """Per-round byte ceilings for a run — thin wrapper over
    ``analysis.contracts.round_byte_budget`` (kept here so obs callers
    need one import; the derivation lives with the contracts)."""
    del engine  # the wire-model ceilings are engine-independent
    from ..analysis.contracts import round_byte_budget
    return round_byte_budget(options, dim=dim, num_workers=num_workers)


def check_byte_drift(rounds, budget: dict, *,
                     rtol: float = DRIFT_RTOL) -> list[dict]:
    """The live contract-drift alarm.

    ``rounds``: an iterable of ``kind="round"`` journal records (other
    kinds are skipped, so a whole journal can be passed).  ``budget``:
    ``{"comm_per_round", "pod_per_round"}`` ceilings from
    :func:`byte_budget_for`.  Returns one structured ``kind="drift"``
    record per (round, metric) whose observed bytes exceed the ceiling —
    empty when the run and its contract agree (the state every committed
    contract combination is pinned to in ``tests/test_obs.py``).
    """
    checks = (("comm_bytes", "comm_per_round"),
              ("pod_bytes", "pod_per_round"))
    out = []
    for rec in rounds:
        if rec.get("kind", "round") != "round":
            continue
        for metric, limit_key in checks:
            if metric not in rec or limit_key not in budget:
                continue
            observed = float(rec[metric])
            limit = float(budget[limit_key])
            if observed > limit * (1.0 + rtol):
                out.append({
                    "kind": "drift", "metric": metric,
                    "t": rec.get("t"), "observed": observed,
                    "budget": limit,
                    "ratio": (observed / limit if limit > 0
                              else float("inf")),
                    "message": (f"round {rec.get('t')}: {metric}="
                                f"{observed:.1f} exceeds the contract "
                                f"byte budget {limit:.1f}"),
                })
    return out
