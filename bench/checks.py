"""The numbers that decide ``correct``, each held against its limit.

Training: each of the first three steps' losses, the norm of the first
gradient as the optimizer got it (worked out from the parameters after
one step), and the norm of the parameters' change over the three steps,
the program's against the reference's.  A norm is compared leaf by
leaf as the gap between the two norms over the larger of the
reference's norm of that leaf and the median leaf's; the worst leaf
counts.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out.
"""

from __future__ import annotations

import numpy as np

__all__ = ["leaf_norms", "worst_leaf_gap", "train_numbers", "judge"]

QUIET = 1e-3   # leaves under this share of the median gradient norm


def leaf_norms(tree) -> dict[str, float]:
    """``"g/<name>"`` / ``"d/<name>"`` -> float64 norm of the leaf."""
    g, d = tree
    out = {}
    for role, params in (("g", g), ("d", d)):
        for name, leaf in params.items():
            x = np.asarray(leaf, np.float64)
            out[f"{role}/{name}"] = float(np.sqrt(np.sum(x * x)))
    return out


def worst_leaf_gap(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    """The worst leaf's gap of norms and that leaf's name."""
    med = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def _diff(a, b):
    """Leafwise ``a - b`` of two ``(g, d)`` trees, in float64."""
    return tuple({k: np.asarray(x[k], np.float64) - np.asarray(y[k],
                                                               np.float64)
                  for k in x} for x, y in zip(a, b))


def train_numbers(cfg: dict, p0, p1, p3, losses: list[dict],
                  ref_first, ref_p3, ref_losses: list[dict]) -> dict:
    """The training numbers: ``loss_gap`` (worst relative gap of any
    step's G or D loss), ``grad_gap`` and ``update_gap`` (worst leaf)."""
    lr = {"g": cfg["optimizer"]["g_lr"], "d": cfg["optimizer"]["d_lr"]}
    loss_gap = max(abs(p[k] - r[k]) / abs(r[k])
                   for p, r in zip(losses, ref_losses) for k in r)
    ref_grad = leaf_norms(ref_first)
    med = float(np.median(list(ref_grad.values())))
    leaves = [k for k, v in ref_grad.items() if v >= QUIET * med]
    moved = leaf_norms(_diff(p0, p1))
    prog_grad = {k: v / lr[k[0]] for k, v in moved.items()}
    grad_gap, grad_leaf = worst_leaf_gap(prog_grad, ref_grad, leaves)
    update_gap, update_leaf = worst_leaf_gap(
        leaf_norms(_diff(p3, p0)), leaf_norms(_diff(ref_p3, p0)), leaves)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap, "_grad_leaf": grad_leaf,
            "_update_leaf": update_leaf,
            "_quiet_leaves": sorted(set(ref_grad) - set(leaves))}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number of the
    cell's limits must be there and at most its limit."""
    checks = {name: {"value": numbers.get(name), "limit": limit}
              for name, limit in limits.items()}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
