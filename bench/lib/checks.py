"""The comparisons that decide ``correct``.

Training: the program's readings from its first steps against the
reference's, as three numbers, each a worst case:

* ``loss_gap``   -- every step's G loss and each member's D loss, as
                    |program - reference| / |reference|;
* ``grad_gap``   -- the first step's gradients (G, and each member's D)
                    as the optimizer got them, leaf by leaf:
                    | ||program|| - ||reference|| | / max(||reference||,
                    the median leaf's ||reference||);
* ``change_gap`` -- the parameters' change over the steps (G, the server
                    D, each trained member's D row), by the same rule.
                    A leaf whose first reference gradient is under 1e-3
                    of the median leaf's moves by round-off alone and is
                    left out;

and two exact counts of the program's store against the schedule
(``federation.store_readings``): ``rows_off``, the users whose D row
changed without training or stayed unchanged though trained, and
``last_round_off``, the users whose last round is wrong.
"""

from __future__ import annotations

import jax
import numpy as np

# A leaf whose reference gradient is below this share of the median
# leaf's moves under Adam by round-off alone.
QUIET_LEAF = 1e-3


def _norms(tree) -> list:
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def leaf_gap(prog, ref, keep=None) -> float:
    """Worst leaf of | ||p|| - ||r|| | / max(||r||, median ||r||)."""
    pn, rn = _norms(prog), _norms(ref)
    med = float(np.median(rn))
    keep = keep if keep is not None else [True] * len(rn)
    gaps = [abs(p - r) / max(r, med) for p, r, k in zip(pn, rn, keep) if k]
    return max(gaps) if gaps else 0.0


def loud(grad_trees) -> list:
    """Per leaf: whether any of the trees' gradients is above
    ``QUIET_LEAF`` of the median leaf's norm."""
    norms = np.array([_norms(t) for t in grad_trees])   # (trees, leaves)
    top = norms.max(axis=0)
    return list(top >= QUIET_LEAF * float(np.median(top)))


def train_numbers(prog: dict, ref: dict) -> dict:
    """Readings are dicts with ``g_loss`` [steps], ``d_loss`` [steps x C],
    ``g_grad`` tree, ``d_grad`` [tree per first-step member],
    ``g_change`` tree, ``server_change`` tree, ``row_change`` {user: tree}.
    """
    loss = 0.0
    for gp, gr in zip(prog["g_loss"], ref["g_loss"]):
        loss = max(loss, abs(gp - gr) / abs(gr))
    for dp, dr in zip(prog["d_loss"], ref["d_loss"]):
        dp, dr = np.asarray(dp, np.float64), np.asarray(dr, np.float64)
        loss = max(loss, float(np.max(np.abs(dp - dr) / np.abs(dr))))
    grad = max([leaf_gap(prog["g_grad"], ref["g_grad"])]
               + [leaf_gap(p, r) for p, r in zip(prog["d_grad"],
                                                  ref["d_grad"])])
    g_keep = loud([ref["g_grad"]])
    d_keep = loud(ref["d_grad"])
    change = max([leaf_gap(prog["g_change"], ref["g_change"], g_keep),
                  leaf_gap(prog["server_change"], ref["server_change"],
                           d_keep)]
                 + [leaf_gap(prog["row_change"][u], ref["row_change"][u],
                             d_keep) for u in ref["row_change"]])
    out = {"loss_gap": loss, "grad_gap": grad, "change_gap": change}
    out.update({k: prog[k] for k in ("rows_off", "last_round_off")
                if k in prog})
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number that is not finite fails."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in shown.values())
    return bool(ok), shown
