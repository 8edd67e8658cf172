"""The comparisons that decide `correct`, and their limits.

Serving: each sampled response against the plain reference on the same
request (reference.cascade_rank at float32 "highest"):

  score_err   the widest gap |served score - reference lp_T| over items
              that both keep. Both sides score in float32 and differ by
              summation order and the chip's exp/log approximations.
  mismatches  survivors, stage counts or order that differ from the
              reference where rounding cannot explain it. An item is a
              near tie, whose membership rounding may flip, when it lies
              within score_err's limit of a stage's cut; a stage count may
              differ by one when the reference's expected count lies
              within KEEP_RTOL of an integer, and the items ranked between
              the two counts are near ties. Everything else must agree
              exactly: the limit is 0. A response that scores another
              number of items than the request is due (its items up to
              its bucket, or the next smaller one where a degraded flush
              says it shrank it) counts every item as a mismatch.

Training: two calls of the program (each one epoch of momentum-SGD steps
on the window's own feed), its first and the window's last, each against
reference.train_ref from the same parameters and momentum on the same
minibatches; each number is the worse of the two:

  loss_gap    the widest |loss - reference loss| / |reference loss| over
              the call's steps.
  state_gap   per leaf, |norm of the optimizer's momentum after the call -
              the reference's| over the larger of the reference leaf's
              norm and the median leaf's; the worst leaf.
  change_gap  the same for the parameters' change over the call.

A gap that is not finite (a loss that is not) fails.

Leaves whose reference momentum is under a thousandth of the median
leaf's (moved by round-off alone) are left out of the two norm gaps.
"""

from __future__ import annotations

import numpy as np

# A stage's expected count within this relative distance of an integer
# may round to either side of it: the count sums up to 256 float32 pass
# probabilities, each within ~1e-6 relative of the reference's.
KEEP_RTOL = 1e-4
# Leaves that round-off alone moves (see the module docstring).
QUIET_LEAF = 1e-3


def _near_ties(lp, surv_ref, keep, got_counts, valid_n, tol):
    """Items whose survival rounding may flip, for one request (numpy):
    lp (n, T), surv_ref (n, T) 0/1, keep (T,) expected counts before the
    ceiling, got_counts (T,) the served stage counts."""
    n, t = lp.shape
    near = np.zeros(n, bool)
    alive = np.ones(n, bool)
    for j in range(t):
        s = np.where(alive, lp[:, j], -np.inf)
        order = np.argsort(-s, kind="stable")
        k_ref = int(surv_ref[:, j].sum())
        k_got = int(got_counts[j])
        if k_ref != k_got:
            near[order[min(k_ref, k_got):max(k_ref, k_got)]] = True
        if 0 < k_ref < valid_n:
            a, b = s[order[k_ref - 1]], s[order[k_ref]]
            if np.isfinite(b) and a - b <= 2 * tol:
                near |= alive & ((np.abs(s - a) <= 2 * tol)
                                 | (np.abs(s - b) <= 2 * tol))
        alive = surv_ref[:, j] > 0
    return near


def _keep_ambiguous(keep: float) -> bool:
    return abs(keep - round(keep)) <= KEEP_RTOL * max(1.0, keep)


def compare_one(served: dict, ref: dict, tol: float) -> tuple[float, int, int]:
    """One request. served: scores (n,), survivors (n,) bool, order (n,),
    stage_counts (T,). ref: lp (n, T), survivors (n, T), keep (T,).
    Returns (score_err, mismatches, near-tie items)."""
    lp, surv_ref, keep = ref["lp"], ref["survivors"] > 0, ref["keep"]
    n = lp.shape[0]
    if len(served["scores"]) != n:
        return 0.0, max(n, 1), 0
    final = surv_ref[:, -1]
    got = np.asarray(served["survivors"], bool)
    counts = np.asarray(served["stage_counts"])
    mismatches = 0
    for j in range(lp.shape[1]):
        if counts[j] != surv_ref[:, j].sum() and not _keep_ambiguous(keep[j]):
            mismatches += 1
    near = _near_ties(lp, surv_ref, keep, counts, n, tol)
    mismatches += int(((got != final) & ~near).sum())
    both = got & final
    err = 0.0
    if both.any():
        err = float(np.abs(np.asarray(served["scores"])[both]
                           - lp[both, -1]).max())
    # the served order ranks its survivors best first; the reference must
    # not rank a later one above an earlier one by more than the tolerance
    kept = [i for i in np.asarray(served["order"]) if got[i]]
    for a, b in zip(kept, kept[1:]):
        if lp[b, -1] - lp[a, -1] > 2 * tol and final[a] and final[b]:
            mismatches += 1
    return err, mismatches, int(near.sum())


def as_served(ref_like: dict, n: int) -> dict:
    """What a server would return from a reference-style output (the
    control put in the program's place): final scores, survivors, order
    and stage counts."""
    surv = ref_like["survivors"][:n] > 0
    scores = np.where(surv[:, -1], ref_like["lp"][:n, -1], -np.inf)
    return {"scores": scores, "survivors": surv[:, -1],
            "order": np.argsort(-scores, kind="stable"),
            "stage_counts": surv.sum(0)}


def serving_readings(pairs, tol: float) -> dict:
    """pairs: (served, ref) per request. Returns the numbers compared."""
    err, bad, near = 0.0, 0, 0
    for served, ref in pairs:
        e, m, k = compare_one(served, ref, tol)
        err, bad, near = max(err, e), bad + m, near + k
    return {"score_err": err, "mismatches": bad, "near_ties": near,
            "compared": len(pairs)}


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def leaf_gap(got: dict, want: dict, keep: list[str]) -> float:
    """Worst leaf of |norm(got) - norm(want)| / max(norm(want), median)."""
    norms = {k: _norm(want[k]) for k in keep}
    med = float(np.median(list(norms.values())))
    return max(abs(_norm(got[k]) - norms[k]) / max(norms[k], med, 1e-30)
               for k in keep)


def training_readings(prog: dict, ref: dict, start: dict) -> dict:
    """prog / ref: losses (S,), params and mu (dicts of leaves) after the
    first call; start: the parameters before it."""
    losses, ref_losses = (np.asarray(prog["losses"], np.float64),
                          np.asarray(ref["losses"], np.float64))
    loss_gap = float(np.max(np.abs(losses - ref_losses)
                            / np.maximum(np.abs(ref_losses), 1e-30)))
    mu_norms = {k: _norm(v) for k, v in ref["mu"].items()}
    med = float(np.median(list(mu_norms.values())))
    keep = [k for k, v in mu_norms.items() if v >= QUIET_LEAF * med]
    change = {k: np.asarray(prog["params"][k]) - np.asarray(start[k])
              for k in keep}
    ref_change = {k: np.asarray(ref["params"][k]) - np.asarray(start[k])
                  for k in keep}
    return {"loss_gap": loss_gap,
            "state_gap": leaf_gap(prog["mu"], ref["mu"], keep),
            "change_gap": leaf_gap(change, ref_change, keep),
            "loss_gap_step0": float(abs(losses[0] - ref_losses[0])
                                    / max(abs(ref_losses[0]), 1e-30)),
            "leaves": len(keep)}


def worse_of(*readings: dict) -> dict:
    """Per number, the worst of several readings: the largest gap, or one
    that is not finite."""
    def badness(v):
        return v if np.isfinite(v) else np.inf
    return {k: max((r[k] for r in readings), key=badness)
            for k in readings[0]}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Every limited number at or under its limit. Returns (ok, the
    numbers beside their limits, in limits' order)."""
    shown = {k: [readings[k], limits[k]] for k in limits}
    ok = all(np.isfinite(readings[k]) and readings[k] <= limits[k]
             for k in limits)
    return ok, shown
