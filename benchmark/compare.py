"""The comparison that decides ``correct``.

What the timed object produced in its first steps is held against the plain
reference's reading of the same steps:

- ``loss_step<k>``: each step's loss, the gap as a share of the reference's
  (in the cells where the control reads three times a sound run or more;
  elsewhere it is printed and not compared, `PERF.md` gives the readings);
- ``grad1_worst_leaf``: the norm of the first gradient as the optimizer got
  it, by the worst leaf;
- ``grad1_median_leaf``: the same by the median leaf, over the leaves whose
  reference norm is not zero (`PERF.md` says why it stands beside the worst
  leaf: the worst leaf swings sixfold from seed to seed, the median leaf is
  what tells half a batch from a whole one);
- ``delta3_worst_leaf``: the norm of the parameters' change after the
  followed steps, by the worst leaf.

A leaf's gap is the distance between the program's norm and the
reference's (not the norm of their difference), measured against the
reference's norm of that leaf or of the median leaf, whichever is larger:
some gradients are all but zero. The median is taken over the leaves whose
reference norm is not exactly zero, because half of a residual network's
leaves have an exactly zero first gradient while the last norm of every
block still stands at its zero start.

Each number has a limit of its own, read from the cell's file.
"""

from __future__ import annotations

import math
import statistics


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf of a tree of device arrays, keyed by its path
    joined with '/': the form in which the program's hook and every
    family's reference hand their gradients and changes to `compare`."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}


def leaf_gaps(prog: dict, ref: dict) -> dict[str, float]:
    """Every leaf's gap; infinite where the trees differ, the reference
    norms are all zero or a norm is not finite."""
    nonzero = [v for v in ref.values() if v > 0.0]
    if set(prog) != set(ref) or not nonzero:
        return {leaf: math.inf for leaf in ref}
    median = statistics.median(nonzero)
    gaps = {leaf: abs(prog[leaf] - r) / max(r, median)
            for leaf, r in ref.items()}
    return {leaf: g if math.isfinite(g) else math.inf
            for leaf, g in gaps.items()}


def worst_leaf(prog: dict, ref: dict) -> tuple[float, str]:
    """(gap, leaf) of the leaf whose norms lie farthest apart."""
    if set(prog) != set(ref):
        return math.inf, f"leaves differ: {sorted(set(prog) ^ set(ref))[:4]}"
    gaps = leaf_gaps(prog, ref)
    leaf = max(reversed(list(gaps)), key=gaps.get)  # the last among equals
    return gaps[leaf], leaf


def loss_gaps(prog: dict, ref: dict) -> list[float]:
    """Each followed step's loss gap, as a share of the reference's."""
    return [abs(p - r) / abs(r) if r and math.isfinite(p) else math.inf
            for p, r in zip(prog["loss"], ref["loss"])]


def observed(prog: dict, ref: dict) -> dict:
    """Further readings, printed and not compared: each step's loss gap
    (compared only in the cells whose file gives it a limit), the 90th
    percentile leaf's gap and the gap of the norm over all leaves."""
    out = {f"loss_step{k}": gap
           for k, gap in enumerate(loss_gaps(prog, ref), start=1)}
    for name, key in (("grad1", "grad1"), ("delta3", "delta")):
        gaps = leaf_gaps(prog[key], ref[key])
        live = sorted(g for leaf, g in gaps.items() if ref[key][leaf] > 0.0)
        if name != "grad1":  # grad1's median leaf is compared
            out[f"{name}_median_leaf"] = statistics.median(live)
        out[f"{name}_p90_leaf"] = live[int(0.9 * (len(live) - 1))]
        whole_p = math.sqrt(sum(v * v for v in prog[key].values()))
        whole_r = math.sqrt(sum(v * v for v in ref[key].values()))
        out[f"{name}_all_leaves"] = abs(whole_p - whole_r) / whole_r
    return out


def compare(prog: dict, ref: dict, limits: dict) -> tuple[bool, list[dict]]:
    """``(correct, rows)``; a row is a number compared beside its limit."""
    rows = []
    if limits.get("loss") is not None:
        for k, gap in enumerate(loss_gaps(prog, ref), start=1):
            rows.append({"name": f"loss_step{k}", "value": gap,
                         "limit": limits["loss"],
                         "program": prog["loss"][k - 1],
                         "reference": ref["loss"][k - 1]})
    if (len(prog["loss"]) != len(ref["loss"])
            or not all(math.isfinite(p) for p in prog["loss"])):
        rows.append({"name": "loss_steps_finite", "value": math.inf,
                     "limit": 0.0})
    for name, key in (("grad1_worst_leaf", "grad1"),
                      ("delta3_worst_leaf", "delta")):
        gap, leaf = worst_leaf(prog[key], ref[key])
        rows.append({"name": name, "value": gap, "limit": limits[key],
                     "leaf": leaf})
    gaps = leaf_gaps(prog["grad1"], ref["grad1"])
    live = [g for leaf, g in gaps.items() if ref["grad1"][leaf] > 0.0]
    rows.insert(-1, {"name": "grad1_median_leaf",
                     "value": statistics.median(live) if live else math.inf,
                     "limit": limits["grad1_median"]})
    correct = all(row["value"] <= row["limit"] for row in rows)
    return correct, rows


def render(rows: list[dict]) -> str:
    """One line a number: name, value, limit, and where it was read."""
    out = []
    for row in rows:
        extra = "".join(f" {k}={row[k]}" for k in ("leaf", "program",
                                                  "reference") if k in row)
        verdict = "ok" if row["value"] <= row["limit"] else "OVER"
        out.append(f"compared {row['name']} value={row['value']:.6g} "
                   f"limit={row['limit']:.6g} {verdict}{extra}")
    return "\n".join(out)
