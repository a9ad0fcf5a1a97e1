"""Whether what the timed path produced is correct: the comparison of its
answers with the plain reference (``bench/reference``).

Each function returns ``{number name: value}``; the limits live in the
configuration file (``limits``) and :func:`judge` sets one against the
other.  A number that cannot be computed is ``inf``: it fails.
"""

from __future__ import annotations

import itertools
import math
from typing import Dict, List, Sequence

import numpy as np

from ..reference import counts as ref
from ..reference.bdeu import bdeu
from . import system

INF = float("inf")


class RefTables:
    """Reference complete tables per lattice point, computed once."""

    def __init__(self, data):
        self.data = data
        self._full: Dict[tuple, ref.Table] = {}

    def complete(self, point) -> ref.Table:
        key = point.atoms
        if key not in self._full:
            self._full[key] = ref.complete_table(self.data,
                                                 system.ref_atoms(point))
        return self._full[key]


def _cmp_complete(got: np.ndarray, want: np.ndarray) -> float:
    if got.shape != want.shape:
        return INF
    top = float(np.max(np.abs(want))) if want.size else 0.0
    return float(np.max(np.abs(got - want))) / max(top, 1.0) if want.size \
        else 0.0


def _true_slice(arr: np.ndarray, axes: Sequence[tuple], atoms) -> np.ndarray:
    """Cells where every atom of the point holds (indicator axes fixed to
    true; edge axes whole, their N/A slot must then be 0), or None when
    some indicator is summed out of ``axes``."""
    rinds = {("rind", a.rel) for a in atoms}
    if not rinds <= set(axes):
        return None
    idx = []
    for ax, n in zip(axes, arr.shape):
        idx.append(1 if ax[0] == "rind" else slice(None))
    return arr[tuple(idx)]


def _reaches(parents, src, dst) -> bool:
    """Is ``dst`` an ancestor of ``src`` (or ``src`` itself)?"""
    stack, seen = [src], set()
    while stack:
        n = stack.pop()
        if n == dst:
            return True
        if n not in seen:
            seen.add(n)
            stack.extend(parents[n])
    return False


def _moves(nodes, parents, max_parents: int):
    """The hill-climber's single-edge moves from a model, as (child, new
    parent set): drop a parent, or add one where the child has room and no
    cycle closes."""
    for src, dst in itertools.permutations(nodes, 2):
        if src in parents[dst]:
            yield dst, parents[dst] - {src}
        elif len(parents[dst]) < max_parents and not _reaches(parents, src,
                                                               dst):
            yield dst, parents[dst] | {src}


def discover_checks(job, data, ess: float, max_parents: int
                    ) -> Dict[str, float]:
    """One job's family tables against the reference's; each point's model
    score against the reference's BDeu of the same model; and each chosen
    model against its neighbours, all scored by the reference."""
    tabs = RefTables(data)
    pos_diff, comp_diff, n_pos = 0.0, 0.0, 0
    first_point = {}
    for point, keep, tab in job.calls:
        first_point.setdefault(keep, point)
        axes = [system.ref_axis(v) for v in keep]
        want = tabs.complete(point).project(axes).astype(np.float64)
        got = system.table_array(tab)
        comp_diff = max(comp_diff, _cmp_complete(got, want))
        w = _true_slice(want, axes, point.atoms)
        if w is not None:
            g = _true_slice(got, axes, point.atoms) \
                if got.shape == want.shape else None
            pos_diff = max(pos_diff, INF if g is None
                           else float(np.max(np.abs(g - w))))
            n_pos += 1
    if not n_pos or not job.calls:
        pos_diff = INF
    out = {"positive_max_abs_diff": pos_diff,
           "complete_max_rel_diff": comp_diff,
           "score_max_rel_diff": INF, "search_gap_rel": INF}
    scores: Dict[tuple, float] = {}

    def score(point, child, parents) -> float:
        # a family is scored on the table of the point where the search
        # first fetched it (its score memo is keyed by family); one it
        # never fetched, on this point's
        keep = tuple(sorted(parents)) + (child,)
        if keep not in scores:
            at = first_point.get(keep, point)
            axes = [system.ref_axis(v) for v in keep]
            scores[keep] = bdeu(tabs.complete(at).project(axes),
                                len(keep) - 1, ess)
        return scores[keep]

    score_diff, gap = 0.0, 0.0
    for point, model in job.result.models.items():
        parents = {c: frozenset(ps) for c, ps in model.parents.items()}
        if any(tuple(sorted(ps)) + (c,) not in first_point
               for c, ps in parents.items()):
            return out                  # a chosen family never fetched
        cur = {c: score(point, c, ps) for c, ps in parents.items()}
        total = sum(cur.values())
        scale = max(abs(total), 1.0)
        score_diff = max(score_diff, abs(float(model.score) - total) / scale)
        for child, ps in _moves(list(model.nodes), parents, max_parents):
            gap = max(gap, (score(point, child, ps) - cur[child]) / scale)
    out.update(score_max_rel_diff=score_diff, search_gap_rel=gap)
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each number beside its limit; a number passes when it is not above
    its limit (and is a number)."""
    out = []
    for name, v in values.items():
        lim = float(limits[name])
        ok = (not math.isnan(v)) and v <= lim
        out.append({"name": name, "value": v, "limit": lim, "ok": ok})
    return out
