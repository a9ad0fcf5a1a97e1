"""Plain reference counts: positive and complete contingency tables.

Independent of the system under test: it reads the generated NumPy data
and counts, for a lattice point (a tree of relationship atoms over
first-order variables), how many groundings (one entity per variable)
fall in each cell.

Axes are plain tuples:

* ``("attr", var, name)`` -- an entity attribute of variable ``var``
  (``var = (entity type, copy)``), size = the attribute's cardinality;
* ``("edge", rel, name)`` -- an edge attribute, size = cardinality + 1,
  the last slot meaning "relationship false" (N/A);
* ``("rind", rel)`` -- the relationship indicator, 0 = false, 1 = true.

Positive counts (every atom true) come from sparse incidence products in
float64, exact below 2**53, and are checked to stay there.  The complete
table follows by inclusion-exclusion over which atoms are forced true;
it is exact in int64.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np
import scipy.sparse as sp

EXACT_F64 = 2 ** 53


@dataclass(frozen=True)
class Atom:
    rel: str
    src: Tuple[str, int]
    dst: Tuple[str, int]


@dataclass
class Table:
    axes: Tuple[tuple, ...]
    counts: np.ndarray                    # int64, one axis per entry of axes

    def project(self, keep: Sequence[tuple]) -> np.ndarray:
        """Sum out every axis not in ``keep``; axes in ``keep`` order."""
        keep = tuple(keep)
        missing = [a for a in keep if a not in self.axes]
        if missing:
            raise KeyError(f"axes {missing} not in the table")
        drop = tuple(i for i, a in enumerate(self.axes) if a not in keep)
        out = self.counts.sum(axis=drop) if drop else self.counts
        rest = [a for a in self.axes if a in keep]
        return np.transpose(out, [rest.index(a) for a in keep])


def point_vars(atoms: Sequence[Atom]) -> List[Tuple[str, int]]:
    return sorted({v for a in atoms for v in (a.src, a.dst)})


def _var_code(data, var) -> Tuple[np.ndarray, List[tuple], List[int]]:
    """Mixed-radix code of all attributes of ``var`` per entity."""
    ent = data.entities[var[0]]
    code = np.zeros(ent.size, dtype=np.int64)
    axes, cards = [], []
    for name, col in ent.attrs.items():
        c = ent.cards[name]
        code = code * c + col.astype(np.int64)
        axes.append(("attr", var, name))
        cards.append(c)
    return code, axes, cards


def _edge_code(rel) -> Tuple[np.ndarray, List[tuple], List[int]]:
    code = np.zeros(rel.num_edges, dtype=np.int64)
    axes, cards = [], []
    for name, col in rel.attrs.items():
        c = rel.cards[name]
        code = code * c + col.astype(np.int64)
        axes.append(("edge", rel.name, name))
        cards.append(c)
    return code, axes, cards


def _to_int(x: np.ndarray) -> np.ndarray:
    if x.size and float(np.max(np.abs(x))) >= EXACT_F64:
        raise OverflowError("a positive count left float64's exact range")
    return np.rint(x).astype(np.int64)


def _component_positive(data, atoms: Sequence[Atom]):
    """Positive counts of one connected tree of atoms, as an int64 tensor
    over (code of each var in sorted order, edge code of each atom in the
    given order).  Messages flow to the root variable of largest degree:
    each is a (parent entity, code) matrix from a sparse incidence product.
    """
    vars_ = point_vars(atoms)
    degree = {v: sum(v in (a.src, a.dst) for a in atoms) for v in vars_}
    root = max(vars_, key=lambda v: (degree[v], v))
    codes = {v: _var_code(data, v) for v in vars_}

    def message(u, via: Atom, parent):
        """Tensor (n_parent, [code(u), edge(via), subtree(u)...]) and its
        axis labels."""
        rel = data.relations[via.rel]
        u_ids, p_ids = ((rel.src, rel.dst) if via.src == u
                        else (rel.dst, rel.src))
        sub, sub_labels = subtree(u, via)
        ucode, _, ucards = codes[u]
        ecode, _, ecards = _edge_code(rel)
        cu, ce = int(np.prod(ucards)), int(np.prod(ecards))
        n_p = data.entities[parent[0]].size
        n_u = data.entities[u[0]].size
        row = (p_ids.astype(np.int64) * cu + ucode[u_ids]) * ce + ecode
        inc = sp.csr_matrix((np.ones(rel.num_edges), (row, u_ids)),
                            shape=(n_p * cu * ce, n_u))
        m = inc @ sub                              # (n_p * cu * ce, D)
        return (m.reshape(n_p, cu * ce * sub.shape[1]),
                [("var", u), ("edge", via.rel)] + sub_labels)

    def subtree(u, came_from):
        """Per-entity tensor over the subtree below ``u`` (excluding u's
        own code), as (n_u, D) float64."""
        n_u = data.entities[u[0]].size
        mats, labels = [], []
        for a in atoms:
            if a is came_from or u not in (a.src, a.dst):
                continue
            child = a.dst if a.src == u else a.src
            m, lab = message(child, a, u)
            mats.append(m)
            labels += lab
        out = np.ones((n_u, 1))
        for m in mats:
            out = (out[:, :, None] * m[:, None, :]).reshape(n_u, -1)
        return out, labels

    sub, labels = subtree(root, None)
    rcode, _, rcards = codes[root]
    cr = int(np.prod(rcards))
    grouped = sp.csr_matrix(
        (np.ones(rcode.shape[0]), (rcode, np.arange(rcode.shape[0]))),
        shape=(cr, rcode.shape[0])) @ sub
    labels = [("var", root)] + labels
    # split every label into its axis sizes, then reorder to vars + atoms
    sizes = []
    for kind, key in labels:
        if kind == "var":
            sizes.append(int(np.prod(codes[key][2])))
        else:
            sizes.append(int(np.prod(_edge_code(data.relations[key])[2])))
    t = _to_int(np.asarray(grouped)).reshape(sizes)
    order = ([labels.index(("var", v)) for v in vars_]
             + [labels.index(("edge", a.rel)) for a in atoms])
    return np.transpose(t, order)


def _components(atoms: Sequence[Atom]) -> List[List[Atom]]:
    left, comps = list(atoms), []
    while left:
        comp = [left.pop(0)]
        vs = {comp[0].src, comp[0].dst}
        grew = True
        while grew:
            grew = False
            for a in list(left):
                if vs & {a.src, a.dst}:
                    comp.append(a)
                    vs |= {a.src, a.dst}
                    left.remove(a)
                    grew = True
        comps.append(comp)
    return comps


def _at_least(data, atoms: Sequence[Atom], forced: Sequence[Atom]):
    """Groundings of all variables of ``atoms`` with every ``forced`` atom
    true: int64 tensor over (var codes in sorted order, edge codes of the
    forced atoms in ``atoms`` order)."""
    vars_ = point_vars(atoms)
    parts, labels = [], []
    covered = set()
    for comp in _components(forced):
        parts.append(_component_positive(data, comp))
        cv = point_vars(comp)
        labels += [("var", v) for v in cv] + [("edge", a.rel) for a in comp]
        covered |= set(cv)
    for v in vars_:
        if v not in covered:
            code, _, cards = _var_code(data, v)
            parts.append(np.bincount(code, minlength=int(np.prod(cards)))
                         .astype(np.int64))
            labels.append(("var", v))
    out = np.ones((), dtype=np.int64)
    for p in parts:
        out = np.multiply.outer(out, p)
    order = ([labels.index(("var", v)) for v in vars_]
             + [labels.index(("edge", a.rel)) for a in atoms if a in forced])
    return np.transpose(out, order)


def _var_axes(data, vars_):
    axes, cards = [], []
    for v in vars_:
        _, ax, cs = _var_code(data, v)
        axes += ax
        cards += cs
    return axes, cards


def positive_table(data, atoms: Sequence[Atom]) -> Table:
    """Every atom true: axes = entity attributes of every variable, then
    the edge attributes of every atom (no N/A slot)."""
    atoms = list(atoms)
    vars_ = point_vars(atoms)
    vaxes, vcards = _var_axes(data, vars_)
    eaxes, ecards = [], []
    for a in atoms:
        _, ax, cs = _edge_code(data.relations[a.rel])
        eaxes += ax
        ecards += cs
    t = _at_least(data, atoms, atoms).reshape(vcards + ecards)
    return Table(tuple(vaxes + eaxes), t)


def complete_table(data, atoms: Sequence[Atom]) -> Table:
    """All groundings, by attribute values, edge attributes (N/A where the
    atom is false) and indicators."""
    atoms = list(atoms)
    k = len(atoms)
    vars_ = point_vars(atoms)
    vaxes, vcards = _var_axes(data, vars_)
    ecodes = [_edge_code(data.relations[a.rel]) for a in atoms]
    eaxes = [ax for _, axs, _ in ecodes for ax in axs]
    ecards = [c for _, _, cs in ecodes for c in cs]
    ce = [int(np.prod(cs)) for _, _, cs in ecodes]
    at_least = {}
    for mask in itertools.product((0, 1), repeat=k):
        forced = [a for a, m in zip(atoms, mask) if m]
        at_least[mask] = _at_least(data, atoms, forced)
    nv = len(vars_)
    if any(len(cs) > 1 for _, _, cs in ecodes):
        raise NotImplementedError("complete tables with several edge "
                                  "attributes per relationship")
    has_edge = [bool(cs) for _, _, cs in ecodes]
    # an atom with an edge attribute gets one axis of card + 1 (N/A last)
    edims = [c + 1 if h else 1 for c, h in zip(ce, has_edge)]
    out = np.zeros([int(np.prod(_var_code(data, v)[2])) for v in vars_]
                   + edims + [2] * k, dtype=np.int64)
    for truth in itertools.product((0, 1), repeat=k):
        exact = 0
        for sup in itertools.product((0, 1), repeat=k):
            if any(t and not s for t, s in zip(truth, sup)):
                continue
            t = at_least[sup]
            # sum out the edge codes of atoms forced true but not in truth
            pos = nv
            drop = []
            for s, tr in zip(sup, truth):
                if s:
                    if not tr:
                        drop.append(pos)
                    pos += 1
            if drop:
                t = t.sum(axis=tuple(drop))
            sign = -1 if (sum(sup) - sum(truth)) % 2 else 1
            exact = exact + sign * t
        idx = [slice(None)] * nv
        for tr, c, h in zip(truth, ce, has_edge):
            idx.append(slice(0, c) if tr else (c if h else 0))
        idx += list(truth)
        out[tuple(idx)] = exact
    shape = vcards + [c + 1 for c in ecards] + [2] * k
    axes = vaxes + eaxes + [("rind", a.rel) for a in atoms]
    return Table(tuple(axes), out.reshape(shape))
