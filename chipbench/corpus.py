"""The corpus a cell serves, and the plain reference that checks it.

``generate`` is ``repro.data.rdf.generate`` copied: the same draws in the
same order, so the same arguments and seed give the same triples.  It drops
duplicates through one packed int64 key per triple instead of
``np.unique(axis=0)``, which sorted rows and took most of the time.

``Reference`` and ``same`` answer and compare the serve-IR ops from the raw
id triples with nothing but numpy; they import nothing of the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.traffic import (
    OP_ANY_ANY_O, OP_CHECK, OP_COL, OP_ROW, OP_S_ANY_ANY, OP_S_ANY_O,
)


@dataclasses.dataclass(frozen=True)
class Corpus:
    ids: np.ndarray  # int64[N, 3] 1-based (s, p, o), unique, sorted by (s, p, o)
    n_so: int
    n_subjects: int
    n_objects: int
    n_preds: int

    @property
    def n_triples(self) -> int:
        return int(self.ids.shape[0])


def _bits(n: int) -> int:
    return max(1, int(n).bit_length())


def pack(a: np.ndarray, b: np.ndarray, c: np.ndarray, nb: int, nc: int
         ) -> np.ndarray:
    """One int64 key per row that sorts as ``(a, b, c)``; ``b < 2**nb``,
    ``c < 2**nc``."""
    return (a << (nb + nc)) | (b << nc) | c


def generate(
    n_triples: int, *, n_subjects: int, n_preds: int, n_objects: int,
    so_frac: float = 0.3, pred_alpha: float = 1.2, obj_alpha: float = 1.05,
    preds_per_subject: int | None = None, seed: int = 0,
) -> Corpus:
    """Power-law synthetic RDF in the paper's 4-range id space (see
    ``repro.data.rdf.generate`` for the model)."""
    rng = np.random.default_rng(seed)
    n_so = int(so_frac * min(n_subjects, n_objects))

    def powerlaw_ids(n, lo, hi, alpha):
        u = rng.random(n)
        span = hi - lo + 1
        ranks = np.floor(span * u ** alpha).astype(np.int64)
        return lo + np.clip(ranks, 0, span - 1)

    s = powerlaw_ids(n_triples, 1, n_subjects, 1.0)
    if preds_per_subject is None:
        p = powerlaw_ids(n_triples, 1, n_preds, pred_alpha)
    else:
        perm = rng.permutation(n_preds).astype(np.int64)
        pool_size = rng.integers(1, preds_per_subject + 1, n_subjects + 1)
        pool_start = rng.integers(0, n_preds, n_subjects + 1)
        slot = rng.integers(0, 1 << 30, n_triples) % pool_size[s]
        p = 1 + perm[(pool_start[s] + slot) % n_preds]
    o = powerlaw_ids(n_triples, 1, n_objects, obj_alpha)
    local = rng.random(n_triples) < 0.6
    spread = max(4, n_objects // 64)
    o_local = 1 + (
        (s - 1) * n_objects // max(n_subjects, 1)
        + rng.integers(0, spread, n_triples)
    ) % n_objects
    o = np.where(local, o_local, o)

    nb, nc = _bits(n_preds), _bits(n_objects)
    if _bits(n_subjects) + nb + nc > 63:
        raise ValueError("ids too wide to pack one triple into an int64")
    key = np.unique(pack(s, p, o, nb, nc))
    ids = np.stack([key >> (nb + nc), (key >> nc) & ((1 << nb) - 1),
                    key & ((1 << nc) - 1)], axis=1)
    return Corpus(ids=ids, n_so=n_so, n_subjects=n_subjects,
                  n_objects=n_objects, n_preds=n_preds)


def from_config(config: dict, seed: int) -> Corpus:
    """The corpus of a ``configs/<name>.json`` at its stated scale."""
    return generate(
        config["triples"], n_subjects=config["subjects"],
        n_preds=config["preds"], n_objects=config["objects"],
        seed=seed, **config["generator"],
    )


class Reference:
    """Answers of the serve-IR ops from the raw (s, p, o) id triples."""

    def __init__(self, ids: np.ndarray):
        ids = np.asarray(ids, np.int64)
        nb = _bits(int(ids[:, 1].max(initial=0)))
        w = _bits(int(ids[:, [0, 2]].max(initial=0)))
        spo = pack(ids[:, 0], ids[:, 1], ids[:, 2], nb, w)
        self.by_s = ids if np.all(spo[1:] > spo[:-1]) else ids[np.argsort(spo)]
        self.by_o = ids[np.argsort(pack(ids[:, 2], ids[:, 1], ids[:, 0], nb, w))]

    @staticmethod
    def _slice(arr, col, key):
        c = arr[:, col]
        return arr[np.searchsorted(c, key, "left"): np.searchsorted(c, key, "right")]

    def answer(self, op: int, s: int, p: int, o: int):
        if op in (OP_CHECK, OP_ROW, OP_S_ANY_ANY, OP_S_ANY_O):
            rows = self._slice(self.by_s, 0, s)
            if op == OP_CHECK:
                return bool(((rows[:, 1] == p) & (rows[:, 2] == o)).any())
            if op == OP_ROW:
                return rows[rows[:, 1] == p, 2]
            if op == OP_S_ANY_O:
                return np.unique(rows[rows[:, 2] == o, 1])
            return {int(q): rows[rows[:, 1] == q, 2] for q in np.unique(rows[:, 1])}
        rows = self._slice(self.by_o, 2, o)
        if op == OP_COL:
            return rows[rows[:, 1] == p, 0]
        if op == OP_ANY_ANY_O:
            return {int(q): rows[rows[:, 1] == q, 0] for q in np.unique(rows[:, 1])}
        raise ValueError(f"not a serve-IR op: {op}")


def same(a, b) -> bool:
    """Equality of two decoded answers (bool, id array, or {pred: ids})."""
    if isinstance(a, dict) or isinstance(b, dict):
        return (isinstance(a, dict) and isinstance(b, dict)
                and a.keys() == b.keys()
                and all(same(a[k], b[k]) for k in a))
    flags = [isinstance(x, (bool, np.bool_)) for x in (a, b)]
    if any(flags):
        return all(flags) and bool(a) == bool(b)
    return np.array_equal(np.asarray(a), np.asarray(b))
