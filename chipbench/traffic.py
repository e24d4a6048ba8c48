"""One general generator for every mix: requests and arrival times from a seed.

A mix (``mixes/<name>.json``) gives op shares by name, the number of tenants
and their Zipf weight, and how anchors are drawn.  The op-mix drawing follows
``repro.launch.serve.make_trace``: each request takes its ids from one
anchor triple of the corpus, so every answer is non-empty, and the ops that
leave the predicate free send ``p = 0``.
"""

from __future__ import annotations

import numpy as np

# the serve IR's op codes (``repro.core.engine``): the wire format
OP_CHECK = 0  # (S, P, O)
OP_ROW = 1  # (S, P, ?O)
OP_COL = 2  # (?S, P, O)
OP_S_ANY_ANY = 3  # (S, ?P, ?O)
OP_ANY_ANY_O = 4  # (?S, ?P, O)
OP_S_ANY_O = 5  # (S, ?P, O)
OPS = {"check": OP_CHECK, "row": OP_ROW, "col": OP_COL,
       "s_any_any": OP_S_ANY_ANY, "any_any_o": OP_ANY_ANY_O,
       "s_any_o": OP_S_ANY_O}
UNBOUNDED_OPS = ("s_any_any", "any_any_o", "s_any_o")

# columns of a request row
TENANT, OP, S, P, O = range(5)


def zipf_weights(n: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return w / w.sum()


def tenant_names(mix: dict) -> list[str]:
    return [f"tenant-{t}" for t in range(mix["tenants"])]


def draw(ids: np.ndarray, mix: dict, n: int, rng: np.random.Generator,
         tenant: int | None = None) -> np.ndarray:
    """``n`` requests as int64 rows ``(tenant, op, s, p, o)``.

    Tenants follow Zipf(``tenant_zipf``) unless ``tenant`` fixes one.
    Anchors are distinct triples drawn uniformly (``"anchors": "uniform"``).
    """
    if mix["anchors"] != "uniform":
        raise ValueError(f"unknown anchor draw {mix['anchors']!r}")
    names = sorted(mix["ops"])
    shares = np.array([mix["ops"][k] for k in names], np.float64)
    ops = np.array([OPS[k] for k in names])[
        rng.choice(len(names), size=n, p=shares / shares.sum())]
    if tenant is None:
        tenants = rng.choice(mix["tenants"], size=n,
                             p=zipf_weights(mix["tenants"], mix["tenant_zipf"]))
    else:
        tenants = np.full(n, tenant)
    rows = ids[rng.choice(ids.shape[0], size=n, replace=n > ids.shape[0])]
    p = np.where(ops >= OP_S_ANY_ANY, 0, rows[:, 1])
    return np.stack([tenants, ops, rows[:, 0], p, rows[:, 2]], axis=1).astype(np.int64)


def poisson_arrivals(rate_per_s: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson stream at ``rate_per_s``,
    conditioned on its expected count so that every seed offers the same
    number of requests: sorted uniform times."""
    n = max(1, round(rate_per_s * seconds))
    return np.sort(rng.random(n)) * seconds


def rngs(seed: int):
    """Independent streams of one seed: (corpus-independent) warm-up,
    window and per-tenant draws."""
    ss = np.random.SeedSequence(seed, spawn_key=(7,))
    return [np.random.default_rng(s) for s in ss.spawn(3)]
