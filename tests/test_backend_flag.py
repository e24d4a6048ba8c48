"""Regression: the kernel-layer env flags must be re-read, not latched —
and must never be read at all inside a compiled ``Plan`` path.

The original ``kernels/ops.py`` captured ``REPRO_SCAN_BACKEND`` once into a
module constant, so a test or notebook setting it after import was silently
ignored; ``scan_backend()`` now consults the environment on every call.
``REPRO_PALLAS_INTERPRET`` had the same bug class (an ``INTERPRET`` module
constant) — ``pallas_interpret()`` resolves it per call too.

The inverse bug class arrived with the plan redesign: ``scan_backend()`` /
``pallas_interpret()`` being consulted *inside* compiled paths whenever an
``override is None`` slipped through the threading.  An ``ExecConfig``
carries explicit values end to end, so a compiled ``Plan.__call__`` must
perform ZERO ``os.environ`` reads — enforced below with an environment
tripwire.
"""

import os

import numpy as np
import pytest

import jax

from repro.core import k2forest
from repro.core.k2tree import K2Meta, hybrid_ks
from repro.kernels import ops


def test_scan_backend_rereads_env(monkeypatch):
    monkeypatch.setenv("REPRO_SCAN_BACKEND", "jnp")
    assert ops.scan_backend() == "jnp"
    # flipping AFTER the first resolve must take effect — the regression
    monkeypatch.setenv("REPRO_SCAN_BACKEND", "pallas")
    assert ops.scan_backend() == "pallas"
    monkeypatch.setenv("REPRO_SCAN_BACKEND", "jnp")
    assert ops.scan_backend() == "jnp"
    monkeypatch.delenv("REPRO_SCAN_BACKEND")
    assert ops.scan_backend() == ops.DEFAULT_SCAN_BACKEND == "pallas"


def test_scan_backend_override_and_validation(monkeypatch):
    monkeypatch.setenv("REPRO_SCAN_BACKEND", "jnp")
    assert ops.scan_backend("pallas") == "pallas"  # per-call override wins
    with pytest.raises(ValueError):
        ops.scan_backend("bogus")
    monkeypatch.setenv("REPRO_SCAN_BACKEND", "bogus")
    with pytest.raises(ValueError):
        ops.scan_backend()


def test_pallas_interpret_rereads_env(monkeypatch):
    """The INTERPRET-latch regression: flipping the var after import must be
    honored by the per-call resolver."""
    on_tpu = jax.default_backend() == "tpu"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.pallas_interpret() == (not on_tpu)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.pallas_interpret() is False  # the flip takes effect
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert ops.pallas_interpret() == (not on_tpu)  # default: interpret off-TPU
    # explicit override wins regardless of the environment
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.pallas_interpret(True) is True
    assert ops.pallas_interpret(False) is False


def test_no_module_level_latch():
    """The latched constant is gone: the module exposes only the resolver."""
    assert not hasattr(ops, "INTERPRET")


def test_resolve_exec_config_skips_env(monkeypatch):
    """An ExecConfig-shaped object resolves without touching the env."""
    from repro.core.query import ExecConfig

    monkeypatch.setenv("REPRO_SCAN_BACKEND", "bogus")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "bogus")
    assert ops.resolve_exec(ExecConfig(backend="jnp", interpret=False)) == (
        "jnp", False,
    )
    assert ops.resolve_exec(ExecConfig(backend="pallas", interpret=True)) == (
        "pallas", True,
    )
    # interpret=None resolves deterministically from the jax backend
    be, interp = ops.resolve_exec(ExecConfig(backend="pallas"))
    assert (be, interp) == ("pallas", jax.default_backend() != "tpu")
    # legacy strings still go through (and hit) the env validation
    with pytest.raises(ValueError):
        ops.resolve_exec(None)


class _EnvTripwire(dict):
    def get(self, k, d=None):
        if str(k).startswith("REPRO_"):
            raise AssertionError(
                f"os.environ read of {k!r} inside a compiled Plan path"
            )
        return super().get(k, d)


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_no_env_read_inside_plan_call(monkeypatch, backend):
    """The redesign bugfix regression: with an explicit ExecConfig, nothing
    on ``Plan.__call__`` — pattern serve, unbounded lanes, joins, BGP —
    consults the REPRO_* environment.  The env holds invalid values AND
    ``kernels.ops`` sees a tripwire mapping, so any read fails loudly."""
    from repro.core import engine as eng, k2triples
    from repro.core.query import BgpQ, ExecConfig, JoinQ, TriplePatternQ
    from repro.data import rdf

    ds = rdf.generate(500, n_subjects=30, n_preds=4, n_objects=40, seed=23)
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    T = set(map(tuple, ds.ids.tolist()))
    E = eng.Engine(store)
    cfg = ExecConfig(backend=backend, interpret=jax.default_backend() != "tpu",
                     cap=128)
    s_, p_, o_ = map(int, ds.ids[3])

    monkeypatch.setenv("REPRO_SCAN_BACKEND", "bogus")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "bogus")
    monkeypatch.setattr(ops.os, "environ", _EnvTripwire(os.environ))

    assert E.compile(TriplePatternQ(s_, p_, o_), cfg)() is True
    assert E.compile(TriplePatternQ(s_, p_, "?o"), cfg)().tolist() == sorted(
        oo for (ss, pp, oo) in T if ss == s_ and pp == p_
    )
    E.compile(TriplePatternQ(s_, "?p", "?o"), cfg)()  # unbounded + gather
    E.compile(TriplePatternQ("?s", p_, "?o"), cfg)()  # pair enumeration
    E.compile(JoinQ("A", "s", "s", p1=p_, c1=o_, p2=p_, c2=o_), cfg)()
    E.compile(JoinQ("D", "s", "o", p1=p_, c1=o_, p2=p_), cfg)()  # rebind kernel
    E.compile(BgpQ((TriplePatternQ(s_, "?p", "?o"),)), cfg)()


def test_env_flip_switches_dispatch(monkeypatch):
    """Both env values drive scan_batch_mixed to identical results — the
    flag actually reaches the dispatch site after an in-session flip."""
    rng = np.random.default_rng(31)
    side = 60
    meta = K2Meta(hybrid_ks(side))
    f, _ = k2forest.build_forest(
        [(rng.integers(0, side, 120), rng.integers(0, side, 120))], meta
    )
    preds = np.zeros(4, np.int32)
    keys = rng.integers(0, side, 4)
    axes = np.array([0, 1, 0, 1], np.int32)
    out = {}
    for be in ("jnp", "pallas"):
        monkeypatch.setenv("REPRO_SCAN_BACKEND", be)
        out[be] = k2forest.scan_batch_mixed(meta, f, preds, keys, axes, 32)
    for a, b in zip(tuple(out["jnp"]), tuple(out["pallas"])):
        assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# interpret mode on a TPU backend is an error, never a quiet fallback
# ---------------------------------------------------------------------------


@pytest.fixture
def steer_backend(monkeypatch):
    """Make the backend check report the given platform (no device used)."""
    from repro.core import query as qapi

    def steer(platform):
        monkeypatch.setattr(qapi, "_backend", lambda: platform)

    return steer


def test_interpret_on_tpu_is_an_error(monkeypatch, steer_backend):
    from repro.core import engine as eng, k2triples
    from repro.core.query import ExecConfig, ServeQ
    from repro.data import rdf

    steer_backend("tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        ExecConfig.from_env()
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        ops.pallas_interpret()
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        ops.pallas_interpret(True)
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        ExecConfig(interpret=True).resolved()
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        ops.resolve_exec(ExecConfig(backend="pallas", interpret=True))
    # the serve path: compiling a plan under such a config refuses
    ds = rdf.generate(200, n_subjects=20, n_preds=3, n_objects=20, seed=5)
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        eng.Engine(store).compile(ServeQ(), ExecConfig(interpret=True))
    # compiled kernels are what a TPU resolves to otherwise
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ExecConfig.from_env().interpret is False
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert ExecConfig.from_env().interpret is False
    assert ExecConfig().resolved().interpret is False
    assert ops.pallas_interpret() is False
    assert ops.resolve_exec(ExecConfig(backend="pallas")) == ("pallas", False)


def test_interpret_default_refuses_other_backends(steer_backend):
    from repro.core.query import ExecConfig, default_interpret

    steer_backend("gpu")
    with pytest.raises(RuntimeError, match="need a TPU backend"):
        default_interpret()
    with pytest.raises(RuntimeError, match="need a TPU backend"):
        ExecConfig().resolved()
    steer_backend("cpu")
    assert default_interpret() is True
