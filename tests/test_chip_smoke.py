"""``chip_smoke.py`` off the chip: it refuses the CPU, and its serve-and-check
logic holds at a small size with the kernels interpreted."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"))


def test_refuses_the_cpu():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], env=_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 2
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_serve_path_never_imports_dryrun():
    """``launch/dryrun.py`` overwrites XLA_FLAGS when imported."""
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "from repro.launch import serve, broker; from repro.core import engine; "
        "assert 'repro.launch.dryrun' not in sys.modules" % str(ROOT)
    )
    subprocess.run([sys.executable, "-c", code], env=_env(), check=True,
                   timeout=120)


def test_smoke_logic_small(monkeypatch, capsys):
    """The whole smoke (build, two broker runs, numpy check) on a small
    geonames-shaped corpus; the Pallas run is interpreted on the CPU, so
    its compiled-kernel guard is steered here."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    from repro.data import rdf
    from repro.launch import serve

    real_gen, real_bench = rdf.generate_like, serve.run_bench
    monkeypatch.setattr(rdf, "generate_like",
                        lambda name, n, seed=0: real_gen(name, 6000, seed=seed))

    def bench(**kw):
        row = real_bench(**kw)
        assert row["interpret"] is True  # the CPU interprets the kernels
        return dict(row, interpret=False)

    monkeypatch.setattr(serve, "run_bench", bench)
    # small answers at this size: start low enough that cap growth fires
    monkeypatch.setattr(chip_smoke, "GROW_CAP", 2)
    lanes = chip_smoke.smoke(seed=3, n_queries=48, four_chips=False)
    out = capsys.readouterr().out
    assert lanes > 30
    assert "run backend=pallas" in out and "run backend=jnp" in out
    assert "cap=2:" in out


def test_reference_answers():
    import numpy as np

    sys.path.insert(0, str(ROOT))
    import chip_smoke
    from repro.core import engine as eng

    ids = np.array([[1, 1, 5], [1, 2, 5], [1, 2, 7], [2, 1, 5]])
    ref = chip_smoke.Reference(ids)
    assert ref.answer(eng.OP_CHECK, 1, 2, 7) is True
    assert ref.answer(eng.OP_CHECK, 2, 2, 7) is False
    assert ref.answer(eng.OP_ROW, 1, 2, 0).tolist() == [5, 7]
    assert ref.answer(eng.OP_COL, 0, 1, 5).tolist() == [1, 2]
    assert ref.answer(eng.OP_S_ANY_O, 1, 0, 5).tolist() == [1, 2]
    got = ref.answer(eng.OP_S_ANY_ANY, 1, 0, 0)
    assert {k: v.tolist() for k, v in got.items()} == {1: [5], 2: [5, 7]}
    got = ref.answer(eng.OP_ANY_ANY_O, 0, 0, 5)
    assert {k: v.tolist() for k, v in got.items()} == {1: [1, 2], 2: [1]}
    assert chip_smoke.same({1: np.array([5])}, {1: [5]})
    assert not chip_smoke.same(True, np.array([1]))
    assert not chip_smoke.same(np.array([5, 7]), np.array([5]))


def test_kernel_differential_small():
    """The chip's kernel differential, rehearsed at small caps with the
    kernels interpreted."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke

    n = chip_smoke.kernel_differential(
        seed=1, sides=(300,), scan_caps=(4, 64), range_caps=(16,),
        n_lanes=40, interpret=True)
    assert n == 2 + 1 + 1 + 2
