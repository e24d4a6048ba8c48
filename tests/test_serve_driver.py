"""Regression tests for the serve-driver bugfixes:

  * ``serve_mesh_shape`` uses EVERY device (the old ``min(4, n)``
    factorization silently dropped devices when ``n % 4 != 0``);
  * ``--sharded`` on a single visible device errors loudly instead of
    silently serving unsharded;
  * ``ExecConfig.from_env`` distinguishes an unset REPRO_PALLAS_INTERPRET
    (auto) from an explicit ``"1"`` (the old expression AND'd the env
    value with ``default_interpret()``, so an explicit 1 was ignored on
    TPU);
  * trace generation sanity for the serving benchmark.
"""

import numpy as np
import pytest

from repro.core import query as qapi
from repro.launch import mesh as meshlib
from repro.launch import serve


# ---------------------------------------------------------------------------
# satellite 1: mesh factorization must use every device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", list(range(1, 17)))
def test_serve_mesh_shape_uses_every_device(n):
    dp, mp = meshlib.serve_mesh_shape(n)
    assert dp * mp == n  # the old bug: 6 -> (1, 4) served on 4 of 6
    assert 1 <= mp <= 4


def test_serve_mesh_shape_known_factorizations():
    assert meshlib.serve_mesh_shape(6) == (2, 3)
    assert meshlib.serve_mesh_shape(8) == (2, 4)
    assert meshlib.serve_mesh_shape(5) == (5, 1)  # prime: model stays 1
    assert meshlib.serve_mesh_shape(12) == (3, 4)
    assert meshlib.serve_mesh_shape(1) == (1, 1)


def test_serve_mesh_shape_rejects_zero_devices():
    with pytest.raises(ValueError):
        meshlib.serve_mesh_shape(0)


def test_serve_mesh_shape_model_max():
    assert meshlib.serve_mesh_shape(16, model_max=8) == (2, 8)
    assert meshlib.serve_mesh_shape(16, model_max=3) == (8, 2)


# ---------------------------------------------------------------------------
# satellite 2: --sharded with one device must not silently degrade
# ---------------------------------------------------------------------------


def test_meshes_have_auto_axes():
    from jax.sharding import AxisType

    m = meshlib.make_mesh((1, 1), ("data", "model"))
    assert m.axis_types == (AxisType.Auto, AxisType.Auto)


def test_peaks_table_is_keyed_by_device_kind():
    p = meshlib.peaks("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        meshlib.peaks("cpu")


def test_sharded_single_device_errors():
    import jax

    if len(jax.devices()) > 1:
        pytest.skip("needs a single-device backend to exercise the guard")
    with pytest.raises(ValueError, match="only one device"):
        serve.run_bench(
            n_triples=500, n_preds=4, n_queries=8, n_tenants=2,
            sharded=True, quiet=True,
        )


# ---------------------------------------------------------------------------
# satellite 3: from_env interpret tri-state
# ---------------------------------------------------------------------------


def test_from_env_interpret_unset_uses_auto(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(qapi, "default_interpret", lambda: False)
    assert qapi.ExecConfig.from_env().interpret is False
    monkeypatch.setattr(qapi, "default_interpret", lambda: True)
    assert qapi.ExecConfig.from_env().interpret is True


def test_from_env_interpret_explicit_1_wins(monkeypatch):
    """The regression: on a real-TPU host default_interpret() is False and
    the old ``env != "0" and default_interpret()`` silently discarded an
    explicit REPRO_PALLAS_INTERPRET=1."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(qapi, "default_interpret", lambda: False)
    assert qapi.ExecConfig.from_env().interpret is True


def test_from_env_interpret_explicit_0_wins(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setattr(qapi, "default_interpret", lambda: True)
    assert qapi.ExecConfig.from_env().interpret is False


def test_from_env_interpret_override_beats_env(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert qapi.ExecConfig.from_env(interpret=True).interpret is True


# ---------------------------------------------------------------------------
# trace generation
# ---------------------------------------------------------------------------


def test_zipf_weights():
    w = serve.zipf_weights(8, 1.1)
    assert w.shape == (8,)
    assert w.sum() == pytest.approx(1.0)
    assert (np.diff(w) < 0).all()  # tenant 0 heaviest, strictly skewed


def test_make_trace_shape_and_ops():
    from repro.data import rdf

    ds = rdf.generate(
        2000, n_subjects=40, n_preds=8, n_objects=60,
        preds_per_subject=3, seed=1,
    )
    trace = serve.make_trace(ds, 500, 4, zipf_a=1.1, seed=2)
    assert len(trace) == 500
    tenants = {t for t, *_ in trace}
    assert tenants <= {f"tenant-{i}" for i in range(4)}
    for _, op, s, p, o in trace:
        assert 0 <= op <= 5
        assert s >= 1 and o >= 1
        # unbounded-?P ops must leave the predicate free
        assert (p == 0) if op >= 3 else (p >= 1)
    # skew: the heaviest tenant dominates
    counts = {t: sum(1 for row in trace if row[0] == t) for t in tenants}
    assert counts["tenant-0"] == max(counts.values())


def test_make_trace_bounded_only():
    from repro.data import rdf

    ds = rdf.generate(
        1000, n_subjects=30, n_preds=6, n_objects=50,
        preds_per_subject=2, seed=3,
    )
    trace = serve.make_trace(ds, 200, 2, unbounded=False, seed=4)
    assert all(op < 3 for _, op, *_ in trace)


# ---------------------------------------------------------------------------
# end-to-end harness smoke (tiny, jnp)
# ---------------------------------------------------------------------------


def test_run_bench_smoke_row():
    row = serve.run_bench(
        n_triples=2000, n_preds=8, n_tenants=3, n_queries=48,
        cap=128, max_batch=16, deadline_ms=1.0, backend="jnp",
        warmup=8, quiet=True,
    )
    assert row["mode"] == "single"
    assert row["queries"] == 48
    assert row["qps"] > 0
    assert row["p50_ms"] is not None and row["p50_ms"] > 0
    assert row["p99_ms"] is None  # 48 samples cannot support a p99
    assert row["shed"] == 0
    assert set(row["per_tenant"]) <= {f"tenant-{i}" for i in range(3)}
    assert "n/a" in serve.format_row(row)  # guard surfaces in the report
