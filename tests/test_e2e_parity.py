"""End-to-end benchmark parity: interop versions and elastic resume.

Two families, both over the REAL benchmark executions (not simulations):

* **Interop parity** — the Gauss–Seidel and IFSKer interop versions must
  produce bit-identical results to the pure reference (the interop mode
  changes how completions are observed, never what is computed).

* **Elastic resume equality** — a run that loses a rank mid-iteration,
  shrinks, and resumes from its last checkpoint must equal the clean
  reference: for IFSKer (decomposition-independent numerics) the
  full-size clean run, bitwise; for Gauss–Seidel (decomposition-coupled
  numerics) a clean run at the SHRUNKEN size seeded from the same
  checkpoint step.
"""

import shutil

import numpy as np
import pytest

from benchmarks import gauss_seidel as gs
from benchmarks import ifsker
from repro import checkpoint as ckpt

_GS = dict(n_ranks=4, nby=2, nbx=2, bs=8, iters=2, seed=3)
_IF = dict(n_ranks=2, n_fields=4, n_grid=16, steps=2, seed=3)


@pytest.mark.parametrize("version", ["interop-blk", "interop-nonblk"])
def test_gauss_seidel_interop_parity(version):
    ref, ref_stats = gs.run_real("pure", **_GS)
    out, stats = gs.run_real(version, **_GS)
    np.testing.assert_array_equal(out, ref)
    for it, v in ref_stats["residuals"].items():
        assert abs(stats["residuals"][it] - v) < 1e-9, it


@pytest.mark.parametrize("version", ["interop-blk", "interop-nonblk"])
def test_ifsker_interop_parity(version):
    ref, _ = ifsker.run_real("pure", **_IF)
    out, _ = ifsker.run_real(version, **_IF)
    np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# elastic resume equality
# ---------------------------------------------------------------------------
@pytest.mark.faults
def test_ifsker_elastic_resume_equals_clean_run(tmp_path):
    """IFSKer numerics are decomposition-independent, so the killed +
    shrunken + resumed run must equal the undisturbed full-size run
    BITWISE — the strongest form of the resume property."""
    clean, ic = ifsker.run_elastic(str(tmp_path / "a"), steps=3, seed=11)
    assert not ic["recoveries"]
    healed, ih = ifsker.run_elastic(str(tmp_path / "b"), steps=3, seed=11,
                                    kill_step=2, kill_rank=3)
    assert ih["recoveries"] and ih["size"] == 3
    assert ih["recoveries"][0]["resumed_step"] == 1
    np.testing.assert_array_equal(clean, healed)


@pytest.mark.faults
@pytest.mark.parametrize("kill_rank", [1, 3])
def test_gauss_seidel_elastic_resume_matches_shrunken_reference(tmp_path,
                                                                kill_rank):
    """Gauss–Seidel numerics depend on the decomposition, so the resume
    property is: the killed run's tail equals a CLEAN run at the
    shrunken size seeded from the same checkpoint step."""
    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    kw = dict(bs=4, iters=4, seed=7)
    healed, info = gs.run_elastic(da, n_ranks=4, nby=3, nbx=3,
                                  kill_iter=3, kill_rank=kill_rank, **kw)
    assert info["recoveries"], info
    rec = info["recoveries"][0]
    assert rec["survivors"] == 3 and rec["resumed_step"] == 2
    assert info["decomposition"] == (3, 2, 6)   # re-shaped (3,1) grid

    # reference: seed a fresh dir with the SAME checkpoint the killed
    # run resumed from, then run clean at 3 ranks over the same global
    # geometry (3*2 x 1*6 blocks = the killed run's 6x6)
    state, step = ckpt.restore_checkpoint(
        da, {"grid": np.empty((6 * 4, 6 * 4))}, step=rec["resumed_step"])
    ckpt.save_checkpoint(db, state, step=step)
    clean, ic = gs.run_elastic(db, n_ranks=3, nby=2, nbx=6, **kw)
    assert not ic["recoveries"]
    np.testing.assert_array_equal(healed, clean)


@pytest.mark.faults
def test_gauss_seidel_elastic_victim_parity(tmp_path):
    """The killed + resumed trajectory itself is victim-invariant: the
    survivors are renumbered into the same shrunken decomposition, so
    losing rank 0 or rank 2 resumes to the same grid."""
    outs = {}
    for victim in (0, 2):
        out, info = gs.run_elastic(str(tmp_path / str(victim)), n_ranks=4,
                                   nby=3, nbx=3, bs=4, iters=3, kill_iter=2,
                                   kill_rank=victim, seed=5)
        assert info["recoveries"][0]["killed"] == [victim]
        outs[victim] = out
    np.testing.assert_array_equal(outs[0], outs[2])
