"""Schedule-IR tests: structure, cost model, selection, persistence.

The tentpole's acceptance criteria live here: (a) every collective ×
algorithm builds a structurally valid schedule whose critical-path rounds
equal the closed-form latency model the simulator always used; (b) the
host interpreter executes segmented schedules to the same result as
unsegmented ones; (c) under the α-β(-γ) cost model — analytic AND
discrete-event — a segmented ring allreduce strictly beats the
unsegmented one for large payloads; (d) α-β selection picks the
latency-optimal algorithm for small payloads and the bandwidth-optimal
(segmented) one for large.
"""

import numpy as np
import pytest

from repro.core import schedule as schedule_ir
from repro.core import simulate
from repro.core import tac
from repro.core.collectives import (Collectives, HaloExchange,
                                    HierarchicalCollectives,
                                    PersistentCollective, n_rounds)
from repro.core.schedule import Recv, Send, build, build_neighbor, \
    best_schedule

RANKS = (1, 2, 3, 4, 5, 7, 8)
ALPHA, BETA, GAMMA = 5e-6, 1e-9, 4e-10


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", schedule_ir.COLLECTIVES)
@pytest.mark.parametrize("alg", schedule_ir.ALGORITHMS)
@pytest.mark.parametrize("n", RANKS)
def test_build_validates_and_matches_closed_form_rounds(name, alg, n):
    sched = build(name, alg, n)
    sched.validate()                      # idempotent structural check
    assert sched.n == n
    assert sched.rounds == n_rounds(name, alg, n)


@pytest.mark.parametrize("n", (2, 3, 5, 8))
def test_transfers_are_matched_pairs(n):
    for name in schedule_ir.COLLECTIVES:
        for alg in schedule_ir.ALGORITHMS:
            sched = build(name, alg, n)
            sends = sum(isinstance(o, Send) for p in sched.programs
                        for o in p)
            recvs = sum(isinstance(o, Recv) for p in sched.programs
                        for o in p)
            assert sends == recvs == len(sched.transfers())


def test_schedules_are_cached_data():
    a = build("allreduce", "ring", 8)
    b = build("allreduce", "ring", 8)
    assert a is b                          # immutable, shared
    assert build("allreduce", "ring", 8, segments=2) is not a


def test_build_rejects_bad_arguments():
    with pytest.raises(ValueError):
        build("allreduce", "butterfly", 4)
    with pytest.raises(ValueError):
        build("gather", "ring", 4)
    with pytest.raises(ValueError):
        build("bcast", "ring", 4, root=4)
    with pytest.raises(ValueError):
        build("bcast", "ring", 4, segments=2)   # only ring allreduce
    with pytest.raises(ValueError):
        build("allreduce", "ring", 0)


@pytest.mark.parametrize("n,segments", [(4, 2), (5, 3), (8, 4)])
def test_segmented_ring_structure(n, segments):
    sched = build("allreduce", "ring", n, segments=segments)
    sched.validate()
    counts = sched.counts()
    # 2(n-1) rounds × S segments × n ranks transfers; combines only on
    # the reduce-scatter leg.
    assert counts["Send"] == 2 * (n - 1) * segments * n
    assert counts["Combine"] == (n - 1) * segments * n


def test_neighbor_schedule_matches_topology():
    world = tac.CommWorld(6)
    cart = world.cart_create((2, 3))
    sched = build_neighbor(cart.topology())
    # one transfer per directed grid edge
    n_edges = sum(len(cart.neighbor_dirs(r)) for r in range(6))
    assert len(sched.transfers()) == n_edges
    assert sched.out_dirs[0] == tuple(d for d, _ in cart.neighbor_dirs(0))
    # same-shape grids share the cached schedule object
    cart2 = tac.CommWorld(8).cart_create((2, 3))
    assert build_neighbor(cart2.topology()) is sched


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_cost_latency_point_equals_rounds():
    for name in schedule_ir.COLLECTIVES:
        for alg in schedule_ir.ALGORITHMS:
            sched = build(name, alg, 7)
            assert sched.cost(1.0, 0.0, 0.0) == pytest.approx(sched.rounds)


def test_cost_algorithm_crossover():
    """doubling wins the latency-bound regime, ring the bandwidth-bound."""
    ring = build("allreduce", "ring", 8)
    dbl = build("allreduce", "doubling", 8)
    small, large = 64, 256 << 20
    assert dbl.cost(ALPHA, BETA, small) < ring.cost(ALPHA, BETA, small)
    assert ring.cost(ALPHA, BETA, large) < dbl.cost(ALPHA, BETA, large)


def test_cost_monotone_in_size_and_alpha():
    sched = build("allreduce", "ring", 5)
    assert sched.cost(ALPHA, BETA, 1 << 20) < sched.cost(ALPHA, BETA,
                                                         1 << 24)
    assert sched.cost(ALPHA, BETA, 1 << 20) < sched.cost(10 * ALPHA, BETA,
                                                         1 << 20)


@pytest.mark.parametrize("segments", (2, 4))
def test_segmented_beats_unsegmented_analytic(segments):
    """Acceptance: S≥2 strictly beats the unsegmented ring for large
    payloads once combines cost anything (γ > 0) — the pipelining win."""
    size = 64 << 20
    un = build("allreduce", "ring", 8).cost(ALPHA, BETA, size, gamma=GAMMA)
    seg = build("allreduce", "ring", 8, segments=segments).cost(
        ALPHA, BETA, size, gamma=GAMMA)
    assert seg < un


@pytest.mark.parametrize("segments", (2, 4))
def test_segmented_beats_unsegmented_in_simulator(segments):
    """Same claim under the discrete-event simulator's replay of the
    schedule DAG (schedule_tasks/schedule_makespan)."""
    size = 64 << 20
    kw = dict(size=size, alpha=ALPHA, beta=BETA, gamma=GAMMA)
    un = simulate.schedule_makespan(build("allreduce", "ring", 8), **kw)
    seg = simulate.schedule_makespan(
        build("allreduce", "ring", 8, segments=segments), **kw)
    assert seg < un


def test_simulator_replay_tracks_analytic_cost():
    """The two consumers of one schedule agree (same DAG, slightly
    different port models): within 25% on a bandwidth-bound ring."""
    sched = build("allreduce", "ring", 8)
    size = 16 << 20
    analytic = sched.cost(ALPHA, BETA, size, gamma=GAMMA)
    replay = simulate.schedule_makespan(sched, size=size, alpha=ALPHA,
                                        beta=BETA, gamma=GAMMA)
    assert replay == pytest.approx(analytic, rel=0.25)


def test_best_schedule_selection():
    small = best_schedule("allreduce", 8, 64, alpha=ALPHA, beta=BETA,
                          gamma=GAMMA)
    assert (small.algorithm, small.segments) == ("doubling", 1)
    large = best_schedule("allreduce", 8, 64 << 20, alpha=ALPHA,
                          beta=BETA, gamma=GAMMA)
    assert large.algorithm == "ring" and large.segments > 1


# ---------------------------------------------------------------------------
# host interpreter over the IR
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", (2, 3, 5, 8))
@pytest.mark.parametrize("segments", (2, 3))
def test_segmented_allreduce_host_equals_unsegmented(n, segments):
    w = tac.CommWorld(n)
    coll = Collectives(w)
    vals = [np.arange(17, dtype=np.float64) * (r + 1) for r in range(n)]
    ref = coll.run_group("allreduce", [{"value": v} for v in vals],
                         algorithm="ring")
    seg = coll.run_group("allreduce", [{"value": v} for v in vals],
                         algorithm="ring", segments=segments)
    for a, b in zip(ref, seg):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, sum(vals))


def test_segmented_allreduce_rejects_doubling():
    coll = Collectives(tac.CommWorld(4))
    with pytest.raises(ValueError):
        coll.allreduce(np.ones(4), rank=0, algorithm="doubling",
                       segments=2)


def test_auto_algorithm_runs_and_matches():
    w = tac.CommWorld(4)
    coll = Collectives(w, alpha=ALPHA, beta=BETA, gamma=GAMMA)
    vals = [np.full(3, float(r)) for r in range(4)]
    out = coll.run_group("allreduce", [{"value": v} for v in vals],
                         algorithm="auto")
    for o in out:
        np.testing.assert_array_equal(o, sum(vals))
    # prediction helper exposes the model
    assert coll.predict("allreduce", 1 << 20) > 0.0


def test_auto_is_deterministic_for_ragged_payloads():
    """Size-based selection only applies to uniform-payload reductions;
    for ragged ops (alltoall blocks, non-root bcast values) every rank
    must resolve the SAME schedule or the collective stalls."""
    w = tac.CommWorld(4)
    coll = Collectives(w, alpha=ALPHA, beta=BETA, gamma=GAMMA)
    # rank 0 ships huge blocks, the rest tiny ones — must not stall
    blocks = [[np.zeros(100_000 if s == 0 else 1) for _ in range(4)]
              for s in range(4)]
    out = coll.run_group("alltoall", [{"blocks": b} for b in blocks],
                         algorithm="auto")
    assert out[1][0].shape == (100_000,)
    # bcast: non-root ranks pass None (0 bytes) while root has data
    got = coll.run_group(
        "bcast", [{"value": np.zeros(100_000) if r == 0 else None}
                  for r in range(4)], algorithm="auto")
    assert all(g.shape == (100_000,) for g in got)


def test_predict_auto_respects_nbytes():
    coll = Collectives(tac.CommWorld(8), alpha=ALPHA, beta=BETA,
                       gamma=GAMMA)
    big = coll.predict("allreduce", 64 << 20, algorithm="auto")
    # auto's choice for the big payload must match explicit best_schedule
    best = best_schedule("allreduce", 8, 64 << 20, alpha=ALPHA, beta=BETA,
                         gamma=GAMMA)
    assert big == pytest.approx(
        best.cost(ALPHA, BETA, 64 << 20, gamma=GAMMA))
    # and must beat the latency-optimal schedule it would pick at 0 bytes
    dbl = coll.predict("allreduce", 64 << 20, algorithm="doubling")
    assert big < dbl


def test_n_rounds_rejects_auto():
    with pytest.raises(ValueError):
        n_rounds("allreduce", "auto", 8)


# ---------------------------------------------------------------------------
# persistent collectives (MPI_*_init analogue)
# ---------------------------------------------------------------------------
def test_persistent_allreduce_reposts_with_isolated_tags():
    w = tac.CommWorld(5)
    coll = Collectives(w)
    p = coll.persistent("allreduce", algorithm="ring")
    assert isinstance(p, PersistentCollective)
    assert p.sched is build("allreduce", "ring", 5)   # pre-built, shared
    for it in range(4):
        vals = [np.arange(9, dtype=np.float64) + it * (r + 1)
                for r in range(5)]
        out = p.run_group(vals)
        for o in out:
            np.testing.assert_array_equal(o, sum(vals))


def test_persistent_alltoall_and_bcast():
    w = tac.CommWorld(4)
    coll = Collectives(w)
    pa = coll.persistent("alltoall")
    blocks = [[f"{s}->{d}" for d in range(4)] for s in range(4)]
    res = pa.run_group(blocks)
    for d in range(4):
        assert res[d] == [f"{s}->{d}" for s in range(4)]
    pb = coll.persistent("bcast", root=2)
    out = pb.run_group(["x" if r == 2 else None for r in range(4)])
    assert out == ["x"] * 4


def test_persistent_rejects_auto():
    coll = Collectives(tac.CommWorld(4))
    with pytest.raises(ValueError):
        coll.persistent("allreduce", algorithm="auto")


def test_persistent_hierarchical_residual_shape():
    """The Gauss–Seidel residual pattern: one persistent handle, one
    posting per iteration, every rank sees the same total."""
    world = tac.CommWorld(6)
    hier = HierarchicalCollectives(world, 3)
    res = hier.persistent(op="sum")
    for it in range(3):
        vals = [float(r + it) for r in range(6)]
        out = res.run_group(vals, key=("res", it))
        assert all(abs(o - sum(vals)) < 1e-12 for o in out)
    assert res.cost(ALPHA, BETA, 8) > 0.0


# ---------------------------------------------------------------------------
# one IR, two executors: neighbourhood parity
# ---------------------------------------------------------------------------
def test_halo_exchange_runs_the_neighbor_schedule():
    world = tac.CommWorld(4)
    cart = world.cart_create((2, 2), periodic=False)
    hx = HaloExchange(cart)
    assert hx.sched is build_neighbor(cart.topology())
    sends = [{d: np.full(2, float(r * 10 + i))
              for i, (d, _) in enumerate(hx.neighbors(r))}
             for r in range(4)]
    got = hx.run_group(sends)
    for r in range(4):
        for d, nbr in hx.neighbors(r):
            opp = (d[0], -d[1])
            np.testing.assert_array_equal(got[r][d], sends[nbr][opp])


def test_hierarchical_cost_latency_point_equals_n_rounds():
    world = tac.CommWorld(7)
    hier = HierarchicalCollectives(world, 3)
    assert hier.cost(1.0, 0.0, 0) == pytest.approx(hier.n_rounds())


def test_rank_translation_hooks():
    """The tac hooks schedule-IR consumers translate through: identity on
    the world, MPI_Group_translate_ranks on groups — including a
    CommWorld as translation target (HierarchicalCollectives' leader
    discovery)."""
    w = tac.CommWorld(6)
    assert w.world_rank(3) == 3
    assert w.group_rank(3) == 3
    assert w.group_rank(6) is None
    with pytest.raises(ValueError):
        w.world_rank(6)
    g = w.group([4, 1, 5])
    assert g.translate_many([0, 1, 2], w) == [4, 1, 5]
    other = w.group([1, 4])
    assert g.translate_many([0, 1, 2], other) == [1, 0, None]


def test_neighbor_schedule_memoised_on_communicator():
    cart = tac.CommWorld(4).cart_create((2, 2))
    coll = Collectives(cart)
    sends = {d: np.zeros(1) for d, _ in
             [(d, n) for d, n in cart.neighbor_dirs(0)]}
    # per-rank postings share one schedule object on the communicator
    from repro.core.collectives import _neighbor_schedule
    s1 = _neighbor_schedule(cart)
    s2 = _neighbor_schedule(cart)
    assert s1 is s2
    assert HaloExchange(cart).sched is s1


# ---------------------------------------------------------------------------
# hierarchical composition: one flat schedule spanning two mesh axes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("intra,inter",
                         [(4, 2), (2, 4), (3, 2), (2, 3), (4, 1), (1, 4),
                          (2, 2), (1, 1)])
def test_hierarchical_structure_and_rounds(intra, inter):
    """build_hierarchical validates and its critical path is the
    composed closed form: 2(intra-1) intra ring rounds + the inter
    doubling rounds (fold/unfold included for non-power-of-two pods)."""
    sched = schedule_ir.build_hierarchical(intra, inter)
    sched.validate()
    assert sched.n == intra * inter
    assert sched.algorithm == "hierarchical"
    assert dict(sched.axes) == {"inter": inter, "intra": intra}
    assert sched.n_chunks == intra
    expect = 2 * (intra - 1)
    if inter > 1:
        expect += n_rounds("allreduce", "doubling", inter)
    assert sched.rounds == expect


def test_hierarchical_is_cached_data():
    a = schedule_ir.build_hierarchical(4, 2)
    assert schedule_ir.build_hierarchical(4, 2) is a
    assert schedule_ir.build_hierarchical(2, 4) is not a


def test_hierarchical_rejects_bad_arguments():
    with pytest.raises(ValueError):
        schedule_ir.build_hierarchical(0, 2)
    with pytest.raises(ValueError):
        schedule_ir.build_hierarchical(2, 2, inter_algorithm="ring")


@pytest.mark.parametrize("intra,inter", [(4, 2), (2, 4), (3, 2), (2, 3)])
def test_hierarchical_host_matches_flat(intra, inter):
    """Level A interprets the composed schedule to the same result as the
    flat ring allreduce and the numpy reduction — including payloads not
    divisible by the chunk count."""
    n = intra * inter
    world = tac.CommWorld(n)
    coll = Collectives(world)
    vals = [np.arange(11, dtype=np.float64) * (r + 1) + 0.25
            for r in range(n)]
    want = np.sum(vals, axis=0)
    got = coll.run_group("allreduce", [{"value": v} for v in vals],
                         hierarchical=intra)
    flat = coll.run_group("allreduce", [{"value": v} for v in vals])
    for g, f in zip(got, flat):
        np.testing.assert_allclose(g, want)
        np.testing.assert_allclose(f, want)


def test_hierarchical_kwarg_validation():
    coll = Collectives(tac.CommWorld(6))
    with pytest.raises(ValueError):        # intra must divide the size
        coll.run_group("allreduce", [{"value": np.ones(2)}] * 6,
                       hierarchical=4)
    with pytest.raises(ValueError):        # composed schedule is fixed
        coll.run_group("allreduce", [{"value": np.ones(2)}] * 6,
                       hierarchical=2, algorithm="ring")


def test_hierarchical_composed_equals_grouped():
    """HierarchicalCollectives: the composed single-schedule form agrees
    with the three-stage sub-group form, and exposes the IR object the
    Level-B lowering consumes."""
    world = tac.CommWorld(8)
    hier = HierarchicalCollectives(world, 4)
    assert hier.sched is schedule_ir.build_hierarchical(4, 2)
    vals = [np.full(7, float(r + 1)) for r in range(8)]
    grouped = hier.run_group(vals)
    composed = hier.run_group(vals, composed=True)
    for g, c in zip(grouped, composed):
        np.testing.assert_allclose(g, np.full(7, 36.0))
        np.testing.assert_allclose(c, np.full(7, 36.0))
    # unequal intra groups: no flat factorisation exists
    ragged = HierarchicalCollectives(tac.CommWorld(6), 4)
    assert ragged.sched is None
    with pytest.raises(ValueError):
        ragged.run_group([np.ones(2)] * 6, composed=True)


def test_hierarchical_cost_beats_flat_ring_on_latency():
    """Uniform constants: (4, 2) moves the same bytes as the flat 8-rank
    ring in half the rounds, so it wins for latency-bound payloads and
    never costs more wire time."""
    hier = schedule_ir.build_hierarchical(4, 2)
    flat = build("allreduce", "ring", 8)
    assert hier.rounds < flat.rounds
    assert hier.cost(ALPHA, BETA, 1024) < flat.cost(ALPHA, BETA, 1024)


def test_hierarchical_simulator_replay_and_two_tier_link():
    """The discrete-event replay of the composed DAG: the latency point
    recovers the closed-form rounds, and under a two-tier machine
    (expensive inter-pod links) the hierarchical composition beats the
    flat ring replayed on the SAME link model — the paper's motivation
    for hierarchy on a production mesh."""
    hier = schedule_ir.build_hierarchical(4, 2)
    flat = build("allreduce", "ring", 8)
    assert simulate.schedule_makespan(
        hier, size=0.0, alpha=1.0, beta=0.0) == pytest.approx(hier.rounds)
    link = simulate.two_tier_link(4, alpha=1e-6, beta=1e-10,
                                  inter_alpha=2e-5, inter_beta=2e-9)
    mh = simulate.schedule_makespan(hier, size=1e6, alpha=1e-6,
                                    beta=1e-10, link=link)
    mf = simulate.schedule_makespan(flat, size=1e6, alpha=1e-6,
                                    beta=1e-10, link=link)
    assert mh < mf


# ---------------------------------------------------------------------------
# calibrated constants (tools/calibrate.py round trip)
# ---------------------------------------------------------------------------
def test_load_calibration_feeds_auto_selection(tmp_path):
    import json
    path = tmp_path / "CALIBRATION.json"
    path.write_text(json.dumps({"alpha": 2e-5, "beta": 3e-9,
                                "gamma": 1e-10, "overhead": 0.5}))
    consts = schedule_ir.load_calibration(path)
    assert consts == {"alpha": 2e-5, "beta": 3e-9, "gamma": 1e-10}
    coll = Collectives(tac.CommWorld(4), calibration=path)
    assert (coll.alpha, coll.beta, coll.gamma) == (2e-5, 3e-9, 1e-10)
    # the calibrated constants drive algorithm="auto" via best_schedule
    sched = best_schedule("allreduce", 4, 8, **consts)
    assert sched.algorithm == "doubling"   # tiny payload: latency-bound
    big = best_schedule("allreduce", 4, 1 << 24, **consts)
    assert big.algorithm == "ring"         # huge payload: bandwidth-bound
    # dicts work too (pre-loaded calibration shared across communicators)
    coll2 = Collectives(tac.CommWorld(4), calibration=consts)
    assert coll2.beta == 3e-9


def test_calibrate_fit_recovers_constants(tmp_path):
    """tools/calibrate.py round trip: synthesise measurements from known
    constants, fit, and gate against a self-written baseline."""
    import json
    import pathlib
    import subprocess
    import sys
    true = {"alpha": 2e-6, "beta": 4e-9, "gamma": 1e-10, "overhead": 3e-3}
    report = {"modes": {}}
    for i, (name, n, size) in enumerate(
            [("fused", 8, 1 << 20), ("bucketed", 8, 1 << 16),
             ("sentinel", 8, 1 << 18), ("tiny", 8, 1 << 8)]):
        sched = build("allreduce", "ring" if i % 2 else "doubling", n)
        feats = {"rounds": sched.cost(1.0, 0.0, 0.0),
                 "wire_bytes": sched.cost(0.0, 1.0, size),
                 "combine_bytes": sched.cost(0.0, 0.0, size, gamma=1.0)}
        measured = (true["alpha"] * feats["rounds"]
                    + true["beta"] * feats["wire_bytes"]
                    + true["gamma"] * feats["combine_bytes"]
                    + true["overhead"])
        report["modes"][name] = {"features": feats, "measured_s": measured}
    bench = tmp_path / "BENCH_overlap.json"
    bench.write_text(json.dumps(report))
    tool = pathlib.Path(__file__).resolve().parents[1] / "tools" / \
        "calibrate.py"
    base = tmp_path / "BENCH_baseline.json"
    out = tmp_path / "CALIBRATION.json"
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--out",
         str(out), "--apply", "--write-baseline", str(base)],
        capture_output=True, text=True)
    assert run.returncode == 0, run.stderr
    consts = json.loads(out.read_text())
    # exact data, 4 unknowns, 4 independent rows: the fit recovers them
    assert consts["alpha"] == pytest.approx(true["alpha"], rel=1e-3)
    assert consts["beta"] == pytest.approx(true["beta"], rel=1e-3)
    assert consts["overhead"] == pytest.approx(true["overhead"], rel=1e-3)
    # calibrated predictions land next to the nominal ones
    applied = json.loads(bench.read_text())
    row = applied["modes"]["fused"]
    assert row["predicted_calibrated_s"] == pytest.approx(
        row["measured_s"], rel=1e-6)
    # and the gate passes against the freshly written baseline...
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--gate",
         "--baseline", str(base), "--out", str(out)],
        capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    # ...but fails once a measurement drifts structurally
    report["modes"]["fused"]["measured_s"] *= 50.0
    bench.write_text(json.dumps(report))
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--gate",
         "--baseline", str(base), "--out", str(out)],
        capture_output=True, text=True)
    assert run.returncode == 1
    assert "DRIFT" in run.stdout
    # ...and when a baseline row vanishes from the report entirely
    del report["modes"]["sentinel"]
    bench.write_text(json.dumps(report))
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--gate",
         "--baseline", str(base), "--out", str(out)],
        capture_output=True, text=True)
    assert run.returncode == 1
    assert "MISSING" in run.stdout


# the default (XLA-leg) family of the synthetic calibration bench
_CAL_TRUE = {"alpha": 2e-6, "beta": 4e-9, "gamma": 1e-10, "overhead": 3e-3}
# the level_a family: its OWN transport constants (host isend/irecv,
# orders of magnitude off the XLA legs') + per-executor overheads
_CAL_FAM = {"alpha": 1.2e-5, "beta": 2e-10, "gamma": 5e-11}


def _level_a_rows(overheads):
    configs = [("ring_small", 112, 7168, 3584),
               ("ring_big", 112, 1835008, 917504),
               ("dbl_small", 24, 12288, 12288),
               ("dbl_big", 24, 3145728, 3145728)]
    fam = _CAL_FAM
    rows = {"compiled": {}, "interpreted": {}}
    for executor, o in overheads.items():
        for name, r, w, v in configs:
            rows[executor][name] = {
                "features": {"rounds": r, "wire_bytes": w,
                             "combine_bytes": v},
                "measured_s": (fam["alpha"] * r + fam["beta"] * w
                               + fam["gamma"] * v + o),
                "overhead_class": f"level_a:{executor}"}
    return rows


def _families_report():
    """Exact synthetic bench rows for the default and level_a families."""
    true = _CAL_TRUE
    report = {"modes": {}}
    for i, (name, size) in enumerate(
            [("fused", 1 << 20), ("bucketed", 1 << 16),
             ("sentinel", 1 << 18), ("tiny", 1 << 8)]):
        sched = build("allreduce", "ring" if i % 2 else "doubling", 8)
        feats = {"rounds": sched.cost(1.0, 0.0, 0.0),
                 "wire_bytes": sched.cost(0.0, 1.0, size),
                 "combine_bytes": sched.cost(0.0, 0.0, size, gamma=1.0)}
        report["modes"][name] = {
            "features": feats,
            "measured_s": (true["alpha"] * feats["rounds"]
                           + true["beta"] * feats["wire_bytes"]
                           + true["gamma"] * feats["combine_bytes"]
                           + true["overhead"])}
    report["level_a"] = _level_a_rows({"compiled": 4e-5,
                                       "interpreted": 2e-4})
    return report


def test_calibrate_per_executor_overheads_and_gate(tmp_path):
    """Rows labelled ``overhead_class`` fit per-family α/β/γ plus a
    per-class overhead intercept; the gate hard-asserts the compiled
    executor's intercept at ≤ 0.5× the interpreted one — and catches a
    compiled-path regression even when no per-row ratio drifts."""
    import json
    import pathlib
    import subprocess
    import sys
    true, fam = _CAL_TRUE, _CAL_FAM
    report = _families_report()
    bench = tmp_path / "BENCH_overlap.json"
    bench.write_text(json.dumps(report))
    tool = pathlib.Path(__file__).resolve().parents[1] / "tools" / \
        "calibrate.py"
    base = tmp_path / "BENCH_baseline.json"
    out = tmp_path / "CALIBRATION.json"
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--out",
         str(out), "--write-baseline", str(base)],
        capture_output=True, text=True)
    assert run.returncode == 0, run.stderr
    consts = json.loads(out.read_text())
    # top-level constants: the default family's, as before labels existed
    assert consts["alpha"] == pytest.approx(true["alpha"], rel=1e-3)
    assert consts["overhead"] == pytest.approx(true["overhead"], rel=1e-3)
    # the level_a family fits its own transport constants...
    la = consts["families"]["level_a"]
    assert la["alpha"] == pytest.approx(fam["alpha"], rel=1e-3)
    assert la["beta"] == pytest.approx(fam["beta"], rel=1e-3)
    # ...and one overhead intercept per executor class
    assert consts["overheads"]["level_a:compiled"] == pytest.approx(
        4e-5, rel=1e-3)
    assert consts["overheads"]["level_a:interpreted"] == pytest.approx(
        2e-4, rel=1e-3)
    # exact synthetic data: every per-row ratio is 1
    cal = json.loads(out.read_text())
    assert all(abs(r["ratio"] - 1.0) < 1e-6 for r in cal["rows"].values())
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--gate",
         "--baseline", str(base), "--out", str(out)],
        capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "executor overhead" in run.stdout
    # compiled overhead regresses to 0.75× interpreted: the per-row
    # ratios barely move (well inside ×tolerance) but the executor
    # assertion fails the gate
    report["level_a"] = _level_a_rows({"compiled": 1.5e-4,
                                       "interpreted": 2e-4})
    bench.write_text(json.dumps(report))
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--gate",
         "--baseline", str(base), "--out", str(out)],
        capture_output=True, text=True)
    assert run.returncode == 1
    assert "compiled executor per-call overhead" in run.stderr
    assert "DRIFT" not in run.stdout


def test_calibrate_history_directory_rolling_window(tmp_path):
    """--history accepts a directory of per-run artifacts; the rolling
    window keeps only the newest N (timestamped names sort
    chronologically), so an ancient outlier stops influencing the fit."""
    import json
    import pathlib
    import subprocess
    import sys
    sched = build("allreduce", "ring", 8)
    size = 1 << 18

    def report(measured):
        feats = {"rounds": sched.cost(1.0, 0.0, 0.0),
                 "wire_bytes": sched.cost(0.0, 1.0, size),
                 "combine_bytes": sched.cost(0.0, 0.0, size, gamma=1.0)}
        return {"modes": {"leg": {"features": feats,
                                  "measured_s": measured}}}

    true_s = 4e-9 * sched.cost(0.0, 1.0, size)
    bench = tmp_path / "BENCH_overlap.json"
    bench.write_text(json.dumps(report(true_s)))
    hist = tmp_path / "bench-history"
    hist.mkdir()
    # oldest artifact is a wild outlier; the next three agree with today
    (hist / "BENCH_overlap-20260101T000000Z.json").write_text(
        json.dumps(report(true_s * 1e4)))
    for i in range(1, 4):
        (hist / f"BENCH_overlap-2026020{i}T000000Z.json").write_text(
            json.dumps(report(true_s)))
    tool = pathlib.Path(__file__).resolve().parents[1] / "tools" / \
        "calibrate.py"
    out = tmp_path / "CALIBRATION.json"

    def fit(window):
        run = subprocess.run(
            [sys.executable, str(tool), "--bench", str(bench), "--out",
             str(out), "--history", str(hist),
             "--history-window", str(window)],
            capture_output=True, text=True)
        assert run.returncode == 0, run.stderr
        data = json.loads(out.read_text())
        return data, run.stdout
    # window 3 drops the outlier: the fit matches today's measurement
    data, stdout = fit(3)
    assert data["n_rows"] == 4                       # bench + 3 newest
    assert stdout.count("history:") == 3
    assert "20260101T000000Z" not in stdout          # oldest pruned
    assert data["rows"]["modes.leg"]["ratio"] == pytest.approx(1.0,
                                                               rel=1e-6)
    # window 0 (unlimited) lets the outlier drag the fit off
    data, stdout = fit(0)
    assert data["n_rows"] == 5 and stdout.count("history:") == 4
    assert data["rows"]["modes.leg"]["ratio"] < 0.5


def test_hierarchical_rejects_segments_at_both_levels():
    """Both executors refuse segments on the fixed composed schedule —
    silently dropping it would fake pipelining (Level B mirrors
    Collectives._resolve)."""
    from repro.core import lowering
    from repro.core import overlap
    with pytest.raises(ValueError, match="segments"):
        lowering.allreduce(None, ("pod", "data"),
                           algorithm="hierarchical", segments=4)
    with pytest.raises(ValueError, match="segments"):
        overlap.sync_grads({"w": np.zeros(4)}, axes=("pod", "data"),
                           hierarchical=True, segments=4)


# ---------------------------------------------------------------------------
# segmented allgather / reduce_scatter rings (Concat reassembly)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("segments", [2, 4])
@pytest.mark.parametrize("executor", ["interpreted", "compiled"])
def test_segmented_allgather_and_reduce_scatter_parity(segments, executor):
    n = 5
    vals = [np.arange(23.0).reshape(1, 23) + 7 * r for r in range(n)]
    coll = Collectives(tac.CommWorld(n), executor=executor)
    base_ag = coll.run_group("allgather", [{"value": v} for v in vals])
    seg_ag = coll.run_group(
        "allgather", [{"value": v, "segments": segments} for v in vals])
    for r in range(n):
        for i in range(n):
            got = np.asarray(seg_ag[r][i])
            assert got.shape == vals[i].shape        # reshaped like "in"
            np.testing.assert_array_equal(got, np.asarray(base_ag[r][i]))
    base_rs = coll.run_group("reduce_scatter", [{"value": v} for v in vals])
    seg_rs = coll.run_group(
        "reduce_scatter", [{"value": v, "segments": segments} for v in vals])
    for r in range(n):
        # Concat of the array_split segments is bit-identical to the
        # unsegmented chunk (split composes with itself).
        np.testing.assert_array_equal(seg_rs[r], base_rs[r])


def test_segmented_rs_pipelines_in_cost_model_and_simulator():
    n, size = 8, float(1 << 24)
    gamma = 8e-10                  # combine-heavy: segmentation must win
    base = build("reduce_scatter", "ring", n)
    seg = build("reduce_scatter", "ring", n, segments=4)
    assert seg.cost(ALPHA, BETA, size, gamma=gamma) < \
        base.cost(ALPHA, BETA, size, gamma=gamma)
    # the discrete-event replay agrees (transport of segment k+1 overlaps
    # the combine of segment k)
    mk = lambda s: simulate.schedule_makespan(
        s, size=size, alpha=ALPHA, beta=BETA, gamma=gamma)
    assert mk(seg) < mk(base)


def test_best_schedule_selects_segments_for_bulk_reduce_scatter():
    s = best_schedule("reduce_scatter", 8, float(1 << 24),
                      alpha=ALPHA, beta=BETA, gamma=1e-9)
    assert (s.algorithm, s.name) == ("ring", "reduce_scatter")
    assert s.segments > 1
    # latency-bound payloads keep the unsegmented log-round schedule
    s = best_schedule("allgather", 8, 64.0, alpha=1e-5, beta=BETA)
    assert s.segments == 1


def test_segmented_builds_rejected_for_unsupported_pairs():
    with pytest.raises(ValueError):
        build("alltoall", "ring", 4, segments=2)
    with pytest.raises(ValueError):
        build("allgather", "doubling", 4, segments=2)
    coll = Collectives(tac.CommWorld(4))
    with pytest.raises(ValueError):
        coll.run_group("allgather", [{"value": np.arange(4.0),
                                      "segments": 2}] * 4,
                       algorithm="doubling")


# ---------------------------------------------------------------------------
# two-tier auto selection (hierarchical candidates under a pod-aware link)
# ---------------------------------------------------------------------------
def test_two_tier_cost_and_hierarchical_auto_selection():
    size = float(1 << 22)
    ring = build("allreduce", "ring", 8)
    # an expensive cross-pod link must make the flat ring cost MORE than
    # under uniform constants (7 of its 8 hops stay intra, 1 crosses)
    def link(src, dst):
        return (ALPHA, BETA) if src // 4 == dst // 4 else (5e-4, 3e-7)
    assert ring.cost(ALPHA, BETA, size, link=link) > \
        ring.cost(ALPHA, BETA, size)
    picked = best_schedule("allreduce", 8, size, alpha=ALPHA, beta=BETA,
                           intra=4, inter_alpha=5e-4, inter_beta=3e-7)
    assert picked.algorithm == "hierarchical"
    assert picked.axes == (("inter", 2), ("intra", 4))
    # degenerate pod structures fall back to the flat candidate set
    flat = best_schedule("allreduce", 8, size, alpha=ALPHA, beta=BETA,
                         intra=8)
    assert flat.algorithm != "hierarchical"


def test_collectives_auto_with_hierarchy_runs_hierarchical():
    n = 8
    vals = [np.arange(32.0) + r for r in range(n)]
    coll = Collectives(tac.CommWorld(n), alpha=1e-6, beta=1e-9,
                       hierarchy=4, inter_alpha=5e-4, inter_beta=3e-7)
    sched = coll._resolve("allreduce", "auto",
                          nbytes=float(1 << 22))
    assert sched.algorithm == "hierarchical"
    out = coll.run_group("allreduce", [{"value": v} for v in vals],
                         algorithm="auto")
    for r in range(n):
        np.testing.assert_array_equal(out[r], np.sum(vals, axis=0))
    with pytest.raises(ValueError):
        Collectives(tac.CommWorld(6), hierarchy=4)   # 4 does not divide 6


def test_load_calibration_families(tmp_path):
    import json
    import pathlib
    import subprocess
    import sys
    bench = tmp_path / "BENCH_overlap.json"
    bench.write_text(json.dumps(_families_report()))
    tool = pathlib.Path(__file__).resolve().parents[1] / "tools" / \
        "calibrate.py"
    out = tmp_path / "CALIBRATION.json"
    run = subprocess.run(
        [sys.executable, str(tool), "--bench", str(bench), "--out",
         str(out)], capture_output=True, text=True)
    assert run.returncode == 0, run.stderr
    consts = schedule_ir.load_calibration(out, family="level_a")
    assert set(consts) == {"alpha", "beta", "gamma"}
    assert consts["alpha"] == pytest.approx(_CAL_FAM["alpha"], rel=1e-3)
    with pytest.raises(KeyError):
        schedule_ir.load_calibration(out, family="no-such-family")
